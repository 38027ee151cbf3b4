"""Device time under one of the program's named scopes (``repro.obs``),
for the per-layer readers of model kinds whose scopes ``scopes.op_class``
does not class (``mla``, ``moe.route``, ``moe.experts``)."""

from __future__ import annotations

from chipbench import scopes


def scope_ms(profile: scopes.Profile, name: str, steps: int) -> float | None:
    """Device ms per step of every op (kernels included) whose scope path
    names ``name``, forward or backward, mean over the chips; None where
    no op does."""
    total, found = 0.0, False
    for ops in profile.ops.values():
        for o in ops:
            if name in scopes.scope_names(o.scope):
                found = True
                total += o.end_ns - o.start_ns
    if not found:
        return None
    return total / len(profile.ops) / steps / 1e6
