"""The program scopes (``repro.obs``) named by compiled HLO text, for the
scope-coverage tests of ``test_obs.py`` and ``test_tpu_compile.py``: the
FCNN period program's and the LM training step's."""

from __future__ import annotations

import re

_OP_NAME = re.compile(r'op_name="([^"]*)"')
_TRANSFORM = re.compile(r"^[\w-]+\((.*)\)$")
_SCOPE = re.compile(r"^(?:fp\d+\.(?:run|xfer)|loss|grad_norm|optimizer|pad"
                    r"|embed|mla|moe\.(?:route|experts|shared)|dense_mlp"
                    r"|layers)$")
_XLA_NAMED = re.compile(r"^(?:shard_map|[a-z][\w-]*\.\d+)$")
STEP = "jit(step)/"


def op_names(text: str) -> list[str]:
    """The ``op_name`` of every instruction of the jitted step."""
    return [n for n in _OP_NAME.findall(text) if n.startswith(STEP)]


def scopes(op_name: str) -> list[str]:
    """Program scopes on an op_name's path, outermost first, transforms
    unwrapped; the last component is the op itself."""
    out = []
    for part in op_name.split("/")[:-1]:
        while (m := _TRANSFORM.match(part)):
            part = m.group(1)
        if _SCOPE.match(part):
            out.append(part)
    return out


def unscoped(text: str) -> list[str]:
    """Step instructions that name no program scope.  Left out are two
    kinds of name that XLA writes, not the program:

    * the ``shard_map`` call's own (its parameters, loop state and folded
      constants), which belong to no period;
    * those ending in an instruction name (``broadcast.36``): XLA merged
      identical instructions of several scopes (the zero fills of every
      ``fp<i>.xfer`` transpose on four chips) and named the one it kept by
      their common prefix."""
    return [n for n in op_names(text)
            if not scopes(n) and not _XLA_NAMED.match(n.rsplit("/", 1)[-1])]


def present(text: str) -> set[str]:
    """Every scope named in the step, plus ``bwd:<scope>`` for a scope
    reached under a ``transpose`` (the backward pass)."""
    out = set()
    for n in op_names(text):
        names = scopes(n)
        out.update(names)
        if "transpose(" in n:
            out.update(f"bwd:{s}" for s in names)
    return out


# what every compiled training step names, forward and backward
REQUIRED = {"optimizer", "grad_norm", "loss", "pad", "fp1.run",
            "bwd:fp1.run", "fp1.xfer"}

# what the LM training step (``launch/steps.build_train_step``) of
# DeepSeek-V2 names, forward and backward
LM_REQUIRED = {"embed", "layers", "mla", "moe.route", "moe.experts",
               "moe.shared", "dense_mlp", "loss", "grad_norm", "optimizer",
               "bwd:mla", "bwd:moe.experts", "bwd:embed"}
