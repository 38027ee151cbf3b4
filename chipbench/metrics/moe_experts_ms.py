"""Device milliseconds per step in ops under the program's ``moe.experts``
scope (the held experts' ``ragged_dot`` grouped matmuls and their SwiGLU;
``models/moe.py``), forward and backward, mean over the chips used; none
where the program names no such scope."""

from chipbench.named_scopes import scope_ms


def read(ctx):
    return scope_ms(ctx.profile, "moe.experts", ctx.steps)
