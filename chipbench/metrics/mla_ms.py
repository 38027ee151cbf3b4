"""Device milliseconds per step in ops under the program's ``mla`` scope
(latent attention: its norm, projections, attention core and residual
add; ``models/deepseek_v2.py``), forward and backward, mean over the chips
used; none where the program names no such scope."""

from chipbench.named_scopes import scope_ms


def read(ctx):
    return scope_ms(ctx.profile, "mla", ctx.steps)
