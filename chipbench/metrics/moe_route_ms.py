"""Device milliseconds per step in ops under the program's ``moe.route``
scope (router, top-k, the sort of (token, slot) pairs by held expert, the
gathers, the combine and the balance loss; ``models/moe.py``), forward and
backward, mean over the chips used; none where the program names no such
scope."""

from chipbench.named_scopes import scope_ms


def read(ctx):
    return scope_ms(ctx.profile, "moe.route", ctx.steps)
