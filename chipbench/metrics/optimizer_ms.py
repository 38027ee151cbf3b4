"""Device milliseconds per step in ops under the program's ``optimizer``
scope (``optim/optimizers.adam`` and the step count, ``exec/api.py``),
mean over the chips used; none where the program names no scope."""

from chipbench import scopes


def read(ctx):
    return scopes.class_ms(ctx.profile, "optimizer", ctx.steps)
