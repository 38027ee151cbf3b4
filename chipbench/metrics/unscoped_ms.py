"""Device milliseconds per step in XLA and collective ops under no program
scope (XLA's own layout copies and prefetches), mean over the chips used;
none where the program names no scope at all."""

from chipbench import scopes


def read(ctx):
    return scopes.class_ms(ctx.profile, "none", ctx.steps)
