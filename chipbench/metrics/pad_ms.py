"""Device milliseconds per step in ops under the program's ``pad`` scope:
the kernel wrappers' edge padding and their slices back to the unpadded
shape (``kernels/fcnn_layer.py``, ``kernels/softmax_xent.py``), mean over
the chips used; none where the program names no scope."""

from chipbench import scopes


def read(ctx):
    return scopes.class_ms(ctx.profile, "pad", ctx.steps)
