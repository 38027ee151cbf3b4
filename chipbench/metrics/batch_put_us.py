"""Host microseconds per step in the program's ``repro.batch.put`` span
(``data/pipeline.Batcher`` putting the batch on the devices), mean over
the traced steps; none where the program records no such span."""

from chipbench import scopes


def read(ctx):
    return scopes.batch_put_us(ctx.profile)
