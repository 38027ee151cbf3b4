"""Percent of the device's idle time in the traced window that overlaps
the program's ``repro.batch.put`` spans, mean over the chips used: how
much of the idling waits on the batch's transfer; none where the program
records no such span."""

from chipbench import scopes


def read(ctx):
    return scopes.idle_put_pct(ctx.profile)
