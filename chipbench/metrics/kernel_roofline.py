"""Least time of the kernels' required work over the kernels' device time,
in percent.  The work is the kind's kernel calls of one step (for FCNN
``chipbench.work.step_calls`` from the unpadded shapes: a layer at degree
d counts its d column chunks once each), and the device time is summed
over the chips used."""

from chipbench import work


def read(ctx):
    kernel_s = ctx.summary.mean("kernel_ns") * len(ctx.summary.devices) / 1e9
    if kernel_s <= 0:
        return None
    least = ctx.steps * work.least_time_s(
        ctx.kernel_calls, ctx.peak["bf16_flops_per_s"],
        ctx.peak["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
