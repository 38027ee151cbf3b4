"""Model FLOP utilisation of the whole training step, in percent: the
kind's model operations per sample (for FCNN
``chipbench.work.model_flops_per_sample``, no recomputation or padding)
times the untraced window's samples per second, over the chips' bf16 peak
(the matmuls run as bf16 passes)."""


def read(ctx):
    flops = ctx.flops_per_sample * ctx.samples_per_s
    chips = len(ctx.summary.devices)
    return 100.0 * flops / (chips * ctx.peak["bf16_flops_per_s"])
