"""What a program's RUNs assume about the data: batch, widths, activation
and element width.

``spmd.check_spmd`` has already tied every RUN to its paper period-layer
and every chunk to its layer (``degree * chunk_width == n_layer``), so
the activation and cotangent widths chain by construction.  What is left
is what the program claims about the data it will be fed:

  * (with a workload) the program's ``batch_size`` and ``layer_sizes``
    are the workload's — a stale or corrupted program fails here with a
    precise error instead of a jit trace error deep inside shard_map;
  * the activation annotation matches the model contract
    (``models.fcnn.period_activation``: hidden layers sigmoid, output
    layer none) and each BP RUN differentiates the same nonlinearity its
    FP mirror applied;
  * (schema v2) each FP RUN's ``param_bytes`` is its
    ``(n_{i-1}+1) x chunk_width`` weight chunk at one element width:
    ``cfg.bytes_per_value`` when a config is given, else period 1's.
"""

from __future__ import annotations

from repro.core.onoc_model import FCNNWorkload, ONoCConfig
from repro.exec.check.spmd import fail
from repro.exec.program import Opcode, PeriodProgram
from repro.models.fcnn import period_activation

__all__ = ["check_shapes"]


def check_shapes(program: PeriodProgram,
                 workload: FCNNWorkload | None = None,
                 cfg: ONoCConfig | None = None) -> None:
    """Raise ``ProgramValidationError`` on the first RUN that disagrees."""
    sizes = program.layer_sizes
    l = program.l
    batch = program.batch_size
    if not isinstance(batch, int) or batch < 1:
        fail(f"shape mismatch: program batch_size {batch!r} is not a "
             f"positive integer")

    if workload is not None:
        if tuple(int(n) for n in workload.layer_sizes) != sizes:
            fail(f"shape mismatch: program layer_sizes {list(sizes)} != "
                 f"workload layer_sizes {list(workload.layer_sizes)}")
        if batch != workload.batch_size:
            fail(f"shape mismatch: RUN period 1 consumes a "
                 f"(batch={batch}, n_0={sizes[0]}) activation block per "
                 f"program.batch_size, but the workload feeds batch "
                 f"{workload.batch_size} — program batch_size disagrees "
                 f"with the workload")

    runs = {i.period: i for i in program.instructions
            if i.opcode is Opcode.RUN}
    if cfg is not None:
        width, width_of = cfg.bytes_per_value, "cfg.bytes_per_value ="
    else:
        width, width_of = None, "period 1's chunk holds"
    for p in range(1, l + 1):
        run = runs[p]
        want_act = period_activation(p, l)
        if run.activation != want_act:
            fail(f"activation mismatch: RUN period {p} (layer {p}) "
                 f"is annotated {run.activation!r} but the model contract "
                 f"(period_activation) requires {want_act!r} — the "
                 f"executor would apply the wrong nonlinearity")
        if program.version >= 2:
            bpv = run.param_bytes / ((sizes[p - 1] + 1) * run.chunk_width)
            if width is None:
                width = bpv
            if bpv != width:
                fail(f"dtype mismatch: RUN period {p} (layer {p}) "
                     f"param_bytes {run.param_bytes!r} hold {bpv!r} bytes "
                     f"per value of its ({sizes[p - 1]}+1, "
                     f"{run.chunk_width}) weight chunk, but "
                     f"{width_of} {width!r}")
    for p in range(l + 1, 2 * l + 1):
        run, fp = runs[p], runs[2 * l - p + 1]
        if run.activation != fp.activation:
            fail(f"activation mismatch: BP RUN period {p} is annotated "
                 f"{run.activation!r} but its FP mirror (period "
                 f"{fp.period}) applied {fp.activation!r} — the backward "
                 f"pass would differentiate the wrong nonlinearity")
