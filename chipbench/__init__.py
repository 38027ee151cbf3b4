"""Chip benchmark of training steps, first of the paper's FCNNs.

``python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s>
--trace <0|1>`` runs one cell of ``BENCHMARK.json`` on the TPU chips of the
machine it is started on and prints one JSON result line.  Everything that
belongs to one configuration, model kind, traffic mix, cell or per-layer
metric lives in a file of its own, found by name:

    configs/<config>.json      its kind, sizes, precision, optimizer, mapping
    kinds/<kind>.py            build, feed, step and check a model of the
                               kind (interface in ``harness.py``)
    traffic/<traffic>.json     dataset and batch parameters
    workloads/<cell>.json      warm-up and traced steps, limits, and what
                               the kind checks (FCNN: expected degrees)
    metrics/<metric>.py        ``read(ctx) -> float | None``
    references/<name>.py       the configuration's plain jnp reference

The yardstick (traffic generation, reference, comparison, trace reduction,
work counters and peak table) lives here; from the program the benchmark
takes only the system under test and its spans, scopes and kernel names.
"""
