"""Run one training cell on the chip and build its result line.

The timed path is the one users call, built by the configuration's model
kind, ``kinds/<kind>.py`` (``"kind"`` in the configuration's file).  Set-up
builds one step and state, drives them through the first
``checks.CHECK_STEPS`` steps (whose readings decide ``correct``), warms up,
and hands the same objects to the window.  The window dispatches steps
with no sync until ``--seconds`` have passed and ends in
``block_until_ready``.  With ``--trace 1`` a traced window of the cell's
fixed step count follows, with host spans ``batch``, ``dispatch`` and
``wait``; its profile is loaded once the traced steps end, for the
per-layer readers.  The plain reference runs last, after the program's
state is freed.

A kind is a module of these functions (``cell`` is a ``spec.Cell``):

    dataset(cell, seed) -> data
        every input of the run, made from the seed, opaque to the harness
    build(cell, devices, kernel_mode) -> (program, step)
        the compiled program, checked against what the configuration and
        the cell state, and its jitted ``step(state, batch) -> (state,
        metrics)``, where ``metrics["loss"]`` is the step's loss
    init_state(cell, program, seed) -> state
        any pytree, made on the devices from the seed
    feed(cell, program, data) -> iterator of batches
    first_steps(cell, step, state, feed) -> (state, checks.Readings)
        the first ``checks.CHECK_STEPS`` steps through ``step`` and
        ``feed``: their losses, the first gradient by leaf (from the
        kind's own optimizer state) and each leaf's change, the leaves
        named as the reference names them (e.g. from their tree paths)
    reference(cell, seed, data, device, planted=None) -> checks.Readings
        the kind's plain reference over the same steps; ``planted`` puts
        the control (``"control"``) or a fault in the program's place
    samples_per_step(cell) -> int
        samples (rows, sequences) that one step trains on
    flops_per_sample(cell) -> float
        model operations a sample, for ``mfu``
    kernel_calls(cell) -> list[work.Call]
        the required work of one step's kernel calls, for
        ``kernel_roofline``
"""

from __future__ import annotations

import dataclasses
import json
import math
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any

import jax

from chipbench import checks, scopes, spec
from chipbench import trace as tracing

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def log(msg: str) -> None:
    print(msg, flush=True)


def require_chips(chips: int) -> list:
    """The first ``chips`` TPU devices; exits without a result otherwise."""
    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"chipbench: needs a TPU, but JAX's first device is "
                         f"on platform {devices[0].platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chipbench: the cell needs {chips} TPU chips, JAX "
                         f"sees {len(devices)}")
    return devices[:chips]


def enable_compile_cache(root: Path) -> Path:
    """JAX's persistent compilation cache at a fixed path in the checkout,
    holding every program however fast it compiled."""
    path = root / ".jax_cache"
    path.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    # No eviction: its bookkeeping files (``-atime``) went missing on the
    # chip's host and then failed every write to the cache.
    jax.config.update("jax_compilation_cache_max_size", -1)
    return path


class CompileCounter:
    """Counts XLA compilations (cache loads included) as they happen."""

    def __init__(self) -> None:
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_: Any) -> None:
        if event == BACKEND_COMPILE:
            self.count += 1


def peaks(kind: str, pkg: Path) -> dict[str, Any]:
    with open(pkg / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


@dataclasses.dataclass(frozen=True)
class Context:
    """What a per-layer metric reader may read."""

    cell: spec.Cell
    flops_per_sample: float       # the kind's model operations a sample
    kernel_calls: list            # the kind's ``work.Call``s of one step
    steps: int                    # traced steps
    host: dict[str, list[float]]  # host seconds per traced step, by span
    summary: tracing.Summary
    profile: scopes.Profile       # the traced window, with program scopes
    peak: dict[str, Any]
    samples_per_s: float


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool,
             devices: list, t0: float, kernel_mode: str = "pallas",
             trace_dir: str | None = None) -> dict[str, Any]:
    """One run of ``cell``; returns the result object (without printing).

    ``t0`` is the process's start on ``time.perf_counter``; ``kernel_mode``
    is the dispatch the run must get."""
    counter = CompileCounter()
    kind = spec.load_kind(cell)
    device_kind = devices[0].device_kind
    peak = (peaks(device_kind, cell.pkg) if devices[0].platform == "tpu"
            else {})

    def phase(name: str) -> None:
        log(f"set-up: {name} done at {time.perf_counter() - t0!r} s")

    phase("JAX start")
    data = kind.dataset(cell, seed)
    phase("dataset")
    program, step = kind.build(cell, devices, kernel_mode)
    phase("plan, compile and analysis")
    log(f"{cell.name}: {len(devices)} {device_kind} chip(s)")
    state = kind.init_state(cell, program, seed)
    jax.block_until_ready(state)
    phase("init_state")
    feed = kind.feed(cell, program, data)

    state, prog = kind.first_steps(cell, step, state, feed)
    phase(f"first {checks.CHECK_STEPS} steps (step compile or cache load)")
    for _ in range(int(cell.params["warmup_steps"])):
        state, metrics = step(state, next(feed))
    jax.block_until_ready(state)
    phase(f"{cell.params['warmup_steps']} warm-up steps; compilations so "
          f"far {counter.count}")

    compiles = counter.count
    losses = []
    start = time.perf_counter()
    setup_s = start - t0
    deadline = start + seconds
    while True:
        state, metrics = step(state, next(feed))
        losses.append(metrics["loss"])
        if time.perf_counter() >= deadline:
            break
    dispatched = time.perf_counter()
    jax.block_until_ready((state, metrics))
    end = time.perf_counter()
    n = len(losses)
    window_compiles = counter.count - compiles
    samples_per_s = n * kind.samples_per_step(cell) / (end - start)
    log(f"window: {n} steps in {end - start!r} s (dispatch loop "
        f"{dispatched - start!r} s, drain {end - dispatched!r} s), "
        f"{samples_per_s!r} samples/s; compilations in the window: "
        f"{window_compiles}")

    device = {"platform": devices[0].platform, "kind": device_kind,
              "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    breakdown = None
    if trace:
        state, steps, host, profile = traced_window(cell, step, state, feed,
                                                    trace_dir)
        summary = tracing.reduce(profile.trace)
        for plane, d in summary.devices.items():
            log(f"{plane}: idle share "
                f"{100 * (1 - d.busy_ns / summary.window_ns)!r} %, kernel "
                f"{d.kernel_ns / steps / 1e6!r} ms/step, xla "
                f"{d.xla_ns / steps / 1e6!r} ms/step, collective "
                f"{d.collective_ns / steps / 1e6!r} ms/step")
        ctx = Context(cell=cell, flops_per_sample=kind.flops_per_sample(cell),
                      kernel_calls=kind.kernel_calls(cell), steps=steps,
                      host=host, summary=summary, profile=profile, peak=peak,
                      samples_per_s=samples_per_s)
        breakdown = summary.breakdown()
        device["busy_s"] = summary.mean("busy_ns") / 1e9
        device["window_s"] = summary.window_ns / 1e9
        metrics_out = {}
        for m in cell.per_layer:
            value = spec.load_reader(cell, m["name"])(ctx)
            if value is not None:
                metrics_out[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"samples_per_s": samples_per_s, "setup_s": setup_s}
        metrics_out = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in cell.end_to_end}

    failed = int(sum(not math.isfinite(float(v))
                     for v in jax.device_get(losses)))
    del state, metrics, losses, feed, step, program

    t = time.perf_counter()
    ref = kind.reference(cell, seed, data, devices[0])
    log(f"reference: {checks.CHECK_STEPS} steps in "
        f"{time.perf_counter() - t!r} s")
    log(f"program losses {list(prog.losses)}, reference {list(ref.losses)}")
    numbers = checks.compare(prog, ref)
    log(f"compared numbers {numbers}")
    correct, table = checks.judge(numbers, cell.params["limits"])

    result: dict[str, Any] = {
        "correct": bool(correct and failed == 0), "attempted": n,
        "failed": failed, "metrics": metrics_out, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = table
    return result


def traced_window(cell, step, state, feed, trace_dir):
    """Trace the cell's fixed number of steps with the host spans; return
    the state, the step count, the host seconds per step by span and the
    window's profile, loaded before its directory is removed."""
    steps = int(cell.params["trace_steps"])
    host: dict[str, list[float]] = {"batch": [], "dispatch": []}
    own_dir = trace_dir is None
    out = Path(trace_dir or tempfile.mkdtemp(prefix="chipbench-trace-"))
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    try:
        jax.block_until_ready(state)
        with jax.profiler.trace(str(out), profiler_options=options):
            for _ in range(steps):
                a = time.perf_counter()
                with jax.profiler.TraceAnnotation("batch"):
                    batch = next(feed)
                b = time.perf_counter()
                with jax.profiler.TraceAnnotation("dispatch"):
                    state, metrics = step(state, batch)
                c = time.perf_counter()
                host["batch"].append(b - a)
                host["dispatch"].append(c - b)
            with jax.profiler.TraceAnnotation("wait"):
                jax.block_until_ready((state, metrics))
        profile = scopes.load(next(out.glob("**/*.xplane.pb")))
    finally:
        if own_dir:
            shutil.rmtree(out, ignore_errors=True)
    return state, steps, host, profile


def main(argv: list[str], t0: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(prog="chipbench/run.py",
                                 description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--trace-dir", default=None,
                    help="keep the traced window's profile here")
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")
    cell = spec.load_cell(args.workload)
    devices = require_chips(cell.chips)
    enable_compile_cache(spec.ROOT)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace),
                      devices, t0, trace_dir=args.trace_dir)
    for name, row in result["checks"].items():
        print(f"check {name} {row['value']!r} limit {row['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result), flush=True)
    return 0
