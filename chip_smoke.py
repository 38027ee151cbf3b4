#!/usr/bin/env python3
"""Smoke run of the paper's FCNN training step on a TPU, through the entry
points a user calls: ``repro.exec.compile`` -> ``Executable.train_step``.

Default (one chip): NN1 and NN6 at batch 128 and the paper's full widths,
compiled with sharded residency and no ``kernel_mode`` (so the dispatch
must pick the fused Pallas kernels).  Step 0's loss and gradients are
checked against the jnp reference, then 10 Adam steps must give finite,
falling losses.

``--chips 4``: only the period program across four chips.  NN1 and NN6 on
a 4-device ring with sharded residency, checked for per-device placement,
against the replicated-residency oracle on the same mesh (loss by loss)
and against the jnp reference's step-0 loss.

Everything runs in this one process, which holds the chip; it starts no
other.  Unless JAX's first device is a TPU it exits non-zero and prints no
result.  The last line of stdout is one JSON object:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.

    python chip_smoke.py [--chips 4]
"""

from __future__ import annotations

import argparse
import functools
import json
import math
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

import repro.exec as rexec  # noqa: E402
from repro.configs.nn_benchmarks import NN_BENCHMARKS, onoc_config  # noqa: E402
from repro.core.onoc_model import FCNNWorkload  # noqa: E402
from repro.data import fcnn_classification_dataset  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import fcnn  # noqa: E402
from repro.optim import adam  # noqa: E402
from repro.parallel.sharding import replicate  # noqa: E402

WORKLOADS = ("NN1", "NN6")
BATCH = 128
STEPS = 10
# Adam moves every weight by about LR per step, all in one direction at
# first, so a logit fed by a 4000-wide layer of sigmoids moves by about
# 2000 * LR per step: at 1e-3 NN6 overshoots and its loss rises on the
# chip.  At 5e-5 that move is 0.1, and both networks' losses fall.
LR = 5e-5
SEED = 0

# Step-0 agreement of the Pallas path with the jnp reference run at
# "highest" (full fp32) matmul precision.  The bounds hold even if the MXU
# takes fp32 kernel operands as single bf16 passes (unit roundoff 2^-9 per
# operand, fp32 accumulation): that moves the mean loss by ~1e-4 relative
# and each gradient leaf by a few 1e-3 of its largest entry.  A structural
# fault (wrong block index, lost bias, mis-padded edge tile) moves them by
# O(1).
LOSS_RTOL = 1e-3     # |loss - loss_ref| / |loss_ref|
GRAD_TOL = 2e-2      # max over leaves of max|g - g_ref| / max|g_ref|


def _require(ok: bool, msg: str) -> None:
    if not ok:
        raise AssertionError(msg)


def _tpu_devices(chips: int) -> list:
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        raise SystemExit(f"chip_smoke: needs a TPU, but JAX's first device "
                         f"is on platform {platform!r}")
    if len(devices) < chips:
        raise SystemExit(f"chip_smoke: needs {chips} TPU chips, JAX sees "
                         f"{len(devices)}")
    return devices


def _compile(name: str, mesh: Mesh, residency: str):
    exe = rexec.compile(FCNNWorkload(NN_BENCHMARKS[name], batch_size=BATCH),
                        onoc_config(), mesh, residency=residency)
    _require(exe.kernel_mode == "pallas",
             f"{name}: dispatch chose kernel mode {exe.kernel_mode!r}, "
             f"not the fused Pallas kernels")
    return exe


def _batches(mesh: Mesh, n_in: int) -> list[dict]:
    """STEPS times the same seeded batch: on a fixed objective a falling
    loss reflects the gradients, not batch-to-batch noise (NN6 starts
    within a few hundredths of log 10, less than that noise)."""
    x, y = fcnn_classification_dataset(BATCH, input_dim=n_in, seed=SEED)
    return [replicate({"x": x, "y": y}, mesh)] * STEPS


def _reference_errors(exe, params, batch) -> tuple[float, float, float]:
    """(reference loss, loss error, gradient error) of the executable's
    step-0 loss and gradients against the jnp reference."""
    loss, grads = jax.jit(jax.value_and_grad(exe.loss_fn))(params, batch)
    if exe.residency == "sharded":
        params, grads = exe.gather_params(params), exe.gather_params(grads)
    ref_vg = jax.jit(jax.value_and_grad(
        functools.partial(fcnn.loss_fn, kernel_mode="ref")))
    with jax.default_matmul_precision("highest"):
        loss_ref, grads_ref = ref_vg(params, batch)
    loss, loss_ref = float(loss), float(loss_ref)
    grad_err = max(
        float(jnp.max(jnp.abs(g - r)) / jnp.maximum(jnp.max(jnp.abs(r)),
                                                     1e-30))
        for g, r in zip(jax.tree.leaves(grads), jax.tree.leaves(grads_ref)))
    return loss_ref, abs(loss - loss_ref) / abs(loss_ref), grad_err


def _train(exe, opt, state, batches):
    """Compile ``exe.train_step`` ahead of time, then take one step per
    batch.  Returns (state, losses, compile seconds, ms per step)."""
    t0 = time.perf_counter()
    step = exe.train_step(opt).lower(state, batches[0]).compile()
    compile_s = time.perf_counter() - t0
    losses = []
    t0 = time.perf_counter()
    for batch in batches:
        state, metrics = step(state, batch)
        losses.append(metrics["loss"])
    jax.block_until_ready((state, losses))
    ms = 1e3 * (time.perf_counter() - t0) / len(batches)
    return state, [float(v) for v in losses], compile_s, ms


def _check_training(name: str, losses: list[float]) -> None:
    _require(all(math.isfinite(v) for v in losses),
             f"{name}: non-finite loss in {losses}")
    _require(losses[-1] < losses[0],
             f"{name}: loss did not fall: {losses[0]} -> {losses[-1]}")


def _check_reference(name: str, loss_err: float, grad_err: float) -> None:
    _require(loss_err <= LOSS_RTOL,
             f"{name}: step-0 loss off the reference by {loss_err} "
             f"(relative, limit {LOSS_RTOL})")
    _require(grad_err <= GRAD_TOL,
             f"{name}: step-0 grads off the reference by {grad_err} "
             f"(limit {GRAD_TOL})")


def _check_placement(name: str, params, n: int) -> None:
    """Sharded residency: every stacked leaf is split one chunk per
    device, so no device holds another's chunks."""
    for leaf in jax.tree.leaves(params):
        shards = leaf.addressable_shards
        devices = {s.device for s in shards}
        _require(len(devices) == n and all(s.data.shape[0] == 1
                                           for s in shards),
                 f"{name}: stacked leaf {leaf.shape} sits on "
                 f"{len(devices)} devices, not one chunk on each of {n}")


def one_chip(devices) -> None:
    mesh = Mesh(np.asarray(devices[:1]), ("cores",))
    for name in WORKLOADS:
        t0 = time.perf_counter()
        exe = _compile(name, mesh, "sharded")
        plan_s = time.perf_counter() - t0
        print(f"{name} {NN_BENCHMARKS[name]} batch {BATCH}: kernel mode "
              f"{exe.kernel_mode}")
        opt = adam(LR)
        state = exe.init_state(jax.random.PRNGKey(SEED), opt)
        batches = _batches(mesh, NN_BENCHMARKS[name][0])
        loss_ref, loss_err, grad_err = _reference_errors(
            exe, state["params"], batches[0])
        print(f"  step 0 vs jnp reference: loss {loss_ref!r}, relative "
              f"loss diff {loss_err!r}, grad diff {grad_err!r}")
        _check_reference(name, loss_err, grad_err)
        state, losses, compile_s, ms = _train(exe, opt, state, batches)
        print(f"  loss {losses[0]!r} -> {losses[-1]!r} over {STEPS} steps: "
              f"{losses}")
        print(f"  plan+analyze {plan_s!r} s, step compile {compile_s!r} s, "
              f"{ms!r} ms/step (smoke timing, not a benchmark)")
        _check_training(name, losses)


def four_chips(devices) -> None:
    n = 4
    mesh = Mesh(np.asarray(devices[:n]), ("cores",))
    for name in WORKLOADS:
        t0 = time.perf_counter()
        sharded = _compile(name, mesh, "sharded")
        oracle = _compile(name, mesh, "replicated")
        plan_s = time.perf_counter() - t0
        degrees = [len(r.devices) for r in sharded.program.runs(phase="fp")]
        print(f"{name} {NN_BENCHMARKS[name]} batch {BATCH} on {n} chips: "
              f"kernel mode {sharded.kernel_mode}, FP degrees {degrees}")
        opt = adam(LR)
        key = jax.random.PRNGKey(SEED)
        state_s = sharded.init_state(key, opt)
        state_r = oracle.init_state(key, opt)
        _check_placement(name, state_s["params"], n)
        batches = _batches(mesh, NN_BENCHMARKS[name][0])
        loss_ref, loss_err, grad_err = _reference_errors(
            sharded, state_s["params"], batches[0])
        print(f"  step 0 vs jnp reference: loss {loss_ref!r}, relative "
              f"loss diff {loss_err!r}, grad diff {grad_err!r}")
        _check_reference(name, loss_err, grad_err)
        state_s, losses_s, cs_s, ms_s = _train(sharded, opt, state_s,
                                               batches)
        state_r, losses_r, cs_r, ms_r = _train(oracle, opt, state_r,
                                               batches)
        diff = max(abs(a - b) for a, b in zip(losses_s, losses_r))
        print(f"  sharded loss {losses_s[0]!r} -> {losses_s[-1]!r}; largest "
              f"|sharded - replicated| loss diff over {STEPS} steps "
              f"{diff!r}")
        print(f"  plan+analyze {plan_s!r} s; step compile sharded "
              f"{cs_s!r} s, replicated {cs_r!r} s; {ms_s!r} / {ms_r!r} "
              f"ms/step sharded / replicated (smoke timing, not a "
              f"benchmark)")
        _check_training(name, losses_s)
        _require(diff <= LOSS_RTOL * abs(losses_r[0]),
                 f"{name}: sharded residency off the replicated oracle by "
                 f"{diff} in loss")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: the default phase; 4: only the period program "
                         "across four chips and its oracles")
    args = ap.parse_args()
    devices = _tpu_devices(args.chips)
    print(f"compile cache: {enable_compile_cache()}")
    if args.chips == 4:
        four_chips(devices)
    else:
        one_chip(devices)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
