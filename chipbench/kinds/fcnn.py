"""The paper's fully connected nets, trained the way users call them:

    exe = repro.exec.compile(FCNNWorkload(sizes, batch), onoc_config(),
                             Mesh(devices[:chips], ("cores",)),
                             strategy=..., residency=...)
    step = exe.train_step(adam(lr))
    for batch in Batcher(dataset, batch_size=batch, mesh=exe.mesh):
        state, metrics = step(state, batch)

A sample is one row of the Gaussian-mixture dataset (``chipbench.traffic``).
The configuration gives ``layer_sizes``, precision, optimizer, mapping and
residency; the traffic its ``batch``; the cell the ``fp_degrees`` the plan
must come out with.  The leaves are named ``w1, b1, w2, ...`` as the
reference (``references/fcnn.py``) names them.
"""

from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

import repro.exec as rexec
from chipbench import checks, spec, traffic, work
from repro.configs.nn_benchmarks import onoc_config
from repro.core.onoc_model import FCNNWorkload
from repro.data import Batcher
from repro.optim import adam


def sizes(cell) -> list[int]:
    return list(cell.config["layer_sizes"])


def batch(cell) -> int:
    return int(cell.traffic["batch"])


# the executable and the optimizer its state was made for
Program = collections.namedtuple("Program", "exe opt")


def dataset(cell, seed: int):
    """(x, y), the whole dataset on the host."""
    return traffic.dataset(seed, cell.traffic, sizes(cell)[0])


def build(cell, devices: list, kernel_mode: str):
    """(program, step) for the cell, checked against what its
    configuration and its cell state."""
    conf = cell.config
    if conf["dtype"] != "float32" or conf["matmul_precision"] != "default":
        raise ValueError(f"{cell.config_name}: the harness runs float32 at "
                         f"default matmul precision")
    exe = rexec.compile(FCNNWorkload(sizes(cell), batch_size=batch(cell)),
                        onoc_config(), Mesh(np.asarray(devices), ("cores",)),
                        strategy=conf["strategy"], residency=conf["residency"])
    if exe.kernel_mode != kernel_mode:
        raise SystemExit(f"chipbench: dispatch chose kernel mode "
                         f"{exe.kernel_mode!r}, not {kernel_mode!r}")
    degrees = [len(r.devices) for r in exe.program.runs(phase="fp")]
    if degrees != cell.params["fp_degrees"]:
        raise SystemExit(f"chipbench: planned FP degrees {degrees}, the cell "
                         f"states {cell.params['fp_degrees']}")
    print(f"{cell.name}: {sizes(cell)} batch {batch(cell)}, kernel mode "
          f"{exe.kernel_mode}, FP degrees {degrees}", flush=True)
    o = conf["optimizer"]
    opt = adam(o["lr"], b1=o["b1"], b2=o["b2"], eps=o["eps"])
    return Program(exe, opt), exe.train_step(opt)


def init_state(cell, program: Program, seed: int):
    return program.exe.init_state(traffic.weights_key(seed), program.opt)


def feed(cell, program: Program, data):
    x, y = data
    return Batcher({"x": x, "y": y}, batch_size=batch(cell),
                   mesh=program.exe.mesh)


def _flat(params: dict) -> list:
    return [layer[kind] for layer in params["layers"] for kind in ("w", "b")]


@jax.jit
def _norms(leaves, scale):
    return jnp.stack([jnp.linalg.norm(a.astype(jnp.float32).ravel())
                      for a in leaves]) * scale


@jax.jit
def _change_norms(new, old):
    return jnp.stack([jnp.linalg.norm((a - b).astype(jnp.float32).ravel())
                      for a, b in zip(new, old)])


_copy = jax.jit(lambda leaves: [jnp.copy(a) for a in leaves])


def _named(cell, params: dict, values) -> dict[str, float]:
    names = spec.load_reference(cell).leaf_names(len(params["layers"]))
    return dict(zip(names, (float(v) for v in jax.device_get(values))))


def first_steps(cell, step, state, batcher):
    """Take the first CHECK_STEPS steps through the window's own step and
    feed; return (state, readings).  The program's params are in its
    stacked chunk layout, whose off-window chunks are zeros, so a leaf's
    norm is the full matrix's."""
    b1 = cell.config["optimizer"]["b1"]
    p0 = _copy(_flat(state["params"]))
    losses, grad_norms = [], None
    for t in range(checks.CHECK_STEPS):
        state, metrics = step(state, next(batcher))
        losses.append(metrics["loss"])
        if t == 0:
            # Adam's first moment after one step is (1 - b1) * g; read it
            # before the next step takes the state.
            grad_norms = _named(cell, state["params"], _norms(
                _flat(state["opt"]["m"]), jnp.float32(1.0 / (1.0 - b1))))
    update_norms = _named(cell, state["params"],
                          _change_norms(_flat(state["params"]), p0))
    del p0
    readings = checks.Readings(
        tuple(float(v) for v in jax.device_get(losses)), grad_norms,
        update_norms)
    return state, readings


def reference(cell, seed: int, data, device, planted: str | None = None):
    """The plain reference over the first steps' rows; ``planted`` puts
    the bf16 control (``"control"``) or a fault of the reference's in the
    program's place."""
    x, y = data
    kw = ({"dtype": "bfloat16"} if planted == "control"
          else {"fault": planted})
    return spec.load_reference(cell).train(
        traffic.weights_key(seed), sizes(cell),
        traffic.step_rows(x, y, batch(cell), checks.CHECK_STEPS),
        cell.config["optimizer"], device=device, **kw)


def samples_per_step(cell) -> int:
    return batch(cell)


def flops_per_sample(cell) -> int:
    return work.model_flops_per_sample(sizes(cell))


def kernel_calls(cell) -> list[work.Call]:
    """The kernel calls of one step at the cell's planned degrees (which
    ``build`` holds the plan to)."""
    return work.step_calls(sizes(cell), batch(cell),
                           cell.params["fp_degrees"])
