"""Plain reference of the paper's FCNN training step, in ``jax.numpy``.

Layer i computes sigmoid(h @ W_i + b_i), the last layer no activation; the
loss is the batch mean of softmax cross-entropy; Adam (bias-corrected,
moments in float32) updates every parameter.  Weights are drawn the way
the configuration states: W_i ~ N(0, 1) / sqrt(n_{i-1}) from
``split(key, l)[i - 1]``, b_i = 0.  No kernel, sharding, padding or
period schedule; it imports nothing of the program.

``train`` runs in ``"float32"`` with every matmul at HIGHEST precision,
the reference, or in ``"bfloat16"``: parameters and activations stored in
bf16, moments and the update in float32, which is the control one
precision below the configuration's.  ``fault`` plants a fault in the
reference put in the program's place: ``"half_batch"`` (the loss is the
mean over the first half of the batch) or ``"altered_answer"`` (the first
row's label logit is lowered by 10 where it is produced).
"""

from __future__ import annotations

import functools
import math
from typing import Any, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.checks import Readings

FAULTS = ("half_batch", "altered_answer")
# A garbage value where the output layer produces the first row's label
# logit: it stands 10 below what the layer computed.
ALTERATION = 10.0


def leaf_names(n_layers: int) -> list[str]:
    """``w1, b1, w2, ...``: the names both sides give the leaves."""
    return [f"{kind}{i}" for i in range(1, n_layers + 1) for kind in "wb"]


def init(key, sizes: Sequence[int]) -> list[tuple[jax.Array, jax.Array]]:
    keys = jax.random.split(key, len(sizes) - 1)
    return [(jax.random.normal(k, (a, b), jnp.float32) / math.sqrt(a),
             jnp.zeros((b,), jnp.float32))
            for k, a, b in zip(keys, sizes[:-1], sizes[1:])]


def loss(params, x, y, dtype, fault: str | None = None) -> jax.Array:
    precision = (jax.lax.Precision.HIGHEST if dtype == jnp.float32
                 else jax.lax.Precision.DEFAULT)
    h = x.astype(dtype)
    for i, (w, b) in enumerate(params):
        z = jnp.dot(h, w, precision=precision,
                    preferred_element_type=jnp.float32) + b
        h = (jax.nn.sigmoid(z) if i < len(params) - 1 else z).astype(dtype)
    z = h.astype(jnp.float32)
    if fault == "altered_answer":
        z = z.at[0, y[0]].add(-ALTERATION)
    if fault == "half_batch":
        z, y = z[:z.shape[0] // 2], y[:y.shape[0] // 2]
    picked = jnp.take_along_axis(z, y[:, None], axis=1)[:, 0]
    return jnp.mean(jax.nn.logsumexp(z, axis=1) - picked)


def _leaf_norms(tree) -> jax.Array:
    return jnp.stack([jnp.linalg.norm(a.astype(jnp.float32).ravel())
                      for layer in tree for a in layer])


@functools.partial(jax.jit,
                   static_argnames=("sizes", "dtype", "fault", "opt"))
def _train(key, xs, ys, *, sizes, dtype, fault, opt):
    lr, b1, b2, eps = opt
    dt = jnp.dtype(dtype)
    p0 = init(key, sizes)
    params = [(w.astype(dt), b.astype(dt)) for w, b in p0]
    zeros = [(jnp.zeros_like(w), jnp.zeros_like(b)) for w, b in p0]

    def step(carry, batch):
        params, m, v = carry
        x, y, t = batch
        value, grads = jax.value_and_grad(loss)(params, x, y, dt, fault)
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g.astype(
            jnp.float32), m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * jnp.square(
            g.astype(jnp.float32)), v, grads)
        mc, vc = 1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)
        params = jax.tree.map(
            lambda p, m_, v_: (p.astype(jnp.float32)
                               - lr * (m_ * mc) / (jnp.sqrt(v_ * vc) + eps)
                               ).astype(p.dtype), params, m, v)
        return (params, m, v), (value, _leaf_norms(grads))

    steps = jnp.arange(1, xs.shape[0] + 1, dtype=jnp.float32)
    (params, _, _), (losses, grad_norms) = jax.lax.scan(
        step, (params, zeros, zeros), (xs, ys, steps))
    change = [(w.astype(jnp.float32) - w0, b.astype(jnp.float32) - b0)
              for (w, b), (w0, b0) in zip(params, p0)]
    return losses, grad_norms[0], _leaf_norms(change)


def train(key, sizes: Sequence[int], rows, optimizer: dict[str, Any],
          dtype: str = "float32", fault: str | None = None,
          device=None) -> Readings:
    """Follow ``len(rows)`` Adam steps over ``rows`` = [(x, y), ...], all
    in one compiled program."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    opt = (float(optimizer["lr"]), float(optimizer["b1"]),
           float(optimizer["b2"]), float(optimizer["eps"]))
    device = device or jax.devices()[0]
    xs = jax.device_put(np.stack([np.asarray(x) for x, _ in rows]), device)
    ys = jax.device_put(np.stack([np.asarray(y) for _, y in rows]), device)
    key = jax.device_put(key, device)
    losses, first, change = jax.device_get(_train(
        key, xs, ys, sizes=tuple(sizes), dtype=jnp.dtype(dtype).name,
        fault=fault, opt=opt))
    names = leaf_names(len(sizes) - 1)
    return Readings(tuple(float(v) for v in losses),
                    dict(zip(names, map(float, first))),
                    dict(zip(names, map(float, change))))
