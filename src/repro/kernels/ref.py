"""Pure-jnp oracles for every Pallas kernel in this package.

These are the ground truth the kernels are validated against
(tests/test_kernels.py sweeps shapes/dtypes and asserts allclose), and the
CPU execution path of ops.py.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

__all__ = [
    "act_deriv_from_output",
    "fcnn_layer_ref",
    "fcnn_layer_dgrad_ref",
    "fcnn_layer_wgrad_ref",
    "softmax_xent_ref",
    "softmax_xent_dlogits_ref",
    "flash_attention_ref",
    "flash_attention_lse_ref",
    "ssd_chunk_ref",
]


def fcnn_layer_ref(x: jax.Array, w: jax.Array, b: jax.Array,
                   activation: str = "sigmoid") -> jax.Array:
    """One FCNN period: act(x @ w + b).  x: (M, K), w: (K, N), b: (N,)."""
    z = jnp.dot(x, w, preferred_element_type=jnp.float32) + b.astype(jnp.float32)
    if activation == "sigmoid":
        z = jax.nn.sigmoid(z)
    elif activation == "relu":
        z = jax.nn.relu(z)
    elif activation == "tanh":
        z = jnp.tanh(z)
    elif activation == "none":
        pass
    else:
        raise ValueError(f"unknown activation {activation!r}")
    return z.astype(x.dtype)


def act_deriv_from_output(y: jax.Array, activation: str) -> jax.Array:
    """A'(z) expressed via the activation OUTPUT y (fp32 in, fp32 out).

    Shared by the oracles below AND the fused Pallas dgrad/wgrad kernels
    (pure jnp, so it traces inside a kernel body) — one table, so a new
    activation cannot silently diverge between kernel and ground truth.
    """
    if activation == "sigmoid":
        return y * (1.0 - y)
    if activation == "relu":
        return (y > 0).astype(jnp.float32)
    if activation == "tanh":
        return 1.0 - y * y
    if activation == "none":
        return jnp.ones_like(y)
    raise ValueError(f"unknown activation {activation!r}")


def _dz(dy: jax.Array, y: jax.Array, activation: str) -> jax.Array:
    return dy.astype(jnp.float32) * act_deriv_from_output(
        y.astype(jnp.float32), activation)


def fcnn_layer_dgrad_ref(dy: jax.Array, y: jax.Array, w: jax.Array,
                         activation: str = "sigmoid") -> jax.Array:
    """dX = (dY ⊙ A'(Y)) @ Wᵀ — oracle for the fused dgrad kernel."""
    dz = _dz(dy, y, activation)
    dx = jnp.dot(dz, w.astype(jnp.float32).T,
                 preferred_element_type=jnp.float32)
    return dx.astype(dy.dtype)


def fcnn_layer_wgrad_ref(x: jax.Array, dy: jax.Array, y: jax.Array,
                         activation: str = "sigmoid"):
    """(dW, db) = (Xᵀ @ dZ, Σ_rows dZ) — oracle for the fused wgrad kernel."""
    dz = _dz(dy, y, activation)
    dw = jnp.dot(x.astype(jnp.float32).T, dz,
                 preferred_element_type=jnp.float32)
    db = jnp.sum(dz, axis=0)
    return dw.astype(x.dtype), db.astype(dy.dtype)


def softmax_xent_ref(logits: jax.Array, labels: jax.Array) -> jax.Array:
    """Mean softmax cross-entropy (the paper's output period, §5.1).

    logits: (B, C); labels: (B,) int.  fp32 scalar — bit-identical to the
    pre-fusion jnp loss this kernel replaced in models/fcnn.loss_fn.
    """
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(nll)


def softmax_xent_dlogits_ref(logits: jax.Array, labels: jax.Array,
                             g: jax.Array) -> jax.Array:
    """dlogits = (softmax − onehot) · g/B — oracle for the fused backward
    of the mean cross-entropy (g is the scalar loss cotangent)."""
    p = jax.nn.softmax(logits.astype(jnp.float32), axis=-1)
    onehot = jax.nn.one_hot(labels, logits.shape[-1], dtype=jnp.float32)
    return ((p - onehot) * (g / logits.shape[0])).astype(logits.dtype)


def _attention_scores(q: jax.Array, k: jax.Array, causal: bool,
                      scale: float | None) -> jax.Array:
    """(B, H, Sq, Sk) f32 scores q·kᵀ·scale, masked to -inf above the
    diagonal where ``causal``; ``scale`` 1/√D where None."""
    d = q.shape[-1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        sq, sk = q.shape[2], k.shape[2]
        mask = jnp.tril(jnp.ones((sq, sk), dtype=bool), k=sk - sq)
        s = jnp.where(mask, s, -jnp.inf)
    return s


def flash_attention_ref(q: jax.Array, k: jax.Array, v: jax.Array,
                        causal: bool = True,
                        scale: float | None = None) -> jax.Array:
    """q, k: (B, H, S, D); v: (B, H, S, Dv) -> (B, H, S, Dv), softmax in
    fp32; ``scale`` 1/√D where None."""
    p = jax.nn.softmax(_attention_scores(q, k, causal, scale), axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return o.astype(q.dtype)


def flash_attention_lse_ref(q: jax.Array, k: jax.Array, causal: bool = True,
                            scale: float | None = None) -> jax.Array:
    """(B, H, S) f32 log-sum-exp of each row of the scores."""
    return jax.nn.logsumexp(_attention_scores(q, k, causal, scale), axis=-1)


def ssd_chunk_ref(x: jax.Array, dt_a: jax.Array, b: jax.Array, c: jax.Array):
    """Intra-chunk SSD for ONE chunk (the Pallas kernel's unit of work).

    x: (Q, H, P); dt_a: (Q, H); b, c: (Q, H, N) (groups pre-broadcast).
    Returns (y_diag (Q, H, P), chunk_state (H, P, N), decay_out (Q, H)):
      y_diag[t]    = sum_{s<=t} C_t·B_s exp(sum_{s<k<=t} dtA_k) x_s
      chunk_state  = sum_s exp(sum_{s<k<=Q} dtA_k) B_s x_s^T
      decay_out[t] = exp(sum_{k<=t} dtA_k)   (for the inter-chunk readout)
    """
    q = x.shape[0]
    a = dt_a.astype(jnp.float32)
    cs = jnp.cumsum(a, axis=0)                                 # (Q, H)
    seg = cs[:, None, :] - cs[None, :, :]                      # (Q, Q, H)
    mask = jnp.tril(jnp.ones((q, q), dtype=bool))
    lmat = jnp.where(mask[..., None], jnp.exp(seg), 0.0)       # (Q, Q, H)
    scores = jnp.einsum("thn,shn->tsh", c.astype(jnp.float32),
                        b.astype(jnp.float32))
    y = jnp.einsum("tsh,tsh,shp->thp", scores, lmat,
                   x.astype(jnp.float32))
    decay_state = jnp.exp(cs[-1][None, :] - cs)                # (Q, H)
    state = jnp.einsum("shn,sh,shp->hpn", b.astype(jnp.float32),
                       decay_state, x.astype(jnp.float32))
    decay_out = jnp.exp(cs)
    return y.astype(x.dtype), state, decay_out
