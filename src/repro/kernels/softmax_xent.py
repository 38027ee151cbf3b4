"""Fused softmax + cross-entropy kernels for the FCNN output period.

The paper's output layer (§5.1) is softmax + cross-entropy over n_l = 10
classes.  Unfused, the loss round-trips the full (B, n_l) logits tensor
through HBM three times (logits read for log-softmax, log-probs written,
log-probs read again for the NLL gather — and the same again for dlogits
in the backward).  These kernels keep everything per-row in VMEM:

  * forward  — one streaming sweep over class tiles per row block,
               carrying the running max m and rescaled exp-sum l in VMEM
               scratch (the flash-attention online-softmax recurrence),
               plus the picked target logit t; the final tile emits
               nll = (m + log l) − t and the log-sum-exp per row.  Neither
               probabilities nor log-probs ever exist in HBM — only the
               two (B,) vectors (nll, lse) come back.
  * backward — dlogits = (softmax − onehot) · scale computed directly from
               the saved (B,) lse residual: p = exp(x − lse), one read of
               the logits and one write of dlogits, nothing else.

Blocking/padding follows the fcnn_layer rules exactly (shared helpers):
blocks auto-selected with sublane unit 8 for the batch dim and lane unit
128 for the class dim, minimizing edge padding; non-aligned shapes — the
paper's n_l = 10 output layers, batch 1 eval rows — are zero-padded to
block multiples and sliced back, so callers never pad.  Padded class
columns are masked to −1e30 inside the forward kernel (a zero-padded
column would otherwise contribute exp(0) to every row's denominator);
padded rows compute garbage that is sliced away.

Per-row vectors (labels, nll, lse, scale and the three carries) travel as
(B, 1) columns in (bb, 1) blocks.  Mosaic refuses a rank-1 (bb,) block
unless it spans the whole vector, so rank-1 blocks would compile only
while one row block is the whole batch.

VMEM per step: one (bb, bc) logits tile + three (bb, 1) fp32 carries,
each padded to a (bb, 128) lane tile — for bb=128, bc=512 that is
~450 KB, far inside a v5e core's ~16 MB.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.fcnn_layer import (
    _LANE,
    _SUBLANE,
    _pad2,
    _select_block,
)

__all__ = ["softmax_xent_fwd", "softmax_xent_dlogits", "select_blocks_xent"]

# Preferred blocks for a (B, C) problem: batch rows on the sublane axis,
# class columns on the lane axis (larger, to amortize the carry revisits).
_DEFAULT_BLOCK_B = 128
_DEFAULT_BLOCK_C = 512

_NEG_INF = -1e30


def _col(v: jax.Array, size: int) -> jax.Array:
    """A (B,) per-row vector as a zero-padded (size, 1) column."""
    return _pad2(v.reshape(-1, 1), size, 1)


def select_blocks_xent(
    b: int, c: int,
    block_b: int | None = None,
    block_c: int | None = None,
) -> tuple[tuple[int, int], tuple[int, int]]:
    """((bb, bc), (b_pad, c_pad)) for a (B, C) logits tensor — same
    minimize-edge-padding rule as ``fcnn_layer.select_blocks``."""
    bb, b_pad = _select_block(b, block_b, _DEFAULT_BLOCK_B, _SUBLANE)
    bc, c_pad = _select_block(c, block_c, _DEFAULT_BLOCK_C, _LANE)
    return (bb, bc), (b_pad, c_pad)


# ---------------------------------------------------------------- forward


def _fwd_kernel(x_ref, lab_ref, nll_ref, lse_ref, m_ref, l_ref, t_ref,
                *, c_steps: int, n_classes: int):
    """Online softmax over class tiles: carry (m, l, t) per row in VMEM."""
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        t_ref[...] = jnp.zeros_like(t_ref)

    x = x_ref[...].astype(jnp.float32)
    bc = x.shape[1]
    cols = j * bc + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    # padded class columns must not feed the max/denominator
    x = jnp.where(cols < n_classes, x, _NEG_INF)

    m_prev = m_ref[...]                                   # (bb, 1)
    m_new = jnp.maximum(m_prev, jnp.max(x, axis=-1, keepdims=True))
    alpha = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(jnp.exp(x - m_new), axis=-1,
                                              keepdims=True)
    m_ref[...] = m_new
    # the label's logit lives in exactly one tile per row
    t_ref[...] += jnp.sum(jnp.where(cols == lab_ref[...], x, 0.0), axis=-1,
                          keepdims=True)

    @pl.when(j == c_steps - 1)
    def _finish():
        lse = m_ref[...] + jnp.log(l_ref[...])
        lse_ref[...] = lse
        nll_ref[...] = lse - t_ref[...]


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_c", "interpret"))
def softmax_xent_fwd(
    logits: jax.Array,
    labels: jax.Array,
    block_b: int | None = None,
    block_c: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """Per-row cross-entropy.  logits: (B, C); labels: (B,) int.

    Returns (nll, lse), both (B,) fp32: nll[r] = lse[r] − logits[r, y_r]
    with lse the log-sum-exp — the only residual the backward needs.
    """
    b, c = logits.shape
    assert labels.shape == (b,)
    (bb, bc), (bp, cp) = select_blocks_xent(b, c, block_b, block_c)
    xp = _pad2(logits, bp, cp)
    labp = _col(labels, bp)
    grid = (bp // bb, cp // bc)   # class tiles innermost: sequential carry
    nll, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, c_steps=grid[1], n_classes=c),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=[
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
            jax.ShapeDtypeStruct((bp, 1), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.float32),
            pltpu.VMEM((bb, 1), jnp.float32),
        ],
        interpret=interpret,
        name="softmax_xent_fwd",
    )(xp, labp)
    with jax.named_scope(obs.PAD):
        return nll[:b, 0], lse[:b, 0]


# --------------------------------------------------------------- backward


def _bwd_kernel(x_ref, lab_ref, lse_ref, scale_ref, dx_ref):
    """dX tile = (exp(x − lse) − onehot) · scale — softmax recomputed from
    the (B,) lse residual, so probabilities never existed in HBM."""
    j = pl.program_id(1)
    x = x_ref[...].astype(jnp.float32)
    bc = x.shape[1]
    cols = j * bc + jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    p = jnp.exp(x - lse_ref[...])
    onehot = (cols == lab_ref[...]).astype(jnp.float32)
    dx_ref[...] = ((p - onehot) * scale_ref[...]).astype(dx_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("block_b", "block_c", "interpret"))
def softmax_xent_dlogits(
    logits: jax.Array,
    labels: jax.Array,
    lse: jax.Array,
    scale: jax.Array,
    block_b: int | None = None,
    block_c: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """dlogits = (softmax(logits) − onehot(labels)) · scale[:, None].

    logits: (B, C); labels, lse, scale: (B,).  ``scale`` carries the loss
    cotangent divided by the batch size (mean reduction), so the kernel
    writes the finished gradient in one pass.
    """
    b, c = logits.shape
    assert labels.shape == (b,) and lse.shape == (b,) and scale.shape == (b,)
    (bb, bc), (bp, cp) = select_blocks_xent(b, c, block_b, block_c)
    xp = _pad2(logits, bp, cp)
    labp, lsep, scalep = (_col(v, bp) for v in (labels, lse, scale))
    grid = (bp // bb, cp // bc)   # independent tiles, no carry
    out = pl.pallas_call(
        _bwd_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((bb, bc), lambda i, j: (i, j)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
            pl.BlockSpec((bb, 1), lambda i, j: (i, 0)),
        ],
        out_specs=pl.BlockSpec((bb, bc), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, cp), logits.dtype),
        interpret=interpret,
        name="softmax_xent_dlogits",
    )(xp, labp, lsep, scalep)
    with jax.named_scope(obs.PAD):
        return out[:b, :c]
