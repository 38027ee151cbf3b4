"""Fused FCNN period kernels: forward act(x @ w + b) plus the matching
backward (dgrad / wgrad) passes, MXU-aligned and VMEM-tiled.

This is the paper's per-period hot loop (Eq. 1) and its BP transpose
(Eqs. 2-3).  On the ONoC each core computes X_i neurons over the batch; on
TPU one chip computes its neuron shard as a blocked GEMM.  Fusing the
element-wise work next to the GEMM removes HBM round-trips of (M, N)
tensors — with batch 128 and n_i = 4000 (NN5/NN6) that's 2 MB per period
per chip per tensor saved at ~819 GB/s:

  * forward  — bias add + activation fused into the x @ w epilogue;
  * dgrad    — dZ = dY ⊙ A'(Y) fused into the dZ @ Wᵀ prologue, so the
               pre-activation gradient never exists in HBM;
  * wgrad    — dW = Xᵀ @ dZ and the db column-reduce in one pass, with the
               same fused dZ recompute (an element-wise flop traded for an
               (M, N) HBM read+write, the flash-attention discipline).

All activation derivatives are expressed in terms of the *output* Y, so the
backward needs only (x, w, y) as tensor residuals — no pre-activation Z is
ever saved (the (N,) bias also rides along, solely to dtype the db
cotangent):

  sigmoid': y (1 - y)     relu': 1[y > 0]     tanh': 1 - y²     none: 1

Blocking: grids put the contraction dimension innermost (sequential on
TPU) with an fp32 accumulator in VMEM scratch.  Block sizes are chosen
automatically (``_select_block``): sublane-unit 8 for M, lane-unit 128 for
K/N, minimizing edge padding.  Non-aligned shapes — the paper's 784/10/…
NN benchmark dims — are zero-padded to block multiples and the result is
sliced back; zero padding is exact for all three passes (padded rows /
columns contribute 0 to every contraction and are discarded on output).

Every block is rank 2.  The (N,) bias and db vectors travel as (1, N)
rows in (1, bn) blocks: Mosaic tiles a rank-1 operand by its full length,
so a rank-1 (bn,) block of a longer vector is refused on the chip
whenever the padded width spans more than one block.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro import obs
from repro.kernels.ref import act_deriv_from_output

__all__ = [
    "fcnn_layer",
    "fcnn_layer_dgrad",
    "fcnn_layer_wgrad",
    "select_blocks",
]

_ACTS = {
    "sigmoid": jax.nn.sigmoid,
    "relu": jax.nn.relu,
    "tanh": jnp.tanh,
    "none": lambda z: z,
}

# Default preferred block sizes (MXU-aligned); the contraction block is
# larger to amortize accumulator revisits.
_DEFAULT_BLOCK_M = 128
_DEFAULT_BLOCK_N = 128
_DEFAULT_BLOCK_K = 512

_SUBLANE = 8    # fp32 sublane unit (second-to-last dim)
_LANE = 128     # lane unit (last dim)


def _round_up(v: int, unit: int) -> int:
    return -(-v // unit) * unit


def _select_block(dim: int, preferred: int | None, default: int,
                  unit: int) -> tuple[int, int]:
    """Pick (block, padded_dim) for one dimension.

    The block is a multiple of ``unit``, at most the preferred size (clamped
    to the dim rounded up to ``unit``), chosen to minimize edge padding —
    ties go to the largest block (fewer grid steps).
    """
    pref = preferred if preferred is not None else default
    pref = min(_round_up(max(pref, unit), unit), _round_up(dim, unit))
    best_b, best_pad = pref, _round_up(dim, pref)
    b = pref - unit
    while b >= unit:
        pad = _round_up(dim, b)
        if pad < best_pad:
            best_b, best_pad = b, pad
        b -= unit
    return best_b, best_pad


def select_blocks(
    m: int, k: int, n: int,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
    """((bm, bn, bk), (m_pad, n_pad, k_pad)) for an (M, K) x (K, N) problem."""
    bm, m_pad = _select_block(m, block_m, _DEFAULT_BLOCK_M, _SUBLANE)
    bn, n_pad = _select_block(n, block_n, _DEFAULT_BLOCK_N, _LANE)
    bk, k_pad = _select_block(k, block_k, _DEFAULT_BLOCK_K, _LANE)
    return (bm, bn, bk), (m_pad, n_pad, k_pad)


def _pad2(x: jax.Array, rows: int, cols: int) -> jax.Array:
    r, c = x.shape
    if r == rows and c == cols:
        return x
    with jax.named_scope(obs.PAD):
        return jnp.pad(x, ((0, rows - r), (0, cols - c)))


# ---------------------------------------------------------------- forward


def _fwd_kernel(x_ref, w_ref, b_ref, o_ref, acc_ref, *, k_steps: int,
                act: str):
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[...], preferred_element_type=jnp.float32
    )

    @pl.when(pl.program_id(2) == k_steps - 1)
    def _finish():
        z = acc_ref[...] + b_ref[...].astype(jnp.float32)
        o_ref[...] = _ACTS[act](z).astype(o_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_m", "block_n", "block_k",
                     "interpret"),
)
def fcnn_layer(
    x: jax.Array,
    w: jax.Array,
    b: jax.Array,
    activation: str = "sigmoid",
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """act(x @ w + b).  x: (M, K); w: (K, N); b: (N,)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2 and b.shape == (n,)
    if activation not in _ACTS:
        raise ValueError(f"unknown activation {activation!r}")
    (bm, bn, bk), (mp, np_, kp) = select_blocks(
        m, k, n, block_m, block_n, block_k)
    xp, wp, bp = _pad2(x, mp, kp), _pad2(w, kp, np_), _pad2(b.reshape(1, n), 1, np_)
    grid = (mp // bm, np_ // bn, kp // bk)
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, k_steps=grid[2], act=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((1, bn), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), x.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        interpret=interpret,
        name="fcnn_layer",
    )(xp, wp, bp)
    with jax.named_scope(obs.PAD):
        return out[:m, :n]


# ------------------------------------------------------------------ dgrad


def _dgrad_kernel(dy_ref, y_ref, w_ref, dx_ref, acc_ref, *, n_steps: int,
                  act: str):
    """dX block += (dY ⊙ A'(Y)) @ Wᵀ — activation derivative fused into the
    GEMM prologue so dZ never touches HBM."""
    @pl.when(pl.program_id(2) == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    y = y_ref[...].astype(jnp.float32)
    dz = dy_ref[...].astype(jnp.float32) * act_deriv_from_output(y, act)
    # (bm, bn) x (bk, bn) contracted on bn -> (bm, bk)   (== dz @ w_blk.T)
    acc_ref[...] += jax.lax.dot_general(
        dz, w_ref[...],
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(pl.program_id(2) == n_steps - 1)
    def _finish():
        dx_ref[...] = acc_ref[...].astype(dx_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_m", "block_n", "block_k",
                     "interpret"),
)
def fcnn_layer_dgrad(
    dy: jax.Array,
    y: jax.Array,
    w: jax.Array,
    activation: str = "sigmoid",
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> jax.Array:
    """dX = (dY ⊙ A'(Y)) @ Wᵀ.  dy, y: (M, N); w: (K, N); returns (M, K)."""
    m, n = dy.shape
    k, n2 = w.shape
    assert y.shape == (m, n) and n == n2
    (bm, bn, bk), (mp, np_, kp) = select_blocks(
        m, k, n, block_m, block_n, block_k)
    dyp, yp, wp = _pad2(dy, mp, np_), _pad2(y, mp, np_), _pad2(w, kp, np_)
    grid = (mp // bm, kp // bk, np_ // bn)   # N innermost: accumulate
    out = pl.pallas_call(
        functools.partial(_dgrad_kernel, n_steps=grid[2], act=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bn), lambda i, j, nn: (i, nn)),
            pl.BlockSpec((bm, bn), lambda i, j, nn: (i, nn)),
            pl.BlockSpec((bk, bn), lambda i, j, nn: (j, nn)),
        ],
        out_specs=pl.BlockSpec((bm, bk), lambda i, j, nn: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, kp), dy.dtype),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        interpret=interpret,
        name="fcnn_layer_dgrad",
    )(dyp, yp, wp)
    with jax.named_scope(obs.PAD):
        return out[:m, :k]


# ------------------------------------------------------------------ wgrad


def _wgrad_kernel(x_ref, dy_ref, y_ref, dw_ref, db_ref, accw_ref, accb_ref,
                  *, m_steps: int, act: str):
    """dW block += Xᵀ @ (dY ⊙ A'(Y));  db block += column-reduce of dZ.

    Grid is (N, K, M) with M innermost.  The db output block depends only
    on the N index, so its VMEM buffer persists across the whole (K, M)
    inner sweep — db work is done only on the K==0 slice to avoid double
    counting, and the buffer is flushed once when N advances.
    """
    j_k = pl.program_id(1)
    j_m = pl.program_id(2)

    @pl.when(j_m == 0)
    def _init_w():
        accw_ref[...] = jnp.zeros_like(accw_ref)

    @pl.when((j_m == 0) & (j_k == 0))
    def _init_b():
        accb_ref[...] = jnp.zeros_like(accb_ref)

    y = y_ref[...].astype(jnp.float32)
    dz = dy_ref[...].astype(jnp.float32) * act_deriv_from_output(y, act)
    # (bm, bk) x (bm, bn) contracted on bm -> (bk, bn)   (== x_blk.T @ dz)
    accw_ref[...] += jax.lax.dot_general(
        x_ref[...], dz,
        dimension_numbers=(((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )

    @pl.when(j_k == 0)
    def _acc_b():
        accb_ref[...] += jnp.sum(dz, axis=0, keepdims=True)

    @pl.when(j_m == m_steps - 1)
    def _finish_w():
        dw_ref[...] = accw_ref[...].astype(dw_ref.dtype)

    @pl.when((j_m == m_steps - 1) & (j_k == 0))
    def _finish_b():
        db_ref[...] = accb_ref[...].astype(db_ref.dtype)


@functools.partial(
    jax.jit,
    static_argnames=("activation", "block_m", "block_n", "block_k",
                     "interpret"),
)
def fcnn_layer_wgrad(
    x: jax.Array,
    dy: jax.Array,
    y: jax.Array,
    activation: str = "sigmoid",
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """(dW, db) = (Xᵀ @ dZ, Σ_rows dZ) with dZ = dY ⊙ A'(Y) recomputed
    in-kernel.  x: (M, K); dy, y: (M, N); returns ((K, N), (N,))."""
    m, k = x.shape
    m2, n = dy.shape
    assert m == m2 and y.shape == (m, n)
    (bm, bn, bk), (mp, np_, kp) = select_blocks(
        m, k, n, block_m, block_n, block_k)
    xp, dyp, yp = _pad2(x, mp, kp), _pad2(dy, mp, np_), _pad2(y, mp, np_)
    grid = (np_ // bn, kp // bk, mp // bm)   # M innermost: accumulate
    dw, db = pl.pallas_call(
        functools.partial(_wgrad_kernel, m_steps=grid[2], act=activation),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda jn, jk, jm: (jm, jk)),
            pl.BlockSpec((bm, bn), lambda jn, jk, jm: (jm, jn)),
            pl.BlockSpec((bm, bn), lambda jn, jk, jm: (jm, jn)),
        ],
        out_specs=[
            pl.BlockSpec((bk, bn), lambda jn, jk, jm: (jk, jn)),
            pl.BlockSpec((1, bn), lambda jn, jk, jm: (0, jn)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((kp, np_), x.dtype),
            jax.ShapeDtypeStruct((1, np_), dy.dtype),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        interpret=interpret,
        name="fcnn_layer_wgrad",
    )(xp, dyp, yp)
    with jax.named_scope(obs.PAD):
        return dw[:k, :n], db[0, :n]
