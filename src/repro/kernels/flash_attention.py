"""Blocked online-softmax (flash) attention for training: a forward that
also returns each row's log-sum-exp, and a backward of two kernels.

softmax(q·kᵀ·scale, causal) v over (B, H, S, ·) arrays, with q and k of
head dim D and v of head dim Dv (D ≠ Dv in latent attention), and the
scale as given.  Matmul operands stay in the inputs' dtype and accumulate
in f32; the running max, the exponentials, the sums and the lse are f32.
The (S, S) scores never leave VMEM.

* forward: grid (B·H, q blocks, kv blocks), kv innermost, the running
  max, denominator and output accumulator carried in VMEM scratch.
* dK/dV: grid (B·H, kv blocks, q blocks), q innermost.  It works on the
  transposed scores k·qᵀ, so the saved lse and the row correction
  delta = Σ o·dO enter as (1, bq) rows and no block is transposed.
* dQ: grid (B·H, q blocks, kv blocks), kv innermost, lse and delta as
  (bq, 1) columns.

delta is computed once, outside the kernels (``flash_attention_bwd``).

Causal blocks strictly above the diagonal are skipped twice over: the
kernel body does not run for them (``pl.when``), and the index maps clamp
the inner operand's block index onto the diagonal (``kv_block``,
``q_block``), so that a skipped step shares its block index with the
nearest step that runs and the pipeline copies in no block for it.  Only
the blocks on the diagonal build the causal mask.  At S = 4096 with
512-blocks, 36 of 64 blocks run.

Blocks (``select_blocks``): of the (bq, bkv) pairs from 128 to 1024 that
divide S and whose VMEM footprint (``vmem_bytes``) fits ``vmem_budget()``,
the one with the least modelled cost: the score elements the blocks that
run compute, plus a fixed cost for every grid step, skipped or not.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fcnn_layer import (
    _DEFAULT_SCOPED_VMEM,
    _VMEM_HEADROOM,
    vmem_budget,
)

__all__ = [
    "flash_attention_fwd",
    "flash_attention_bwd",
    "kv_block",
    "q_block",
    "select_blocks",
    "vmem_bytes",
]

_NEG_INF = -1e30    # finite: exp(_NEG_INF - m) is 0, never NaN
_LANE = 128
_SUBLANE = 8
_BLOCKS = (1024, 512, 256, 128)
_STEP_ELEMS = 64 * 1024    # a grid step's fixed cost, in score elements
_NT = (((1,), (1,)), ((), ()))   # a · bᵀ


def _last_kv(qi, bq: int, bkv: int):
    """The last kv block that q block ``qi`` sees under the causal mask."""
    return ((qi + 1) * bq - 1) // bkv


def _first_q(kvi, bq: int, bkv: int):
    """The first q block that sees kv block ``kvi`` under the causal mask."""
    return (kvi * bkv) // bq


def kv_block(qi, kvi, bq: int, bkv: int, causal: bool):
    """The kv block that grid step (q block ``qi``, kv block ``kvi``)
    fetches: ``kvi``, clamped down onto the diagonal where ``causal``, so
    that a step above it repeats the index of the step before it."""
    return jnp.minimum(kvi, _last_kv(qi, bq, bkv)) if causal else kvi


def q_block(kvi, qi, bq: int, bkv: int, causal: bool):
    """The q block that dK/dV's grid step (kv block ``kvi``, q block
    ``qi``) fetches: ``qi``, clamped up onto the diagonal where
    ``causal``, so that a step above it takes the index of the first step
    that runs."""
    return jnp.maximum(qi, _first_q(kvi, bq, bkv)) if causal else qi


def _causal_mask(s, row0, col0, transposed: bool):
    """``s`` with the entries above the diagonal set to _NEG_INF: rows are
    queries from ``row0`` and columns keys from ``col0``, or the other way
    round where ``transposed``."""
    rows = row0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
    cols = col0 + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    keep = rows <= cols if transposed else rows >= cols
    return jnp.where(keep, s, _NEG_INF)


def _blockwise(causal: bool, q0, kv0, bq: int, bkv: int, body):
    """Run ``body(masked)`` for a block pair whose queries start at ``q0``
    and keys at ``kv0``: not at all above the diagonal, with the mask on
    it, without it below."""
    if not causal:
        body(False)
        return
    runs = kv0 <= q0 + bq - 1
    on_diagonal = kv0 + bkv - 1 > q0

    @pl.when(runs & on_diagonal)
    def _masked():
        body(True)

    @pl.when(runs & jnp.logical_not(on_diagonal))
    def _below():
        body(False)


# ---------------------------------------------------------------- forward


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref,
                *, causal: bool, scale: float, bq: int, bkv: int,
                kv_steps: int):
    qi, kvi = pl.program_id(1), pl.program_id(2)

    @pl.when(kvi == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(masked: bool):
        v = v_ref[0]
        s = jax.lax.dot_general(q_ref[0], k_ref[0], _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, qi * bq, kvi * bkv, transposed=False)
        m_prev = m_ref[...]                                   # (bq, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - m_new)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    _blockwise(causal, qi * bq, kvi * bkv, bq, bkv, step)

    @pl.when(kvi == kv_steps - 1)
    def _finish():
        l = l_ref[...]
        o_ref[0] = (acc_ref[...] / l).astype(o_ref.dtype)
        lse_ref[0] = m_ref[...] + jnp.log(l)


# --------------------------------------------------------------- backward


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dk_ref, dv_ref, dk_acc, dv_acc,
                *, causal: bool, scale: float, bq: int, bkv: int,
                q_steps: int):
    kvi, qi = pl.program_id(1), pl.program_id(2)

    @pl.when(qi == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(masked: bool):
        q, do = q_ref[0], do_ref[0]
        st = jax.lax.dot_general(k_ref[0], q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            st = _causal_mask(st, kvi * bkv, qi * bq, transposed=True)
        pt = jnp.exp(st - lse_ref[0])                         # (bkv, bq)
        dv_acc[...] += jnp.dot(pt.astype(do.dtype), do,
                               preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[0], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - delta_ref[0])         # dsᵀ / scale
        dk_acc[...] += jnp.dot(dst.astype(q.dtype), q,
                               preferred_element_type=jnp.float32)

    _blockwise(causal, qi * bq, kvi * bkv, bq, bkv, step)

    @pl.when(qi == q_steps - 1)
    def _finish():
        dk_ref[0] = (dk_acc[...] * scale).astype(dk_ref.dtype)
        dv_ref[0] = dv_acc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               dq_acc, *, causal: bool, scale: float, bq: int, bkv: int,
               kv_steps: int):
    qi, kvi = pl.program_id(1), pl.program_id(2)

    @pl.when(kvi == 0)
    def _init():
        dq_acc[...] = jnp.zeros_like(dq_acc)

    def step(masked: bool):
        k = k_ref[0]
        s = jax.lax.dot_general(q_ref[0], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal_mask(s, qi * bq, kvi * bkv, transposed=False)
        p = jnp.exp(s - lse_ref[0])                           # (bq, bkv)
        dp = jax.lax.dot_general(do_ref[0], v_ref[0], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - delta_ref[0])            # ds / scale
        dq_acc[...] += jnp.dot(ds.astype(k.dtype), k,
                               preferred_element_type=jnp.float32)

    _blockwise(causal, qi * bq, kvi * bkv, bq, bkv, step)

    @pl.when(kvi == kv_steps - 1)
    def _finish():
        dq_ref[0] = (dq_acc[...] * scale).astype(dq_ref.dtype)


# ----------------------------------------------------------------- blocks


def vmem_bytes(kind: str, bq: int, bkv: int, d: int, dv: int,
               itemsize: int) -> int:
    """VMEM one grid step of pass ``kind`` ("fwd", "dkv" or "dq") holds:
    its blocks double-buffered (an f32 (bq, 1) column fills 128 lanes, a
    (1, bq) row 8 sublanes), its f32 accumulators, and the f32 score-sized
    values its body makes."""
    tile = 4 * bq * bkv
    col = 2 * 4 * bq * _LANE
    row = 2 * 4 * _SUBLANE * bq
    ins = bq * d + bkv * d + bkv * dv
    if kind == "fwd":
        return (2 * itemsize * (ins + bq * dv) + col
                + 4 * (bq * dv + 2 * bq * _LANE) + 3 * tile)
    if kind == "dkv":
        return (2 * itemsize * (ins + bq * dv + bkv * (d + dv)) + 2 * row
                + 4 * bkv * (d + dv) + 5 * tile)
    if kind == "dq":
        return (2 * itemsize * (ins + bq * dv + bq * d) + 2 * col
                + 4 * bq * d + 5 * tile)
    raise ValueError(f"unknown pass {kind!r}")


def _vmem_limit(kind: str, bq: int, bkv: int, d: int, dv: int,
                itemsize: int) -> int | None:
    need = int(vmem_bytes(kind, bq, bkv, d, dv, itemsize) * _VMEM_HEADROOM)
    return need if need > _DEFAULT_SCOPED_VMEM else None


def _blocks_run(s: int, bq: int, bkv: int, causal: bool) -> int:
    """Block pairs whose body runs."""
    if not causal:
        return (s // bq) * (s // bkv)
    return sum(_last_kv(i, bq, bkv) + 1 for i in range(s // bq))


@functools.lru_cache(maxsize=None)
def select_blocks(s: int, d: int, dv: int, itemsize: int,
                  causal: bool = True) -> tuple[int, int] | None:
    """(bq, bkv) for sequence length ``s``, q/k head dim ``d`` and v head
    dim ``dv`` in a dtype of ``itemsize`` bytes, or None where no block
    from 128 to 1024 divides ``s``."""
    budget = vmem_budget()
    best = None
    for bq in _BLOCKS:
        for bkv in _BLOCKS:
            if s % bq or s % bkv or max(
                    vmem_bytes(kind, bq, bkv, d, dv, itemsize)
                    for kind in ("fwd", "dkv", "dq")) > budget:
                continue
            cost = (_blocks_run(s, bq, bkv, causal) * bq * bkv
                    + (s // bq) * (s // bkv) * _STEP_ELEMS)
            if best is None or cost < best[0]:
                best = (cost, (bq, bkv))
    return None if best is None else best[1]


def _blocks(s: int, d: int, dv: int, dtype, causal: bool,
            block_q: int | None, block_kv: int | None) -> tuple[int, int]:
    if block_q is None or block_kv is None:
        chosen = select_blocks(s, d, dv, jnp.dtype(dtype).itemsize, causal)
        if chosen is None:
            raise ValueError(f"no block from 128 to 1024 divides seq {s}")
        block_q = block_q or chosen[0]
        block_kv = block_kv or chosen[1]
    if s % block_q or s % block_kv:
        raise ValueError(f"seq {s} not divisible by blocks "
                         f"({block_q},{block_kv})")
    return block_q, block_kv


def _row_maps(bq: int, bkv: int, causal: bool):
    """Index maps of the q-side and kv-side blocks on a grid (g, q block
    i, kv block j), the forward's and dQ's."""
    def q_map(g, i, j):
        return (g, i, 0)

    def kv_map(g, i, j):
        return (g, kv_block(i, j, bq, bkv, causal), 0)

    return q_map, kv_map


def _params(kind, bq, bkv, d, dv, itemsize):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=_vmem_limit(kind, bq, bkv, d, dv, itemsize))


# ---------------------------------------------------------------- entries


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_kv", "interpret"))
def flash_attention_fwd(
    q: jax.Array, k: jax.Array, v: jax.Array,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array]:
    """q, k: (B, H, S, D); v: (B, H, S, Dv) -> (o (B, H, S, Dv) in q's
    dtype, lse (B, H, S) f32).  ``scale`` defaults to 1/√D."""
    b, h, s, d = q.shape
    dv = v.shape[-1]
    if k.shape != q.shape or v.shape[:3] != q.shape[:3]:
        raise ValueError(f"shapes q {q.shape}, k {k.shape}, v {v.shape}")
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    bq, bkv = _blocks(s, d, dv, q.dtype, causal, block_q, block_kv)
    bh = b * h

    q_map, kv_map = _row_maps(bq, bkv, causal)
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale, bq=bq,
                          bkv=bkv, kv_steps=s // bkv),
        grid=(bh, s // bq, s // bkv),
        in_specs=[pl.BlockSpec((1, bq, d), q_map),
                  pl.BlockSpec((1, bkv, d), kv_map),
                  pl.BlockSpec((1, bkv, dv), kv_map)],
        out_specs=[pl.BlockSpec((1, bq, dv), q_map),
                   pl.BlockSpec((1, bq, 1), q_map)],
        out_shape=[jax.ShapeDtypeStruct((bh, s, dv), q.dtype),
                   jax.ShapeDtypeStruct((bh, s, 1), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, 1), jnp.float32),
                        pltpu.VMEM((bq, dv), jnp.float32)],
        compiler_params=_params("fwd", bq, bkv, d, dv, q.dtype.itemsize),
        interpret=interpret,
        name="flash_attention_fwd",
    )(q.reshape(bh, s, d), k.reshape(bh, s, d), v.reshape(bh, s, dv))
    return o.reshape(b, h, s, dv), lse.reshape(b, h, s)


@functools.partial(
    jax.jit,
    static_argnames=("causal", "scale", "block_q", "block_kv", "interpret"))
def flash_attention_bwd(
    q: jax.Array, k: jax.Array, v: jax.Array, o: jax.Array, lse: jax.Array,
    do: jax.Array,
    causal: bool = True,
    scale: float | None = None,
    block_q: int | None = None,
    block_kv: int | None = None,
    interpret: bool = False,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """(dq, dk, dv) of ``flash_attention_fwd`` for the output cotangent
    ``do``, from its output ``o`` and lse; each in its primal's dtype."""
    b, h, s, d = q.shape
    dv = v.shape[-1]
    scale = 1.0 / math.sqrt(d) if scale is None else scale
    bq, bkv = _blocks(s, d, dv, q.dtype, causal, block_q, block_kv)
    bh = b * h
    itemsize = q.dtype.itemsize
    qf, kf = q.reshape(bh, s, d), k.reshape(bh, s, d)
    vf, dof = v.reshape(bh, s, dv), do.reshape(bh, s, dv)
    delta = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)
    lse_col, delta_col = lse.reshape(bh, s, 1), delta.reshape(bh, s, 1)
    lse_row, delta_row = lse.reshape(bh, 1, s), delta.reshape(bh, 1, s)

    # dK/dV: grid (g, kv block j, q block i)
    def q_first(g, j, i):
        return (g, q_block(j, i, bq, bkv, causal), 0)

    def row_first(g, j, i):
        return (g, 0, q_block(j, i, bq, bkv, causal))

    def kv_own(g, j, i):
        return (g, j, 0)

    dk, dvv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale, bq=bq,
                          bkv=bkv, q_steps=s // bq),
        grid=(bh, s // bkv, s // bq),
        in_specs=[pl.BlockSpec((1, bq, d), q_first),
                  pl.BlockSpec((1, bkv, d), kv_own),
                  pl.BlockSpec((1, bkv, dv), kv_own),
                  pl.BlockSpec((1, bq, dv), q_first),
                  pl.BlockSpec((1, 1, bq), row_first),
                  pl.BlockSpec((1, 1, bq), row_first)],
        out_specs=[pl.BlockSpec((1, bkv, d), kv_own),
                   pl.BlockSpec((1, bkv, dv), kv_own)],
        out_shape=[jax.ShapeDtypeStruct((bh, s, d), k.dtype),
                   jax.ShapeDtypeStruct((bh, s, dv), v.dtype)],
        scratch_shapes=[pltpu.VMEM((bkv, d), jnp.float32),
                        pltpu.VMEM((bkv, dv), jnp.float32)],
        compiler_params=_params("dkv", bq, bkv, d, dv, itemsize),
        interpret=interpret,
        name="flash_attention_dkv",
    )(qf, kf, vf, dof, lse_row, delta_row)

    # dQ: grid (g, q block i, kv block j), the forward's
    q_map, kv_map = _row_maps(bq, bkv, causal)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale, bq=bq,
                          bkv=bkv, kv_steps=s // bkv),
        grid=(bh, s // bq, s // bkv),
        in_specs=[pl.BlockSpec((1, bq, d), q_map),
                  pl.BlockSpec((1, bkv, d), kv_map),
                  pl.BlockSpec((1, bkv, dv), kv_map),
                  pl.BlockSpec((1, bq, dv), q_map),
                  pl.BlockSpec((1, bq, 1), q_map),
                  pl.BlockSpec((1, bq, 1), q_map)],
        out_specs=pl.BlockSpec((1, bq, d), q_map),
        out_shape=jax.ShapeDtypeStruct((bh, s, d), q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), jnp.float32)],
        compiler_params=_params("dq", bq, bkv, d, dv, itemsize),
        interpret=interpret,
        name="flash_attention_dq",
    )(qf, kf, vf, dof, lse_col, delta_col)
    return (dq.reshape(b, h, s, d), dk.reshape(b, h, s, d),
            dvv.reshape(b, h, s, dv))
