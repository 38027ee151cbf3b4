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
automatically (``select_blocks``), per pass and per (M, K, N).  Least
edge padding comes first: each dim pads to its unit, sublane 8 for M and
lane 128 for K/N (``_select_block``).  Then, among the blocks that divide
the padded dims and whose double-buffered blocks plus f32 scratch fit
``vmem_budget()`` (three eighths of the TensorCore's VMEM, 48 MiB on
v5e), the rule takes the set that moves the fewest HBM bytes
(a block is fetched again only when its index changes between grid
steps), counting each grid step's fixed cost as bytes.  At NN5/NN6 widths
that means the whole batch in one block and the contraction in
512–1024-wide steps, so each operand is read about once.  Where the blocks
need more than Mosaic's default scoped VMEM, the kernel raises
``vmem_limit_bytes`` to the footprint with headroom.  Every operand and
result of a kernel is held in HBM, so the kernel's own DMAs make the HBM
traffic it is modelled by.  Non-aligned shapes —
the paper's 784/10/… NN benchmark dims — are zero-padded to block
multiples and the result is sliced back; zero padding is exact for all
three passes (padded rows / columns contribute 0 to every contraction and
are discarded on output).

Every block is rank 2.  The (N,) bias and db vectors travel as (1, N)
rows in (1, bn) blocks: Mosaic tiles a rank-1 operand by its full length,
so a rank-1 (bn,) block of a longer vector is refused on the chip
whenever the padded width spans more than one block.
"""

from __future__ import annotations

import functools
import itertools

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

_SUBLANE = 8    # fp32 sublane unit (second-to-last dim)
_LANE = 128     # lane unit (last dim)

# Automatic blocks (``select_blocks``): of the block sets whose VMEM
# footprint fits the budget, the one with the least modelled cost.
_VMEM_BUDGET_SHARE = 3 / 8          # of the TensorCore's VMEM (48 MiB on v5e)
_VMEM_FALLBACK = 128 * 2**20        # v5e's, where the default device is no TPU
_DEFAULT_SCOPED_VMEM = 16 * 2**20   # what Mosaic grants unless told more
_VMEM_HEADROOM = 1.25
_STEP_BYTES = 256 * 1024   # a grid step's fixed cost, in HBM bytes' time
_MAX_BLOCK = 1024          # on any axis

# Each pass's grid order (outermost first) and its blocks, named by the
# dims they span: its HBM inputs and outputs, and the f32 values it keeps
# in VMEM besides them (accumulators, the dot's result, dZ, wgrad's
# transposed x block).  A one-letter block is a (1, b) row.  The grid and
# BlockSpecs of each ``pallas_call`` are built from this (``_grid_specs``).
_PASSES = {
    "fwd": ("mnk", ("mk", "kn", "n"), ("mn",), ("mn", "mn")),
    "dgrad": ("mkn", ("mn", "mn", "kn"), ("mk",), ("mk", "mk", "mn")),
    "wgrad": ("nkm", ("mk", "mn", "mn"), ("kn", "n"),
              ("kn", "n", "kn", "mn", "mk")),
}


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


def _block_elems(dims: str, blocks: dict[str, int]) -> int:
    """Elements of one block; a (1, b) row fills an (8, b) sublane tile."""
    if len(dims) == 1:
        return _SUBLANE * blocks[dims]
    return blocks[dims[0]] * blocks[dims[1]]


def vmem_budget() -> int:
    """VMEM the automatic blocks may fill: a share of the default TPU
    device's VMEM, so the scoped limit they ask for stays well inside it."""
    try:
        capacity = pltpu.get_tpu_info().vmem_capacity_bytes
    except ValueError:   # the default device is no TPU
        capacity = _VMEM_FALLBACK
    return int(capacity * _VMEM_BUDGET_SHARE)


def vmem_bytes(kind: str, bm: int, bn: int, bk: int,
               itemsize: int = 4) -> int:
    """VMEM one grid step of pass ``kind`` holds: its HBM operands' blocks,
    double-buffered, and its f32 values (``_PASSES``)."""
    _, ins, outs, f32 = _PASSES[kind]
    blocks = {"m": bm, "n": bn, "k": bk}
    return (2 * itemsize * sum(_block_elems(d, blocks) for d in ins + outs)
            + 4 * sum(_block_elems(d, blocks) for d in f32))


def _block_spec(order: str, dims: str, blocks: dict[str, int]):
    """The BlockSpec of an operand spanning ``dims`` on a grid in
    ``order``; a one-letter block is a (1, b) row."""
    at = [order.index(d) for d in dims]
    if len(dims) == 1:
        return pl.BlockSpec((1, blocks[dims]), lambda *g: (0, g[at[0]]))
    return pl.BlockSpec((blocks[dims[0]], blocks[dims[1]]),
                        lambda *g: (g[at[0]], g[at[1]]))


def _grid_specs(kind: str, blocks: tuple[int, int, int],
                padded: tuple[int, int, int]):
    """(grid, in_specs, out_specs) of pass ``kind`` from ``_PASSES``:
    ``blocks`` and ``padded`` are (m, n, k) as ``select_blocks`` gives."""
    order, ins, outs, _ = _PASSES[kind]
    b, p = dict(zip("mnk", blocks)), dict(zip("mnk", padded))
    grid = tuple(p[a] // b[a] for a in order)
    return (grid, [_block_spec(order, d, b) for d in ins],
            [_block_spec(order, d, b) for d in outs])


def _modelled_cost(kind: str, blocks: dict[str, int],
                   padded: dict[str, int], itemsize: int) -> int:
    """HBM bytes a block set costs pass ``kind``: every fetch and write
    back of a block (the pipeline fetches a block again only when its
    index changes from the grid step before), the first step's inputs and
    the last step's outputs again (no compute hides them), and
    ``_STEP_BYTES`` a grid step."""
    order, ins, outs, _ = _PASSES[kind]
    grid = [padded[a] // blocks[a] for a in order]
    moved = exposed = 0
    for dims in ins + outs:
        size = _block_elems(dims, blocks) * itemsize
        # fetched once per step of every grid axis out to the innermost
        # one with more than one block that the block's index follows
        inner = max((j for j, a in enumerate(order)
                     if a in dims and grid[j] > 1), default=-1)
        fetches = 1
        for g in grid[:inner + 1]:
            fetches *= g
        moved += fetches * size
        exposed += size
    steps = grid[0] * grid[1] * grid[2]
    return moved + exposed + steps * _STEP_BYTES


def _vmem_limit(kind: str, blocks: tuple[int, int, int],
                itemsize: int) -> int | None:
    """The scoped-VMEM limit to ask Mosaic for, or None where its default
    holds the blocks with headroom."""
    need = int(vmem_bytes(kind, *blocks, itemsize) * _VMEM_HEADROOM)
    return need if need > _DEFAULT_SCOPED_VMEM else None


def _divisor_blocks(padded: int, unit: int) -> list[int]:
    """Multiples of ``unit`` that divide ``padded``, up to ``_MAX_BLOCK``."""
    return [b for b in range(unit, min(padded, _MAX_BLOCK) + 1, unit)
            if padded % b == 0]


def select_blocks(
    m: int, k: int, n: int,
    block_m: int | None = None,
    block_n: int | None = None,
    block_k: int | None = None,
    kind: str = "fwd",
    itemsize: int = 4,
) -> tuple[tuple[int, int, int], tuple[int, int, int]]:
    """((bm, bn, bk), (m_pad, n_pad, k_pad)) for an (M, K) x (K, N) problem
    in pass ``kind`` ("fwd", "dgrad" or "wgrad").

    Each dim pads to its unit, the least padding ``_select_block`` can
    give.  An explicit block is taken as ``_select_block`` takes it.  The
    other blocks divide their padded dims: of the sets within
    ``vmem_budget()``, the one of least ``_modelled_cost``, then of fewest
    grid steps."""
    dims = ((m, block_m, _SUBLANE), (n, block_n, _LANE), (k, block_k, _LANE))
    padded = tuple(_round_up(d, unit) for d, _, unit in dims)
    cands = list(itertools.product(*(
        [_select_block(d, b, b, unit)[0]] if b else _divisor_blocks(p, unit)
        for (d, b, unit), p in zip(dims, padded))))
    budget = vmem_budget()
    fits = [c for c in cands
            if vmem_bytes(kind, *c, itemsize) <= budget] or cands[:1]
    blocks = min(fits, key=lambda c: (
        _modelled_cost(kind, dict(zip("mnk", c)), dict(zip("mnk", padded)),
                       itemsize),
        -c[0] * c[1] * c[2]))
    return blocks, padded


def _in_hbm(interpret: bool, *xs: jax.Array) -> list[jax.Array]:
    """The kernel's operands, held in HBM on the chip (``_hbm_shape``)."""
    if interpret:
        return list(xs)
    return [pltpu.with_memory_space_constraint(x, pltpu.HBM) for x in xs]


def _hbm_shape(shape: tuple[int, ...], dtype, interpret: bool):
    """A kernel result's type, held in HBM on the chip.  Left free, XLA
    places small operands and results in VMEM, and the ops around the
    kernel then make the kernel's HBM reads and writes for it."""
    if interpret:
        return jax.ShapeDtypeStruct(shape, dtype)
    return pltpu.HBM(shape, dtype)


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
    blocks, (mp, np_, kp) = select_blocks(
        m, k, n, block_m, block_n, block_k, "fwd", x.dtype.itemsize)
    bm, bn, _ = blocks
    xp, wp, bp = _pad2(x, mp, kp), _pad2(w, kp, np_), _pad2(b.reshape(1, n), 1, np_)
    grid, in_specs, (out_spec,) = _grid_specs("fwd", blocks, (mp, np_, kp))
    out = pl.pallas_call(
        functools.partial(_fwd_kernel, k_steps=grid[2], act=activation),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=_hbm_shape((mp, np_), x.dtype, interpret),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit("fwd", blocks, x.dtype.itemsize)),
        interpret=interpret,
        name="fcnn_layer",
    )(*_in_hbm(interpret, xp, wp, bp))
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
    blocks, (mp, np_, kp) = select_blocks(
        m, k, n, block_m, block_n, block_k, "dgrad", dy.dtype.itemsize)
    bm, _, bk = blocks
    dyp, yp, wp = _pad2(dy, mp, np_), _pad2(y, mp, np_), _pad2(w, kp, np_)
    # grid (M, K, N), N innermost: accumulate
    grid, in_specs, (out_spec,) = _grid_specs("dgrad", blocks, (mp, np_, kp))
    out = pl.pallas_call(
        functools.partial(_dgrad_kernel, n_steps=grid[2], act=activation),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=_hbm_shape((mp, kp), dy.dtype, interpret),
        scratch_shapes=[pltpu.VMEM((bm, bk), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit("dgrad", blocks, dy.dtype.itemsize)),
        interpret=interpret,
        name="fcnn_layer_dgrad",
    )(*_in_hbm(interpret, dyp, yp, wp))
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
    blocks, (mp, np_, kp) = select_blocks(
        m, k, n, block_m, block_n, block_k, "wgrad", x.dtype.itemsize)
    _, bn, bk = blocks
    xp, dyp, yp = _pad2(x, mp, kp), _pad2(dy, mp, np_), _pad2(y, mp, np_)
    # grid (N, K, M), M innermost: accumulate
    grid, in_specs, out_specs = _grid_specs("wgrad", blocks, (mp, np_, kp))
    dw, db = pl.pallas_call(
        functools.partial(_wgrad_kernel, m_steps=grid[2], act=activation),
        grid=grid,
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=[
            _hbm_shape((kp, np_), x.dtype, interpret),
            _hbm_shape((1, np_), dy.dtype, interpret),
        ],
        scratch_shapes=[
            pltpu.VMEM((bk, bn), jnp.float32),
            pltpu.VMEM((1, bn), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=_vmem_limit("wgrad", blocks, x.dtype.itemsize)),
        interpret=interpret,
        name="fcnn_layer_wgrad",
    )(*_in_hbm(interpret, xp, dyp, yp))
    with jax.named_scope(obs.PAD):
        return dw[:k, :n], db[0, :n]
