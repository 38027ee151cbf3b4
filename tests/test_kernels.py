"""Per-kernel validation: Pallas (interpret mode) vs pure-jnp oracle,
swept over shapes and dtypes — forward values AND (for the fused fcnn
kernel) gradients through the custom VJP."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import ref as R
from repro.configs.nn_benchmarks import NN_BENCHMARKS
from repro.kernels.fcnn_layer import (
    fcnn_layer,
    fcnn_layer_dgrad,
    fcnn_layer_wgrad,
    select_blocks,
    vmem_bytes,
    vmem_budget,
)
from repro.kernels.flash_attention import (
    _blocks_run,
    flash_attention_fwd,
    kv_block,
    q_block,
    select_blocks as flash_select_blocks,
    vmem_bytes as flash_vmem_bytes,
)

RNG = np.random.default_rng(7)


def _arr(shape, dtype, scale=1.0):
    return jnp.asarray(RNG.normal(size=shape) * scale, dtype)


@pytest.mark.parametrize("m,k,n,bm,bn,bk", [
    (128, 128, 128, 128, 128, 128),
    (256, 512, 128, 128, 128, 256),
    (128, 1024, 256, 64, 128, 512),
    (384, 256, 384, 128, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("act", ["sigmoid", "relu", "none"])
def test_fcnn_layer_kernel(m, k, n, bm, bn, bk, dtype, act):
    x, w, b = _arr((m, k), dtype), _arr((k, n), dtype, 0.05), _arr((n,), dtype)
    out = ops.fcnn_layer(x, w, b, act, force="pallas_interpret",
                         block_m=bm, block_n=bn, block_k=bk)
    refv = R.fcnn_layer_ref(x, w, b, act)
    tol = 5e-6 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(refv, np.float32),
                               rtol=tol, atol=tol)


# The paper's NN benchmark layer shapes are NOT 128-divisible (784 inputs,
# 10 output classes); the kernel must pad edge tiles instead of raising.
@pytest.mark.parametrize("m,k,n", [
    (32, 784, 1000),    # NN1 layer 1
    (32, 500, 10),      # NN1 output layer
    (100, 64, 64),      # non-divisible batch
    (8, 1024, 4000),    # NN5/NN6 wide layer
    (7, 13, 5),         # everything tiny and ragged
])
@pytest.mark.parametrize("act", ["sigmoid", "relu", "tanh", "none"])
def test_fcnn_layer_kernel_nonaligned(m, k, n, act):
    x = _arr((m, k), jnp.float32)
    w = _arr((k, n), jnp.float32, 0.05)
    b = _arr((n,), jnp.float32)
    out = ops.fcnn_layer(x, w, b, act, force="pallas_interpret")
    refv = R.fcnn_layer_ref(x, w, b, act)
    np.testing.assert_allclose(np.asarray(out), np.asarray(refv),
                               rtol=5e-6, atol=5e-6)


@pytest.mark.parametrize("m,k,n", [
    (32, 784, 1000),    # NN1 layer 1 (non-128-divisible K)
    (32, 1000, 500),
    (32, 500, 10),      # 10-class output layer
    (16, 1024, 4000),   # NN5/NN6 wide layer
])
@pytest.mark.parametrize("act", ["sigmoid", "relu", "tanh", "none"])
def test_fcnn_layer_grad_matches_ref(m, k, n, act):
    """jax.grad through the Pallas custom-VJP dispatch == autodiff of the
    oracle, for x, w and b (acceptance criterion: 1e-5 fp32)."""
    x = _arr((m, k), jnp.float32)
    w = _arr((k, n), jnp.float32, 0.05)
    b = _arr((n,), jnp.float32)
    t = _arr((m, n), jnp.float32)

    def loss(p, mode):
        y = ops.fcnn_layer(p["x"], p["w"], p["b"], act, force=mode)
        return jnp.mean((y.astype(jnp.float32) - t) ** 2)

    g_pallas = jax.grad(lambda p: loss(p, "pallas_interpret"))(
        {"x": x, "w": w, "b": b})
    g_ref = jax.grad(lambda p: loss(p, "ref"))({"x": x, "w": w, "b": b})
    for name in ("x", "w", "b"):
        np.testing.assert_allclose(
            np.asarray(g_pallas[name]), np.asarray(g_ref[name]),
            rtol=1e-5, atol=1e-5, err_msg=f"d{name} act={act}")


@pytest.mark.parametrize("act", ["sigmoid", "relu", "tanh", "none"])
def test_fcnn_backward_kernels_match_oracles(act):
    """The dgrad/wgrad Pallas kernels against their ref.py oracles."""
    m, k, n = 48, 200, 75
    x = _arr((m, k), jnp.float32)
    w = _arr((k, n), jnp.float32, 0.05)
    b = _arr((n,), jnp.float32)
    dy = _arr((m, n), jnp.float32)
    y = R.fcnn_layer_ref(x, w, b, act)

    dx = fcnn_layer_dgrad(dy, y, w, act, interpret=True)
    dx_ref = R.fcnn_layer_dgrad_ref(dy, y, w, act)
    np.testing.assert_allclose(np.asarray(dx), np.asarray(dx_ref),
                               rtol=5e-6, atol=5e-6)

    dw, db = fcnn_layer_wgrad(x, dy, y, act, interpret=True)
    dw_ref, db_ref = R.fcnn_layer_wgrad_ref(x, dy, y, act)
    np.testing.assert_allclose(np.asarray(dw), np.asarray(dw_ref),
                               rtol=5e-6, atol=5e-6)
    np.testing.assert_allclose(np.asarray(db), np.asarray(db_ref),
                               rtol=5e-6, atol=5e-6)


@pytest.mark.parametrize("kind", ["fwd", "dgrad", "wgrad"])
def test_fcnn_kernels_multiblock_defaults(kind):
    """Each pass at its automatic blocks, on a ragged shape that they split
    into more than one block on every grid axis: the contraction
    accumulates over several steps, and wgrad's db sums row blocks on the
    K==0 slice only."""
    m, k, n = 1030, 1150, 1100
    (bm, bn, bk), (mp, np_, kp) = select_blocks(m, k, n, kind=kind)
    assert min(mp // bm, np_ // bn, kp // bk) > 1
    act = "sigmoid"
    x = _arr((m, k), jnp.float32)
    w = _arr((k, n), jnp.float32, 0.05)
    b = _arr((n,), jnp.float32)
    y = R.fcnn_layer_ref(x, w, b, act)
    dy = _arr((m, n), jnp.float32)
    if kind == "fwd":
        got = [fcnn_layer(x, w, b, act, interpret=True)]
        want = [y]
    elif kind == "dgrad":
        got = [fcnn_layer_dgrad(dy, y, w, act, interpret=True)]
        want = [R.fcnn_layer_dgrad_ref(dy, y, w, act)]
    else:
        got = fcnn_layer_wgrad(x, dy, y, act, interpret=True)
        want = R.fcnn_layer_wgrad_ref(x, dy, y, act)
    for g, r in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(r),
                                   rtol=5e-6, atol=5e-5)


def test_fcnn_grad_through_model_loss():
    """End-to-end: grad of the FCNN cross-entropy loss, fused vs ref."""
    from repro.models import fcnn

    sizes = [784, 64, 10]   # non-aligned input layer
    params = fcnn.init(jax.random.PRNGKey(0), sizes)
    batch = {
        "x": _arr((16, sizes[0]), jnp.float32),
        "y": jnp.asarray(RNG.integers(0, sizes[-1], size=16), jnp.int32),
    }
    g_pallas = jax.grad(
        lambda p: fcnn.loss_fn(p, batch, kernel_mode="pallas_interpret")
    )(params)
    g_ref = jax.grad(
        lambda p: fcnn.loss_fn(p, batch, kernel_mode="ref"))(params)
    flat_p, _ = jax.tree_util.tree_flatten(g_pallas)
    flat_r, _ = jax.tree_util.tree_flatten(g_ref)
    for a, b_ in zip(flat_p, flat_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_),
                                   rtol=1e-5, atol=1e-5)


# ---------------------------------------------------- fused softmax/xent


@pytest.mark.parametrize("b", [1, 64, 128])
@pytest.mark.parametrize("n", [10, 26])
def test_softmax_xent_matches_ref(b, n):
    """Fused loss AND its gradient vs jax.grad of the ref loss, on the
    paper's non-128-aligned class counts (10 classes, batch down to 1)."""
    logits = _arr((b, n), jnp.float32, 3.0)
    labels = jnp.asarray(RNG.integers(0, n, size=b), jnp.int32)

    loss_p = ops.softmax_xent(logits, labels, force="pallas_interpret")
    loss_r = ops.softmax_xent(logits, labels, force="ref")
    np.testing.assert_allclose(float(loss_p), float(loss_r),
                               rtol=1e-6, atol=1e-6)

    g_p = jax.grad(lambda x: ops.softmax_xent(
        x, labels, force="pallas_interpret"))(logits)
    g_r = jax.grad(lambda x: ops.softmax_xent(x, labels, force="ref"))(logits)
    np.testing.assert_allclose(np.asarray(g_p), np.asarray(g_r),
                               rtol=1e-5, atol=1e-6)


def test_softmax_xent_kernels_match_oracles():
    """The forward (nll, lse) and backward (dlogits) Pallas kernels against
    their ref.py oracles, including a non-default block override."""
    from repro.kernels.softmax_xent import (
        softmax_xent_dlogits,
        softmax_xent_fwd,
    )

    b, n = 37, 300   # ragged in both dims, several class tiles at bc=128
    logits = _arr((b, n), jnp.float32, 2.0)
    labels = jnp.asarray(RNG.integers(0, n, size=b), jnp.int32)

    nll, lse = softmax_xent_fwd(logits, labels, block_c=128, interpret=True)
    logp = jax.nn.log_softmax(np.asarray(logits, np.float32), axis=-1)
    nll_ref = -np.take_along_axis(logp, np.asarray(labels)[:, None], 1)[:, 0]
    lse_ref = np.log(np.sum(np.exp(np.asarray(logits, np.float32)), axis=-1))
    np.testing.assert_allclose(np.asarray(nll), nll_ref, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(lse), lse_ref, rtol=1e-5, atol=1e-5)

    g = jnp.float32(0.7)
    scale = jnp.full((b,), g / b, jnp.float32)
    dl = softmax_xent_dlogits(logits, labels, lse, scale,
                              block_c=128, interpret=True)
    dl_ref = R.softmax_xent_dlogits_ref(logits, labels, g)
    np.testing.assert_allclose(np.asarray(dl), np.asarray(dl_ref),
                               rtol=1e-5, atol=1e-6)


def test_fcnn_loss_fn_matches_prefusion_value():
    """End-to-end: fcnn.loss_fn (now dispatching the fused kernel) agrees
    with the pre-fusion jnp log-softmax + NLL loss in every mode."""
    from repro.models import fcnn

    sizes = [784, 64, 10]
    params = fcnn.init(jax.random.PRNGKey(3), sizes)
    batch = {
        "x": _arr((16, sizes[0]), jnp.float32),
        "y": jnp.asarray(RNG.integers(0, sizes[-1], size=16), jnp.int32),
    }

    def prefusion_loss(mode):
        logits = fcnn.forward(params, batch["x"], kernel_mode=mode)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
        return jnp.mean(
            -jnp.take_along_axis(logp, batch["y"][:, None], axis=-1)[:, 0])

    for mode in ("ref", "pallas_interpret"):
        fused = float(fcnn.loss_fn(params, batch, kernel_mode=mode))
        np.testing.assert_allclose(fused, float(prefusion_loss(mode)),
                                   rtol=1e-6, atol=1e-6)


def test_select_blocks_minimizes_padding():
    (bm, bn, bk), (mp, np_, kp) = select_blocks(784, 784, 10)
    assert mp % bm == 0 and np_ % bn == 0 and kp % bk == 0
    assert mp - 784 < bm and kp - 784 < 128 + bk  # minimal edge padding
    assert np_ == 128  # 10 -> one lane tile


def _rule_shapes(name):
    """(n_in, n_out) of every layer of paper network ``name``, and of each
    layer's column chunk on a 2- or 4-chip ring (n_out / d)."""
    sizes = NN_BENCHMARKS[name]
    layers = list(zip(sizes[:-1], sizes[1:]))
    return layers + [(k, n // d) for k, n in layers for d in (2, 4)
                     if n % d == 0]


@pytest.mark.parametrize("kind", ["fwd", "dgrad", "wgrad"])
@pytest.mark.parametrize("batch", [64, 128, 1024])
@pytest.mark.parametrize("name", sorted(NN_BENCHMARKS))
def test_select_blocks_rule(name, batch, kind):
    """Automatic blocks keep the least edge padding (sublane 8 for M, lane
    128 for K and N, as the 128/128/512 blocks gave), divide their padded
    dims and fit the VMEM budget the rule selects under."""
    def least(v, unit):
        return -(-v // unit) * unit

    for k, n in _rule_shapes(name):
        (bm, bn, bk), (mp, np_, kp) = select_blocks(batch, k, n, kind=kind)
        assert (mp, np_, kp) == (least(batch, 8), least(n, 128),
                                 least(k, 128)), (k, n)
        assert mp % bm == 0 and np_ % bn == 0 and kp % bk == 0, (k, n)
        assert bm % 8 == 0 and bn % 128 == 0 and bk % 128 == 0, (k, n)
        assert vmem_bytes(kind, bm, bn, bk) <= vmem_budget(), (k, n)


@pytest.mark.parametrize("b,h,s,d,bq", [
    (1, 2, 128, 32, 64),
    (2, 4, 256, 64, 128),
    (1, 1, 64, 128, 32),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_kernel(b, h, s, d, bq, causal, dtype):
    q, k, v = (_arr((b, h, s, d), dtype) for _ in range(3))
    out = ops.flash_attention(q, k, v, causal=causal,
                              force="pallas_interpret",
                              block_q=bq, block_kv=bq)
    refv = R.flash_attention_ref(q, k, v, causal=causal)
    tol = 2e-5 if dtype == jnp.float32 else 5e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(refv, np.float32),
                               rtol=tol, atol=tol)


def _rel_err(got, want) -> float:
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    return float(np.max(np.abs(got - want)) / np.max(np.abs(want)))


@pytest.mark.parametrize("d,dv,bq,bkv", [
    (64, 64, 64, 64),
    (48, 32, 64, 64),
    (192, 128, 64, 64),
    (48, 32, 128, 64),
    (48, 32, 64, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_training_kernels(d, dv, bq, bkv, causal, dtype):
    """Forward output and lse, and the custom VJP's dq, dk and dv, against
    the f32 reference at a given scale, S = 256: 4 x 4 blocks of 64 (or
    2 x 4 and 4 x 2), so that causal blocks are skipped and their index
    maps clamped."""
    b, h, s, scale = 2, 2, 256, 0.3
    q, k = _arr((b, h, s, d), dtype), _arr((b, h, s, d), dtype)
    v, do = _arr((b, h, s, dv), dtype), _arr((b, h, s, dv), dtype)
    blocks = dict(block_q=bq, block_kv=bkv)
    o, lse = flash_attention_fwd(q, k, v, causal=causal, scale=scale,
                                 interpret=True, **blocks)
    out, vjp = jax.vjp(lambda q, k, v: ops.flash_attention(
        q, k, v, causal, scale=scale, force="pallas_interpret", **blocks),
        q, k, v)
    f32 = [a.astype(jnp.float32) for a in (q, k, v, do)]
    with jax.default_matmul_precision("highest"):
        want, ref_vjp = jax.vjp(
            lambda q, k, v: R.flash_attention_ref(q, k, v, causal, scale),
            *f32[:3])
        want_lse = R.flash_attention_lse_ref(*f32[:2], causal, scale)
        want_grads = ref_vjp(f32[3])
    tol = 1e-5 if dtype == jnp.float32 else 1e-2
    assert o.dtype == dtype and lse.dtype == jnp.float32
    assert _rel_err(o, want) <= tol and _rel_err(out, o) == 0.0
    assert _rel_err(lse, want_lse) <= 1e-5    # f32 sums of exact products
    for got, ref in zip(vjp(do), want_grads):
        assert got.dtype == dtype and _rel_err(got, ref) <= 2 * tol


def test_flash_attention_blocks():
    """Automatic blocks divide S and fit the VMEM budget; at latent
    attention's 4,096 positions (q/k 192, v 128, bf16) they are 1024 x
    1024, and 512-blocks would run 36 of their 64 causal block pairs."""
    assert flash_select_blocks(4096, 192, 128, 2) == (1024, 1024)
    assert _blocks_run(4096, 512, 512, True) == 36
    assert _blocks_run(4096, 512, 512, False) == 64
    for s, d, dv, itemsize in [(4096, 192, 128, 2), (1024, 24, 16, 4),
                               (384, 64, 64, 4), (32768, 192, 128, 2)]:
        bq, bkv = flash_select_blocks(s, d, dv, itemsize)
        assert s % bq == 0 and s % bkv == 0
        assert all(flash_vmem_bytes(kind, bq, bkv, d, dv, itemsize)
                   <= vmem_budget() for kind in ("fwd", "dkv", "dq"))
    assert flash_select_blocks(100, 64, 64, 4) is None


@pytest.mark.parametrize("bq,bkv", [(512, 512), (1024, 512), (512, 1024)])
def test_flash_attention_skipped_blocks_copy_nothing(bq, bkv):
    """At 4,096 causal positions the clamped index maps copy in no block
    for the skipped steps: over every grid step of each pass the block
    index changes no more often than over the steps whose body runs, and
    with the index unclamped it would."""
    s = 4096

    def fetches(indices):
        """Copies the pipeline makes: one wherever the index changes."""
        return sum(a != b for a, b in zip([None] + indices, indices))

    def check(steps):
        every = [int(index) for index, _, _ in steps]
        running = [int(index) for index, _, runs in steps if runs]
        unclamped = [raw for _, raw, _ in steps]
        assert fetches(every) == fetches(running) < fetches(unclamped)

    # forward and dQ: kv blocks innermost
    check([(kv_block(i, j, bq, bkv, True), j, j * bkv < (i + 1) * bq)
           for i in range(s // bq) for j in range(s // bkv)])
    # dK/dV: q blocks innermost
    check([(q_block(j, i, bq, bkv, True), i, j * bkv < (i + 1) * bq)
           for j in range(s // bkv) for i in range(s // bq)])


@pytest.mark.parametrize("bc,q,h,p,n,bh", [
    (2, 16, 8, 8, 4, 4),
    (1, 32, 4, 16, 8, 4),
    (3, 8, 16, 8, 16, 8),
])
def test_ssd_chunk_kernel(bc, q, h, p, n, bh):
    x = _arr((bc, q, h, p), jnp.float32)
    dt_a = -jnp.abs(_arr((bc, q, h), jnp.float32)) * 0.3
    b = _arr((bc, q, h, n), jnp.float32)
    c = _arr((bc, q, h, n), jnp.float32)
    y, st, dec = ops.ssd_chunk(x, dt_a, b, c, force="pallas_interpret",
                               block_h=bh)
    y2, st2, dec2 = ops.ssd_chunk(x, dt_a, b, c, force="ref")
    np.testing.assert_allclose(y, y2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(st, st2, rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(dec, dec2, rtol=1e-5, atol=1e-5)


def test_ops_dispatch_cpu_uses_ref():
    """Off-TPU the public wrappers run the oracle path."""
    x, w, b = _arr((8, 8), jnp.float32), _arr((8, 8), jnp.float32), _arr((8,), jnp.float32)
    out = ops.fcnn_layer(x, w, b)           # no force: CPU -> ref
    np.testing.assert_allclose(out, R.fcnn_layer_ref(x, w, b), rtol=1e-6)


def test_kernel_nondivisible_blocks_pad_instead_of_raising():
    """Explicit block overrides that don't divide the shape are treated as
    preferred sizes: the kernel pads edge tiles rather than raising."""
    x, w, b = _arr((100, 64), jnp.float32), _arr((64, 64), jnp.float32), _arr((64,), jnp.float32)
    out = ops.fcnn_layer(x, w, b, force="pallas_interpret", block_m=64)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(R.fcnn_layer_ref(x, w, b)),
                               rtol=5e-6, atol=5e-6)


def test_kernel_unknown_activation_raises():
    x, w, b = _arr((8, 8), jnp.float32), _arr((8, 8), jnp.float32), _arr((8,), jnp.float32)
    with pytest.raises(ValueError):
        ops.fcnn_layer(x, w, b, "swish", force="pallas_interpret")
