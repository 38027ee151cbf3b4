"""Per-architecture smoke tests: reduced config of the same family, one
forward/train step on CPU, asserting output shapes and no NaNs — plus
prefill/decode consistency against the full forward pass."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import list_archs, smoke_config
from repro.models.api import get_model

KEY = jax.random.PRNGKey(0)


def _lm_batch(cfg, b=2, s=16):
    if cfg.family in ("ssm", "hybrid"):
        s = max(s, cfg.ssm_chunk)
        s = (s // cfg.ssm_chunk) * cfg.ssm_chunk
    tok = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
    return {"tokens": tok, "labels": tok}, b, s


def _batch_for(cfg, b=2, s=16):
    if cfg.family == "vlm":
        emb = jax.random.normal(KEY, (b, s, cfg.d_model), jnp.float32)
        pos = jnp.broadcast_to(jnp.arange(s, dtype=jnp.int32), (b, s))
        pos3 = jnp.broadcast_to(pos, (3, b, s))
        lab = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
        return {"embeds": emb, "positions": pos3, "labels": lab}, b, s
    if cfg.family == "encdec":
        enc = jax.random.normal(KEY, (b, s, cfg.d_model), jnp.float32)
        dec = jax.random.randint(KEY, (b, s), 0, cfg.vocab_size)
        return {"enc_embeds": enc, "dec_tokens": dec, "labels": dec}, b, s
    return _lm_batch(cfg, b, s)


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_forward_and_train_step(arch):
    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = model.init(KEY)
    batch, b, s = _batch_for(cfg)

    logits = model.forward(params, batch)
    assert logits.shape == (b, s, cfg.padded_vocab)
    assert bool(jnp.all(jnp.isfinite(logits)))

    loss, grads = jax.value_and_grad(model.loss_fn)(params, batch)
    assert bool(jnp.isfinite(loss))
    leaves = jax.tree.leaves(grads)
    assert all(bool(jnp.all(jnp.isfinite(g))) for g in leaves)
    assert any(float(jnp.max(jnp.abs(g))) > 0 for g in leaves)


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_param_axes_structure(arch):
    """Every param leaf has a same-rank logical-axes tuple."""
    cfg = smoke_config(arch)
    model = get_model(cfg)
    spec = jax.eval_shape(model.init, KEY)
    axes = model.param_axes()
    spec_leaves = jax.tree_util.tree_flatten(spec)[0]
    is_ax = lambda x: x is None or (isinstance(x, tuple) and all(  # noqa: E731
        i is None or isinstance(i, str) for i in x))
    axes_leaves = jax.tree_util.tree_flatten(axes, is_leaf=is_ax)[0]
    assert len(spec_leaves) == len(axes_leaves)
    for sp, ax in zip(spec_leaves, axes_leaves):
        if ax is not None:
            assert len(ax) == len(sp.shape), f"{arch}: {ax} vs {sp.shape}"


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_prefill_decode_matches_forward(arch):
    cfg = smoke_config(arch)
    model = get_model(cfg)
    params = model.init(KEY)
    batch, b, s = _batch_for(cfg)
    max_len = s + 4

    kw = {"enc_len": s} if cfg.family == "encdec" else {}
    logits, cache = model.prefill(params, batch, max_len)
    assert logits.shape == (b, 1, cfg.padded_vocab)
    nxt = jnp.argmax(logits, -1).astype(jnp.int32)
    logits2, cache2 = model.decode_step(params, cache, {"tokens": nxt})
    assert bool(jnp.all(cache2["len"] == cache["len"] + 1))

    # extend the original sequence by the decoded token; the full forward
    # at position s must match the decode-step logits
    if cfg.family == "vlm":
        emb_tok = jnp.take(params["embedding"]["w"], nxt, axis=0)
        ext = dict(batch)
        ext["embeds"] = jnp.concatenate([batch["embeds"], emb_tok], axis=1)
        pos = jnp.broadcast_to(jnp.arange(s + 1, dtype=jnp.int32), (b, s + 1))
        ext["positions"] = jnp.broadcast_to(pos, (3, b, s + 1))
        full = model.forward(params, ext)
    elif cfg.family == "encdec":
        ext = dict(batch)
        ext["dec_tokens"] = jnp.concatenate([batch["dec_tokens"], nxt], 1)
        full = model.forward(params, ext)
    else:
        toks = jnp.concatenate([batch["tokens"], nxt], axis=1)
        if cfg.family in ("ssm", "hybrid"):
            pad = (-toks.shape[1]) % cfg.ssm_chunk
            toks = jnp.pad(toks, ((0, 0), (0, pad)))
        full = model.forward(params, {"tokens": toks})
    err = float(jnp.max(jnp.abs(full[:, s, :] - logits2[:, 0, :])))
    assert err < 2e-4, f"{arch}: decode/forward mismatch {err}"


@pytest.mark.parametrize("arch", list_archs())
def test_smoke_input_specs_cover_shapes(arch):
    from repro.configs import SHAPES, shape_cells, smoke_config

    cfg = smoke_config(arch)
    model = get_model(cfg)
    for shape_name, runnable, _ in shape_cells(cfg):
        if not runnable:
            continue
        specs = model.input_specs(SHAPES[shape_name])
        assert specs, f"{arch}/{shape_name} has empty input specs"
        for k, v in specs.items():
            assert v.shape[0] in (SHAPES[shape_name].global_batch, 3), k
