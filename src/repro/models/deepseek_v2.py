"""DeepSeek-V2 decoder LM (DeepSeek-V2-Lite, arXiv:2405.04434): latent
attention (MLA) with YaRN RoPE in every layer, ``first_k_dense`` leading
dense SwiGLU layers, then MoE layers of routed and shared experts
(``models/moe.py``'s dropless layer, told which experts it holds).

Equations follow HF ``modeling_deepseek.py`` (``q_lora_rank`` null):

* MLA.  q = x W_q, (H, d_nope + d_rope), split into q_nope and q_pe;
  [c_kv, k_pe] = x W_kva; c_kv ← RMSNorm(c_kv); [k_nope, v] = c_kv W_kvb,
  (H, d_nope + d_v); k_pe is one head, broadcast to all H.  RoPE acts on
  q_pe and k_pe only, HF's way: each is de-interleaved (even lanes, then
  odd) before rotate-half.  Causal softmax((q·k) · s) v over heads, times
  W_o, with s = (d_nope + d_rope)^-½ · m², m = 0.1 · mscale_all_dim ·
  ln(factor) + 1.
* YaRN inverse frequencies over d_rope: θ^(−2i/d) blended with
  θ^(−2i/d) / factor by a linear ramp over the correction range of
  β_fast and β_slow at the original context length.
* Block: x + MLA(RMSNorm x), then + FFN(RMSNorm ·); the dense layers run
  once each, then a ``lax.scan`` over the MoE layers, each layer
  rematerialised as ``cfg.remat_policy`` says.
* Loss: mean next-token cross-entropy through ``kernels/ops.softmax_xent``
  on f32 logits, plus ``router_aux_coef`` × Σ over MoE layers of the
  sequence-level balance loss.

Weights are stored in ``param_dtype`` (f32 master weights) and cast to
``dtype`` (bf16) where they are used; matmuls accumulate in f32, norms,
softmax and the router run in f32.  Every weight matrix is drawn
N(0, ``init_std``²) from ``fold_in(key, crc32(path))``, where ``path``
is the leaf's place in the tree (``"layers/moe/w_gate"``), so the draw
depends on a leaf's name and shape alone; norm scales start at 1.
"""

from __future__ import annotations

import math
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro import obs
from repro.configs.base import ModelConfig
from repro.kernels import ops
from repro.kernels.flash_attention import select_blocks as flash_blocks
from repro.models import layers as L
from repro.models import moe
from repro.models import transformer as T

Params = dict[str, Any]


# --------------------------------------------------------------- weights

def _shapes(cfg: ModelConfig) -> Params:
    """Every leaf's shape, the layers of a kind stacked on a leading
    axis."""
    d, h = cfg.d_model, cfg.n_heads
    dn, dr, dv, r = (cfg.qk_nope_head_dim, cfg.qk_rope_head_dim,
                     cfg.v_head_dim, cfg.kv_lora_rank)

    def block(n: int, ffn: Params) -> Params:
        return {
            "ln1": {"scale": (n, d)},
            "attn": {"wq": (n, d, h, dn + dr), "wkv_a": (n, d, r + dr),
                     "kv_norm": {"scale": (n, r)},
                     "wkv_b": (n, r, h, dn + dv), "wo": (n, h, dv, d)},
            "ln2": {"scale": (n, d)},
            **ffn,
        }

    def swiglu(n: int, f: int) -> Params:
        return {"w_gate": (n, d, f), "w_up": (n, d, f), "w_down": (n, f, d)}

    lo, hi = cfg.held_experts
    n_moe = cfg.n_layers - cfg.first_k_dense
    f = cfg.moe_d_ff
    experts = {"w_gate": (n_moe, hi - lo, d, f), "w_up": (n_moe, hi - lo, d, f),
               "w_down": (n_moe, hi - lo, f, d)}
    out: Params = {
        "embedding": {"w": (cfg.vocab_size, d)},
        "dense": block(cfg.first_k_dense,
                       {"mlp": swiglu(cfg.first_k_dense, cfg.d_ff)}),
        "layers": block(n_moe, {"moe": {
            "router": (n_moe, d, cfg.n_experts), **experts,
            "shared": swiglu(n_moe, cfg.n_shared_experts * f)}}),
        "final_norm": {"scale": (d,)},
        "unembed": {"w": (cfg.vocab_size, d)},
    }
    return out


def _is_shape(x) -> bool:
    return isinstance(x, tuple)


def init(key, cfg: ModelConfig) -> Params:
    dtype = jnp.dtype(cfg.param_dtype)

    def leaf(path, shape):
        name = "/".join(str(getattr(k, "key", k)) for k in path)
        if name.endswith("scale"):
            return jnp.ones(shape, dtype)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))
        return (jax.random.normal(k, shape, jnp.float32)
                * cfg.init_std).astype(dtype)

    return jax.tree_util.tree_map_with_path(leaf, _shapes(cfg),
                                            is_leaf=_is_shape)


def param_axes(cfg: ModelConfig) -> Params:
    def block(ffn: Params) -> Params:
        return {
            "ln1": {"scale": ("layers", None)},
            "attn": {"wq": ("layers", "embed", "heads", "head_dim"),
                     "wkv_a": ("layers", "embed", None),
                     "kv_norm": {"scale": ("layers", None)},
                     "wkv_b": ("layers", None, "heads", "head_dim"),
                     "wo": ("layers", "heads", "head_dim", "embed")},
            "ln2": {"scale": ("layers", None)},
            **ffn,
        }

    mlp = {k: ("layers",) + ax for k, ax in L.MLP_AXES.items()}
    experts = {k: ("layers",) + tuple(ax) for k, ax in
               moe.moe_mlp_axes(cfg.replace(n_shared_experts=0)).items()}
    return {
        "embedding": {"w": ("vocab", "table_embed")},
        "dense": block({"mlp": mlp}),
        "layers": block({"moe": {**experts, "shared": mlp}}),
        "final_norm": {"scale": (None,)},
        "unembed": {"w": ("vocab", "table_embed")},
    }


def _cast(tree: Params, dtype) -> Params:
    return jax.tree.map(lambda a: a.astype(dtype), tree)


# ------------------------------------------------------------ YaRN RoPE

def yarn_mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope_inv_freq(cfg: ModelConfig) -> np.ndarray:
    """(d_rope / 2,) inverse frequencies, YaRN-blended where
    ``cfg.yarn_factor`` is set."""
    dim, base = cfg.qk_rope_head_dim, cfg.rope_theta
    freq_extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    if not cfg.yarn_factor:
        return freq_extra
    freq_inter = freq_extra / cfg.yarn_factor

    def correction(rotations: float) -> float:
        return (dim * math.log(cfg.yarn_original_max_pos
                               / (rotations * 2 * math.pi))
                / (2 * math.log(base)))

    low = max(math.floor(correction(cfg.yarn_beta_fast)), 0)
    high = min(math.ceil(correction(cfg.yarn_beta_slow)), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0.0, 1.0)
    mask = 1.0 - ramp
    return freq_inter * (1.0 - mask) + freq_extra * mask


def rope_scale(cfg: ModelConfig) -> float:
    """The factor on cos and sin: m(factor, mscale) / m(factor,
    mscale_all_dim)."""
    if not cfg.yarn_factor:
        return 1.0
    return (yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale)
            / yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim))


def softmax_scale(cfg: ModelConfig) -> float:
    s = (cfg.qk_nope_head_dim + cfg.qk_rope_head_dim) ** -0.5
    if cfg.yarn_factor and cfg.yarn_mscale_all_dim:
        m = yarn_mscale(cfg.yarn_factor, cfg.yarn_mscale_all_dim)
        s = s * m * m
    return s


def apply_rope(x: jax.Array, positions: jax.Array,
               cfg: ModelConfig) -> jax.Array:
    """x: (B, L, H, d_rope); positions: (B, L).  De-interleave, then
    rotate-half (HF DeepSeek-V2)."""
    b, l, h, d = x.shape
    ang = positions[..., None].astype(jnp.float32) * rope_inv_freq(cfg)
    ang = jnp.concatenate([ang, ang], axis=-1)[:, :, None, :]
    scale = rope_scale(cfg)
    cos, sin = jnp.cos(ang) * scale, jnp.sin(ang) * scale
    x = x.astype(jnp.float32).reshape(b, l, h, d // 2, 2)
    x = jnp.swapaxes(x, -1, -2).reshape(b, l, h, d)
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    rotated = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos + rotated * sin


# ------------------------------------------------------ latent attention

def _latent(p: Params, x: jax.Array, positions, cfg: ModelConfig):
    """(c_kv normed, k_pe roped (B, L, d_rope)) of x (B, L, d)."""
    pet = L.pet()
    r = cfg.kv_lora_rank
    kva = jnp.einsum("bld,dr->blr", x, p["wkv_a"], preferred_element_type=pet)
    c_kv = L.rms_norm(p["kv_norm"], kva[..., :r].astype(x.dtype),
                      cfg.norm_eps)
    k_pe = apply_rope(kva[..., None, r:], positions, cfg)[:, :, 0]
    return c_kv, k_pe.astype(x.dtype)


def _queries(p: Params, x: jax.Array, positions, cfg: ModelConfig):
    dn = cfg.qk_nope_head_dim
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"],
                   preferred_element_type=L.pet())
    q_pe = apply_rope(q[..., dn:], positions, cfg)
    return jnp.concatenate([q[..., :dn], q_pe], axis=-1).astype(x.dtype)


def _keys_values(p: Params, c_kv: jax.Array, k_pe: jax.Array,
                 cfg: ModelConfig):
    """k (B, S, H, d_nope + d_rope), v (B, S, H, d_v) from the latent."""
    dn = cfg.qk_nope_head_dim
    kv = jnp.einsum("bsr,rhk->bshk", c_kv, p["wkv_b"],
                    preferred_element_type=L.pet()).astype(c_kv.dtype)
    k_pe = jnp.broadcast_to(k_pe[:, :, None, :],
                            kv.shape[:3] + (k_pe.shape[-1],))
    return jnp.concatenate([kv[..., :dn], k_pe], axis=-1), kv[..., dn:]


def _out(p: Params, o: jax.Array, dtype, heads_first: bool = False):
    """o (B, L, H, d_v), or (B, H, L, d_v) where ``heads_first``, times
    W_o."""
    spec = "bhlv,hvd->bld" if heads_first else "blhv,hvd->bld"
    return jnp.einsum(spec, o, p["wo"],
                      preferred_element_type=L.pet()).astype(dtype)


def mla(p: Params, x: jax.Array, positions, cfg: ModelConfig) -> jax.Array:
    """Causal latent self-attention of x (B, L, d); p in x's dtype.

    The attention core runs in the Pallas flash kernel
    (``ops.flash_attention``, heads first) where the kernels dispatch to
    Pallas and a block divides L; else in the kv-chunked jnp scan above
    ``cfg.attn_chunk_threshold`` score elements, and in one softmax below
    it.  ``obs`` counts the calls traced onto the kernel and onto the
    scan."""
    c_kv, k_pe = _latent(p, x, positions, cfg)
    q = _queries(p, x, positions, cfg)
    k, v = _keys_values(p, c_kv, k_pe, cfg)
    lq = q.shape[1]
    scale = softmax_scale(cfg)
    mode = ops.resolve_mode(None)
    if mode != "ref" and flash_blocks(lq, q.shape[-1], v.shape[-1],
                                      q.dtype.itemsize) is not None:
        obs.bump(obs.ATTENTION_FLASH)
        o = ops.flash_attention(*(jnp.swapaxes(t, 1, 2) for t in (q, k, v)),
                                causal=True, scale=scale, force=mode)
        return _out(p, o, x.dtype, heads_first=True)
    if lq * lq > cfg.attn_chunk_threshold and lq % L._SDPA_CHUNK == 0:
        obs.bump(obs.ATTENTION_CHUNKED)
        o = L._sdpa_chunked_causal(q, k, v, L._SDPA_CHUNK,
                                   L.scan_unroll_of(cfg), scale)
    else:
        idx = jnp.arange(lq)
        mask = (idx[None, :] <= idx[:, None])[None, None, None]
        o = L._sdpa(q, k, v, mask, scale)
    return _out(p, o, x.dtype)


# ----------------------------------------------------------------- blocks

def _attend(p: Params, h: jax.Array, positions, cfg: ModelConfig):
    with jax.named_scope(obs.MLA):
        x = L.rms_norm(p["ln1"], h, cfg.norm_eps)
        return h + mla(_cast(p["attn"], h.dtype), x, positions, cfg)


def dense_block(p: Params, h: jax.Array, positions, cfg: ModelConfig):
    h = _attend(p, h, positions, cfg)
    with jax.named_scope(obs.DENSE_MLP):
        x = L.rms_norm(p["ln2"], h, cfg.norm_eps)
        return h + L.mlp(_cast(p["mlp"], h.dtype), x)


def moe_block(p: Params, h: jax.Array, positions, cfg: ModelConfig):
    """-> (h, balance loss, tokens per held expert)."""
    h = _attend(p, h, positions, cfg)
    with jax.named_scope(obs.MOE_ROUTE):
        x = L.rms_norm(p["ln2"], h, cfg.norm_eps)
    y, aux, counts = moe.moe_mlp(p["moe"], x, cfg)
    with jax.named_scope(obs.MOE_ROUTE):
        return h + y, aux, counts


def _layer(stacked: Params, i: int) -> Params:
    return jax.tree.map(lambda a: a[i], stacked)


def _embed(params: Params, tokens: jax.Array, cfg: ModelConfig):
    with jax.named_scope(obs.EMBED):
        return jnp.take(params["embedding"]["w"], tokens,
                        axis=0).astype(jnp.dtype(cfg.dtype))


def _trunk(params: Params, tokens: jax.Array, cfg: ModelConfig):
    """-> (final hidden (B, L, d), Σ balance loss, (MoE layers, held)
    token counts)."""
    h = _embed(params, tokens, cfg)
    with jax.named_scope(obs.LAYERS):
        positions = T._positions_of({"tokens": tokens}, cfg)
        for i in range(cfg.first_k_dense):
            h = L.remat_wrap(cfg, lambda lp, hh: dense_block(
                lp, hh, positions, cfg))(_layer(params["dense"], i), h)

        def body(carry, lp):
            hh, aux = carry
            hh, a, counts = moe_block(lp, hh, positions, cfg)
            return (hh, aux + a), counts

        (h, aux), counts = lax.scan(
            L.remat_wrap(cfg, body), (h, jnp.zeros((), jnp.float32)),
            params["layers"], unroll=L.scan_unroll_of(cfg))
    return h, aux, counts


def _logits(params: Params, h: jax.Array, cfg: ModelConfig) -> jax.Array:
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    return jnp.einsum("bld,vd->blv", h,
                      params["unembed"]["w"].astype(h.dtype),
                      preferred_element_type=jnp.float32)


def forward(params: Params, batch: Params, cfg: ModelConfig) -> jax.Array:
    """Train/prefill logits: (B, L, V) f32."""
    h, _, _ = _trunk(params, batch["tokens"], cfg)
    return _logits(params, h, cfg)


def loss_and_metrics(params: Params, batch: Params, cfg: ModelConfig):
    """(loss, {"expert_tokens_max", "expert_tokens_mean"}): the tokens
    routed this step to the busiest held expert of any MoE layer, and to a
    held expert on average."""
    h, aux, counts = _trunk(params, batch["tokens"], cfg)
    with jax.named_scope(obs.LOSS):
        logits = _logits(params, h, cfg)
        v = logits.shape[-1]
        ce = ops.softmax_xent(logits.reshape(-1, v),
                              batch["labels"].reshape(-1))
        loss = ce + cfg.router_aux_coef * aux
        counts = counts.astype(jnp.float32)
        metrics = {"expert_tokens_max": jnp.max(counts),
                   "expert_tokens_mean": jnp.mean(counts)}
    return loss, metrics


def loss_fn(params: Params, batch: Params, cfg: ModelConfig) -> jax.Array:
    return loss_and_metrics(params, batch, cfg)[0]


# ---------------------------------------------------------------- serving

def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> Params:
    """The latent cache: normed c_kv and roped k_pe per layer."""
    dtype = jnp.dtype(cfg.dtype)

    def stack(n: int) -> Params:
        return {"c_kv": jnp.zeros((n, batch, max_len, cfg.kv_lora_rank),
                                  dtype),
                "k_pe": jnp.zeros((n, batch, max_len, cfg.qk_rope_head_dim),
                                  dtype)}

    return {"dense": stack(cfg.first_k_dense),
            "layers": stack(cfg.n_layers - cfg.first_k_dense),
            "len": jnp.zeros((batch,), jnp.int32)}


def cache_axes(cfg: ModelConfig) -> Params:
    ax = ("layers", "cache_batch", "cache_length", None)
    return {"dense": {"c_kv": ax, "k_pe": ax},
            "layers": {"c_kv": ax, "k_pe": ax}, "len": ("cache_batch",)}


def _ffn(p: Params, h: jax.Array, cfg: ModelConfig, dense: bool):
    x = L.rms_norm(p["ln2"], h, cfg.norm_eps)
    if dense:
        return h + L.mlp(_cast(p["mlp"], h.dtype), x)
    return h + moe.moe_mlp(p["moe"], x, cfg)[0]


def prefill(params: Params, batch: Params, cfg: ModelConfig, max_len: int):
    """Run the prompt, fill the latent cache, return last-token logits."""
    tokens = batch["tokens"]
    b, s = tokens.shape
    h = _embed(params, tokens, cfg)
    positions = T._positions_of(batch, cfg)

    def run(stacked: Params, h, dense: bool):
        def body(hh, lp):
            x = L.rms_norm(lp["ln1"], hh, cfg.norm_eps)
            attn = _cast(lp["attn"], hh.dtype)
            latent = _latent(attn, x, positions, cfg)
            hh = hh + mla(attn, x, positions, cfg)
            return _ffn(lp, hh, cfg, dense), latent

        h, (c_kv, k_pe) = lax.scan(body, h, stacked)
        pad = ((0, 0), (0, 0), (0, max_len - s), (0, 0))
        return h, {"c_kv": jnp.pad(c_kv, pad), "k_pe": jnp.pad(k_pe, pad)}

    h, dense = run(params["dense"], h, True)
    h, layers = run(params["layers"], h, False)
    cache = {"dense": dense, "layers": layers,
             "len": jnp.full((b,), s, jnp.int32)}
    return _logits(params, h[:, -1:], cfg), cache


def decode_step(params: Params, cache: Params, batch: Params,
                cfg: ModelConfig):
    """One token for every sequence.  batch["tokens"]: (B, 1)."""
    h = _embed(params, batch["tokens"], cfg)
    b = h.shape[0]
    n = cache["len"]
    positions = n[:, None]
    rows = jnp.arange(b)

    def run(stacked: Params, c: Params, h, dense: bool):
        def body(hh, xs):
            lp, c_kv, k_pe = xs
            x = L.rms_norm(lp["ln1"], hh, cfg.norm_eps)
            attn = _cast(lp["attn"], hh.dtype)
            c_new, pe_new = _latent(attn, x, positions, cfg)
            c_kv = c_kv.at[rows, n].set(c_new[:, 0], mode="drop")
            k_pe = k_pe.at[rows, n].set(pe_new[:, 0], mode="drop")
            k, v = _keys_values(attn, c_kv, k_pe, cfg)
            mask = (jnp.arange(c_kv.shape[1])[None, :] <= n[:, None])
            o = L._sdpa(_queries(attn, x, positions, cfg), k, v,
                        mask[:, None, None, None, :], softmax_scale(cfg))
            hh = hh + _out(attn, o, hh.dtype)
            return _ffn(lp, hh, cfg, dense), (c_kv, k_pe)

        h, (c_kv, k_pe) = lax.scan(body, h, (stacked, c["c_kv"], c["k_pe"]))
        return h, {"c_kv": c_kv, "k_pe": k_pe}

    h, dense = run(params["dense"], cache["dense"], h, True)
    h, layers = run(params["layers"], cache["layers"], h, False)
    new_cache = {"dense": dense, "layers": layers, "len": n + 1}
    return _logits(params, h, cfg), new_cache
