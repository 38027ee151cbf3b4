"""Shared model primitives — pure-functional JAX, sharding-annotated.

Conventions:
  * params are dict pytrees of jnp arrays; initializers take an rng key.
  * activations run in cfg.dtype (bf16), matmuls accumulate in fp32 via
    preferred_element_type, norms/softmax in fp32.
  * every primitive takes logical-axis annotations from parallel.sharding.
"""

from __future__ import annotations

import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.parallel.sharding import shard_constraint

__all__ = [
    "rms_norm", "init_rms_norm",
    "init_dense", "dense",
    "init_embedding", "embed", "unembed",
    "rope_freqs", "apply_rope", "apply_mrope",
    "init_attention", "attention", "decode_attention",
    "init_mlp", "mlp",
    "cross_entropy_loss",
]

Params = dict[str, Any]

# Dynamically-scoped matmul output dtype (preferred_element_type).  f32 by
# default; the bf16comm perf variant sets bf16 — on TPU the MXU still
# accumulates in f32 internally, this only narrows cross-shard partial sums
# and the backward all-reduces to bf16 (halving their bytes).  Norms, RoPE
# and softmax stay f32 regardless.
_PET = [jnp.float32]


class use_accum_dtype:
    def __init__(self, dtype):
        self.dtype = jnp.dtype(dtype)

    def __enter__(self):
        _PET.append(self.dtype)
        return self.dtype

    def __exit__(self, *exc):
        _PET.pop()
        return False


def pet():
    return _PET[-1]


# --------------------------------------------------------------------------
# norms
# --------------------------------------------------------------------------

def init_rms_norm(d: int, dtype) -> Params:
    return {"scale": jnp.ones((d,), dtype=dtype)}

def rms_norm(p: Params, x: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    y = x32 * jax.lax.rsqrt(var + eps)
    return (y * p["scale"].astype(jnp.float32)).astype(dt)


# --------------------------------------------------------------------------
# dense
# --------------------------------------------------------------------------

def init_dense(key, d_in: int, d_out: int, dtype, bias: bool = False) -> Params:
    scale = 1.0 / math.sqrt(d_in)
    w = jax.random.normal(key, (d_in, d_out), dtype=jnp.float32) * scale
    p = {"w": w.astype(dtype)}
    if bias:
        p["b"] = jnp.zeros((d_out,), dtype=dtype)
    return p

def dense(p: Params, x: jax.Array) -> jax.Array:
    y = jnp.einsum("...d,df->...f", x, p["w"],
                   preferred_element_type=pet())
    if "b" in p:
        y = y + p["b"].astype(jnp.float32)
    return y.astype(x.dtype)


# --------------------------------------------------------------------------
# embeddings
# --------------------------------------------------------------------------

def init_embedding(key, vocab: int, d: int, dtype) -> Params:
    w = jax.random.normal(key, (vocab, d), dtype=jnp.float32) * 0.02
    return {"w": w.astype(dtype)}

def embed(p: Params, tokens: jax.Array, onehot: bool = False,
          chunk: int = 512) -> jax.Array:
    if onehot:
        # one-hot matmul: SPMD-native (plain contraction over vocab) where
        # a gather with (data,model)-sharded indices vs model-sharded table
        # forces GSPMD into involuntary full rematerialization (a
        # replicated (B, S, d) gather output).  Chunked over length so the
        # (chunk, V) one-hot slab stays ~100 MB.  ~2·B·S·V/shards extra
        # MXU flops — noise next to a transformer block.
        b, l = tokens.shape
        if l % chunk:
            chunk = l

        def body(_, tok_c):
            oh = jax.nn.one_hot(tok_c, p["w"].shape[0], dtype=p["w"].dtype)
            out_c = jnp.einsum("blv,vd->bld", oh, p["w"],
                               preferred_element_type=pet())
            return None, out_c.astype(p["w"].dtype)

        tok = jnp.moveaxis(tokens.reshape(b, l // chunk, chunk), 1, 0)
        _, out = jax.lax.scan(body, None, tok)
        out = jnp.moveaxis(out, 0, 1).reshape(b, l, p["w"].shape[1])
    else:
        out = jnp.take(p["w"], tokens, axis=0)
    return shard_constraint(out, ("activation_batch", "residual_length",
                                  "activation_embed"))

def unembed(p: Params, x: jax.Array) -> jax.Array:
    logits = jnp.einsum("...d,vd->...v", x, p["w"],
                        preferred_element_type=pet())
    return shard_constraint(logits, ("activation_batch", "activation_length",
                                     "activation_vocab"))


# --------------------------------------------------------------------------
# RoPE (+ M-RoPE)
# --------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    """(head_dim/2,) inverse frequencies."""
    return 1.0 / (theta ** (np.arange(0, head_dim, 2) / head_dim))

def _rope_cos_sin(positions: jax.Array, inv_freq: jax.Array):
    # positions: (..., L) -> cos/sin (..., L, head_dim/2)
    ang = positions[..., None].astype(jnp.float32) * inv_freq
    return jnp.cos(ang), jnp.sin(ang)

def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (B, L, H, D); positions: (B, L)."""
    d = x.shape[-1]
    inv = rope_freqs(d, theta)
    cos, sin = _rope_cos_sin(positions, inv)       # (B, L, D/2)
    cos = cos[:, :, None, :]
    sin = sin[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)

def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections: tuple[int, ...]) -> jax.Array:
    """Qwen2-VL multimodal RoPE.

    positions: (3, B, L) — temporal / height / width position streams.
    ``sections`` splits head_dim/2 frequency slots among the 3 streams
    (e.g. (16, 24, 24) for head_dim 128).
    """
    d = x.shape[-1]
    if sum(sections) != d // 2:
        raise ValueError(f"mrope sections {sections} must sum to {d // 2}")
    inv = rope_freqs(d, theta)                     # (D/2,)
    # pick, per frequency slot, which positional stream drives it
    stream = np.repeat(np.arange(len(sections)), sections)   # (D/2,)
    pos_sel = jnp.take(positions, stream, axis=0)  # (D/2, B, L) gather streams
    pos_sel = jnp.moveaxis(pos_sel, 0, -1)         # (B, L, D/2)
    ang = pos_sel.astype(jnp.float32) * inv
    cos, sin = jnp.cos(ang)[:, :, None, :], jnp.sin(ang)[:, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# attention (GQA, optional qk-norm / bias / M-RoPE / windowing)
# --------------------------------------------------------------------------

def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, dtype, qkv_bias: bool = False,
                   qk_norm: bool = False) -> Params:
    ks = jax.random.split(key, 4)
    s_q = 1.0 / math.sqrt(d_model)
    p: Params = {
        "wq": (jax.random.normal(ks[0], (d_model, n_heads, head_dim)) * s_q).astype(dtype),
        "wk": (jax.random.normal(ks[1], (d_model, n_kv_heads, head_dim)) * s_q).astype(dtype),
        "wv": (jax.random.normal(ks[2], (d_model, n_kv_heads, head_dim)) * s_q).astype(dtype),
        "wo": (jax.random.normal(ks[3], (n_heads, head_dim, d_model))
               * (1.0 / math.sqrt(n_heads * head_dim))).astype(dtype),
    }
    if qkv_bias:
        p["bq"] = jnp.zeros((n_heads, head_dim), dtype=dtype)
        p["bk"] = jnp.zeros((n_kv_heads, head_dim), dtype=dtype)
        p["bv"] = jnp.zeros((n_kv_heads, head_dim), dtype=dtype)
    if qk_norm:
        p["q_norm"] = init_rms_norm(head_dim, dtype)
        p["k_norm"] = init_rms_norm(head_dim, dtype)
    return p


def attention_param_axes(qkv_bias: bool = False, qk_norm: bool = False) -> Params:
    p: Params = {
        "wq": ("embed", "heads", "head_dim"),
        "wk": ("embed", "kv_heads", "head_dim"),
        "wv": ("embed", "kv_heads", "head_dim"),
        "wo": ("heads", "head_dim", "embed"),
    }
    if qkv_bias:
        p["bq"] = ("heads", "head_dim")
        p["bk"] = ("kv_heads", "head_dim")
        p["bv"] = ("kv_heads", "head_dim")
    if qk_norm:
        p["q_norm"] = {"scale": ("head_dim",)}
        p["k_norm"] = {"scale": ("head_dim",)}
    return p


def _project_qkv(p: Params, x: jax.Array, positions, theta,
                 qk_norm: bool, eps: float, mrope_sections=()):
    q = jnp.einsum("bld,dhk->blhk", x, p["wq"],
                   preferred_element_type=pet()).astype(x.dtype)
    k = jnp.einsum("bld,dhk->blhk", x, p["wk"],
                   preferred_element_type=pet()).astype(x.dtype)
    v = jnp.einsum("bld,dhk->blhk", x, p["wv"],
                   preferred_element_type=pet()).astype(x.dtype)
    if "bq" in p:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    if qk_norm:
        q = rms_norm(p["q_norm"], q, eps)
        k = rms_norm(p["k_norm"], k, eps)
    if mrope_sections:
        q = apply_mrope(q, positions, theta, mrope_sections)
        k = apply_mrope(k, positions, theta, mrope_sections)
    else:
        q = apply_rope(q, positions, theta)
        k = apply_rope(k, positions, theta)
    q = shard_constraint(q, ("activation_batch", "activation_length",
                             "activation_heads", None))
    k = shard_constraint(k, ("activation_batch", "activation_length",
                             "activation_kv_heads", None))
    v = shard_constraint(v, ("activation_batch", "activation_length",
                             "activation_kv_heads", None))
    return q, k, v


def _sdpa(q, k, v, mask, scale: float | None = None):
    """q: (B,Lq,H,D); k: (B,Lk,KV,D); v: (B,Lk,KV,Dv); GQA via head
    grouping; scores scaled by ``scale``, 1/sqrt(D) where None."""
    b, lq, h, d = q.shape
    kv = k.shape[2]
    g = h // kv
    qg = q.reshape(b, lq, kv, g, d)
    logits = jnp.einsum("blkgd,bmkd->bkglm", qg, k,
                        preferred_element_type=jnp.float32)
    logits = logits / math.sqrt(d) if scale is None else logits * scale
    logits = jnp.where(mask, logits, -1e30)
    w = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkglm,bmkd->blkgd", w.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, lq, h, v.shape[-1]).astype(q.dtype)


# Above this many score elements per head-group, attention switches to the
# kv-chunked online-softmax path (flash-style: O(L·chunk) live memory).
# On TPU, latent attention (models/deepseek_v2.mla) runs the Pallas kernel
# (kernels/flash_attention.py) in its place; ``attention`` below and every
# other model keep this jnp scan on every backend.
_CHUNKED_SDPA_THRESHOLD = 4096 * 4096
_SDPA_CHUNK = 1024


def scan_unroll_of(cfg) -> bool | int:
    """lax.scan unroll argument honoring the dry-run cost probes."""
    return True if getattr(cfg, "probe_unroll", False) else 1


def remat_wrap(cfg, body):
    """Apply the configured activation-checkpoint policy to a scan body."""
    if not cfg.remat:
        return body
    if getattr(cfg, "remat_policy", "full") == "dots":
        policy = jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    else:
        policy = jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint(body, policy=policy)


def _softmax_scale(d: int, scale: float | None) -> float:
    return 1.0 / math.sqrt(d) if scale is None else scale


def _flash_fwd_core(qg, kc, vc, chunk, unroll, scale=None):
    """qg: (B,Lq,KV,G,D); kc: (B,NC,chunk,KV,D); vc: (B,NC,chunk,KV,Dv)
    -> out grouped (B,KV,G,Lq,Dv) + lse."""
    b, lq = qg.shape[0], qg.shape[1]
    kv, g, d = qg.shape[2], qg.shape[3], qg.shape[4]
    dv = vc.shape[-1]
    nc = kc.shape[1]
    scale = _softmax_scale(d, scale)
    rows = jnp.arange(lq)

    def body(carry, xs):
        m_prev, l_prev, acc = carry
        ci, kb, vb = xs
        s = jnp.einsum("blkgd,bmkd->bkglm", qg, kb,
                       preferred_element_type=jnp.float32) * scale
        cols = ci * chunk + jnp.arange(chunk)
        causal = rows[:, None] >= cols[None, :]
        s = jnp.where(causal[None, None, None], s, -1e30)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1))
        p = jnp.exp(s - m_new[..., None])
        alpha = jnp.exp(m_prev - m_new)
        l_new = l_prev * alpha + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bkglm,bmkd->bkgld", p.astype(vb.dtype), vb,
                         preferred_element_type=jnp.float32)
        acc = acc * alpha[..., None] + upd
        return (m_new, l_new, acc), None

    m0 = jnp.full((b, kv, g, lq), -1e30, jnp.float32)
    l0 = jnp.zeros((b, kv, g, lq), jnp.float32)
    a0 = jnp.zeros((b, kv, g, lq, dv), jnp.float32)
    (m_f, l_f, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (jnp.arange(nc), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)),
        unroll=unroll)
    out_g = acc / l_f[..., None]                          # (B,KV,G,Lq,D) f32
    lse = m_f + jnp.log(l_f)                              # (B,KV,G,Lq)
    return out_g, lse


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5))
def _sdpa_chunked_causal(q, k, v, chunk: int = _SDPA_CHUNK,
                         unroll: bool | int = 1, scale: float | None = None):
    """Causal flash attention, kv-chunked online softmax with a flash-style
    custom VJP: the backward recomputes per-chunk probabilities from the
    saved log-sum-exp instead of letting scan-AD stack O(Lq·Lk) residuals
    (which would erase the memory win: 3×2.7 GB per layer at 4k before
    this VJP existed).

    q: (B,Lq,H,D); k: (B,Lk,KV,D); v: (B,Lk,KV,Dv), where Dv may differ
    from D (latent attention); Lq == Lk (self-attention prefill).  The
    scores are scaled by ``scale``, 1/sqrt(D) where None.
    """
    out, _ = _sdpa_chunked_fwd(q, k, v, chunk, unroll, scale)
    return out


def _sdpa_chunked_fwd(q, k, v, chunk, unroll, scale=None):
    b, lq, h, d = q.shape
    lk, kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kv
    nc = lk // chunk
    qg = q.reshape(b, lq, kv, g, d)
    kc = k.reshape(b, nc, chunk, kv, d)
    vc = v.reshape(b, nc, chunk, kv, dv)
    out_g, lse = _flash_fwd_core(qg, kc, vc, chunk, unroll, scale)
    # (B,KV,G,Lq,Dv) -> (B,Lq,KV,G,Dv)
    out = jnp.moveaxis(out_g, 3, 1).reshape(b, lq, h, dv).astype(q.dtype)
    return out, (q, k, v, out, lse)


def _sdpa_chunked_bwd(chunk, unroll, scale, res, dout):
    q, k, v, out, lse = res
    b, lq, h, d = q.shape
    lk, kv = k.shape[1], k.shape[2]
    dv = v.shape[-1]
    g = h // kv
    nc = lk // chunk
    scale = _softmax_scale(d, scale)
    qg = q.reshape(b, lq, kv, g, d).astype(jnp.float32)
    og = jnp.moveaxis(dout.reshape(b, lq, kv, g, dv), 1, 3).astype(jnp.float32)
    outg = jnp.moveaxis(out.reshape(b, lq, kv, g, dv), 1, 3).astype(jnp.float32)
    # delta[r] = sum_d out[r,d] * dout[r,d]  (flash-bwd row correction)
    delta = jnp.sum(outg * og, axis=-1)                   # (B,KV,G,Lq)
    kc = k.reshape(b, nc, chunk, kv, d)
    vc = v.reshape(b, nc, chunk, kv, dv)
    rows = jnp.arange(lq)

    def body(dq_acc, xs):
        ci, kb, vb = xs
        s = jnp.einsum("blkgd,bmkd->bkglm", qg.astype(q.dtype), kb,
                       preferred_element_type=jnp.float32) * scale
        cols = ci * chunk + jnp.arange(chunk)
        causal = rows[:, None] >= cols[None, :]
        s = jnp.where(causal[None, None, None], s, -1e30)
        p = jnp.exp(s - lse[..., None])                   # (B,KV,G,Lq,chunk)
        dv_c = jnp.einsum("bkglm,bkgld->bmkd", p.astype(og.dtype), og,
                          preferred_element_type=jnp.float32)
        dp = jnp.einsum("bkgld,bmkd->bkglm", og.astype(vb.dtype), vb,
                        preferred_element_type=jnp.float32)
        ds = p * (dp - delta[..., None]) * scale
        dq_c = jnp.einsum("bkglm,bmkd->blkgd", ds.astype(kb.dtype), kb,
                          preferred_element_type=jnp.float32)
        dk_c = jnp.einsum("bkglm,blkgd->bmkd", ds.astype(q.dtype),
                          qg.astype(q.dtype),
                          preferred_element_type=jnp.float32)
        return dq_acc + dq_c, (dk_c, dv_c)

    dq0 = jnp.zeros((b, lq, kv, g, d), jnp.float32)
    dq, (dk_c, dv_c) = jax.lax.scan(
        body, dq0,
        (jnp.arange(nc), jnp.moveaxis(kc, 1, 0), jnp.moveaxis(vc, 1, 0)),
        unroll=unroll)
    dk = jnp.moveaxis(dk_c, 0, 1).reshape(b, lk, kv, d).astype(k.dtype)
    dval = jnp.moveaxis(dv_c, 0, 1).reshape(b, lk, kv, dv).astype(v.dtype)
    return dq.reshape(b, lq, h, d).astype(q.dtype), dk, dval


_sdpa_chunked_causal.defvjp(_sdpa_chunked_fwd, _sdpa_chunked_bwd)


def attention(p: Params, x: jax.Array, positions: jax.Array, *,
              theta: float, qk_norm: bool = False, eps: float = 1e-6,
              mrope_sections: tuple[int, ...] = (),
              kv_override: tuple[jax.Array, jax.Array] | None = None,
              causal: bool = True, window: int = 0,
              unroll: bool | int = 1,
              chunk_threshold: int = _CHUNKED_SDPA_THRESHOLD) -> jax.Array:
    """Full (prefill/train) attention.  kv_override enables cross-attention."""
    q, k, v = _project_qkv(p, x, positions, theta, qk_norm, eps, mrope_sections)
    if kv_override is not None:
        k, v = kv_override
    lq, lk = q.shape[1], k.shape[1]
    plain_causal = causal and kv_override is None and (window == 0 or window >= lk)
    if (plain_causal and lq == lk and lq * lk > chunk_threshold
            and lk % _SDPA_CHUNK == 0):
        out = _sdpa_chunked_causal(q, k, v, unroll=unroll)
    else:
        if causal and kv_override is None:
            idx_q = jnp.arange(lq)[:, None]
            idx_k = jnp.arange(lk)[None, :]
            mask = idx_k <= idx_q
            if window > 0:
                mask &= idx_k > idx_q - window
            mask = mask[None, None, None, :, :]
        else:
            mask = jnp.ones((1, 1, 1, lq, lk), dtype=bool)
        out = _sdpa(q, k, v, mask)
    y = jnp.einsum("blhd,hdm->blm", out, p["wo"],
                   preferred_element_type=pet()).astype(x.dtype)
    return shard_constraint(y, ("activation_batch", "residual_length",
                                "activation_embed"))


def prefill_attention_kv(p: Params, x, positions, *, theta, qk_norm=False,
                         eps=1e-6, mrope_sections=()):
    """Return (k, v) for cache seeding."""
    _, k, v = _project_qkv(p, x, positions, theta, qk_norm, eps, mrope_sections)
    return k, v


def decode_attention(p: Params, x: jax.Array, cache_k: jax.Array,
                     cache_v: jax.Array, cache_len: jax.Array,
                     positions: jax.Array, *, theta: float,
                     qk_norm: bool = False, eps: float = 1e-6,
                     mrope_sections: tuple[int, ...] = (),
                     window: int = 0,
                     write_pos: jax.Array | None = None):
    """One decode step.  x: (B,1,d); cache_k/v: (B,S,KV,D); cache_len: (B,).

    ``write_pos`` overrides the slot the new KV lands in (ring-buffer
    caches pass cache_len % S; RoPE is applied before caching so key order
    in the buffer is irrelevant).  Returns (y, new_cache_k, new_cache_v).
    """
    q, k, v = _project_qkv(p, x, positions, theta, qk_norm, eps, mrope_sections)
    b, s = cache_k.shape[0], cache_k.shape[1]
    wp = cache_len if write_pos is None else write_pos
    # scatter-write only the touched slot — a one-hot multiply would
    # read+rewrite the full (B,S,KV,D) cache every decode step (measured
    # ~2 cache-sizes of HBM traffic per layer; see EXPERIMENTS.md §Perf)
    bidx = jnp.arange(b)
    cache_k = cache_k.at[bidx, wp].set(k[:, 0], mode="drop")
    cache_v = cache_v.at[bidx, wp].set(v[:, 0], mode="drop")
    idx = jnp.arange(s)[None, :]
    mask = idx <= cache_len[:, None]
    if window > 0:
        mask &= idx > (cache_len[:, None] - window)
    mask = mask[:, None, None, None, :]                          # (B,1,1,1,S)
    out = _sdpa(q, cache_k, cache_v, mask)
    y = jnp.einsum("blhd,hdm->blm", out, p["wo"],
                   preferred_element_type=pet()).astype(x.dtype)
    return y, cache_k, cache_v


# --------------------------------------------------------------------------
# MLP (SwiGLU)
# --------------------------------------------------------------------------

def init_mlp(key, d_model: int, d_ff: int, dtype) -> Params:
    ks = jax.random.split(key, 3)
    s_in = 1.0 / math.sqrt(d_model)
    s_out = 1.0 / math.sqrt(d_ff)
    return {
        "w_gate": (jax.random.normal(ks[0], (d_model, d_ff)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[1], (d_model, d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[2], (d_ff, d_model)) * s_out).astype(dtype),
    }

MLP_AXES = {
    "w_gate": ("embed", "mlp"),
    "w_up": ("embed", "mlp"),
    "w_down": ("mlp", "embed"),
}

def mlp(p: Params, x: jax.Array) -> jax.Array:
    g = jnp.einsum("...d,df->...f", x, p["w_gate"],
                   preferred_element_type=pet())
    u = jnp.einsum("...d,df->...f", x, p["w_up"],
                   preferred_element_type=pet())
    h = (jax.nn.silu(g) * u).astype(x.dtype)
    h = shard_constraint(h, ("activation_batch", "activation_length",
                             "activation_mlp"))
    y = jnp.einsum("...f,fd->...d", h, p["w_down"],
                   preferred_element_type=pet()).astype(x.dtype)
    return shard_constraint(y, ("activation_batch", "residual_length",
                                "activation_embed"))


# --------------------------------------------------------------------------
# loss
# --------------------------------------------------------------------------

def cross_entropy_loss(logits: jax.Array, labels: jax.Array,
                       mask: jax.Array | None = None) -> jax.Array:
    """Mean token cross-entropy in fp32. logits (B,L,V), labels (B,L)."""
    logits = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(logits, axis=-1)
    gold = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - gold
    if mask is None:
        return jnp.mean(nll)
    mask = mask.astype(jnp.float32)
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def fused_unembed_ce(emb: Params, h: jax.Array, labels: jax.Array,
                     chunk: int = 512, unroll: bool | int = 1) -> jax.Array:
    """Fused unembed + cross-entropy, chunked over length: the (B, L, V)
    logits tensor is never materialized — each scan step computes one
    (B, chunk, V) slab, reduces it to (lse, gold) and discards it.  The
    Megatron fused-loss pattern; removes ~B*L*V*(2+4) bytes of HBM
    residency for free (the slabs were going to be computed anyway)."""
    b, l, d = h.shape
    if l % chunk:
        return cross_entropy_loss(unembed(emb, h), labels)
    hc = h.reshape(b, l // chunk, chunk, d)
    lc = labels.reshape(b, l // chunk, chunk)

    def body(acc, xs):
        h_c, lab_c = xs                                # (B,chunk,d),(B,chunk)
        logits = jnp.einsum("bld,vd->blv", h_c, emb["w"],
                            preferred_element_type=jnp.float32)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, lab_c[..., None], axis=-1)[..., 0]
        return acc + jnp.sum(lse - gold), None

    total, _ = jax.lax.scan(body, jnp.zeros((), jnp.float32),
                            (jnp.moveaxis(hc, 1, 0), jnp.moveaxis(lc, 1, 0)),
                            unroll=unroll)
    return total / (b * l)
