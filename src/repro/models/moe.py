"""Token-choice top-k Mixture-of-Experts transformer (granite-3.0-moe,
qwen2-moe with shared experts), and the expert layer that DeepSeek-V2
(``models/deepseek_v2.py``) shares.

The expert layer is dropless and is told which experts it holds
(``cfg.held_experts``, [lo, hi) of ``n_experts``): the router scores
every expert and picks each token's top k, and the layer computes only
its own experts' part of the result.  It sorts the (token, slot) pairs by
held expert (pairs bound for an absent expert sort last), gathers their
rows and runs each expert's SwiGLU as ``jax.lax.ragged_dot`` grouped
matmuls over the sorted rows, then adds weight × output back to each
token.  No expert has a capacity, so no token is dropped: the grouped
matmuls take all tokens × k sorted pairs, the held ones first, and the
group sizes end where the held pairs do.  An absent expert's part is left
out, as expert parallelism leaves it to the chip that holds it; a model
holding every expert computes the whole layer.

Expert parallelism: under a mesh whose rules split the ``experts`` axis
(``"model"`` by default), the routed part runs under ``shard_map``.  Each
device holds its slice of the held experts and sorts and computes the
pairs bound for them among its batch rows' tokens (whole over the expert
axes, split over the others), and a sum over the expert axes adds the
devices' parts — the parts of disjoint held ranges add up to the whole
layer.

The router's sequence-level load-balance loss (DeepSeek-V2's, over all
experts) is threaded through the layer-scan carry — no out-of-band state,
no leaked tracers.

ONoC-planner note (DESIGN.md §Arch-applicability): experts map onto the
paper's "neurons evenly mapped to m_i cores" with the all-to-all replacing
the ring broadcast; g() gains an all-to-all term in core/planner.py.
"""

from __future__ import annotations

import math
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro import obs
from repro.configs.base import ModelConfig
from repro.models import layers as L
from repro.models import transformer as T
from repro.parallel.sharding import shard_constraint, split_axes

Params = dict[str, Any]


def init_moe_mlp(key, cfg: ModelConfig) -> Params:
    ks = jax.random.split(key, 5)
    dtype = jnp.dtype(cfg.param_dtype)
    lo, hi = cfg.held_experts
    e, d, f = hi - lo, cfg.d_model, cfg.moe_d_ff
    s_in, s_out = 1.0 / math.sqrt(d), 1.0 / math.sqrt(f)
    p: Params = {
        "router": (jax.random.normal(ks[0], (d, cfg.n_experts))
                   * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d)) * s_out).astype(dtype),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = L.init_mlp(
            ks[4], d, cfg.n_shared_experts * cfg.moe_d_ff, dtype)
    return p


def moe_mlp_axes(cfg: ModelConfig) -> Params:
    p: Params = {
        "router": ("embed", None),
        "w_gate": ("experts", "embed", "expert_mlp"),
        "w_up": ("experts", "embed", "expert_mlp"),
        "w_down": ("experts", "expert_mlp", "embed"),
    }
    if cfg.n_shared_experts > 0:
        p["shared"] = dict(L.MLP_AXES)
    return p


class Routing(NamedTuple):
    weights: jax.Array     # (N, k) f32, the combine weight of each slot
    experts: jax.Array     # (N, k) int32, the expert of each slot
    scores: jax.Array      # (N, E) f32, softmax over every expert


def route(router: jax.Array, x: jax.Array, cfg: ModelConfig) -> Routing:
    """Softmax scores over all experts in f32, greedy top-k; the weights
    are renormalised over the k where ``cfg.norm_topk_prob``.  x: (N, d)."""
    logits = jnp.dot(x.astype(jnp.float32), router,
                     precision=lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    weights, experts = lax.top_k(scores, cfg.experts_per_token)
    if cfg.norm_topk_prob:
        weights = weights / jnp.sum(weights, axis=-1, keepdims=True)
    return Routing(weights, experts, scores)


def sequence_aux_loss(r: Routing, batch: int, cfg: ModelConfig) -> jax.Array:
    """DeepSeek-V2's sequence-level balance loss over all experts, before
    its coefficient: mean over sequences of Σ_i f_i P_i, with f_i =
    E / (k·S) · (slots of the sequence routed to i) and P_i the mean score
    of i over the sequence."""
    e, k = cfg.n_experts, cfg.experts_per_token
    scores = r.scores.reshape(batch, -1, e)
    s = scores.shape[1]
    counts = jnp.sum(jax.nn.one_hot(r.experts.reshape(batch, s * k), e,
                                    dtype=jnp.float32), axis=1)
    f = counts * (e / (k * s))
    return jnp.mean(jnp.sum(f * jnp.mean(scores, axis=1), axis=-1))


class Dispatch(NamedTuple):
    order: jax.Array        # (N·k,) pair indices, held experts' first
    tokens: jax.Array       # (N·k,) the token of each sorted pair
    weights: jax.Array      # (N·k,) its combine weight, 0 where not held
    group_sizes: jax.Array  # (held,) pairs per held expert, in order


def _held_index(r: Routing, lo, held: int) -> jax.Array:
    """(N·k,) each pair's place among the held experts [lo, lo + held),
    ``held`` for a pair bound for an absent expert."""
    e = r.experts.reshape(-1)
    return jnp.where((e >= lo) & (e < lo + held), e - lo, held)


def dispatch(r: Routing, lo, held: int) -> Dispatch:
    """Sort the (token, slot) pairs by held expert ([lo, lo + held)),
    stable, with the pairs bound for an absent expert last.  ``lo`` may be
    traced: an expert-parallel shard's first expert."""
    k = r.experts.shape[-1]
    local = _held_index(r, lo, held)
    order = jnp.argsort(local, stable=True)
    group_sizes = jnp.bincount(local, length=held + 1)[:held]
    weights = jnp.where(local < held, r.weights.reshape(-1), 0.0)[order]
    return Dispatch(order, order // k, weights, group_sizes.astype(jnp.int32))


def _grouped(lhs: jax.Array, rhs: jax.Array, d: Dispatch,
             grouped: jax.Array) -> jax.Array:
    """``ragged_dot`` of the sorted pairs' rows by their experts' weights,
    f32.  Rows past the held pairs belong to no group, and the grouped
    matmul leaves them undefined, forward and in its lhs gradient (the
    TPU's kernel does): the rows are zeroed on the way in and on the way
    out, so that neither direction lets them reach a token."""
    lhs = jnp.where(grouped[:, None], lhs, 0)
    out = lax.ragged_dot(lhs, rhs.astype(lhs.dtype), d.group_sizes,
                         preferred_element_type=jnp.float32)
    return jnp.where(grouped[:, None], out, 0.0)


def held_experts(p: Params, x: jax.Array, d: Dispatch, n_tokens: int,
                 cfg: ModelConfig) -> jax.Array:
    """Σ over each token's slots held here of weight × SwiGLU_expert(x):
    (N, d) in x's dtype.  x: (N, d)."""
    k = cfg.experts_per_token
    with jax.named_scope(obs.MOE_ROUTE):
        xs = x[d.tokens]                                  # (N·k, d)
        grouped = jnp.arange(xs.shape[0]) < jnp.sum(d.group_sizes)
    with jax.named_scope(obs.MOE_EXPERTS):
        gate = _grouped(xs, p["w_gate"], d, grouped)
        up = _grouped(xs, p["w_up"], d, grouped)
        h = (jax.nn.silu(gate) * up).astype(x.dtype)
        out = _grouped(h, p["w_down"], d, grouped)
    with jax.named_scope(obs.MOE_ROUTE):
        out = out * d.weights[:, None]
        # back to (token, slot) order, then sum each token's slots
        inverse = jnp.argsort(d.order)
        y = jnp.sum(out[inverse].reshape(n_tokens, k, -1), axis=1)
    return y.astype(x.dtype)


def _routed(p: Params, tokens: jax.Array, r: Routing, cfg: ModelConfig):
    """The held experts' part of the routed output, (N, d), and the pairs
    routed to each held expert."""
    lo, hi = cfg.held_experts
    with jax.named_scope(obs.MOE_ROUTE):
        d = dispatch(r, lo, hi - lo)
    return held_experts(p, tokens, d, tokens.shape[0], cfg), d.group_sizes


def _routed_expert_parallel(p: Params, tokens: jax.Array, r: Routing,
                            axes: tuple[str, ...], batch: int,
                            cfg: ModelConfig):
    """``_routed`` with the held experts split over the mesh ``axes`` as
    their weights are, under ``shard_map`` over the whole mesh: each
    device computes its own experts' part for its tokens (those of its
    batch rows, whole over ``axes``), and a sum over ``axes`` adds the
    parts, as the parts of disjoint held ranges add up to the layer."""
    lo, hi = cfg.held_experts
    experts = {k: p[k] for k in ("w_gate", "w_up", "w_down")}
    rows = tuple(a for a in split_axes("activation_batch", batch)
                 if a not in axes)

    def part(w, tokens, r):
        # each device of ``axes`` reads every one of these tokens and
        # routing slots: marked varying, so that their gradients are
        # summed over the axes (a gather from an operand left invariant
        # drops the other devices' parts)
        tokens, r = lax.pcast((tokens, r), axes, to="varying")
        held = w["w_gate"].shape[0]
        first = lo + lax.axis_index(axes) * held
        with jax.named_scope(obs.MOE_ROUTE):
            d = dispatch(r, first, held)
        y = held_experts(w, tokens, d, tokens.shape[0], cfg)
        with jax.named_scope(obs.MOE_ROUTE):
            return lax.psum(y.astype(jnp.float32), axes).astype(y.dtype)

    y = jax.shard_map(part, in_specs=(P(axes), P(rows), P(rows)),
                      out_specs=P(rows))(experts, tokens, r)
    with jax.named_scope(obs.MOE_ROUTE):
        counts = jnp.bincount(_held_index(r, lo, hi - lo),
                              length=hi - lo + 1)[:hi - lo]
    return y, counts.astype(jnp.int32)


def moe_mlp(p: Params, x: jax.Array, cfg: ModelConfig):
    """x: (B, S, d) -> (y, aux_loss, tokens per held expert).

    y is the held experts' part of the routed output plus the shared
    experts' whole output.  Under a mesh that splits the experts
    (``sharding.split_axes("experts", ...)``) the held experts run
    expert-parallel."""
    b, s, dm = x.shape
    tokens = x.reshape(b * s, dm)
    with jax.named_scope(obs.MOE_ROUTE):
        r = route(p["router"], tokens, cfg)
        aux = sequence_aux_loss(r, b, cfg)
    lo, hi = cfg.held_experts
    axes = split_axes("experts", hi - lo)
    if axes:
        y, counts = _routed_expert_parallel(p, tokens, r, axes, b, cfg)
    else:
        y, counts = _routed(p, tokens, r, cfg)
    y = y.reshape(b, s, dm)
    if "shared" in p:
        with jax.named_scope(obs.MOE_SHARED):
            y = y + L.mlp(jax.tree.map(lambda w: w.astype(x.dtype),
                                       p["shared"]), x)
    y = shard_constraint(y, ("activation_batch", "residual_length",
                             "activation_embed"))
    return y, aux, counts


# ------------------------- block + assembly -------------------------------

def init_block(key, cfg: ModelConfig) -> Params:
    k1, k2 = jax.random.split(key)
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": L.init_rms_norm(cfg.d_model, dtype),
        "attn": L.init_attention(
            k1, cfg.d_model, cfg.n_heads, cfg.n_kv_heads,
            cfg.resolved_head_dim, dtype,
            qkv_bias=cfg.qkv_bias, qk_norm=cfg.qk_norm),
        "ln2": L.init_rms_norm(cfg.d_model, dtype),
        "moe": init_moe_mlp(key=k2, cfg=cfg),
    }


def block_axes(cfg: ModelConfig) -> Params:
    return {
        "ln1": {"scale": (None,)},
        "attn": L.attention_param_axes(cfg.qkv_bias, cfg.qk_norm),
        "ln2": {"scale": (None,)},
        "moe": moe_mlp_axes(cfg),
    }


def block_apply_aux(p: Params, h, positions, cfg: ModelConfig):
    a = L.attention(p["attn"], L.rms_norm(p["ln1"], h, cfg.norm_eps),
                    positions, theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
                    eps=cfg.norm_eps, causal=True,
                    unroll=L.scan_unroll_of(cfg),
                    chunk_threshold=cfg.attn_chunk_threshold)
    h = h + a
    y, aux, _ = moe_mlp(p["moe"], L.rms_norm(p["ln2"], h, cfg.norm_eps), cfg)
    return h + y, aux


def block_apply(p: Params, h, positions, cfg: ModelConfig):
    return block_apply_aux(p, h, positions, cfg)[0]


def block_decode(p: Params, h, ck, cv, cache_len, positions, cfg: ModelConfig):
    a, ck, cv = L.decode_attention(
        p["attn"], L.rms_norm(p["ln1"], h, cfg.norm_eps), ck, cv, cache_len,
        positions, theta=cfg.rope_theta, qk_norm=cfg.qk_norm,
        eps=cfg.norm_eps, window=cfg.attn_window)
    h = h + a
    y, _, _ = moe_mlp(p["moe"], L.rms_norm(p["ln2"], h, cfg.norm_eps), cfg)
    return h + y, ck, cv


def init(key, cfg: ModelConfig) -> Params:
    return T.init(key, cfg, init_one=init_block)

def param_axes(cfg: ModelConfig) -> Params:
    return T.param_axes(cfg, one_axes=block_axes)

def forward(params, batch, cfg: ModelConfig):
    return T.forward(params, batch, cfg, apply_one=block_apply)


def loss_fn(params, batch, cfg: ModelConfig):
    """Cross-entropy + router aux, aux threaded through the scan carry."""
    h = T._embed_in(params, batch, cfg)
    positions = T._positions_of(batch, cfg)

    def body(carry, lp):
        hh, aux = carry
        hh, a = block_apply_aux(lp, hh, positions, cfg)
        return (hh, aux + a), None

    body = L.remat_wrap(cfg, body)
    (h, aux), _ = lax.scan(body, (h, jnp.zeros((), jnp.float32)),
                           params["layers"], unroll=L.scan_unroll_of(cfg))
    h = L.rms_norm(params["final_norm"], h, cfg.norm_eps)
    emb = params["embedding"] if cfg.tie_embeddings else params["unembed"]
    logits = L.unembed(emb, h)
    loss = L.cross_entropy_loss(logits, batch["labels"], batch.get("mask"))
    return loss + cfg.router_aux_coef * aux / cfg.n_layers


init_cache = T.init_cache
cache_axes = T.cache_axes

def prefill(params, batch, cfg: ModelConfig, max_len: int):
    return T.prefill(params, batch, cfg, max_len, apply_one=block_apply)

def decode_step(params, cache, batch, cfg: ModelConfig):
    return T.decode_step(params, cache, batch, cfg, decode_one=block_decode)
