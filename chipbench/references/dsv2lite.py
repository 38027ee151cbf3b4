"""Plain reference of DeepSeek-V2's training step, in ``jax.numpy``.

It follows HF ``modeling_deepseek.py`` (DeepSeek-V2, arXiv:2405.04434)
from the configuration's own keys, and imports nothing of the program:

* latent attention: q = x W_q split into (q_nope, q_pe); [c_kv, k_pe] =
  x W_kva; c_kv ← RMSNorm(c_kv); [k_nope, v] = c_kv W_kvb; k_pe is one
  head shared by all; RoPE on q_pe and k_pe, each de-interleaved (even
  lanes, then odd) and rotated by halves, as HF does; causal softmax of
  (q·k) · (d_nope + d_rope)^-½ · m², m = 0.1 · mscale_all_dim · ln(factor)
  + 1; then W_o.  YaRN blends θ^(−2i/d) and θ^(−2i/d) / factor over the
  correction range of β_fast and β_slow;
* the router: softmax over every routed expert in f32, greedy top-k, the
  raw scores as weights (renormalised only where ``norm_topk_prob``), and
  the sequence-level balance loss α · mean over sequences of Σ_i f_i P_i;
* the held experts [lo, hi) computed densely: every token through every
  held expert's SwiGLU, weighted by its routing weight where it chose that
  expert and by 0 elsewhere (a mask, not a sort); the shared MLP for
  every token;
* pre-norm residual blocks, the first ``first_k_dense_replace`` dense;
  mean next-token cross-entropy plus the balance losses of every MoE
  layer;
* AdamW (bias-corrected, decoupled decay scaled by the learning rate)
  after a global-norm clip.

Weights are drawn as the configuration's ``init`` says: every matrix
N(0, ``init_std``²) from ``fold_in(key, crc32(path))``, norm scales 1.

Departures that do not change the mathematics, so that the float32 step
fits one chip's memory: each layer is rematerialised (``jax.checkpoint``),
the MoE layers run as a ``lax.scan`` over their stacked weights,
attention is computed one block of 512 queries at a time, and each FFN
(dense, routed and shared) one block of 1024 tokens at a time, each block
rematerialised.

``train`` runs in ``"float32"`` with every matmul at HIGHEST precision,
the reference, or in ``"bfloat16"``: parameters and activations stored in
bf16, matmuls at default precision accumulating in f32, moments and the
update in f32 — the control one precision below the configuration's f32
master weights.  ``fault`` plants a fault in the reference put in the
program's place (as a traced flag, so that one compiled step serves the
reference and every fault): ``"half_batch"`` (the loss over the first half of the
batch's sequences), ``"altered_answer"`` (the first token's label logit
lowered by 10), ``"capacity_drop"`` (each held expert takes at most
ceil(1.25 · 512 · k / E) of every 512 tokens, in token order, and drops
the rest: a capacity-bound dispatch) or ``"no_mscale"`` (the attention
scale without YaRN's m²).
"""

from __future__ import annotations

import functools
import math
import zlib
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from chipbench.checks import Readings

FAULTS = ("half_batch", "altered_answer", "capacity_drop", "no_mscale")
ALTERATION = 10.0
CAPACITY_FACTOR = 1.25
CAPACITY_GROUP = 512
Q_BLOCK = 512
TOKEN_BLOCK = 1024


# ----------------------------------------------------------------- weights

def shapes(c: dict[str, Any]) -> dict:
    """Every leaf's shape by name; the layers of a kind are stacked."""
    d, h = c["hidden_size"], c["num_attention_heads"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], c["v_head_dim"]
    r = c["kv_lora_rank"]
    n_dense = c["first_k_dense_replace"]
    n_moe = c["num_hidden_layers"] - n_dense
    lo, hi = c["experts_held"]
    f = c["moe_intermediate_size"]

    def block(n, ffn):
        return {"ln1": {"scale": (n, d)},
                "attn": {"wq": (n, d, h, dn + dr), "wkv_a": (n, d, r + dr),
                         "kv_norm": {"scale": (n, r)},
                         "wkv_b": (n, r, h, dn + dv), "wo": (n, h, dv, d)},
                "ln2": {"scale": (n, d)}, **ffn}

    def swiglu(n, width):
        return {"w_gate": (n, d, width), "w_up": (n, d, width),
                "w_down": (n, width, d)}

    return {
        "embedding": {"w": (c["vocab_size"], d)},
        "dense": block(n_dense,
                       {"mlp": swiglu(n_dense, c["intermediate_size"])}),
        "layers": block(n_moe, {"moe": {
            "router": (n_moe, d, c["experts_routed_over"]),
            "w_gate": (n_moe, hi - lo, d, f), "w_up": (n_moe, hi - lo, d, f),
            "w_down": (n_moe, hi - lo, f, d),
            "shared": swiglu(n_moe, c["n_shared_experts"] * f)}}),
        "final_norm": {"scale": (d,)},
        "unembed": {"w": (c["vocab_size"], d)},
    }


def _name(path) -> str:
    return "/".join(str(getattr(k, "key", k)) for k in path)


def leaf_names(c: dict[str, Any]) -> list[str]:
    """The leaves' names, in the order ``train`` reports them."""
    paths = jax.tree_util.tree_flatten_with_path(
        shapes(c), is_leaf=lambda x: isinstance(x, tuple))[0]
    return [_name(p) for p, _ in paths]


def init(key, c: dict[str, Any]) -> dict:
    def leaf(path, shape):
        name = _name(path)
        if name.endswith("scale"):
            return jnp.ones(shape, jnp.float32)
        k = jax.random.fold_in(key, zlib.crc32(name.encode()))
        return jax.random.normal(k, shape, jnp.float32) * c["init_std"]

    return jax.tree_util.tree_map_with_path(
        leaf, shapes(c), is_leaf=lambda x: isinstance(x, tuple))


# -------------------------------------------------------------------- model

def _mscale(factor: float, m: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * m * math.log(factor) + 1.0


def inv_freq(c: dict[str, Any]) -> np.ndarray:
    dim, base = c["qk_rope_head_dim"], c["rope_theta"]
    s = c["rope_scaling"]
    extra = 1.0 / base ** (np.arange(0, dim, 2) / dim)
    inter = 1.0 / (s["factor"] * base ** (np.arange(0, dim, 2) / dim))

    def corr(n_rot):
        return (dim * math.log(s["original_max_position_embeddings"]
                               / (n_rot * 2 * math.pi))) / (2 * math.log(base))

    low = max(math.floor(corr(s["beta_fast"])), 0)
    high = min(math.ceil(corr(s["beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(dim // 2) - low) / (high - low), 0, 1)
    keep = 1.0 - ramp
    return inter * (1 - keep) + extra * keep


def attention_scale(c: dict[str, Any], planted):
    """(d_nope + d_rope)^-½ · m², or without m² where ``no_mscale`` is
    planted."""
    s = c["rope_scaling"]
    m2 = _mscale(s["factor"], s["mscale_all_dim"]) ** 2
    return ((c["qk_nope_head_dim"] + c["qk_rope_head_dim"]) ** -0.5
            * (m2 + planted["no_mscale"] * (1.0 - m2)))


def _rope(x, c):
    """x: (B, L, H, d); HF: de-interleave, then rotate half."""
    b, l, h, d = x.shape
    s = c["rope_scaling"]
    ang = jnp.arange(l, dtype=jnp.float32)[:, None] * inv_freq(c)
    ang = jnp.concatenate([ang, ang], axis=-1)[None, :, None, :]
    m = _mscale(s["factor"], s["mscale"]) / _mscale(s["factor"],
                                                    s["mscale_all_dim"])
    x = x.astype(jnp.float32)
    x = x.reshape(b, l, h, d // 2, 2).swapaxes(-1, -2).reshape(b, l, h, d)
    rot = jnp.concatenate([-x[..., d // 2:], x[..., :d // 2]], axis=-1)
    return x * (jnp.cos(ang) * m) + rot * (jnp.sin(ang) * m)


class _Ops:
    """Matmuls, norms and casts at one precision."""

    def __init__(self, dtype):
        self.dt = jnp.dtype(dtype)
        self.precision = (jax.lax.Precision.HIGHEST
                          if self.dt == jnp.float32
                          else jax.lax.Precision.DEFAULT)

    def mm(self, spec, a, b):
        return jnp.einsum(spec, a.astype(self.dt), b.astype(self.dt),
                          precision=self.precision,
                          preferred_element_type=jnp.float32).astype(self.dt)

    def norm(self, x, w, eps):
        x32 = x.astype(jnp.float32)
        y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
        return (y * w).astype(self.dt)


def _attention(o: _Ops, q, k, v, scale):
    """Causal softmax attention, one block of queries at a time."""
    b, l, h, dq = q.shape
    blk = min(Q_BLOCK, l)
    qb = q.reshape(b, l // blk, blk, h, dq).swapaxes(0, 1)

    @jax.checkpoint
    def one(args):
        i, qi = args
        s = jnp.einsum("bqhd,bkhd->bhqk", qi.astype(o.dt), k.astype(o.dt),
                       precision=o.precision,
                       preferred_element_type=jnp.float32) * scale
        rows = i * blk + jnp.arange(blk)
        s = jnp.where(rows[:, None] >= jnp.arange(l)[None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("bhqk,bkhd->bqhd", p.astype(o.dt), v.astype(o.dt),
                          precision=o.precision,
                          preferred_element_type=jnp.float32).astype(o.dt)

    out = jax.lax.map(one, (jnp.arange(l // blk), qb))
    return out.swapaxes(0, 1).reshape(b, l, h, v.shape[-1])


def _mla(o: _Ops, p, x, c, planted):
    dn, r = c["qk_nope_head_dim"], c["kv_lora_rank"]
    h = c["num_attention_heads"]
    q = o.mm("bld,dhk->blhk", x, p["wq"])
    kva = o.mm("bld,dr->blr", x, p["wkv_a"])
    c_kv = o.norm(kva[..., :r], p["kv_norm"]["scale"], c["rms_norm_eps"])
    kv = o.mm("blr,rhk->blhk", c_kv, p["wkv_b"])
    q_pe = _rope(q[..., dn:], c).astype(o.dt)
    k_pe = _rope(kva[..., None, r:], c).astype(o.dt)
    q = jnp.concatenate([q[..., :dn], q_pe], axis=-1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_pe, kv.shape[:2] + (h,)
                                        + k_pe.shape[-1:])], axis=-1)
    att = _attention(o, q, k, kv[..., dn:], attention_scale(c, planted))
    return o.mm("blhv,hvd->bld", att, p["wo"])


def _swiglu(o: _Ops, p, x):
    g = o.mm("nd,df->nf", x, p["w_gate"]).astype(jnp.float32)
    u = o.mm("nd,df->nf", x, p["w_up"]).astype(jnp.float32)
    return o.mm("nf,fd->nd", (jax.nn.silu(g) * u).astype(o.dt), p["w_down"])


def _by_token_blocks(f, *xs):
    """f over blocks of TOKEN_BLOCK rows of each x, rematerialised."""
    n = xs[0].shape[0]
    blk = min(TOKEN_BLOCK, n)
    out = jax.lax.map(jax.checkpoint(lambda a: f(*a)),
                      tuple(x.reshape((n // blk, blk) + x.shape[1:])
                            for x in xs))
    return out.reshape((n,) + out.shape[2:])


def _capacity_keep(chose, c):
    """(N, E) 0/1: of each group of tokens, the first C that chose an
    expert keep it."""
    n, e = chose.shape
    g = min(CAPACITY_GROUP, n)
    cap = math.ceil(g * c["num_experts_per_tok"] / c["experts_routed_over"]
                    * CAPACITY_FACTOR)
    grouped = chose.reshape(n // g, g, e)
    slot = jnp.cumsum(grouped, axis=1) - 1.0
    return (grouped * (slot < cap)).reshape(n, e)


def _moe(o: _Ops, p, x, batch, c, planted):
    """(held experts' part + shared output (N, d), balance loss)."""
    e, k = c["experts_routed_over"], c["num_experts_per_tok"]
    lo, hi = c["experts_held"]
    n = x.shape[0]
    logits = jnp.dot(x.astype(jnp.float32), p["router"],
                     precision=jax.lax.Precision.HIGHEST)
    scores = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(scores, k)
    if c["norm_topk_prob"]:
        w = w / jnp.sum(w, axis=-1, keepdims=True)
    w = w * c["routed_scaling_factor"]
    chose = jnp.zeros((n, e), jnp.float32).at[
        jnp.arange(n)[:, None], idx].set(1.0)
    gates = jnp.zeros((n, e), jnp.float32).at[
        jnp.arange(n)[:, None], idx].set(w)
    drop = planted["capacity_drop"]
    gates = gates * (1.0 - drop + drop * _capacity_keep(chose, c))
    seq = n // batch
    f = jnp.sum(chose.reshape(batch, seq, e), axis=1) * (e / (k * seq))
    aux = jnp.mean(jnp.sum(f * jnp.mean(scores.reshape(batch, seq, e),
                                        axis=1), axis=-1))

    def experts(xb, gb):
        g = o.mm("nd,edf->enf", xb, p["w_gate"]).astype(jnp.float32)
        u = o.mm("nd,edf->enf", xb, p["w_up"]).astype(jnp.float32)
        out = o.mm("enf,efd->end", (jax.nn.silu(g) * u).astype(o.dt),
                   p["w_down"]).astype(jnp.float32)
        y = jnp.einsum("ne,end->nd", gb, out,
                       precision=jax.lax.Precision.HIGHEST)
        return (y + _swiglu(o, p["shared"], xb).astype(jnp.float32)
                ).astype(o.dt)

    return _by_token_blocks(experts, x, gates[:, lo:hi]), aux


def _layer(stacked, i):
    return jax.tree.map(lambda a: a[i], stacked)


def loss(params, tokens, labels, c, dtype="float32", planted=None):
    """Mean next-token cross-entropy plus α · Σ_layers balance loss.
    tokens, labels: (B, L); ``planted`` maps each of ``FAULTS`` to 1.0
    where it is planted, 0.0 elsewhere (traced, so that one compiled step
    serves every fault)."""
    if planted is None:
        planted = dict.fromkeys(FAULTS, 0.0)
    o = _Ops(dtype)
    b, l = tokens.shape
    d = c["hidden_size"]
    eps = c["rms_norm_eps"]
    h = params["embedding"]["w"][tokens].astype(o.dt)

    def block(p, h, dense):
        x = o.norm(h, p["ln1"]["scale"], eps)
        h = h + _mla(o, p["attn"], x, c, planted)
        x = o.norm(h, p["ln2"]["scale"], eps).reshape(b * l, d)
        if dense:
            y = _by_token_blocks(functools.partial(_swiglu, o, p["mlp"]), x)
            return h + y.reshape(b, l, d), 0.0
        y, aux = _moe(o, p["moe"], x, b, c, planted)
        return h + y.reshape(b, l, d), aux

    for i in range(c["first_k_dense_replace"]):
        h, _ = jax.checkpoint(functools.partial(block, dense=True))(
            _layer(params["dense"], i), h)

    @jax.checkpoint
    def moe_layer(carry, p):
        h, total = carry
        h, aux = block(p, h, dense=False)
        return (h, total + aux), None

    (h, total_aux), _ = jax.lax.scan(moe_layer, (h, jnp.float32(0.0)),
                                     params["layers"])
    x = o.norm(h, params["final_norm"]["scale"], eps).reshape(b * l, d)
    z = jnp.einsum("nd,vd->nv", x.astype(o.dt),
                   params["unembed"]["w"].astype(o.dt), precision=o.precision,
                   preferred_element_type=jnp.float32)
    y = labels.reshape(-1)
    # altered_answer: the first token's label logit lowered; half_batch:
    # the mean over the first half of the sequences alone
    z = z.at[0, y[0]].add(-ALTERATION * planted["altered_answer"])
    picked = jnp.take_along_axis(z, y[:, None], axis=1)[:, 0]
    nll = jax.nn.logsumexp(z, axis=1) - picked
    first = (jnp.arange(y.shape[0]) < y.shape[0] // 2).astype(jnp.float32)
    w = first + (1.0 - first) * (1.0 - planted["half_batch"])
    ce = jnp.sum(nll * w) / jnp.sum(w)
    return ce + c["aux_loss_alpha"] * total_aux


# ------------------------------------------------------------------ train

def _leaf_norms(tree):
    return jnp.stack([jnp.linalg.norm(a.astype(jnp.float32).ravel())
                      for a in jax.tree.leaves(tree)])


KEYS = ("hidden_size", "num_attention_heads", "qk_nope_head_dim",
        "qk_rope_head_dim", "v_head_dim", "kv_lora_rank",
        "first_k_dense_replace", "num_hidden_layers", "experts_held",
        "moe_intermediate_size", "intermediate_size", "n_shared_experts",
        "experts_routed_over", "num_experts_per_tok", "norm_topk_prob",
        "routed_scaling_factor", "vocab_size", "rms_norm_eps", "rope_theta",
        "rope_scaling", "aux_loss_alpha", "init_std", "optimizer")


def _frozen(x):
    if isinstance(x, dict):
        return tuple(sorted((k, _frozen(v)) for k, v in x.items()))
    return tuple(x) if isinstance(x, list) else x


@functools.lru_cache(maxsize=None)
def _step(frozen, dtype: str):
    """One jitted AdamW step ``((params, m, v), tokens, labels, t,
    planted) -> ((params, m, v), loss, gradient norms)``, the state
    donated."""
    c = {k: dict(v) if k in ("rope_scaling", "optimizer") else v
         for k, v in frozen}
    o = c["optimizer"]
    lr, b1, b2, eps = o["lr"], o["b1"], o["b2"], o["eps"]
    wd, clip = o["weight_decay"], o["grad_clip"]

    def step(state, tok, lab, t, planted):
        params, m, v = state
        value, grads = jax.value_and_grad(loss)(params, tok, lab, c, dtype,
                                                planted)
        grads = jax.tree.map(lambda g: g.astype(jnp.float32), grads)
        norm = jnp.sqrt(sum(jnp.sum(g * g) for g in jax.tree.leaves(grads)))
        scale = jnp.minimum(1.0, clip / (norm + 1e-12))
        grads = jax.tree.map(lambda g: g * scale, grads)
        m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
        v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
        mc, vc = 1.0 / (1.0 - b1 ** t), 1.0 / (1.0 - b2 ** t)

        def update(p, m_, v_):
            p32 = p.astype(jnp.float32)
            u = (m_ * mc) / (jnp.sqrt(v_ * vc) + eps) + wd * p32
            return (p32 - lr * u).astype(p.dtype)

        params = jax.tree.map(update, params, m, v)
        return (params, m, v), value, _leaf_norms(grads)

    return jax.jit(step, donate_argnums=(0,))


def _start(params, dtype: str):
    """(params in ``dtype``, zero moments): the step's first state."""
    return (jax.tree.map(lambda a: a.astype(dtype), params),
            jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params),
            jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params))


def train(key, config: dict[str, Any], tokens, labels,
          dtype: str = "float32", fault: str | None = None,
          device=None) -> Readings:
    """Follow ``len(tokens)`` AdamW steps over ``tokens``/``labels``
    ((steps, B, L) each) from the configuration's weights, one compiled
    step with its state donated; the parameters' change is taken on the
    host in float64."""
    if fault is not None and fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")
    dtype = jnp.dtype(dtype).name
    step = _step(_frozen({k: config[k] for k in KEYS}), dtype)
    planted = {f: jnp.float32(f == fault) for f in FAULTS}
    device = device or jax.devices()[0]
    losses, first = [], None
    with jax.default_device(device), jax.default_matmul_precision("highest"):
        p0 = jax.jit(lambda k: init(k, config))(jax.device_put(key, device))
        before = jax.device_get(p0)
        state = jax.jit(_start, static_argnums=1, donate_argnums=0)(p0, dtype)
        for t in range(len(tokens)):
            state, value, norms = step(
                state, jax.device_put(np.asarray(tokens[t]), device),
                jax.device_put(np.asarray(labels[t]), device),
                jnp.float32(t + 1), planted)
            losses.append(value)
            if t == 0:
                first = norms
        after = jax.device_get(state[0])
        losses, first = jax.device_get((losses, first))
    del state
    names = leaf_names(config)
    change = [float(np.linalg.norm(np.asarray(a, np.float64)
                                   - np.asarray(b, np.float64)))
              for a, b in zip(jax.tree.leaves(after),
                              jax.tree.leaves(before))]
    return Readings(tuple(float(v) for v in losses),
                    dict(zip(names, map(float, first))),
                    dict(zip(names, change)))
