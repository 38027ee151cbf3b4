"""Public jit'd wrappers for the Pallas kernels.

Dispatch policy
---------------
Every wrapper resolves a *mode* per call:

  * ``force=None`` (default) — ``"pallas"`` on a TPU backend (compiled
    kernels), ``"ref"`` anywhere else (the pure-jnp oracles from ref.py,
    bit-compatible semantics).  Models and launch code therefore call these
    unconditionally; CPU tests and lowering-only dry-runs transparently get
    the oracle path.
  * ``force="ref"`` — the oracle, always.  Differentiable by ordinary JAX
    autodiff; this is the ground truth the kernels are validated against.
  * ``force="pallas"`` — the compiled TPU kernel regardless of backend
    (will fail off-TPU; used by hardware benchmarks).
  * ``force="pallas_interpret"`` — the Pallas kernels in interpreter mode:
    same kernel code, runs on CPU.  Used by tests/test_kernels.py to
    validate both values and gradients without hardware.

fcnn_layer: fused forward AND backward
--------------------------------------
``fcnn_layer`` is the production hot path of the paper's per-period FCNN
loop, so its Pallas modes carry a ``jax.custom_vjp``: the forward saves
(x, w, b, y) — b only to dtype the db cotangent, never a pre-activation
Z — and the backward runs two fused kernels —

  * dgrad: dX = (dY ⊙ A'(Y)) @ Wᵀ, activation derivative fused into the
    GEMM prologue (the pre-activation gradient dZ never reaches HBM);
  * wgrad: dW = Xᵀ @ dZ and db = Σ_rows dZ in one pass, recomputing the
    cheap element-wise dZ instead of materializing it.

so ``jax.grad`` through a Pallas-dispatched ``fcnn_layer`` stays fused end
to end, while ``force="ref"`` keeps plain autodiff of the oracle.  Both
paths agree to fp32 tolerance (see tests/test_kernels.py).

softmax_xent: the fused output period
-------------------------------------
``softmax_xent`` closes the loop on the 2l-period pipeline: the loss
itself.  Its Pallas modes also carry a ``jax.custom_vjp`` —

  * forward: one online-softmax sweep over class tiles returning per-row
    (nll, lse); the loss is the mean of nll, and lse is the ONLY tensor
    residual beyond the primals (two (B,) vectors — probabilities and
    log-probs never reach HBM);
  * backward: dlogits = (softmax − onehot) · ḡ/B recomputed from lse in
    a single fused pass.

Labels are integer class ids and get a ``None`` cotangent.

flash_attention: causal attention for training
----------------------------------------------
``flash_attention``'s Pallas modes carry a ``jax.custom_vjp`` too: the
forward kernel returns the output and each row's f32 log-sum-exp, the
residuals are (q, k, v, o, lse), and the backward runs the dK/dV and dQ
kernels of ``kernels/flash_attention.py``.  The scale is taken as given
and v's head dim may differ from q's (latent attention).

Block sizes & padding: kernels auto-select MXU-aligned blocks and
zero-pad edge tiles, so non-128-divisible shapes (784, 10, …) are
accepted in every mode; explicit ``block_m/n/k`` overrides act as
preferred sizes rather than hard divisibility requirements.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.fcnn_layer import (
    fcnn_layer as _fcnn_pallas,
    fcnn_layer_dgrad as _fcnn_dgrad_pallas,
    fcnn_layer_wgrad as _fcnn_wgrad_pallas,
)
from repro.kernels.flash_attention import (
    flash_attention_bwd as _flash_bwd_pallas,
    flash_attention_fwd as _flash_fwd_pallas,
)
from repro.kernels.softmax_xent import (
    softmax_xent_dlogits as _xent_dlogits_pallas,
    softmax_xent_fwd as _xent_fwd_pallas,
)
from repro.kernels.ssd_scan import ssd_chunk as _ssd_pallas

__all__ = ["fcnn_layer", "softmax_xent", "flash_attention", "ssd_chunk",
           "resolve_mode"]


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_mode(force: str | None) -> str:
    """Resolve the dispatch mode every wrapper below uses: ``force`` if
    given, else "pallas" on TPU and "ref" elsewhere.  Public so long-lived
    callers (the period-program executor, benchmark harnesses) can freeze
    one mode up front instead of re-resolving per call."""
    if force is not None:
        if force not in ("ref", "pallas", "pallas_interpret"):
            raise ValueError(f"unknown kernel mode {force!r}")
        return force
    return "pallas" if _on_tpu() else "ref"


_mode = resolve_mode


@functools.lru_cache(maxsize=None)
def _fused_fcnn(activation: str, interpret: bool, blocks: tuple):
    """custom_vjp-wrapped fused forward/backward for one static config."""
    bl = dict(blocks)

    @jax.custom_vjp
    def f(x, w, b):
        return _fcnn_pallas(x, w, b, activation, interpret=interpret, **bl)

    def fwd(x, w, b):
        y = _fcnn_pallas(x, w, b, activation, interpret=interpret, **bl)
        return y, (x, w, b, y)

    def bwd(res, dy):
        x, w, b, y = res
        dx = _fcnn_dgrad_pallas(dy, y, w, activation,
                                interpret=interpret, **bl)
        dw, db = _fcnn_wgrad_pallas(x, dy, y, activation,
                                    interpret=interpret, **bl)
        return (dx.astype(x.dtype), dw.astype(w.dtype), db.astype(b.dtype))

    f.defvjp(fwd, bwd)
    return f


def fcnn_layer(x, w, b, activation: str = "sigmoid", *,
               force: str | None = None, **blocks):
    mode = _mode(force)
    if mode == "ref":
        return _ref.fcnn_layer_ref(x, w, b, activation)
    interp = mode == "pallas_interpret"
    fused = _fused_fcnn(activation, interp, tuple(sorted(blocks.items())))
    return fused(x, w, b)


@functools.lru_cache(maxsize=None)
def _fused_xent(interpret: bool, blocks: tuple):
    """custom_vjp-wrapped fused softmax/cross-entropy for one config."""
    bl = dict(blocks)

    @jax.custom_vjp
    def f(logits, labels):
        nll, _ = _xent_fwd_pallas(logits, labels, interpret=interpret, **bl)
        return jnp.mean(nll)

    def fwd(logits, labels):
        nll, lse = _xent_fwd_pallas(logits, labels, interpret=interpret,
                                    **bl)
        return jnp.mean(nll), (logits, labels, lse)

    def bwd(res, g):
        logits, labels, lse = res
        # fold the mean's 1/B and the loss cotangent into one per-row scale
        scale = jnp.full((logits.shape[0],), g / logits.shape[0],
                         jnp.float32)
        dl = _xent_dlogits_pallas(logits, labels, lse, scale,
                                  interpret=interpret, **bl)
        return dl.astype(logits.dtype), None   # labels: integer, no grad

    f.defvjp(fwd, bwd)
    return f


def softmax_xent(logits, labels, *, force: str | None = None, **blocks):
    """Mean softmax cross-entropy loss.  logits: (B, C); labels: (B,) int."""
    mode = _mode(force)
    if mode == "ref":
        return _ref.softmax_xent_ref(logits, labels)
    interp = mode == "pallas_interpret"
    fused = _fused_xent(interp, tuple(sorted(blocks.items())))
    return fused(logits, labels)


@functools.lru_cache(maxsize=None)
def _fused_flash(causal: bool, scale, interpret: bool, blocks: tuple):
    """custom_vjp-wrapped flash attention for one static config."""
    kw = dict(blocks, causal=causal, scale=scale, interpret=interpret)

    @jax.custom_vjp
    def f(q, k, v):
        return _flash_fwd_pallas(q, k, v, **kw)[0]

    def fwd(q, k, v):
        o, lse = _flash_fwd_pallas(q, k, v, **kw)
        return o, (q, k, v, o, lse)

    def bwd(res, do):
        return _flash_bwd_pallas(*res, do, **kw)

    f.defvjp(fwd, bwd)
    return f


def flash_attention(q, k, v, causal: bool = True, *,
                    scale: float | None = None, force: str | None = None,
                    **blocks):
    """softmax(q·kᵀ·scale) v.  q, k: (B, H, S, D); v: (B, H, S, Dv);
    ``scale`` 1/√D where None.  Its Pallas modes carry a ``custom_vjp``:
    the forward saves (q, k, v, o, lse), the backward runs the dK/dV and
    dQ kernels."""
    mode = _mode(force)
    if mode == "ref":
        return _ref.flash_attention_ref(q, k, v, causal, scale)
    interp = mode == "pallas_interpret"
    fused = _fused_flash(causal, scale, interp, tuple(sorted(blocks.items())))
    return fused(q, k, v)


def ssd_chunk(x, dt_a, b, c, *, force: str | None = None, **blocks):
    mode = _mode(force)
    if mode == "ref":
        f = jax.vmap(_ref.ssd_chunk_ref)
        return f(x, dt_a, b, c)
    interp = mode == "pallas_interpret"
    return _ssd_pallas(x, dt_a, b, c, interpret=interp, **blocks)
