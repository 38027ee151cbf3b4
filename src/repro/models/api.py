"""Uniform model interface: ``get_model(cfg)`` returns a ``Model`` whose
functions close over nothing — params/batches are explicit pytrees, so
every function jits and shards cleanly.

Families: dense | moe | mla_moe | ssm | hybrid | encdec | vlm.

``loss_and_metrics(params, batch) -> (loss, metrics)`` is the loss with
the model's own step metrics (a dict of scalars; empty for most
families); ``loss_fn`` is its loss alone.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeSpec

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    init: Callable[..., Params]
    param_axes: Callable[[], Params]
    forward: Callable[..., jax.Array]
    loss_fn: Callable[..., jax.Array]
    loss_and_metrics: Callable[..., tuple[jax.Array, Params]]
    init_cache: Callable[..., Params]
    cache_axes: Callable[[], Params]
    prefill: Callable[..., tuple]
    decode_step: Callable[..., tuple]
    input_specs: Callable[[ShapeSpec], Params]
    batch_axes: Callable[[ShapeSpec], Params]


def _lm_input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Params:
    b = shape.global_batch
    if shape.kind == "train":
        return {
            "tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
        }
    if shape.kind == "prefill":
        return {"tokens": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32)}
    # decode: one new token against a seq_len-deep cache
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}


def _lm_batch_axes(cfg: ModelConfig, shape: ShapeSpec) -> Params:
    ax = ("activation_batch", None)
    if shape.kind == "train":
        return {"tokens": ax, "labels": ax}
    return {"tokens": ax}


def _vlm_input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Params:
    b = shape.global_batch
    dt = jnp.dtype(cfg.dtype)
    if shape.kind == "train":
        return {
            "embeds": jax.ShapeDtypeStruct((b, shape.seq_len, cfg.d_model), dt),
            "positions": jax.ShapeDtypeStruct((3, b, shape.seq_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32),
        }
    if shape.kind == "prefill":
        return {
            "embeds": jax.ShapeDtypeStruct((b, shape.seq_len, cfg.d_model), dt),
            "positions": jax.ShapeDtypeStruct((3, b, shape.seq_len), jnp.int32),
        }
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}


def _vlm_batch_axes(cfg: ModelConfig, shape: ShapeSpec) -> Params:
    if shape.kind == "decode":
        return {"tokens": ("activation_batch", None)}
    out = {
        "embeds": ("activation_batch", "activation_length", "activation_embed"),
        "positions": (None, "activation_batch", None),
    }
    if shape.kind == "train":
        out["labels"] = ("activation_batch", None)
    return out


def _encdec_input_specs(cfg: ModelConfig, shape: ShapeSpec) -> Params:
    b = shape.global_batch
    dt = jnp.dtype(cfg.dtype)
    enc_len = shape.seq_len // 2
    dec_len = shape.seq_len - enc_len
    if shape.kind == "train":
        return {
            "enc_embeds": jax.ShapeDtypeStruct((b, enc_len, cfg.d_model), dt),
            "dec_tokens": jax.ShapeDtypeStruct((b, dec_len), jnp.int32),
            "labels": jax.ShapeDtypeStruct((b, dec_len), jnp.int32),
        }
    if shape.kind == "prefill":
        return {
            "enc_embeds": jax.ShapeDtypeStruct((b, enc_len, cfg.d_model), dt),
            "dec_tokens": jax.ShapeDtypeStruct((b, dec_len), jnp.int32),
        }
    return {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}


def _encdec_batch_axes(cfg: ModelConfig, shape: ShapeSpec) -> Params:
    if shape.kind == "decode":
        return {"tokens": ("activation_batch", None)}
    out = {
        "enc_embeds": ("activation_batch", "activation_length",
                       "activation_embed"),
        "dec_tokens": ("activation_batch", None),
    }
    if shape.kind == "train":
        out["labels"] = ("activation_batch", None)
    return out


def get_model(cfg: ModelConfig) -> Model:
    fam = cfg.family
    if fam in ("dense",):
        from repro.models import transformer as mod
        specs, baxes = _lm_input_specs, _lm_batch_axes
    elif fam == "moe":
        from repro.models import moe as mod
        specs, baxes = _lm_input_specs, _lm_batch_axes
    elif fam == "mla_moe":
        from repro.models import deepseek_v2 as mod
        specs, baxes = _lm_input_specs, _lm_batch_axes
    elif fam == "ssm":
        from repro.models import mamba2 as mod
        specs, baxes = _lm_input_specs, _lm_batch_axes
    elif fam == "hybrid":
        from repro.models import zamba2 as mod
        specs, baxes = _lm_input_specs, _lm_batch_axes
    elif fam == "encdec":
        from repro.models import encdec as mod
        specs, baxes = _encdec_input_specs, _encdec_batch_axes
    elif fam == "vlm":
        from repro.models import vlm as mod
        specs, baxes = _vlm_input_specs, _vlm_batch_axes
    else:
        raise ValueError(f"unknown family {fam!r}")

    def init_cache(batch: int, max_len: int, **kw):
        if fam == "encdec":
            return mod.init_cache(cfg, batch, max_len,
                                  kw.get("enc_len", max_len))
        return mod.init_cache(cfg, batch, max_len)

    if hasattr(mod, "loss_and_metrics"):
        def loss_and_metrics(p, b):
            return mod.loss_and_metrics(p, b, cfg)
    else:
        def loss_and_metrics(p, b):
            return mod.loss_fn(p, b, cfg), {}

    return Model(
        cfg=cfg,
        init=lambda key: mod.init(key, cfg),
        param_axes=lambda: mod.param_axes(cfg),
        forward=lambda p, b: mod.forward(p, b, cfg),
        loss_fn=lambda p, b: mod.loss_fn(p, b, cfg),
        loss_and_metrics=loss_and_metrics,
        init_cache=init_cache,
        cache_axes=lambda: mod.cache_axes(cfg),
        prefill=lambda p, b, max_len: mod.prefill(p, b, cfg, max_len),
        decode_step=lambda p, c, b: mod.decode_step(p, c, b, cfg),
        input_specs=lambda shape: specs(cfg, shape),
        batch_axes=lambda shape: baxes(cfg, shape),
    )
