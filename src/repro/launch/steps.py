"""jit-compiled train / serve step builders with full sharding closure.

``build_train_step``: (TrainState, batch) -> (TrainState, metrics), with
in/out shardings derived from the model's logical axes (shape-aware: axes
that don't divide are demoted — see parallel.sharding.resolve_spec),
donated state, and optional int8 gradient compression (error-feedback
residual rides in the state).  The metrics are the loss, the gradient's
global norm and the model's own step metrics; the clip and the norm run
under the ``grad_norm`` scope, AdamW under ``optimizer`` (``repro.obs``).

``build_prefill_step`` / ``build_decode_step``: the serving pair; decode
donates the KV cache (in-place update at scale).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro import obs
from repro.configs.base import ShapeSpec
from repro.models.api import Model
from repro.optim import adamw, clip_by_global_norm
from repro.parallel.sharding import (
    AxisRules,
    DEFAULT_RULES,
    shape_aware_shardings,
)
from repro.parallel import gradsync

Params = dict[str, Any]


@dataclasses.dataclass(frozen=True)
class TrainSettings:
    learning_rate: float = 3e-4
    weight_decay: float = 0.01
    b2: float = 0.999                     # AdamW's second-moment decay
    grad_clip: float = 1.0
    grad_compression: str = "none"        # "none" | "int8"
    microbatches: int = 1


def state_axes(model: Model, settings: TrainSettings) -> Params:
    """Logical axes of the full TrainState (opt state mirrors params)."""
    p_axes = model.param_axes()
    st = {
        "params": p_axes,
        "opt": {"m": p_axes, "v": p_axes},
        "step": None,
    }
    if settings.grad_compression == "int8":
        st["residual"] = p_axes
    return st


def optimizer(settings: TrainSettings):
    """AdamW as ``settings`` states it."""
    return adamw(settings.learning_rate, b2=settings.b2,
                 weight_decay=settings.weight_decay)


def init_train_state(model: Model, settings: TrainSettings, key) -> Params:
    params = model.init(key)
    opt = optimizer(settings)
    st = {
        "params": params,
        "opt": opt.init(params),
        "step": jnp.zeros((), jnp.int32),
    }
    if settings.grad_compression == "int8":
        st["residual"] = gradsync.init_residual(params)
    return st


def train_state_spec(model: Model, settings: TrainSettings) -> Params:
    return jax.eval_shape(
        lambda k: init_train_state(model, settings, k), jax.random.PRNGKey(0))


def build_train_step(
    model: Model,
    mesh: Mesh,
    shape: ShapeSpec,
    settings: TrainSettings = TrainSettings(),
    rules: AxisRules = DEFAULT_RULES,
):
    """Returns (jitted step, state_shardings, batch_shardings, state_spec)."""
    opt = optimizer(settings)

    state_spec = train_state_spec(model, settings)
    st_shardings = shape_aware_shardings(
        state_spec, state_axes(model, settings), mesh, rules)
    batch_spec = model.input_specs(shape)
    batch_shardings = shape_aware_shardings(
        batch_spec, model.batch_axes(shape), mesh, rules)

    def step(state, batch):
        if settings.microbatches > 1:
            mb = jax.tree.map(
                lambda x: x.reshape((settings.microbatches,
                                     x.shape[0] // settings.microbatches)
                                    + x.shape[1:]), batch)
            from repro.models.layers import scan_unroll_of
            loss, grads = gradsync.accumulate_grads(
                model.loss_fn, state["params"], mb,
                unroll=scan_unroll_of(model.cfg))
            metrics = {}
        else:
            (loss, metrics), grads = jax.value_and_grad(
                model.loss_and_metrics, has_aux=True)(state["params"], batch)
        with jax.named_scope(obs.GRAD_NORM):
            grads, gnorm = clip_by_global_norm(grads, settings.grad_clip)
        new_state = dict(state)
        if settings.grad_compression == "int8":
            grads, new_res = gradsync.compress_grads_ef(
                grads, state["residual"])
            new_state["residual"] = new_res
        with jax.named_scope(obs.OPTIMIZER):
            params, opt_state = opt.update(grads, state["opt"],
                                           state["params"], state["step"])
            new_state["params"] = params
            new_state["opt"] = opt_state
            new_state["step"] = state["step"] + 1
        return new_state, {"loss": loss, "grad_norm": gnorm, **metrics}

    jitted = jax.jit(
        step,
        in_shardings=(st_shardings, batch_shardings),
        out_shardings=(st_shardings, NamedSharding(mesh, P())),
        donate_argnums=(0,),
    )
    return jitted, st_shardings, batch_shardings, state_spec


def build_fcnn_program_step(
    program,
    mesh: Mesh,
    settings: TrainSettings = TrainSettings(),
    kernel_mode: str | None = None,
):
    """Period-program analogue of ``build_train_step`` for the paper's
    FCNN: the loss is a compiled RUN/SEND/RECV/FREE schedule
    (exec.program.PeriodProgram) interpreted under shard_map on ``mesh``
    (exec.runtime), with the same AdamW + global-norm clipping as the
    generic step.  Returns (jitted step, executor); state is the plain
    {"params", "opt", "step"} dict (init via ``init_fcnn_program_state``).

    .. deprecated:: ISSUE 8 — thin shim over the façade
       (``repro.exec.Executable``), pinned to the replicated-residency
       oracle the old surface assumed.  New code should call
       ``repro.exec.compile(...)`` and ``Executable.train_step``.
    """
    from repro.deprecation import warn_deprecated
    from repro.exec.api import Executable

    warn_deprecated(
        "launch.steps.build_fcnn_program_step",
        "build_fcnn_program_step is deprecated; use repro.exec.compile(...)"
        " or Executable.from_program(...).train_step(...)")
    opt = adamw(settings.learning_rate, weight_decay=settings.weight_decay)
    exe = Executable.from_program(program, mesh, residency="replicated",
                                  kernel_mode=kernel_mode)
    step = exe.train_step(opt, grad_clip=settings.grad_clip)
    return step, exe.executor


def init_fcnn_program_state(program, settings: TrainSettings, key) -> Params:
    """TrainState for ``build_fcnn_program_step`` (params from the
    program's layer sizes, AdamW moments, step counter)."""
    from repro.models import fcnn

    params = fcnn.init(key, program.layer_sizes)
    opt = adamw(settings.learning_rate, weight_decay=settings.weight_decay)
    return {
        "params": params,
        "opt": opt.init(params),
        "step": jnp.zeros((), jnp.int32),
    }


def _serving_specs(model: Model, mesh: Mesh, shape: ShapeSpec,
                   rules: AxisRules, max_len: int):
    p_spec = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    p_sh = shape_aware_shardings(p_spec, model.param_axes(), mesh, rules)
    kw = {}
    if model.cfg.family == "encdec":
        kw["enc_len"] = shape.seq_len // 2
    c_spec = jax.eval_shape(
        lambda: model.init_cache(shape.global_batch, max_len, **kw))
    c_sh = shape_aware_shardings(c_spec, model.cache_axes(), mesh, rules)
    return p_spec, p_sh, c_spec, c_sh


def _logits_sharding(model: Model, mesh: Mesh, shape: ShapeSpec,
                     rules: AxisRules):
    v = model.cfg.padded_vocab
    b_ax = rules.physical("activation_batch", mesh)
    v_ax = rules.physical("activation_vocab", mesh)
    from repro.parallel.sharding import _axis_size
    if v_ax is not None and v % _axis_size(mesh, v_ax) != 0:
        v_ax = None
    if b_ax is not None and shape.global_batch % _axis_size(mesh, b_ax) != 0:
        b_ax = None
    return NamedSharding(mesh, P(b_ax, None, v_ax))


def build_prefill_step(model: Model, mesh: Mesh, shape: ShapeSpec,
                       max_len: int | None = None,
                       rules: AxisRules = DEFAULT_RULES):
    max_len = max_len or shape.seq_len
    p_spec, p_sh, c_spec, c_sh = _serving_specs(model, mesh, shape, rules,
                                                max_len)
    batch_spec = model.input_specs(shape)
    b_sh = shape_aware_shardings(batch_spec, model.batch_axes(shape), mesh,
                                 rules)
    logits_sh = _logits_sharding(model, mesh, shape, rules)

    def fn(params, batch):
        return model.prefill(params, batch, max_len)

    jitted = jax.jit(fn, in_shardings=(p_sh, b_sh),
                     out_shardings=(logits_sh, c_sh))
    return jitted, p_sh, b_sh, c_sh


def build_decode_step(model: Model, mesh: Mesh, shape: ShapeSpec,
                      rules: AxisRules = DEFAULT_RULES):
    decode_shape = ShapeSpec(shape.name, shape.seq_len, shape.global_batch,
                             "decode")
    p_spec, p_sh, c_spec, c_sh = _serving_specs(model, mesh, decode_shape,
                                                rules, shape.seq_len)
    batch_spec = model.input_specs(decode_shape)
    b_sh = shape_aware_shardings(
        batch_spec, model.batch_axes(decode_shape), mesh, rules)
    logits_sh = _logits_sharding(model, mesh, decode_shape, rules)

    def fn(params, cache, batch):
        return model.decode_step(params, cache, batch)

    jitted = jax.jit(fn, in_shardings=(p_sh, c_sh, b_sh),
                     out_shardings=(logits_sh, c_sh),
                     donate_argnums=(1,))
    return jitted, p_sh, b_sh, c_sh
