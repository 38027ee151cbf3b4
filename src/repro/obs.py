"""Names for the program's own work, as a profiler sees it.

Run the program under ``jax.profiler.trace`` and the profile holds:

* host spans ``repro.<name>`` on the profiler's own clock, the clock of the
  device trace: ``repro.batch.index`` (the ``Batcher``'s row gather) and
  ``repro.batch.put`` (its host-to-device put).  With no profiler active a
  span costs about a microsecond and records nothing.
* device scopes (``jax.named_scope``) on every op of the training step:
  ``fp<i>.run`` (period i's layer), ``fp<i>.xfer`` (its transition: the
  all-gather, window select and reshape), ``loss``, ``grad_norm``,
  ``optimizer`` and ``pad`` (the kernel wrappers' edge padding and
  slicing).  The LM training step (``launch/steps.build_train_step``)
  names ``embed`` (the token lookup), ``mla`` (latent attention: its norm,
  projections, attention core and residual add), ``moe.route`` (router,
  top-k, sort, gathers, combine and the balance loss), ``moe.experts``
  (the grouped matmuls of the held experts), ``moe.shared`` (the shared
  experts), ``dense_mlp`` (a dense layer's MLP), ``layers`` (the layer
  scan's own slicing and loop work), ``loss``, ``grad_norm`` and
  ``optimizer``.  The backward pass inherits them as
  ``transpose(jvp(<scope>))``.  They are op metadata only: the compiled
  instructions do not change.

Counters are a plain dict of trace-time counts, read with ``counters()``:
``train_step.traces`` counts traces of ``Executable.train_step``'s body,
so a value that grows after warm-up means the step recompiled.
``attention.flash`` and ``attention.chunked`` count the latent-attention
calls (``models/deepseek_v2.mla``) traced onto the Pallas flash kernel
and onto the kv-chunked jnp scan.
"""

from __future__ import annotations

import collections

import jax

SPAN_PREFIX = "repro."
BATCH_INDEX = "batch.index"
BATCH_PUT = "batch.put"

LOSS = "loss"
GRAD_NORM = "grad_norm"
OPTIMIZER = "optimizer"
PAD = "pad"

EMBED = "embed"
MLA = "mla"
MOE_ROUTE = "moe.route"
MOE_EXPERTS = "moe.experts"
MOE_SHARED = "moe.shared"
DENSE_MLP = "dense_mlp"
LAYERS = "layers"

TRAIN_STEP_TRACES = "train_step.traces"
ATTENTION_FLASH = "attention.flash"
ATTENTION_CHUNKED = "attention.chunked"

_counters: collections.Counter[str] = collections.Counter()


def run_scope(period: int) -> str:
    """Scope of forward period ``period``'s layer."""
    return f"fp{period}.run"


def xfer_scope(period: int) -> str:
    """Scope of the transition out of forward period ``period``."""
    return f"fp{period}.xfer"


def span(name: str) -> jax.profiler.TraceAnnotation:
    """Host span ``repro.<name>``: use as ``with obs.span(obs.BATCH_PUT):``."""
    return jax.profiler.TraceAnnotation(SPAN_PREFIX + name)


def bump(name: str) -> None:
    _counters[name] += 1


def counters() -> dict[str, int]:
    """A copy of every counter."""
    return dict(_counters)
