"""Executor: interpret a PeriodProgram under ``shard_map`` on a device mesh.

The program is a static SPMD schedule; every device runs the same
interpretation loop and resolves its role per period from
``jax.lax.axis_index`` against the program's device windows.  Lowering of
the instruction set to mesh operations:

  RUN (fp, layer i)   each device in the period's window computes one
                      column chunk of layer i — ``ops.fcnn_layer`` on the
                      (B, n_{i-1}) activation and its (n_{i-1}, n_i/d_i)
                      weight slice, i.e. the fused Pallas kernel on TPU and
                      the jnp oracle / interpreted kernel elsewhere.
                      Devices outside the window redundantly compute the
                      window head's chunk; their output is never selected
                      (see FREE) so it is dead code to XLA.
  SEND + RECV (fp)    one ``jax.lax.all_gather`` over the ring axis plus a
                      static window-ordered selection: chunk j of the next
                      activation comes from device window[j].  This is the
                      paper's inter-period WDM broadcast: senders are the
                      current window, receivers the next.
  FREE                devices released at a transition simply stop
                      contributing: their chunks are not selected, so both
                      their forward values and their gradients are exactly
                      zero-influence from that period on.
  RUN/SEND/RECV (bp)  realized by JAX AD, exactly as the model docstring
                      promises: differentiating the interpreted forward
                      turns each all_gather into its transpose
                      (psum_scatter — the BP reduce-scatter, "senders of
                      period i are receivers of period 2l-i+1", Eq. 11) and
                      runs the fused dgrad/wgrad kernels of
                      ``kernels.ops.fcnn_layer``'s custom_vjp as the BP
                      RUNs.  The BP instructions in the program are the
                      cost-annotated contract for what AD emits.

The loss period (the FP->BP turnaround at period l) gathers the logit
chunks within the final window and evaluates the fused
``ops.softmax_xent``; the program schedules no transition there (the
paper keeps data in place at the turnaround, g(m_l) = 0).

Two **residency** modes select the params-layout contract (ISSUE 8):

  replicated   the PR-6 oracle.  Params and batch enter fully replicated
               (``PartitionSpec()``); every device holds the full model and
               slices its chunk per period; FREE is a cost annotation.
  sharded      the weight-sharded path (schema-v2 programs only).  Params
               enter *stacked*: layer i is ``w: (n_dev, n_{i-1}, n_i/d_i)``
               / ``b: (n_dev, n_i/d_i)``, sharded ``P(axis)`` on the
               leading device axis, so each device materializes exactly one
               column chunk per layer — its own chunk if it is in the
               layer's window (``shard_params`` places chunk
               ``owner_chunk[j]`` on device j), zeros otherwise.  Weights
               are never re-gathered whole: only *activations* move
               (all_gather of the (B, n_i/d_i) period output).  Off-window
               zero chunks produce unselected outputs, therefore zero
               cotangents, therefore zero grads — plain elementwise
               optimizers keep them exactly zero.  Per-device live
               parameter bytes match the program's residency annotations
               (``exec.residency.ResidencyTracker``): ~1/d of the
               replicated model per degree-d period.

Numerics: in both modes each chunk of each weight matrix is computed by
exactly one selected device with identical inputs, so the sharded path is
bit-identical to the replicated oracle — losses, grads and optimizer
trajectories match with zero tolerance (pinned by
tests/test_exec_residency.py on the 8-device CPU ring, ref and
pallas_interpret kernels; tests/test_exec_runtime.py pins the oracle
against the single-device fused path).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P

from repro.exec.program import PeriodProgram
from repro.kernels import ops
from repro.optim.optimizers import Optimizer

Params = dict[str, Any]

__all__ = ["ProgramExecutor", "build_train_step"]


@dataclasses.dataclass(frozen=True)
class _PeriodLayout:
    """Static per-FP-period geometry precomputed from RUN instructions."""

    layer: int                 # 1-based
    width: int                 # output columns per chunk (n_i / d_i)
    n_out: int                 # n_i
    activation: str
    window: np.ndarray         # device id of chunk j, shape (d_i,)
    owner_chunk: np.ndarray    # chunk index each device computes, shape (n,)


class ProgramExecutor:
    """Interprets a compiled PeriodProgram on a 1-axis device mesh.

    ``loss_fn(params, batch)`` has the same signature and semantics as
    ``models.fcnn.loss_fn`` and is an ordinary traceable JAX function —
    jit, grad and optimizers compose with it as usual.
    """

    def __init__(self, program: PeriodProgram, mesh: Mesh,
                 kernel_mode: str | None = None,
                 residency: str = "replicated"):
        if len(mesh.axis_names) != 1:
            raise ValueError(
                f"executor mesh must have one (ring) axis, got "
                f"{mesh.axis_names}")
        n = mesh.devices.size
        if n != program.n_devices:
            raise ValueError(
                f"program compiled for {program.n_devices} devices, mesh "
                f"has {n}")
        if residency not in ("replicated", "sharded"):
            raise ValueError(
                f"residency must be 'replicated' or 'sharded', got "
                f"{residency!r}")
        if residency == "sharded" and program.version < 2:
            raise ValueError(
                f"sharded residency needs a schema-v2 program with "
                f"residency annotations; this one is v{program.version} "
                f"— recompile with compile_program")
        self.program = program
        self.mesh = mesh
        self.axis = mesh.axis_names[0]
        self.residency = residency
        # Freeze the kernel dispatch for the program's whole lifetime so
        # every period of every step takes the same path.
        self.kernel_mode = ops.resolve_mode(kernel_mode)
        # Byte-level accounting of the layout this executor runs under.
        from repro.exec.residency import ResidencyTracker
        self.tracker = ResidencyTracker(program, mode=residency)

        self._layout: list[_PeriodLayout] = []
        for run in program.runs(phase="fp"):
            window = np.asarray(run.devices, dtype=np.int32)
            owner = np.zeros(n, dtype=np.int32)
            owner[window] = np.arange(len(window), dtype=np.int32)
            self._layout.append(_PeriodLayout(
                layer=run.layer, width=run.chunk_width,
                n_out=program.layer_sizes[run.layer],
                activation=run.activation, window=window,
                owner_chunk=owner,
            ))

        self._rebuild()

    def _rebuild(self) -> None:
        if self.residency == "sharded":
            body = self._device_program_sharded
            pspec = self.param_spec()
        else:
            body = self._device_program
            pspec = P()
        self._sharded = jax.shard_map(
            body, mesh=self.mesh,
            in_specs=(pspec, P(), P()), out_specs=P(),
            # loss is replicated by construction (identical full logits on
            # every device after the final gather); collective use below is
            # beyond what the static replication checker can verify.
            check_vma=False,
        )

    def degrade(self, mode: str = "ref") -> str:
        """Graceful degradation: swap the kernel dispatch (typically fused
        Pallas -> jnp reference path) after a kernel failure and rebuild
        the sharded interpreter.  Returns the previous mode.  Callers
        holding a jitted step around the old ``loss_fn`` must rebuild it —
        the degraded-mode runner (runtime/degraded.py) does, and records
        the fallback in its FaultReport."""
        previous = self.kernel_mode
        self.kernel_mode = ops.resolve_mode(mode)
        self._rebuild()
        return previous

    # ------------------------------------------------------------- interpret

    def _device_program(self, params: Params, x: jax.Array,
                        y: jax.Array) -> jax.Array:
        """One device's view of the program: FP RUNs + transitions + loss."""
        me = jax.lax.axis_index(self.axis)
        h = x
        batch = x.shape[0]
        for lay in self._layout:
            lp = params["layers"][lay.layer - 1]
            # RUN: this device's column chunk of W/b (freed devices shadow
            # the window head's chunk; their result is never selected).
            chunk = jnp.asarray(lay.owner_chunk)[me]
            w_loc = jax.lax.dynamic_slice_in_dim(
                lp["w"], chunk * lay.width, lay.width, axis=1)
            b_loc = jax.lax.dynamic_slice_in_dim(
                lp["b"], chunk * lay.width, lay.width, axis=0)
            y_loc = ops.fcnn_layer(h, w_loc, b_loc, lay.activation,
                                   force=self.kernel_mode)
            # SEND/RECV (or the period-l turnaround gather): one collective;
            # chunk j of the next activation comes from device window[j].
            gathered = jax.lax.all_gather(y_loc, self.axis)   # (n, B, width)
            h = jnp.moveaxis(gathered[lay.window], 0, 1)      # (B, d, width)
            h = h.reshape(batch, lay.n_out)
        return ops.softmax_xent(h, y, force=self.kernel_mode)

    def _device_program_sharded(self, params: Params, x: jax.Array,
                                y: jax.Array) -> jax.Array:
        """Sharded-residency view: params arrive pre-chunked — this
        device's block of the stacked layout is its resident column chunk
        (zeros off-window), so RUN needs no slice and the weights are
        never re-gathered whole; only the (B, width) activations move."""
        h = x
        batch = x.shape[0]
        for lay in self._layout:
            lp = params["layers"][lay.layer - 1]
            w_loc = lp["w"][0]                    # (n_in, width) chunk
            b_loc = lp["b"][0]                    # (width,)
            y_loc = ops.fcnn_layer(h, w_loc, b_loc, lay.activation,
                                   force=self.kernel_mode)
            # Same window-ordered selection as the oracle: chunk j of the
            # next activation comes from device window[j], whose stacked
            # slot holds exactly chunk j (shard_params' placement).
            gathered = jax.lax.all_gather(y_loc, self.axis)   # (n, B, width)
            h = jnp.moveaxis(gathered[lay.window], 0, 1)      # (B, d, width)
            h = h.reshape(batch, lay.n_out)
        return ops.softmax_xent(h, y, force=self.kernel_mode)

    # ------------------------------------------------------- sharded layout

    @property
    def n_devices(self) -> int:
        return self.program.n_devices

    def param_spec(self) -> Params:
        """PartitionSpec pytree of the stacked sharded params layout."""
        return {"layers": [{"w": P(self.axis), "b": P(self.axis)}
                           for _ in range(self.program.l)]}

    def shard_params(self, params: Params) -> Params:
        """Full layout -> stacked residency layout.

        For layer i, device j's slot is column chunk ``owner_chunk[j]`` of
        (W_i, b_i) if j is in the layer's window, zeros otherwise — the
        memory image the program's residency annotations account for.
        Traceable (static slices), so it can run inside a jitted step to
        realise the "sliced once at step start" contract."""
        self._check_params(params, layout="full")
        n = self.n_devices
        layers = []
        for lay in self._layout:
            lp = params["layers"][lay.layer - 1]
            w, b = lp["w"], lp["b"]
            in_window = np.zeros(n, dtype=bool)
            in_window[lay.window] = True
            sw, sb = [], []
            for j in range(n):
                if in_window[j]:
                    c = int(lay.owner_chunk[j])
                    sw.append(w[:, c * lay.width:(c + 1) * lay.width])
                    sb.append(b[c * lay.width:(c + 1) * lay.width])
                else:
                    sw.append(jnp.zeros_like(w[:, :lay.width]))
                    sb.append(jnp.zeros_like(b[:lay.width]))
            layers.append({"w": jnp.stack(sw), "b": jnp.stack(sb)})
        return {"layers": layers}

    def gather_params(self, sparams: Params) -> Params:
        """Stacked residency layout -> full layout (chunk j of layer i
        comes from device window[j]'s slot).  The only place the full
        matrices are reassembled — used for eval/checkpoint interop, never
        inside the sharded loss."""
        self._check_params(sparams, layout="sharded")
        layers = []
        for lay in self._layout:
            sp = sparams["layers"][lay.layer - 1]
            w = jnp.concatenate([sp["w"][d] for d in lay.window], axis=1)
            b = jnp.concatenate([sp["b"][d] for d in lay.window], axis=0)
            layers.append({"w": w, "b": b})
        return {"layers": layers}

    # ------------------------------------------------------------------ api

    def loss_fn(self, params: Params, batch: Params) -> jax.Array:
        """Mean softmax cross-entropy of the program on ``batch``.

        ``params`` must be in the executor's residency layout: full
        (replicated mode) or stacked chunks from ``shard_params``
        (sharded mode)."""
        self._check_params(params, layout="full" if
                           self.residency == "replicated" else "sharded")
        return self._sharded(params, batch["x"], batch["y"])

    def _check_params(self, params: Params, layout: str = "full") -> None:
        sizes = self.program.layer_sizes
        layers = params["layers"]
        if len(layers) != self.program.l:
            raise ValueError(
                f"program has {self.program.l} layers, params have "
                f"{len(layers)}")
        for i, (lp, lay) in enumerate(zip(layers, self._layout)):
            if layout == "full":
                want = (sizes[i], sizes[i + 1])
            else:
                want = (self.n_devices, sizes[i], lay.width)
            if tuple(lp["w"].shape) != want:
                raise ValueError(
                    f"layer {i + 1}: weight shape {tuple(lp['w'].shape)} "
                    f"!= {layout}-layout shape {want}")


def build_train_step(
    program: PeriodProgram,
    mesh: Mesh,
    optimizer: Optimizer,
    kernel_mode: str | None = None,
) -> tuple[Callable, ProgramExecutor]:
    """A jitted ``step(params, opt_state, batch, i)`` whose loss is the
    compiled program executed under shard_map.  Drop-in for the plain
    single-device step of examples/train_fcnn_onoc.py.

    .. deprecated:: ISSUE 8 — use the façade:
       ``repro.exec.compile(...)`` / ``Executable.from_program(...)``
       and ``Executable.train_step(optimizer)``.  Kept as a thin
       replicated-residency shim."""
    from repro.deprecation import warn_deprecated
    warn_deprecated(
        "exec.runtime.build_train_step",
        "build_train_step is deprecated; use repro.exec.compile(...) "
        "or Executable.from_program(...).train_step(optimizer)")
    ex = ProgramExecutor(program, mesh, kernel_mode=kernel_mode)

    @jax.jit
    def step(params, opt_state, batch, i):
        loss, grads = jax.value_and_grad(ex.loss_fn)(params, batch)
        params, opt_state = optimizer.update(grads, opt_state, params, i)
        return params, opt_state, loss

    return step, ex
