"""Elastic scaling: the ONoC allocator is the re-planning oracle.

When cluster membership changes (node loss / capacity grant), the
paper's model answers "how many workers should each stage use now?" —
Lemma 1 with the new m.  ``ElasticPlanner`` re-derives the allocation,
rebuilds the mesh + sharding rules, and the checkpointer's
restore-with-shardings moves the state onto the new layout.
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh

from repro.core.onoc_model import FCNNWorkload, ONoCConfig, optimal_cores
from repro.core.allocation import MappingStrategy, map_cores


@dataclasses.dataclass
class ElasticPlanner:
    workload: FCNNWorkload
    base_cfg: ONoCConfig
    strategy: MappingStrategy = MappingStrategy.ORRM

    def plan_for(self, n_devices: int):
        """Re-run the paper's allocator for a new device count."""
        cfg = dataclasses.replace(self.base_cfg, m=n_devices)
        cores = optimal_cores(self.workload, cfg, refine_plateau=True)
        cores = [min(c, n_devices) for c in cores]
        mapping = map_cores(self.workload, cfg, self.strategy, cores)
        return cfg, cores, mapping

    def replan_program(self, n_devices: int, backend=None):
        """Degraded-mode replan: Lemma-1 plan on the surviving ring plus a
        freshly compiled (and checked) period program for it.

        Returns ``(cfg, plan, program)`` where ``cfg`` is the base config
        shrunk to ``n_devices`` cores.  ``compile_program`` runs the
        static checker (``exec.check``) on the new schedule, so a bad
        replan is a hard ``ProgramValidationError`` before anything
        executes.
        """
        from repro.core.planner import plan_fcnn, ring_mesh_axes
        from repro.exec.program import compile_program

        cfg = dataclasses.replace(self.base_cfg, m=n_devices)
        plan = plan_fcnn(self.workload, cfg, ring_mesh_axes(n_devices),
                         strategy=self.strategy)
        program = compile_program(plan, self.workload, cfg, n_devices,
                                  backend=backend)
        return cfg, plan, program

    def make_mesh(self, devices=None, axis: str = "data") -> Mesh:
        devices = devices if devices is not None else jax.devices()
        return Mesh(np.asarray(devices), (axis,))

    def remesh_state(self, state: Any, old_mesh: Mesh, new_mesh: Mesh,
                     shardings_fn) -> Any:
        """Re-device_put a state pytree onto a new mesh.  shardings_fn maps
        a mesh to a same-structure pytree of NamedShardings."""
        target = shardings_fn(new_mesh)
        return jax.tree.map(
            lambda x, s: jax.device_put(np.asarray(x), s), state, target)
