"""Logical-axis sharding rules (MaxText-style), consumed by every model.

Tensors are annotated with *logical* axis names; the mesh maps them to
physical axes.  The ONoC planner (core/planner.py) edits these rules to
realize its per-period parallelism degrees: a layer planned at degree 1
gets its "mlp"/"heads" axes mapped to None (replicated), a layer planned at
full degree keeps "model" (+ "data" for fused degrees).

Physical axes:
  "pod"    across pods (multi-pod mesh only)
  "data"   data parallel + FSDP (ZeRO-3 weight sharding)
  "model"  tensor parallel
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import (
    AbstractMesh, Mesh, NamedSharding, PartitionSpec as P)

__all__ = [
    "AxisRules",
    "DEFAULT_RULES",
    "logical_to_mesh",
    "named_sharding",
    "replicate",
    "shard_constraint",
    "shard_stacked",
    "tree_shardings",
]

# logical name -> physical axis (or tuple of axes, or None)
_DEFAULT = {
    # activations
    "activation_batch": ("pod", "data"),
    "activation_length": None,
    "residual_length": None,  # inter-block residual stream (Megatron-SP
                              # shards this on "model" between blocks)
    "activation_embed": None,
    "activation_heads": "model",
    "activation_kv_heads": "model",
    "activation_mlp": "model",
    "activation_vocab": "model",
    "activation_exp": "model",
    # weights
    "embed": "data",          # FSDP axis of weight matrices
    "vocab": "model",
    "table_embed": "data",    # embedding table d_model axis (separable from
                              # "embed" so vocab-parallel embedding can
                              # unshard it without touching FSDP)
    "heads": "model",
    "kv_heads": "model",
    "q_per_kv": None,
    "head_dim": None,
    "mlp": "model",
    "experts": "model",       # expert parallelism
    "expert_mlp": None,
    "conv_kernel": None,
    "state": None,
    "layers": None,           # scan axis of stacked layer params
    # kv-cache
    "cache_batch": ("pod", "data"),
    "cache_length": None,
    "cache_kv_heads": "model",
    "cache_head_dim": None,
}


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """An immutable logical->physical mapping with functional overrides."""

    table: Mapping[str, Any] = dataclasses.field(
        default_factory=lambda: dict(_DEFAULT)
    )

    def override(self, **changes: Any) -> "AxisRules":
        t = dict(self.table)
        for k, v in changes.items():
            if k not in t:
                raise KeyError(f"unknown logical axis {k!r}")
            t[k] = v
        return AxisRules(table=t)

    def physical(self, logical: str | None, mesh: Mesh) -> Any:
        if logical is None:
            return None
        if logical not in self.table:
            raise KeyError(f"unknown logical axis {logical!r}")
        phys = self.table[logical]
        if phys is None:
            return None
        if isinstance(phys, str):
            return phys if phys in mesh.axis_names else None
        # tuple of axes — keep only those present on this mesh
        kept = tuple(a for a in phys if a in mesh.axis_names)
        return kept if kept else None


DEFAULT_RULES = AxisRules()

# Dynamically-scoped active rules: in-model shard_constraint calls resolve
# against these, so planners/experiments retarget every internal constraint
# without threading a rules object through model code.  Trace-time scoped:
# wrap the .lower()/jit call in ``use_rules``.
_ACTIVE_RULES: list[AxisRules] = [DEFAULT_RULES]


class use_rules:
    def __init__(self, rules: AxisRules):
        self.rules = rules

    def __enter__(self):
        _ACTIVE_RULES.append(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _ACTIVE_RULES.pop()
        return False


def active_rules() -> AxisRules:
    return _ACTIVE_RULES[-1]


def logical_to_mesh(
    logical_axes: Sequence[str | None], mesh: Mesh, rules: AxisRules = DEFAULT_RULES
) -> P:
    """Translate a tuple of logical axis names into a PartitionSpec."""
    return P(*(rules.physical(a, mesh) for a in logical_axes))


def named_sharding(
    logical_axes: Sequence[str | None], mesh: Mesh, rules: AxisRules = DEFAULT_RULES
) -> NamedSharding:
    return NamedSharding(mesh, logical_to_mesh(logical_axes, mesh, rules))


def shard_constraint(
    x: jax.Array,
    logical_axes: Sequence[str | None],
    mesh: Mesh | None = None,
    rules: AxisRules | None = None,
) -> jax.Array:
    """with_sharding_constraint by logical names; no-op outside a mesh.

    ``mesh`` defaults to the one scoped by
    ``jax.sharding.use_abstract_mesh(mesh.abstract_mesh)``; ``rules`` to
    the dynamically-scoped active rules (use_rules)."""
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    rules = rules or active_rules()
    spec = logical_to_mesh(logical_axes, mesh, rules)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def replicate(tree: Any, mesh: Mesh) -> Any:
    """device_put a pytree fully replicated across ``mesh``.

    The period-program executor's *replicated*-residency placement
    (exec/runtime.py oracle path): every device holds the full
    params/batch and slices its per-period chunk on-device.  The
    weight-sharded residency path instead stacks per-device chunks and
    splits them over the ring axis (``shard_stacked``), holding ~1/d of
    the model per device."""
    sh = NamedSharding(mesh, P())
    return jax.tree.map(lambda x: jax.device_put(x, sh), tree)


def shard_stacked(tree: Any, mesh: Mesh, axis: str | None = None) -> Any:
    """device_put a pytree of *stacked* per-device leaves — shape
    ``(n_devices, ...)`` — split over ``axis`` (default: the mesh's only
    axis), leaving scalars and non-stacked leaves replicated.

    This is the resident layout of the weight-sharded period-program
    executor (exec/runtime.py): leaf ``[j]`` is device j's column chunk,
    so the device materializes exactly its ``param_bytes`` of each layer
    (exec.residency accounting)."""
    axis = axis or mesh.axis_names[0]
    n = _axis_size(mesh, axis)

    def put(x):
        stacked = getattr(x, "ndim", 0) >= 1 and x.shape[0] == n
        spec = P(axis) if stacked else P()
        return jax.device_put(x, NamedSharding(mesh, spec))

    return jax.tree.map(put, tree)


def _current_mesh() -> AbstractMesh | None:
    """The mesh of the enclosing
    ``with jax.sharding.use_abstract_mesh(mesh.abstract_mesh):`` block."""
    m = jax.sharding.get_abstract_mesh()
    return None if m.empty else m


def tree_shardings(
    tree_axes: Any, mesh: Mesh, rules: AxisRules = DEFAULT_RULES
) -> Any:
    """Map a pytree of logical-axes tuples to a pytree of NamedShardings.

    Leaves are tuples/lists of logical names (or None for fully replicated).
    """

    def leaf(ax):
        if ax is None:
            return NamedSharding(mesh, P())
        return named_sharding(tuple(ax), mesh, rules)

    return jax.tree.map(
        leaf, tree_axes, is_leaf=lambda x: x is None or isinstance(x, (tuple, list))
    )


def _axis_size(mesh: Mesh, names) -> int:
    if names is None:
        return 1
    if isinstance(names, str):
        names = (names,)
    n = 1
    for a in names:
        n *= mesh.shape[a]
    return n


def resolve_spec(shape: tuple[int, ...], logical_axes, mesh: Mesh,
                 rules: AxisRules = DEFAULT_RULES) -> P:
    """Shape-aware PartitionSpec: demote any mesh axis that does not divide
    its dimension (e.g. 8 GQA kv-heads over a 16-way "model" axis, or 60
    experts over 16) to the longest dividing prefix, else replicate.

    This is the production fallback: the plan stays valid on every mesh and
    the roofline report shows where demotion cost capacity (a hillclimb
    lever, see EXPERIMENTS.md §Perf)."""
    if logical_axes is None:
        return P()
    spec = []
    for dim, ax in zip(shape, tuple(logical_axes)):
        phys = rules.physical(ax, mesh)
        if phys is None:
            spec.append(None)
            continue
        names = (phys,) if isinstance(phys, str) else tuple(phys)
        if dim % _axis_size(mesh, names) == 0:
            spec.append(phys)
            continue
        kept = []
        cur = 1
        for a in names:
            if dim % (cur * mesh.shape[a]) == 0:
                kept.append(a)
                cur *= mesh.shape[a]
            else:
                break
        spec.append(tuple(kept) if kept else None)
    # pad spec for trailing unlisted dims
    return P(*spec)


def shape_aware_shardings(
    spec_tree: Any, axes_tree: Any, mesh: Mesh,
    rules: AxisRules = DEFAULT_RULES,
) -> Any:
    """Like tree_shardings, but consults leaf shapes (ShapeDtypeStructs or
    arrays) and demotes non-dividing axes — every returned sharding is
    valid for jit in_shardings on this mesh.

    The two trees must have the same structure; axes leaves are tuples of
    logical names or None (fully replicated)."""
    spec_leaves, treedef = jax.tree_util.tree_flatten(spec_tree)
    is_axes_leaf = lambda x: x is None or (  # noqa: E731
        isinstance(x, tuple)
        and all(i is None or isinstance(i, str) for i in x))
    axes_leaves, _ = jax.tree_util.tree_flatten(axes_tree,
                                                is_leaf=is_axes_leaf)
    if len(spec_leaves) != len(axes_leaves):
        raise ValueError(
            f"structure mismatch: {len(spec_leaves)} arrays vs "
            f"{len(axes_leaves)} axes leaves")
    shardings = [
        NamedSharding(mesh, resolve_spec(tuple(s.shape), a, mesh, rules))
        for s, a in zip(spec_leaves, axes_leaves)
    ]
    return jax.tree_util.tree_unflatten(treedef, shardings)
