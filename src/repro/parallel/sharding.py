"""Logical-axis sharding rules (MaxText-style) for the production mesh.

Every parameter / activation dimension carries a *logical* axis name
('batch', 'fsdp', 'tp', 'expert', 'kv_seq', ...).  A rules table maps logical
names to physical mesh axes; the same model code then runs on the single-pod
``("data", "model")`` mesh and the multi-pod ``("pod", "data", "model")``
mesh — rules referencing absent physical axes degrade to replication on the
missing axis, which is what makes the pod axis "free" to add.

Parallelism realized on the production mesh:

* DP/FSDP — batch and parameter 'fsdp' dims over ``(pod, data)``; XLA turns
  parameter use into all-gather and gradients into reduce-scatter (ZeRO-3).
* TP      — attention heads, FFN hidden, vocab over ``model``.
* EP      — MoE experts over ``model`` (the EP group == TP group).
* SP      — decode-time KV-cache *sequence* over ``model`` (flash-decode);
  train-time sequence stays local.
* PP      — deliberately not used: with 2 pods the pipeline would have 2
  stages and bubble ≥ 1/(2·microbatches); FSDP over the pod axis (with the
  ICI-friendly layer-granularity all-gathers XLA emits) costs less at this
  scale (see DESIGN §5).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Mapping, Sequence

import jax
from jax.sharding import AbstractMesh, Mesh, NamedSharding, PartitionSpec as P


LogicalAxes = tuple[str | None, ...]


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """Mapping logical axis -> physical mesh axis (or tuple thereof)."""

    rules: Mapping[str, Any]

    def physical(self, logical: str | None, mesh: Mesh):
        if logical is None:
            return None
        if logical not in self.rules:
            raise KeyError(f"no sharding rule for logical axis {logical!r}")
        phys = self.rules[logical]
        if phys is None:
            return None
        scalar = isinstance(phys, str)
        if scalar:
            phys = (phys,)
        present = tuple(a for a in phys if a in mesh.axis_names)
        if not present:
            return None
        # A composite rule stays a tuple even when pruned to one axis, so
        # spec equality is stable across meshes; a plain rule stays a string.
        return present[0] if scalar else present

    def spec(self, axes: Sequence[str | None], mesh: Mesh) -> P:
        """PartitionSpec for a tensor with the given logical axes."""
        return P(*(self.physical(a, mesh) for a in axes))

    def replace(self, **kw) -> "AxisRules":
        new = dict(self.rules)
        new.update(kw)
        return AxisRules(new)


#: The rules used by every config unless it overrides them.
#: Non-divisible dims fall back to replication via ``sized_spec`` (e.g.
#: 36 q-heads over 16, kv_heads=4 over 16, batch=1 long-context cells).
DEFAULT_RULES = AxisRules({
    "batch": ("pod", "data"),      # data parallel batch dim
    "fsdp": ("pod", "data"),       # ZeRO-3 parameter shard dim
    "tp": "model",                 # tensor-parallel dim (ffn hidden etc.)
    "heads": "model",              # attention q-heads
    "kv_heads": "model",           # kv heads (falls back when < 16)
    "expert": "model",             # expert parallel dim (EP group == TP group)
    "kv_seq": "model",             # decode-time KV sequence sharding (SP)
    "seq": None,                   # train-time sequence stays local
    "layers": None,                # scan dim
    "vocab": "model",
    "stack": None,
})


def logical_to_mesh(rules: AxisRules, axes: Sequence[str | None],
                    mesh: Mesh) -> P:
    return rules.spec(axes, mesh)


def make_named_sharding(mesh: Mesh, rules: AxisRules,
                        axes: Sequence[str | None]) -> NamedSharding:
    return NamedSharding(mesh, rules.spec(axes, mesh))


def shard_constraint(x, rules: AxisRules, axes: Sequence[str | None],
                     mesh: Mesh | None = None):
    """with_sharding_constraint by logical axes (no-op outside a mesh ctx).

    Size-aware: dims not divisible by their mesh axes are left unsharded.
    """
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, sized_spec(rules, axes, x.shape, mesh)))


def _current_mesh() -> AbstractMesh:
    """The mesh set by ``jax.set_mesh`` (empty outside one)."""
    return jax.sharding.get_abstract_mesh()


def tree_specs(axes_tree, rules: AxisRules, mesh: Mesh):
    """Map a pytree of logical-axes tuples to PartitionSpecs."""
    return jax.tree.map(
        lambda axes: rules.spec(axes, mesh),
        axes_tree, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


def tree_shardings(axes_tree, rules: AxisRules, mesh: Mesh):
    """Map a pytree of logical-axes tuples to NamedShardings."""
    return jax.tree.map(
        lambda axes: NamedSharding(mesh, rules.spec(axes, mesh)),
        axes_tree, is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))


def _axis_size(mesh: Mesh, phys) -> int:
    if phys is None:
        return 1
    if isinstance(phys, str):
        phys = (phys,)
    out = 1
    for a in phys:
        out *= mesh.shape[a]
    return out


def sized_spec(rules: AxisRules, axes: Sequence[str | None],
               shape: Sequence[int], mesh: Mesh) -> P:
    """PartitionSpec with a divisibility fallback: any tensor dim that is
    not an exact multiple of its mesh-axes product is replicated instead.
    (Keeps every cell lowerable: e.g. batch=1 long_500k, 36-head archs.)"""
    parts = []
    for a, n in zip(axes, shape):
        phys = rules.physical(a, mesh)
        if phys is not None and n % _axis_size(mesh, phys) != 0:
            phys = None
        parts.append(phys)
    return P(*parts)


def constrain_tree(tree, axes_tree, rules: AxisRules | None = None,
                   mesh: Mesh | None = None):
    """with_sharding_constraint over a pytree by logical-axes tree
    (size-aware; no-op outside a mesh context)."""
    rules = rules or DEFAULT_RULES
    mesh = mesh or _current_mesh()
    if mesh is None or mesh.empty:
        return tree
    leaves, treedef = jax.tree.flatten(tree)
    axes_leaves = treedef.flatten_up_to(axes_tree)
    out = [jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, sized_spec(rules, axes, x.shape, mesh)))
        for x, axes in zip(leaves, axes_leaves)]
    return treedef.unflatten(out)


def tree_shardings_sized(axes_tree, spec_tree, rules: AxisRules, mesh: Mesh):
    """NamedShardings from (logical-axes tree, ShapeDtypeStruct tree)."""
    is_axes = lambda x: isinstance(x, tuple) and all(  # noqa: E731
        isinstance(a, (str, type(None))) for a in x)
    return jax.tree.map(
        lambda axes, s: NamedSharding(
            mesh, sized_spec(rules, axes, s.shape, mesh)),
        axes_tree, spec_tree, is_leaf=is_axes)
