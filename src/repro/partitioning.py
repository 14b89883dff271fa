"""Logical-axis partitioning with divisibility fallback.

The framework annotates every parameter / state tensor with *logical* axis
names (e.g. ``('layers', 'embed', 'mlp')``).  A rule table maps logical names
to mesh axes.  At sharding time each rule is validated against the actual
dimension size: a rule whose dimension is not divisible by the mesh axis size
is dropped (the dim stays replicated).  This is the TPU analogue of MobiRNN's
device-shape-aware factorization: the same model gets a different, valid
decomposition on every device mesh without per-model hand tuning.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Mapping, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec


def shard_map(f: Callable, mesh: Mesh, in_specs: Any, out_specs: Any
              ) -> Callable:
    """``jax.shard_map`` with replication checking off — the one spelling
    the framework call sites (models/moe.py, models/rwkv.py) use."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)

# ---------------------------------------------------------------------------
# Logical axis names used throughout the framework.
# ---------------------------------------------------------------------------
#   batch     global batch dimension of activations
#   seq       sequence dimension of activations / caches
#   cache_seq sequence dimension of decode KV caches (shardable on model axis)
#   embed     d_model dimension of weights (FSDP axis)
#   mlp       hidden/ffn output dimension of weights (tensor-parallel axis)
#   heads     query-head dimension (tensor-parallel axis)
#   kv_heads  kv-head dimension (tensor-parallel axis)
#   experts   MoE expert dimension (expert-parallel axis)
#   vocab     vocabulary dimension (tensor-parallel axis)
#   layers    stacked-layer leading dim of scanned params (never sharded)
#   state     recurrent state channels (tensor-parallel when divisible)

DEFAULT_RULES: dict[str, tuple[str, ...]] = {
    "batch": ("pod", "data"),
    "seq": (),
    "seq_model": ("model",),     # sequence parallelism (cfg.seq_shard)
    "cache_seq": ("model",),
    "embed": ("data",),          # FSDP-style weight sharding over data axis
    "embed_nofsdp": (),
    "mlp": ("model",),
    "heads": ("model",),
    "kv_heads": ("model",),
    "experts": ("model",),
    "vocab": ("model",),
    "layers": (),
    "state": ("model",),
    None: (),
}


@dataclasses.dataclass(frozen=True)
class AxisRules:
    """A rule table bound to a mesh; resolves logical names -> PartitionSpec."""

    rules: Mapping[str, tuple[str, ...]]
    mesh: Mesh

    def mesh_axis_size(self, names: tuple[str, ...]) -> int:
        size = 1
        for n in names:
            size *= self.mesh.shape.get(n, 1)
        return size

    def spec_for(self, logical_axes: Sequence[str | None], shape: Sequence[int]
                 ) -> PartitionSpec:
        if len(logical_axes) != len(shape):
            raise ValueError(
                f"logical axes {logical_axes} rank != shape {shape} rank")
        used: set[str] = set()
        parts: list[Any] = []
        for name, dim in zip(logical_axes, shape):
            mesh_axes = tuple(a for a in self.rules.get(name, ())
                              if a in self.mesh.shape and a not in used)
            if not mesh_axes:
                parts.append(None)
                continue
            # divisibility fallback: drop trailing mesh axes until divisible
            while mesh_axes and dim % self.mesh_axis_size(mesh_axes) != 0:
                mesh_axes = mesh_axes[:-1]
            if not mesh_axes:
                parts.append(None)
                continue
            used.update(mesh_axes)
            parts.append(mesh_axes if len(mesh_axes) > 1 else mesh_axes[0])
        # strip trailing Nones for a tidy spec
        while parts and parts[-1] is None:
            parts.pop()
        return PartitionSpec(*parts)

    def sharding_for(self, logical_axes: Sequence[str | None],
                     shape: Sequence[int]) -> NamedSharding:
        return NamedSharding(self.mesh, self.spec_for(logical_axes, shape))


def make_rules(mesh: Mesh, overrides: Mapping[str, tuple[str, ...]] | None = None
               ) -> AxisRules:
    rules = dict(DEFAULT_RULES)
    if overrides:
        rules.update(overrides)
    return AxisRules(rules=rules, mesh=mesh)


# ---------------------------------------------------------------------------
# Activation-sharding context.
#
# Model code calls ``constrain(x, logical_axes)`` at layer boundaries; under
# a ``use_rules(rules)`` context (set by the dry-run / training / serving
# drivers) this lowers to ``with_sharding_constraint`` so XLA keeps
# activations batch-sharded instead of back-propagating weight layouts into
# them.  Outside the context it is a no-op (single-device tests).
# ---------------------------------------------------------------------------
_ACTIVE_RULES: list[AxisRules] = []


class use_rules:
    def __init__(self, rules: AxisRules):
        self.rules = rules

    def __enter__(self):
        _ACTIVE_RULES.append(self.rules)
        return self.rules

    def __exit__(self, *exc):
        _ACTIVE_RULES.pop()


def constrain(x: Any, logical_axes: Sequence[str | None]) -> Any:
    if not _ACTIVE_RULES:
        return x
    rules = _ACTIVE_RULES[-1]
    return jax.lax.with_sharding_constraint(
        x, rules.sharding_for(logical_axes, x.shape))


# ---------------------------------------------------------------------------
# Annotated parameter trees.
#
# Model init functions build a pytree whose leaves are ``Annot`` records —
# an array (or ShapeDtypeStruct) plus its logical axes.  ``split`` separates
# the value tree from the axes tree; ``tree_specs`` turns an axes tree +
# value tree into a PartitionSpec tree.
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Annot:
    value: Any                       # jnp array or jax.ShapeDtypeStruct
    axes: tuple[str | None, ...]     # logical axis names, one per dim

    def __post_init__(self):
        shape = getattr(self.value, "shape", None)
        if shape is not None and isinstance(self.axes, tuple) \
                and len(self.axes) != len(shape):
            raise ValueError(
                f"axes {self.axes} rank mismatch with shape {shape}")


# Registered as a pytree node (axes are static metadata) so Annot trees pass
# through jax transforms — in particular jax.eval_shape for abstract init.
jax.tree_util.register_pytree_node(
    Annot,
    lambda a: ((a.value,), a.axes),
    lambda axes, children: Annot(children[0], axes),
)


def is_annot(x: Any) -> bool:
    return isinstance(x, Annot)


def split(tree: Any) -> tuple[Any, Any]:
    """Split an Annot tree into (values, axes) trees of identical structure."""
    values = jax.tree.map(lambda a: a.value, tree, is_leaf=is_annot)
    axes = jax.tree.map(lambda a: a.axes, tree, is_leaf=is_annot)
    return values, axes


def tree_specs(axes_tree: Any, value_tree: Any, rules: AxisRules) -> Any:
    """PartitionSpec tree from an axes tree and matching value tree."""
    return jax.tree.map(
        lambda ax, v: rules.spec_for(ax, v.shape),
        axes_tree, value_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(axes_tree: Any, value_tree: Any, rules: AxisRules) -> Any:
    return jax.tree.map(
        lambda s: NamedSharding(rules.mesh, s),
        tree_specs(axes_tree, value_tree, rules),
        is_leaf=lambda x: isinstance(x, PartitionSpec),
    )


def stack_axes(axes: tuple[str | None, ...]) -> tuple[str | None, ...]:
    """Axes tuple for a param stacked over layers (scan-over-layers)."""
    return ("layers",) + tuple(axes)


def param_count(params: Any) -> int:
    return int(sum(np.prod(p.shape) for p in jax.tree.leaves(params)))


def bytes_of(tree: Any) -> int:
    return int(sum(np.prod(p.shape) * jax.dtypes.canonicalize_dtype(p.dtype).itemsize
                   for p in jax.tree.leaves(tree)))


# Convenience initializers ---------------------------------------------------
def trunc_normal(key: jax.Array, shape: Sequence[int], scale: float,
                 dtype: Any) -> jax.Array:
    import jax.numpy as jnp
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32)
            * scale).astype(dtype)


def dense_init(key: jax.Array, shape: Sequence[int], axes: tuple,
               dtype: Any, scale: float | None = None) -> Annot:
    """Fan-in scaled truncated-normal init, annotated."""
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else fan_in ** -0.5
    return Annot(trunc_normal(key, shape, s, dtype), axes)


def zeros_init(shape: Sequence[int], axes: tuple, dtype: Any) -> Annot:
    import jax.numpy as jnp
    return Annot(jnp.zeros(shape, dtype), axes)


def ones_init(shape: Sequence[int], axes: tuple, dtype: Any) -> Annot:
    import jax.numpy as jnp
    return Annot(jnp.ones(shape, dtype), axes)
