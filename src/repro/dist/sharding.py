"""Logical-axis sharding substrate.

Params are built as trees of ``P`` leaves — an array *boxed* with the
logical axis names of its dimensions (``embed``, ``mlp``, ``heads``, ...).
``ShardingRules`` maps logical axes to mesh axes; ``spec`` resolves a
boxed leaf's axes to a ``PartitionSpec``, dropping mesh axes absent from
the mesh (e.g. ``pod`` on a single-pod run) and deduplicating mesh axes
that an earlier dimension already consumed (GSPMD allows each mesh axis
at most once per spec).

Model code calls ``shard(x, *logical_axes)`` on activations: a no-op
outside an ``axis_rules(mesh, rules)`` context, a
``with_sharding_constraint`` inside one — so the same forward pass runs
unsharded on CPU smoke tests and sharded on the production mesh.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.configs.base import ModelConfig, ShapeConfig

Axis = Optional[str]
MeshAxes = Union[None, str, Tuple[str, ...]]


class P:
    """A pytree *leaf*: an array boxed with its logical axis names."""

    __slots__ = ("value", "axes")

    def __init__(self, value, axes: Sequence[Axis]):
        self.value = value
        self.axes = tuple(axes)

    def __repr__(self):
        return f"P(shape={getattr(self.value, 'shape', None)}, " \
               f"axes={self.axes})"


# P is a pytree node (value is the child, axes ride along as aux data) so
# jax transforms — lax.map in transformer.stack_init — pass through the box;
# unbox/axes_of still stop at P via is_leaf.
jax.tree_util.register_pytree_node(
    P, lambda p: ((p.value,), p.axes), lambda axes, kids: P(kids[0], axes))


class _AxesLeaf:
    """Opaque leaf wrapping an axes tuple (a bare tuple would be
    flattened as a pytree container)."""

    __slots__ = ("axes",)

    def __init__(self, axes: Tuple[Axis, ...]):
        self.axes = tuple(axes)

    def __repr__(self):
        return f"Axes{self.axes}"


def _is_p(x) -> bool:
    return isinstance(x, P)


def unbox(tree):
    """P-tree -> plain array tree."""
    return jax.tree.map(lambda p: p.value if _is_p(p) else p, tree,
                        is_leaf=_is_p)


def axes_of(tree):
    """P-tree -> tree of axes leaves (same structure as ``unbox``)."""
    return jax.tree.map(
        lambda p: _AxesLeaf(p.axes) if _is_p(p) else _AxesLeaf(()),
        tree, is_leaf=_is_p)


def box_like(values, axes_tree):
    """Inverse of (unbox, axes_of): re-box plain arrays with their axes."""
    return jax.tree.map(lambda v, a: P(v, a.axes), values, axes_tree)


class ShardingRules(dict):
    """logical axis -> mesh axis (or tuple of mesh axes, or None)."""

    def spec(self, axes: Sequence[Axis], mesh=None) -> PartitionSpec:
        axes = getattr(axes, "axes", axes)
        mesh_axes = set(mesh.axis_names) if mesh is not None else None
        used = set()
        entries = []
        for ax in axes:
            mapped = self.get(ax) if ax is not None else None
            if mapped is None:
                entries.append(None)
                continue
            cand = (mapped,) if isinstance(mapped, str) else tuple(mapped)
            keep = [c for c in cand
                    if (mesh_axes is None or c in mesh_axes)
                    and c not in used]
            used.update(keep)
            if not keep:
                entries.append(None)
            elif len(keep) == 1:
                entries.append(keep[0])
            else:
                entries.append(tuple(keep))
        return PartitionSpec(*entries)


# Batch prefers (pod, data); params FSDP-shard embed over data and tensor-
# shard the wide dims over model.  Axes not listed stay replicated.
TRAIN_RULES = ShardingRules({
    "batch": ("pod", "data"),
    "embed": "data",
    "mlp": "model",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "expert": "model",
    "expert_mlp": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
})

# Serving replicates small params, tensor-shards wide dims, and data-
# parallelizes the batch.
SERVE_RULES = ShardingRules({
    "batch": "data",
    "mlp": "model",
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "expert": "model",
    "expert_mlp": "model",
    "ssm_inner": "model",
    "ssm_heads": "model",
})

# Long-context decode: context-parallel KV over data (callers override
# batch/kv_seq per shape; see rules_for).
LONG_CTX_RULES = ShardingRules({**SERVE_RULES, "batch": None,
                                "kv_seq": "data"})


def rules_for(cfg: ModelConfig, shape: ShapeConfig,
              model_axis: int = 16) -> ShardingRules:
    """The rules for one (architecture, input shape) on a mesh whose
    model axis has ``model_axis`` devices."""
    if shape.mode == "train":
        base = TRAIN_RULES
    elif shape.name == "long_500k":
        base = ShardingRules({**LONG_CTX_RULES, "batch": None,
                              "kv_seq": ("pod", "data")})
    else:
        base = SERVE_RULES
    rules = ShardingRules(base)
    # kv heads that don't divide the model axis: shard head_dim instead of
    # padding the KV cache 4-16x (GSPMD would pad uneven head sharding)
    if (cfg.num_kv_heads and cfg.num_kv_heads % model_axis != 0
            and not cfg.use_mla):
        rules["kv_heads"] = None
        rules["head_dim"] = "model"
    if cfg.num_heads and cfg.num_heads % model_axis != 0:
        rules["heads"] = None
    if cfg.num_experts and cfg.num_experts % model_axis != 0:
        rules["expert"] = "data"
    return rules


def named_sharding_tree(axes_tree, mesh, rules: ShardingRules):
    return jax.tree.map(
        lambda a: NamedSharding(mesh, rules.spec(a, mesh)), axes_tree)


_ctx = threading.local()


@contextlib.contextmanager
def axis_rules(mesh, rules: ShardingRules):
    """Activate sharding constraints for ``shard`` calls in this thread."""
    prev = getattr(_ctx, "active", None)
    _ctx.active = (mesh, rules)
    try:
        yield
    finally:
        _ctx.active = prev


def shard(x, *axes: Axis):
    """Constrain activation ``x`` to its logical axes; no-op without an
    active ``axis_rules`` context."""
    active = getattr(_ctx, "active", None)
    if active is None:
        return x
    mesh, rules = active
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, rules.spec(axes, mesh)))
