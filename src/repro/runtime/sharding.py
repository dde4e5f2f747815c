"""Logical-axis sharding: MaxText-style rules mapping names -> mesh axes.

Models annotate activations with *logical* names (``logical_constraint``)
and expose parameter spec trees of logical names; the launch layer binds a
rule set (``ShardingRules``) + mesh, turning names into ``PartitionSpec``.
With no rules bound (unit tests, single device) annotations are no-ops, so
model code never depends on the mesh.
"""

from __future__ import annotations

import contextlib
import threading
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

__all__ = ["ShardingRules", "use_rules", "current_mesh", "logical_constraint",
           "logical_to_spec", "spec_tree", "DEFAULT_RULES", "MULTIPOD_RULES"]

MeshAxes = Union[str, Tuple[str, ...], None]


class ShardingRules(dict):
    """logical axis name -> mesh axis (or tuple of mesh axes, or None)."""

    def spec(self, names: Sequence[Optional[str]]) -> P:
        return P(*(self.get(n) if n is not None else None for n in names))


# The production rule sets. "fsdp" dim of weights -> data axis; tensor-
# parallel dim -> model axis; batch -> (pod,) data. KV-cache sequence dim
# shards over model when kv-head count can't fill it (flash-decoding SP).
DEFAULT_RULES = ShardingRules({
    "batch": "data",
    "embed": None,            # activation d_model: replicated within shard
    "seq": None,
    "vocab": "model",
    "heads": "model",
    "kv_heads": "model",
    "kv_seq": None,
    "kv_seq_shard": "model",  # sequence-sharded KV cache (decode SP)
    "mlp": "model",
    "experts": "model",
    "embed_fsdp": "data",     # weight d_model dim: FSDP-sharded
    "ff_fsdp": "data",
    "norm": None,
    "conv": None,
    "state": None,
})

MULTIPOD_RULES = ShardingRules({**DEFAULT_RULES, "batch": ("pod", "data")})


class _Ctx(threading.local):
    def __init__(self):
        self.rules: Optional[ShardingRules] = None
        self.mesh: Optional[Mesh] = None


_ctx = _Ctx()


@contextlib.contextmanager
def use_rules(rules: ShardingRules, mesh: Optional[Mesh] = None):
    """Bind sharding rules (and optionally a mesh) for model tracing."""
    prev = (_ctx.rules, _ctx.mesh)
    _ctx.rules, _ctx.mesh = rules, mesh
    try:
        yield
    finally:
        _ctx.rules, _ctx.mesh = prev


def current_mesh() -> Optional[Mesh]:
    """The mesh bound by :func:`use_rules` (None outside one)."""
    return _ctx.mesh


def logical_constraint(x: jnp.ndarray, *names: Optional[str]) -> jnp.ndarray:
    """Annotate an intermediate with logical axis names (no-op without rules).

    Entries whose mesh-axis product does not divide the dimension are
    dropped (replicated): asking GSPMD to shard 14 heads over a 16-wide
    axis triggers involuntary full rematerialization — far worse than
    replicating that dim.
    """
    if _ctx.rules is None:
        return x
    spec = _ctx.rules.spec(names)
    if _ctx.mesh is not None:
        sizes = dict(zip(_ctx.mesh.axis_names, _ctx.mesh.devices.shape))
        entries = []
        for dim, entry in zip(x.shape, tuple(spec) + (None,) * (x.ndim - len(spec))):
            if entry is None:
                entries.append(None)
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            total = 1
            for a in axes:
                total *= sizes.get(a, 1)
            entries.append(entry if total and dim % total == 0 else None)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(_ctx.mesh, P(*entries)))
    return jax.lax.with_sharding_constraint(x, spec)


def logical_to_spec(rules: ShardingRules, names: Sequence[Optional[str]]) -> P:
    return rules.spec(names)


def spec_tree(rules: ShardingRules, logical_tree):
    """Map a pytree of logical-name tuples to a pytree of PartitionSpec."""
    return jax.tree_util.tree_map(
        lambda names: rules.spec(names),
        logical_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(n, (str, type(None))) for n in x),
    )
