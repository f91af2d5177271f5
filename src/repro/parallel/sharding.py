"""Logical-axis sharding rules → NamedSharding (MaxText-style).

Arrays are annotated with *logical axes* (tuples of names like
("batch", "seq", "embed")); a rule table maps logical names to mesh axes.
`logical_to_spec` resolves the rules with divisibility fallback: a logical
axis whose size does not divide the mesh axis product is left replicated
(e.g. smollm's 15 attention heads on a 16-wide model axis) — the framework
never emits an invalid sharding, it degrades to replication and the roofline
shows the cost.
"""
from __future__ import annotations

from typing import Any, Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[str, Tuple[str, ...], None]

# default rule table; configs may override entries
DEFAULT_RULES: Dict[str, MeshAxes] = {
    # data-parallel axes
    "batch": ("pod", "data"),
    "query": ("pod", "data"),          # serving query stream
    "edges": ("pod", "data"),          # GNN edge partition
    # tensor-parallel axes
    "embed": None,                      # activations' model dim: replicated
    "heads": "model",
    "kv_heads": "model",
    # attention output reshaped to [B, S, H*hd]: the FUSED head dim shards
    # cleanly over model even when kv_heads alone is indivisible (e.g.
    # phi3.5's kv=8 on a 16-wide model axis) — forcing this before the wo
    # projection keeps the contraction sharded instead of SPMD all-gathering
    # the heads (§Perf iteration 4)
    "heads_flat": "model",
    "mlp": "model",                     # d_ff
    "vocab": "model",
    "experts": "model",                 # EP
    # SP for long-context decode caches; picks up the data axes too when the
    # batch is too small to use them (long_500k: batch=1)
    "kv_seq": ("data", "model"),
    "table_rows": "model",              # recsys embedding table rows
    "nodes": ("pod", "data"),          # GNN node partition (full-graph)
    "expert_cap": "data",               # MoE expert-capacity dim
    "index_nodes": None,                # ferrari packed index rows (replicated
                                        # by default; 'model' = sharded mode)
    "hidden": None,
    # never sharded
    "seq": None,
    "layers": None,
    "stack": None,
    "capsule": None,
    "feat": None,
}


def shard_map_compat(f, *, mesh, in_specs, out_specs, check: bool = False):
    """``jax.shard_map`` with the varying-manual-axes check off by default
    (the serving kernels psum masked partials the checker cannot see)."""
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check)


def mesh_axis_size(mesh: Mesh, axes: MeshAxes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    return size


def logical_to_spec(logical: Sequence[Optional[str]], shape: Sequence[int],
                    mesh: Mesh, rules: Optional[Dict[str, MeshAxes]] = None,
                    ) -> P:
    """Resolve logical axis names to a PartitionSpec with divisibility
    fallback. ``logical`` entries may be None (replicated)."""
    rules = {**DEFAULT_RULES, **(rules or {})}
    used: set = set()
    spec = []
    for name, dim in zip(logical, shape):
        tgt = rules.get(name) if name is not None else None
        if tgt is None:
            spec.append(None)
            continue
        axes = (tgt,) if isinstance(tgt, str) else tuple(tgt)
        # drop axes not present in this mesh (e.g. 'pod' on single-pod)
        axes = tuple(a for a in axes if a in mesh.shape and a not in used)
        size = int(np.prod([mesh.shape[a] for a in axes])) if axes else 1
        if not axes or size == 1 or dim % size != 0:
            # divisibility fallback: try a prefix of the axes tuple
            while axes and (dim % int(np.prod([mesh.shape[a] for a in axes])) != 0):
                axes = axes[:-1]
            if not axes:
                spec.append(None)
                continue
        used.update(axes)
        spec.append(axes[0] if len(axes) == 1 else axes)
    return P(*spec)


def named_sharding(logical, shape, mesh, rules=None) -> NamedSharding:
    return NamedSharding(mesh, logical_to_spec(logical, shape, mesh, rules))


def tree_shardings(logical_tree, shape_tree, mesh, rules=None):
    """Map matching pytrees of logical-axis tuples and shapes to shardings."""
    return jax.tree.map(
        lambda lg, shp: named_sharding(lg, shp, mesh, rules),
        logical_tree, shape_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def zero1_spec(spec: P, shape: Sequence[int], mesh: Mesh) -> P:
    """ZeRO-1: additionally shard optimizer-state tensors over the data axes
    on the first unsharded, divisible dimension."""
    entries = list(spec) + [None] * (len(shape) - len(spec))
    used = set()
    for e in entries:
        if e is None:
            continue
        used.update((e,) if isinstance(e, str) else e)
    dp_axes = tuple(a for a in ("pod", "data") if a in mesh.shape and a not in used)
    if not dp_axes:
        return spec
    size = int(np.prod([mesh.shape[a] for a in dp_axes]))
    for i, (e, dim) in enumerate(zip(entries, shape)):
        if e is None and dim % size == 0 and dim > 0:
            entries[i] = dp_axes if len(dp_axes) > 1 else dp_axes[0]
            return P(*entries)
        if e is None and len(dp_axes) > 1 and dim % mesh.shape[dp_axes[-1]] == 0:
            entries[i] = dp_axes[-1]
            return P(*entries)
    return spec


class ShardingCtx:
    """Carries (mesh, rules) through model code; ``None`` mesh = no-op
    constraints (single-device tests and smoke runs)."""

    def __init__(self, mesh: Optional[Mesh] = None,
                 rules: Optional[Dict[str, MeshAxes]] = None):
        self.mesh = mesh
        self.rules = rules

    def constrain(self, x, logical):
        if self.mesh is None:
            return x
        spec = logical_to_spec(logical, x.shape, self.mesh, self.rules)
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(self.mesh, spec))

    def sharding(self, logical, shape) -> Optional[NamedSharding]:
        if self.mesh is None:
            return None
        return named_sharding(logical, shape, self.mesh, self.rules)


NO_SHARDING = ShardingCtx(None)
