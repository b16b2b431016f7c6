"""Logical-axis sharding rules with divisibility fallback (MaxText-style).

Every tensor dim is annotated with a *logical* name ("batch", "heads",
"mlp", …).  Rules map logical names to an ordered list of mesh-axis
candidates; the first candidate whose size divides the dim is chosen, else the
dim is replicated.  This is what lets all 10 assigned architectures lower on
the same (data=16, model=16) / (pod=2, data=16, model=16) meshes even when
e.g. kv_heads=8 cannot split 16 ways (DESIGN.md §7).
"""
from __future__ import annotations

import math
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# candidates are tuples-of-mesh-axes (a tuple shards a dim over several axes)
Rules = Mapping[str, Sequence[tuple[str, ...]]]

DEFAULT_RULES: Rules = {
    # activations
    "batch":      [("pod", "data"), ("data",)],
    "seq":        [()],                       # replicated (SP via halo path)
    "seq_shard":  [("data",)],                # sequence parallelism opt-in
    "embed":      [()],
    # params
    "vocab":      [("model",)],
    "heads":      [("model",)],
    "kv_heads":   [("model",)],
    "head_dim":   [()],
    "mlp":        [("model",)],
    "experts":    [("model",)],
    "expert_cap": [("model",)],   # MoE fallback: shard capacity when E can't
    "cache_seq":  [("model",)],   # KV-cache positions: kv_heads never divide
                                  # 16 on the assigned archs, so decode shards
                                  # the cache *sequence* instead (the dry-run
                                  # caught 74 GiB/dev unsharded caches)
    "fsdp":       [("data",)],                # param leading-dim FSDP
    "conv_k":     [()],
    "stencil_x":  [("data",)],                # distributed stencil strips
    "stencil_y":  [("pod",)],
}


# Serving layout: identical to DEFAULT_RULES except params are NOT
# FSDP-sharded — decode would otherwise re-all-gather every weight on every
# step (EXPERIMENTS.md §Perf cell B).
INFERENCE_RULES: Rules = {**DEFAULT_RULES, "fsdp": [()]}


def _axes_size(mesh: Mesh, axes: tuple[str, ...]) -> int:
    return math.prod(mesh.shape[a] for a in axes)


def resolve_spec(shape: tuple[int, ...], logical: tuple[str | None, ...],
                 mesh: Mesh, rules: Rules | None = None) -> P:
    """Pick a PartitionSpec for ``shape`` given per-dim logical names.

    Falls back to replication when no candidate divides the dim or the mesh
    lacks the axis.  A mesh axis is used at most once per tensor (pjit
    requirement); earlier dims win.
    """
    rules = rules or DEFAULT_RULES
    assert len(shape) == len(logical), (shape, logical)
    used: set[str] = set()
    parts = []
    for dim, name in zip(shape, logical):
        chosen: tuple[str, ...] | None = None
        if name:
            for cand in rules.get(name, [()]):
                cand = tuple(a for a in cand if a in mesh.shape)
                if not cand or any(a in used for a in cand):
                    continue
                if dim % _axes_size(mesh, cand) == 0:
                    chosen = cand
                    break
        if chosen:
            used.update(chosen)
            parts.append(chosen if len(chosen) > 1 else chosen[0])
        else:
            parts.append(None)
    while parts and parts[-1] is None:
        parts.pop()
    return P(*parts)


def named_sharding(shape: tuple[int, ...], logical: tuple[str | None, ...],
                   mesh: Mesh, rules: Rules | None = None) -> NamedSharding:
    return NamedSharding(mesh, resolve_spec(shape, logical, mesh, rules))


def constrain(x, logical: tuple, rules: Rules | None = None):
    """Activation sharding constraint by logical names, resolved against the
    ambient mesh (``jax.sharding.set_mesh``).  No-op when no mesh is set
    (single-device tests) — models stay mesh-agnostic.

    Without these anchors the SPMD partitioner loses the batch sharding at
    gathers (token embedding) and silently replicates the whole network —
    caught by the dry-run flop accounting (EXPERIMENTS.md §Perf, iteration 0).
    """
    try:
        am = jax.sharding.get_abstract_mesh()
    except Exception:
        return x
    if am is None or not getattr(am, "shape", None):
        return x
    spec = resolve_spec(tuple(x.shape), logical, am, rules)
    if not spec:
        return x
    return jax.lax.with_sharding_constraint(x, spec)


def tree_shardings(tree_of_shapes, tree_of_logical, mesh: Mesh,
                   rules: Rules | None = None):
    """Map (shape-tree, logical-tree) -> NamedSharding tree (same structure)."""
    return jax.tree.map(
        lambda sh, lg: named_sharding(tuple(sh), tuple(lg), mesh, rules),
        tree_of_shapes, tree_of_logical,
        is_leaf=lambda x: isinstance(x, (tuple, list)) and
        (not x or not isinstance(x[0], (tuple, list))))
