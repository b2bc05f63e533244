"""Logical-axis sharding rules (MaxText-style, dependency-free).

Model code annotates arrays with *logical* axis names
(``shard(x, ("batch", "seq", "embed"))``); a rule-set maps logical names to
mesh axes. Outside a rule context the annotations are no-ops, so the same
model code runs single-device smoke tests and 512-chip dry-runs unchanged.

Default production mapping (see DESIGN.md §6):

  batch   → ("pod", "data")   activations data-parallel across pods × hosts
  fsdp    → "data"            parameters fully sharded over the data axis
  heads/kv/mlp/vocab/expert_mlp → "model"   tensor parallel
  seq_ctx → "model"           context parallelism for long-sequence decode
"""

from __future__ import annotations

import contextlib
import threading
from typing import Mapping, Sequence

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

__all__ = [
    "LogicalRules",
    "axis_rules",
    "current_rules",
    "shard",
    "logical_to_spec",
    "named_sharding",
    "block_mesh_axes",
    "DEFAULT_RULES",
    "SINGLE_POD_RULES",
]

# logical axis name → mesh axis (or tuple of mesh axes), None → replicated
DEFAULT_RULES: dict[str, str | tuple[str, ...] | None] = {
    "batch": ("pod", "data"),
    "fsdp": "data",
    "embed": None,
    "seq": None,
    "seq_ctx": "model",  # context-parallel KV for long decode
    "heads": "model",
    "kv_heads": "model",
    "head_dim": None,
    "mlp": "model",
    "vocab": "model",
    # Expert parallelism with automatic fallback: `experts` is listed before
    # `expert_mlp` in every MoE axes tuple, so when n_experts divides the
    # model axis (deepseek 160, jamba 16) the experts shard (true EP) and the
    # hidden dim replicates; when it doesn't (mixtral 8 on 16), the
    # shape-divisibility fallback drops `experts` and the hidden dim takes
    # the model axis instead (TP-within-expert).
    "experts": "model",
    "expert_mlp": "model",
    "conv": None,
    "state": None,
    "blocks": ("pod", "data"),  # PBVD parallel blocks
}

SINGLE_POD_RULES = dict(DEFAULT_RULES, batch="data", blocks="data")

_local = threading.local()


class LogicalRules:
    def __init__(self, mesh: Mesh, rules: Mapping[str, str | tuple[str, ...] | None]):
        self.mesh = mesh
        self.rules = dict(rules)
        # drop mappings that reference axes the mesh doesn't have
        for k, v in list(self.rules.items()):
            axes = (v,) if isinstance(v, str) else (v or ())
            if any(a not in mesh.axis_names for a in axes):
                self.rules[k] = None

    def spec(
        self, logical_axes: Sequence[str | None], shape: Sequence[int] | None = None
    ) -> PartitionSpec:
        """Map logical axes to a PartitionSpec. With ``shape`` given, mesh
        axes that do not divide the corresponding dimension are dropped
        greedily (JAX requires exact tiling for argument shardings — e.g.
        GQA kv=8 on a 16-way model axis falls back to replicated KV)."""
        parts = []
        used: set[str] = set()
        for i, ax in enumerate(logical_axes):
            if ax is None:
                parts.append(None)
                continue
            m = self.rules.get(ax)
            if m is None:
                parts.append(None)
                continue
            maxes = (m,) if isinstance(m, str) else tuple(m)
            maxes = tuple(a for a in maxes if a not in used)
            if shape is not None:
                dim = shape[i]
                while maxes:
                    prod = 1
                    for a in maxes:
                        prod *= self.mesh.shape[a]
                    if prod and dim % prod == 0:
                        break
                    maxes = maxes[:-1]
            used.update(maxes)
            if not maxes:
                parts.append(None)
            elif len(maxes) == 1:
                parts.append(maxes[0])
            else:
                parts.append(maxes)
        return PartitionSpec(*parts)


def current_rules() -> LogicalRules | None:
    return getattr(_local, "rules", None)


@contextlib.contextmanager
def axis_rules(mesh: Mesh, rules: Mapping[str, str | tuple[str, ...] | None] | None = None):
    """Activate a logical→mesh rule-set (and the mesh) for the enclosed code."""
    prev = getattr(_local, "rules", None)
    if rules is None:
        rules = DEFAULT_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES
    _local.rules = LogicalRules(mesh, rules)
    try:
        with jax.set_mesh(mesh):
            yield _local.rules
    finally:
        _local.rules = prev


def logical_to_spec(logical_axes: Sequence[str | None]) -> PartitionSpec:
    r = current_rules()
    if r is None:
        return PartitionSpec()
    return r.spec(logical_axes)


def named_sharding(logical_axes: Sequence[str | None]) -> NamedSharding | None:
    r = current_rules()
    if r is None:
        return None
    return NamedSharding(r.mesh, r.spec(logical_axes))


def block_mesh_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes the PBVD ``blocks`` logical axis maps to on ``mesh``.

    Resolves the ``"blocks"`` rule (``("pod", "data")`` multi-pod,
    ``"data"`` single-pod) and drops axes the mesh does not have — the
    engine's default ``block_axes`` when bound to a mesh without an explicit
    override (``DecoderEngine(cfg, mesh=m, block_axes=None)``).
    """
    rules = DEFAULT_RULES if "pod" in mesh.axis_names else SINGLE_POD_RULES
    m = rules["blocks"]
    axes = (m,) if isinstance(m, str) else tuple(m or ())
    resolved = tuple(a for a in axes if a in mesh.axis_names)
    if not resolved:
        raise ValueError(
            f"no 'blocks' rule axis {axes} exists on mesh axes "
            f"{tuple(mesh.axis_names)}; pass block_axes explicitly"
        )
    return resolved


def shard(x: jax.Array, logical_axes: Sequence[str | None]) -> jax.Array:
    """Annotate ``x`` with a sharding constraint; no-op outside a rule context."""
    r = current_rules()
    if r is None:
        return x
    spec = r.spec(logical_axes, shape=x.shape)
    return jax.lax.with_sharding_constraint(x, NamedSharding(r.mesh, spec))


def tree_shardings(sds_tree, axes_tree, rules: LogicalRules):
    """Shape-aware NamedShardings for a pytree of ShapeDtypeStructs/arrays.

    ``axes_tree`` mirrors ``sds_tree`` with logical-axis tuples as leaves.
    """
    flat_sds, treedef = jax.tree.flatten(sds_tree)
    # axes leaves are PLAIN tuples of axis names; NamedTuples (KVCache etc.)
    # must still be traversed as pytrees
    flat_axes = jax.tree.leaves(axes_tree, is_leaf=lambda a: type(a) is tuple)
    if len(flat_sds) != len(flat_axes):
        raise ValueError(
            f"sds tree has {len(flat_sds)} leaves but axes tree has {len(flat_axes)}"
        )
    out = [
        NamedSharding(rules.mesh, rules.spec(a, shape=s.shape))
        for s, a in zip(flat_sds, flat_axes)
    ]
    return jax.tree.unflatten(treedef, out)
