"""Elastic scaling: rebuild the mesh after node loss/gain and reshard state.

Strategy (single-controller JAX):
  * the `model` axis extent is fixed (TP degree is baked into layer math
    perf-wise, but **not** into the checkpoint — shards are reassembled to
    global arrays on restore, so even TP can change);
  * the (`pod` × `data`) product absorbs failures: losing a host rebuilds a
    mesh with a smaller `data` extent, restores the latest checkpoint with
    the new shardings, and rescales the data pipeline (`host_count` drops);
  * a failed step is retried from the last checkpoint — see
    launch/train.py's failure loop (tested with failure injection).

`plan_rescale` computes the largest valid mesh after losing `lost` chips;
`reshard` moves a live pytree onto a new mesh (host round-trip — the
simple, always-correct path; production would use device-to-device
resharding collectives).
"""

from __future__ import annotations

import dataclasses
from typing import Any

import jax
import numpy as np

from repro.sharding.rules import LogicalRules, tree_shardings

__all__ = [
    "plan_rescale",
    "plan_decode_rescale",
    "rescale_decode_engine",
    "reshard",
    "RescalePlan",
]


@dataclasses.dataclass(frozen=True)
class RescalePlan:
    old_shape: tuple
    new_shape: tuple
    axis_names: tuple
    dropped_chips: int

    @property
    def new_chip_count(self) -> int:
        return int(np.prod(self.new_shape))


def plan_rescale(
    mesh: jax.sharding.Mesh,
    lost_chips: int,
    *,
    shrink_axes: tuple[str, ...] | None = None,
) -> RescalePlan:
    """Largest mesh obtainable by shrinking ``shrink_axes`` after losing
    ``lost_chips`` devices.

    ``shrink_axes`` defaults to every axis except ``model`` (the train-mesh
    contract above: TP degree is baked into layer math). A decode fleet
    passes its engine's ``block_axes`` instead — the lane axis is the only
    thing a PBVD mesh shards, so those are the axes a casualty can shrink
    (see :func:`plan_decode_rescale`).

    The search maximizes the surviving chip count over ALL candidate
    shrink-axis shapes. The old implementation ``break``-ed out of a
    lexicographically descending enumeration at the first shape that fit,
    which is only the maximum when a single axis shrinks: with two 4-wide
    data-like axes and 7 chips lost it returned 4×2 = 8 chips when 3×3 = 9
    fit (the counterexample pinned in tests/test_fault_tolerance.py).
    """
    names = mesh.axis_names
    shape = dict(mesh.shape)
    total = int(np.prod(list(shape.values())))
    target = total - int(lost_chips)
    if shrink_axes is None:
        shrink_axes = tuple(n for n in names if n != "model")
    else:
        shrink_axes = tuple(shrink_axes)
        unknown = [a for a in shrink_axes if a not in shape]
        if unknown:
            raise ValueError(
                f"shrink_axes {unknown} not in mesh axes {tuple(names)}"
            )
    fixed = int(np.prod([shape[n] for n in names if n not in shrink_axes]))
    cur = [shape[n] for n in shrink_axes]
    best: tuple[int, ...] | None = None
    best_prod = 0

    def search(idx: int, acc: tuple[int, ...], prod: int) -> None:
        nonlocal best, best_prod
        # remaining axes contribute a factor >= 1 each, so prod*fixed is a
        # lower bound on the finished candidate — prune overshoots early
        if prod * fixed > target:
            return
        if idx == len(cur):
            if prod > best_prod:
                best, best_prod = acc, prod
            return
        for v in range(cur[idx], 0, -1):
            search(idx + 1, acc + (v,), prod * v)

    search(0, (), 1)
    if best is None:
        # even the all-ones shrink exceeds the survivors (fixed axes alone
        # are too big): report the degenerate minimum and let the caller
        # decide (the decode port drops to meshless dispatch)
        best = tuple(1 for _ in cur)
    new_shape = tuple(
        best[shrink_axes.index(n)] if n in shrink_axes else shape[n] for n in names
    )
    return RescalePlan(
        old_shape=tuple(shape[n] for n in names),
        new_shape=new_shape,
        axis_names=tuple(names),
        dropped_chips=total - int(np.prod(new_shape)),
    )


def plan_decode_rescale(
    mesh: jax.sharding.Mesh,
    block_axes: tuple[str, ...],
    lost_chips: int,
) -> RescalePlan | None:
    """Rescale plan for a decode-fleet mesh: only the engine's lane-carrying
    ``block_axes`` may shrink (every other axis is launch geometry the
    compiled decode depends on).

    Returns ``None`` when no valid smaller mesh exists — the survivors
    cannot host even the all-ones shrink — in which case the caller should
    drop to meshless dispatch (:func:`rescale_decode_engine` does).
    """
    plan = plan_rescale(mesh, lost_chips, shrink_axes=block_axes)
    total = int(np.prod(plan.old_shape))
    if plan.new_chip_count > total - int(lost_chips) or plan.new_chip_count < 1:
        return None
    return plan


def rescale_decode_engine(engine, lost_chips: int):
    """A replacement engine for ``engine`` after ``lost_chips`` devices died.

    Shrinks the mesh along the engine's ``block_axes`` per
    :func:`plan_decode_rescale` and rebuilds the engine on the smaller mesh;
    when no useful mesh survives (no plan, or a single-chip remnant whose
    sharding overhead buys nothing) the engine drops to meshless dispatch.
    Either way the decode is bit-exact to the original engine — the mesh
    only places lanes, it never changes what a launch computes — so a
    serving layer can swap engines under live sessions and replay their
    ready-but-undecoded blocks from session state (DESIGN.md §14).
    """
    from repro.core.engine import DecoderEngine
    from repro.launch.mesh import shrink_mesh

    if engine.mesh is None:
        return engine
    plan = plan_decode_rescale(engine.mesh, engine.block_axes, lost_chips)
    if plan is None or plan.new_chip_count < 2:
        return DecoderEngine(engine.cfg, mesh=None, block_axes=("data",))
    new_mesh = shrink_mesh(engine.mesh, plan.new_shape)
    return DecoderEngine(engine.cfg, mesh=new_mesh, block_axes=engine.block_axes)


def reshard(tree: Any, axes_tree: Any, new_mesh: jax.sharding.Mesh, rules_map=None) -> Any:
    """Move a pytree onto ``new_mesh`` with its logical axes re-resolved."""
    from repro.sharding.rules import DEFAULT_RULES, SINGLE_POD_RULES

    if rules_map is None:
        rules_map = DEFAULT_RULES if "pod" in new_mesh.axis_names else SINGLE_POD_RULES
    rules = LogicalRules(new_mesh, rules_map)
    shardings = tree_shardings(tree, axes_tree, rules)
    host = jax.tree.map(lambda l: np.asarray(l), tree)
    return jax.tree.map(jax.device_put, host, shardings)
