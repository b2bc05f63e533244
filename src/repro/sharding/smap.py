"""The lane-axis ``shard_map`` dispatch the mesh-bound decode path uses."""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

__all__ = ["lane_shard_map", "lane_sharding"]


def _lane_spec(axes, rank: int) -> P:
    return P(*([None] * (rank - 1) + [tuple(axes)]))


def lane_sharding(mesh, axes, rank: int) -> NamedSharding:
    """The placement :func:`lane_shard_map` expects of its operand: the
    trailing (lane) axis split over ``axes``, the leading axes replicated."""
    return NamedSharding(mesh, _lane_spec(axes, rank))


def lane_shard_map(f, *, mesh, axes, in_rank: int, out_rank: int):
    """shard_map ``f`` over ONLY the trailing (lane) axis of its operand.

    The PBVD decode contract shards nothing but the last axis — parallel
    blocks never interact, so ``f`` runs per-shard on its local lanes with
    zero collectives. ``axes`` is the tuple of mesh axis names carrying the
    lane axis; ``in_rank``/``out_rank`` are the operand/result ranks (the
    leading axes are replicated).

    The engine builds its mesh launch once, as a ``jax.jit`` of this map,
    and hands it lanes already placed with :func:`lane_sharding`, so a
    repeated lane shape reuses the compiled launch and no transfer hides
    inside it.
    """
    return jax.shard_map(
        f,
        mesh=mesh,
        in_specs=_lane_spec(axes, in_rank),
        out_specs=_lane_spec(axes, out_rank),
        check_vma=False,
    )
