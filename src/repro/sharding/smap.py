"""The lane-axis ``shard_map`` dispatch the mesh-bound decode path uses."""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

__all__ = ["lane_shard_map"]


def lane_shard_map(f, *, mesh, axes, in_rank: int, out_rank: int):
    """shard_map ``f`` over ONLY the trailing (lane) axis of its operand.

    The PBVD decode contract shards nothing but the last axis — parallel
    blocks never interact, so ``f`` runs per-shard on its local lanes with
    zero collectives. ``axes`` is the tuple of mesh axis names carrying the
    lane axis; ``in_rank``/``out_rank`` are the operand/result ranks (the
    leading axes are replicated).
    """
    in_specs = P(*([None] * (in_rank - 1) + [tuple(axes)]))
    out_specs = P(*([None] * (out_rank - 1) + [tuple(axes)]))
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False
    )
