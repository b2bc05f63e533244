"""The ``blocks`` sharding rule: which mesh axes carry the PBVD lanes.

A multi-pod mesh shards the parallel blocks over ``("pod", "data")``, a
single-pod mesh over ``"data"`` (see DESIGN.md §6).
"""

from __future__ import annotations

from jax.sharding import Mesh

__all__ = ["block_mesh_axes"]

_MULTI_POD_BLOCKS = ("pod", "data")
_SINGLE_POD_BLOCKS = ("data",)


def block_mesh_axes(mesh: Mesh) -> tuple[str, ...]:
    """Mesh axes the PBVD ``blocks`` logical axis maps to on ``mesh``.

    Resolves the ``blocks`` rule (``("pod", "data")`` multi-pod,
    ``"data"`` single-pod) and drops axes the mesh does not have — the
    engine's default ``block_axes`` when bound to a mesh without an explicit
    override (``DecoderEngine(cfg, mesh=m, block_axes=None)``).
    """
    axes = _MULTI_POD_BLOCKS if "pod" in mesh.axis_names else _SINGLE_POD_BLOCKS
    resolved = tuple(a for a in axes if a in mesh.axis_names)
    if not resolved:
        raise ValueError(
            f"no 'blocks' rule axis {axes} exists on mesh axes "
            f"{tuple(mesh.axis_names)}; pass block_axes explicitly"
        )
    return resolved
