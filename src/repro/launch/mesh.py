"""Device meshes: production shapes, local test meshes, and the decode-fleet
launch recipe.

``make_production_mesh`` is a function (not a module-level constant) so that
importing this module never touches JAX device state.

Multi-process launch recipe (one process per host, à la the MaxText XPK
multi-slice scripts — SNIPPETS.md #2/#3):

    # per host i of N (same command everywhere, only PROCESS_ID varies):
    JAX_COORDINATOR_ADDRESS=host0:8476 JAX_NUM_PROCESSES=N JAX_PROCESS_ID=i \\
        python -m repro.launch.serve_decoder --mesh data=<total chips> \\
        --streams 64 --backend fused

    # single-host CI / laptop rehearsal of the SAME path on CPU, no TPU:
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \\
        python -m repro.launch.serve_decoder --mesh data=8

The measured four-chip path is the benchmark cell ``ccsds-x4.playback``
(``bench/configs/ccsds-r12-x4.json``: one process, one service, a
``data=4`` mesh on one TPU v5e host):

    python3 bench/run.py --workload ccsds-x4.playback --seed 1 --seconds 30 --trace 0

:func:`maybe_init_distributed` reads the ``JAX_COORDINATOR_ADDRESS`` /
``JAX_NUM_PROCESSES`` / ``JAX_PROCESS_ID`` triplet and calls
``jax.distributed.initialize`` when (and only when) all three are present,
so the same entry point serves single-process runs untouched. The decoder's
mesh path is collective-free (parallel blocks never interact), so the
multi-process fleet needs no cross-host traffic beyond the jit partitioning
handshake.
"""

from __future__ import annotations

import os

import jax
import numpy as np

__all__ = [
    "make_production_mesh",
    "make_local_mesh",
    "parse_mesh_spec",
    "make_decode_mesh",
    "shrink_mesh",
    "maybe_init_distributed",
]


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / examples).

    Every invalid shape fails HERE with a clear ``ValueError`` — notably
    ``model`` not dividing the device count, which used to flow a zero or
    short mesh shape into ``jax.make_mesh`` (silently building a mesh over
    a device subset, or failing with an opaque downstream error).
    """
    n = len(jax.devices())
    if model < 1:
        raise ValueError(f"model axis size must be >= 1, got {model}")
    if data is None:
        if n % model:
            raise ValueError(
                f"model={model} does not divide the {n} available device(s); "
                f"pick a divisor of {n} or pass data= explicitly"
            )
        data = n // model
    if data < 1:
        raise ValueError(f"data axis size must be >= 1, got {data}")
    if data * model > n:
        raise ValueError(
            f"mesh shape ({data}, {model}) needs {data * model} devices, "
            f"only {n} available"
        )
    return jax.make_mesh((data, model), ("data", "model"))


def parse_mesh_spec(spec: str) -> tuple[tuple[str, ...], tuple[int, ...]]:
    """Parse ``"data=8"`` / ``"pod=2,data=4"`` → (axis names, axis sizes)."""
    names, sizes = [], []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, eq, size = part.partition("=")
        name = name.strip()
        try:
            n = int(size) if eq else -1
        except ValueError:
            n = -1
        if not name or n < 1:
            raise ValueError(
                f"bad mesh spec {spec!r}: expected AXIS=N[,AXIS=N...] with "
                f"positive integer sizes, got segment {part!r}"
            )
        if name in names:
            raise ValueError(f"bad mesh spec {spec!r}: axis {name!r} repeated")
        names.append(name)
        sizes.append(n)
    if not names:
        raise ValueError(f"bad mesh spec {spec!r}: no axes")
    return tuple(names), tuple(sizes)


def make_decode_mesh(spec: str, *, devices=None):
    """Build the decode-fleet mesh from a ``--mesh`` spec string.

    ``spec`` is ``"data=N"`` (or multi-axis ``"pod=2,data=8"``); the mesh is
    laid over the first ``prod(sizes)`` devices, so a sub-mesh of the
    available fleet is legal (the devices-sweep benchmark relies on it).
    """
    from jax.sharding import Mesh

    names, sizes = parse_mesh_spec(spec)
    devs = list(jax.devices()) if devices is None else list(devices)
    need = 1
    for s in sizes:
        need *= s
    if need > len(devs):
        raise ValueError(
            f"mesh spec {spec!r} needs {need} devices, only {len(devs)} "
            f"available (CPU rehearsal: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={need})"
        )
    return Mesh(np.asarray(devs[:need]).reshape(sizes), names)


def shrink_mesh(mesh, new_shape, *, devices=None):
    """Rebuild ``mesh`` at ``new_shape`` (same axis names) over surviving
    devices — the mesh-loss fallback of :func:`repro.launch.elastic.
    rescale_decode_engine`.

    ``devices`` lists the survivors explicitly; by default the first
    ``prod(new_shape)`` devices of the old mesh are kept (the right default
    for rehearsals and tests — a real casualty passes the live device set).
    Device choice never affects decoded bits: the decode mesh only places
    independent lanes.
    """
    from jax.sharding import Mesh

    new_shape = tuple(int(n) for n in new_shape)
    if len(new_shape) != len(mesh.axis_names):
        raise ValueError(
            f"new_shape {new_shape} has {len(new_shape)} axes, mesh has "
            f"{len(mesh.axis_names)} ({tuple(mesh.axis_names)})"
        )
    need = 1
    for n in new_shape:
        if n < 1:
            raise ValueError(f"new_shape {new_shape} has a non-positive axis")
        need *= n
    devs = list(mesh.devices.flat) if devices is None else list(devices)
    if need > len(devs):
        raise ValueError(
            f"new_shape {new_shape} needs {need} devices, only {len(devs)} survive"
        )
    return Mesh(np.asarray(devs[:need]).reshape(new_shape), tuple(mesh.axis_names))


def maybe_init_distributed() -> bool:
    """Initialize multi-process JAX from the launch env, if configured.

    Returns True when ``jax.distributed.initialize`` was called (all of
    ``JAX_COORDINATOR_ADDRESS``/``JAX_NUM_PROCESSES``/``JAX_PROCESS_ID``
    present in the environment), False for single-process runs. Call BEFORE
    any other JAX API (device queries included) — the recipe at the top of
    this module.
    """
    addr = os.environ.get("JAX_COORDINATOR_ADDRESS")
    num = os.environ.get("JAX_NUM_PROCESSES")
    pid = os.environ.get("JAX_PROCESS_ID")
    if not (addr and num and pid):
        return False
    jax.distributed.initialize(
        coordinator_address=addr, num_processes=int(num), process_id=int(pid)
    )
    return True
