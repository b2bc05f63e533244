"""Compile the main-path decode kernels for a described TPU v5e.

Interpret-mode tests cannot see what the chip's compiler refuses: a
slice not aligned to the sublane tiling, a kernel that cannot be
partitioned. These tests lower and compile for a ``v5e:2x2`` topology that
is described, not attached, at the paper's Table III geometry (D=512,
L=42: T = D + 2L = 596 stages, 1024 lanes). Nothing runs, so they say
nothing about results or times.

The topology is described inside a fixture, never on import: only one
process may load the TPU library at a time, and pytest-xdist workers all
import this file.
"""

import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding

from repro.core.codespec import get_code_spec
from repro.core.engine import DecoderEngine
from repro.core.pbvd import PBVDConfig
from repro.kernels.ops import pbvd_decode_blocks

D, L = 512, 42
T = D + 2 * L
LANES = 1024
_COLLECTIVE = re.compile(r"all-reduce|all-gather|all-to-all|collective-permute|reduce-scatter")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this environment
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile_launch(code, sharding, **knobs):
    y = jax.ShapeDtypeStruct((T, code.R, LANES), jnp.int8, sharding=sharding)
    launch = jax.jit(
        lambda y: pbvd_decode_blocks(
            y, code, decode_start=L, n_decode=D, interpret=False, **knobs
        )
    )
    return launch.lower(y).compile()


@pytest.mark.parametrize("backend", ["pallas", "fused"])
@pytest.mark.parametrize("metric_mode", ["f32", "i8"])
def test_served_kernels_compile_at_table3_geometry(one_chip, backend, metric_mode):
    """The served configuration (ccsds, q=8 int8 symbols) per backend and
    metric mode compiles for one chip, with the kernel in the program."""
    code = get_code_spec("ccsds").code
    compiled = _compile_launch(code, one_chip, backend=backend, metric_mode=metric_mode)
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("name", ["ccsds", "lte-1/3"])
def test_fused_dbuf_int8_compiles(one_chip, name):
    """The fused double-buffered symbol pipeline with int8 symbols, on a
    rate-1/2 and a rate-1/3 code — both once refused for DMA slices not
    aligned to the sublane tiling."""
    code = get_code_spec(name).code
    compiled = _compile_launch(
        code, one_chip, backend="fused", metric_mode="i8", acs_radix=4
    )
    assert "tpu_custom_call" in compiled.as_text()


def _four_chip_engine(topo, backend):
    mesh = Mesh(np.asarray(topo.devices).reshape(4), ("data",))
    cfg = PBVDConfig(spec=get_code_spec("ccsds"), D=D, L=L, q=8, backend=backend)
    return mesh, DecoderEngine(cfg, mesh=mesh)


def test_four_chip_mesh_launch_is_collective_free(topo):
    """A ``data=4`` engine's launch compiles through its shard_map dispatch:
    the kernel is in the program and no collective is."""
    mesh, engine = _four_chip_engine(topo, "fused")
    y = jax.ShapeDtypeStruct(
        (T, 2, LANES), jnp.int8, sharding=NamedSharding(mesh, P(None, None, "data"))
    )
    hlo = (
        jax.jit(lambda y: engine._decode_blocks(y, (LANES,), False))
        .lower(y)
        .compile()
        .as_text()
    )
    assert "tpu_custom_call" in hlo
    assert not _COLLECTIVE.search(hlo)


def test_mosaic_kernel_refuses_automatic_partitioning(topo):
    """Why a mesh launch always goes through shard_map: the same launch
    left to automatic partitioning is refused by the compiler."""
    mesh, _ = _four_chip_engine(topo, "fused")
    code = get_code_spec("ccsds").code
    with pytest.raises(Exception, match="cannot be automatically partitioned"):
        _compile_launch(
            code, NamedSharding(mesh, P(None, None, "data")), backend="fused"
        )
