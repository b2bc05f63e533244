"""Elastic rescale planning for a decode fleet: the mesh a fleet shrinks to
after chip loss, and the engine swap behind the serving layer's mesh-loss
fallback."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest


# ---- elastic rescale ------------------------------------------------------------------------
def test_plan_rescale():
    from repro.launch.elastic import plan_rescale

    mesh = jax.make_mesh((1, 1), ("data", "model"))

    class FakeMesh:
        axis_names = ("pod", "data", "model")
        shape = {"pod": 2, "data": 16, "model": 16}

    plan = plan_rescale(FakeMesh(), lost_chips=16)
    assert plan.new_chip_count <= 512 - 16
    assert plan.new_shape[plan.axis_names.index("model")] == 16
    # losing one host of 16 chips should drop exactly one data slice
    assert plan.new_chip_count == 496 or plan.new_chip_count == 480


def test_plan_rescale_maximizes_across_multiple_axes():
    """The counterexample behind the early-``break`` fix: with TWO 4-wide
    data-like axes and 7 chips lost, the lexicographic first-fit accepted
    4×2 = 8 chips; the exhaustive max-product search finds 3×3 = 9."""
    from repro.launch.elastic import plan_rescale

    class TwoAxis:
        axis_names = ("a", "b")
        shape = {"a": 4, "b": 4}

    plan = plan_rescale(TwoAxis(), lost_chips=7)
    assert plan.new_chip_count == 9
    assert sorted(plan.new_shape) == [3, 3]
    assert plan.dropped_chips == 7


def test_plan_rescale_shrink_axes_port():
    from repro.launch.elastic import plan_rescale

    class DecodeMesh:
        axis_names = ("pod", "data")
        shape = {"pod": 2, "data": 8}

    # the decode port: only the engine's block_axes may shrink — pod is
    # launch geometry here and must stay fixed at 2
    plan = plan_rescale(DecodeMesh(), lost_chips=4, shrink_axes=("data",))
    assert plan.new_shape == (2, 6) and plan.new_chip_count == 12
    with pytest.raises(ValueError, match="shrink_axes"):
        plan_rescale(DecodeMesh(), lost_chips=1, shrink_axes=("bogus",))


def test_plan_decode_rescale_none_when_nothing_survives():
    from repro.launch.elastic import plan_decode_rescale

    class OneChip:
        axis_names = ("data",)
        shape = {"data": 1}

    # a 1-chip mesh losing its only chip has no valid smaller mesh
    assert plan_decode_rescale(OneChip(), ("data",), lost_chips=1) is None

    class Fixed:
        axis_names = ("pod", "data")
        shape = {"pod": 4, "data": 2}

    # fixed axes alone (pod=4) exceed the 3 survivors: the all-ones shrink
    # still needs 4 chips, so there is no plan
    assert plan_decode_rescale(Fixed(), ("data",), lost_chips=5) is None


def test_rescale_decode_engine_drops_to_meshless_bit_exact():
    from repro.core.codespec import get_code_spec
    from repro.core.engine import DecoderEngine
    from repro.core.pbvd import PBVDConfig
    from repro.launch.elastic import rescale_decode_engine
    from repro.launch.mesh import make_decode_mesh

    spec = get_code_spec("ccsds")
    cfg = PBVDConfig(spec=spec, backend="ref", D=64, L=16, q=8)
    eng = DecoderEngine(cfg, mesh=make_decode_mesh("data=1"), block_axes=("data",))
    new = rescale_decode_engine(eng, lost_chips=1)
    assert new.mesh is None and new.block_axes == ("data",)
    # meshless engines pass through unchanged (nothing to rescale)
    assert rescale_decode_engine(new, lost_chips=1) is new

    rng = np.random.default_rng(0)
    y = jnp.asarray(rng.normal(size=(512, spec.code.R)).astype(np.float32))
    np.testing.assert_array_equal(
        np.asarray(new.decode(y, 192)), np.asarray(eng.decode(y, 192))
    )
