"""Mesh-bound engine semantics that need no multi-device runtime.

The real N-chip behavior is exercised by ``tests/test_distributed.py``
(subprocess, 8 forced host devices, ``slow``); this module keeps the mesh
code paths — shard-aware lane budgeting, the shard_map dispatch, eager
validation, pool grouping by mesh *content* — inside the tier-1 gate with
trivial single-device meshes (the sharding is degenerate, the code path is
not).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from repro.core.channel import transmit
from repro.core.codespec import get_code_spec
from repro.core.encoder import encode_jax, terminate
from repro.core.engine import DecoderEngine, _pow2_at_least
from repro.core.pbvd import PBVDConfig, decode_stream_sharded
from repro.kernels.ops import check_mesh_launch
from repro.launch.mesh import make_decode_mesh, make_local_mesh, parse_mesh_spec
from repro.launch.serve_decoder import SessionPool
from repro.sharding.rules import block_mesh_axes


def _tx(name, n, seed, ebn0=4.5):
    spec = get_code_spec(name)
    rng = np.random.default_rng(seed)
    bits = terminate(rng.integers(0, 2, n), spec.code)
    coded = encode_jax(jnp.asarray(bits), spec.code)
    tx = spec.puncture_stream(coded) if spec.is_punctured else coded
    return transmit(jax.random.PRNGKey(seed), tx, ebn0, spec.rate)


def _mesh1(axes=("data",)):
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:1]).reshape((1,) * len(axes)), axes)


# ---------------------------------------------------------------------------
# shard-aware lane budget
# ---------------------------------------------------------------------------
def test_lane_budget_is_pow2_without_mesh():
    eng = DecoderEngine(PBVDConfig(backend="ref"))
    for n in (1, 2, 3, 5, 8, 17, 100):
        assert eng._lane_budget(n) == _pow2_at_least(n)


def test_lane_budget_folds_shard_rounding_into_one_bounded_pad():
    """budget = lcm(pow2, n_shards): divisible by the shard count AND drawn
    from a log-bounded shape set — never pow2-then-pad-again."""
    eng = DecoderEngine(PBVDConfig(backend="ref"), mesh=_mesh1())
    eng.n_shards = 6  # non-power-of-two fleet, as if on 6 chips
    budgets = {n: eng._lane_budget(n) for n in range(1, 65)}
    assert all(b % 6 == 0 for b in budgets.values())
    assert all(b >= n for n, b in budgets.items())
    # one budget per pow2 bracket: 64 fleet sizes collapse to ~log shapes
    assert len(set(budgets.values())) <= 7
    assert budgets[5] == 24  # lcm(8, 6)
    eng.n_shards = 8
    assert eng._lane_budget(5) == 8  # pow2 shard counts change nothing


# ---------------------------------------------------------------------------
# mesh-bound decode parity (degenerate 1-device mesh, real code path)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("metric_mode", ["f32", "i8"])
def test_mesh_engine_matches_unsharded(metric_mode):
    spec = get_code_spec("ccsds")
    cfg = PBVDConfig(spec=spec, D=64, L=16, q=8, backend="ref", metric_mode=metric_mode)
    base = DecoderEngine(cfg)
    eng = DecoderEngine(cfg, mesh=_mesh1())
    assert eng.n_shards == 1 and eng.block_axes == ("data",)
    lens = [96, 190, 96]
    ys = [_tx("ccsds", n, 40 + i) for i, n in enumerate(lens)]
    # one-shot
    np.testing.assert_array_equal(
        np.asarray(base.decode(ys[0], lens[0])),
        np.asarray(eng.decode(ys[0], lens[0])),
    )
    # batched (ragged fleet)
    for a, b in zip(base.decode_batch(ys, lens), eng.decode_batch(ys, lens)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    # streaming session on the mesh engine
    sess = eng.session()
    got = np.concatenate([sess.decode(np.asarray(ys[1])), sess.finish(lens[1])])
    np.testing.assert_array_equal(got, np.asarray(base.decode(ys[1], lens[1])))


def test_decode_stream_sharded_passthrough():
    spec = get_code_spec("ccsds")
    cfg = PBVDConfig(spec=spec, D=64, L=16, q=8, backend="ref")
    y = _tx("ccsds", 128, 3)
    ref = np.asarray(DecoderEngine(cfg).decode(y, 128))
    out = decode_stream_sharded(y, 128, cfg, _mesh1(), block_axes=None)
    np.testing.assert_array_equal(ref, np.asarray(out))


# ---------------------------------------------------------------------------
# eager validation + rules resolution
# ---------------------------------------------------------------------------
def test_check_mesh_launch_rejects_bad_bindings_eagerly():
    mesh = _mesh1(("data", "model"))
    assert check_mesh_launch(mesh, ("data",), "ref") == 1
    assert check_mesh_launch(mesh, ("data", "model"), "ref") == 1
    with pytest.raises(ValueError, match="not in mesh axes"):
        check_mesh_launch(mesh, ("pod",), "ref")
    with pytest.raises(ValueError, match="repeats"):
        check_mesh_launch(mesh, ("data", "data"), "ref")
    with pytest.raises(ValueError, match="at least one"):
        check_mesh_launch(mesh, (), "ref")
    with pytest.raises(KeyError):
        check_mesh_launch(mesh, ("data",), "no_such_backend")
    # the engine runs the same check at CONSTRUCTION, not at first decode
    with pytest.raises(ValueError, match="not in mesh axes"):
        DecoderEngine(PBVDConfig(backend="ref"), mesh=mesh, block_axes=("pod",))


def test_block_axes_resolve_from_logical_rules():
    assert block_mesh_axes(_mesh1(("data", "model"))) == ("data",)
    assert block_mesh_axes(_mesh1(("pod", "data", "model"))) == ("pod", "data")
    with pytest.raises(ValueError, match="blocks"):
        block_mesh_axes(_mesh1(("model",)))
    eng = DecoderEngine(
        PBVDConfig(backend="ref"), mesh=_mesh1(("data", "model")), block_axes=None
    )
    assert eng.block_axes == ("data",)


# ---------------------------------------------------------------------------
# launch/mesh.py helpers
# ---------------------------------------------------------------------------
def test_parse_mesh_spec():
    assert parse_mesh_spec("data=8") == (("data",), (8,))
    assert parse_mesh_spec("pod=2, data=4") == (("pod", "data"), (2, 4))
    for bad in ("", "data", "data=0", "data=x", "data=2,data=4", "=4"):
        with pytest.raises(ValueError, match="mesh spec"):
            parse_mesh_spec(bad)


def test_make_decode_mesh_single_device():
    mesh = make_decode_mesh("data=1")
    assert dict(mesh.shape) == {"data": 1}
    with pytest.raises(ValueError, match="devices"):
        make_decode_mesh(f"data={len(jax.devices()) + 1}")


def test_make_local_mesh_rejects_bad_shapes():
    n = len(jax.devices())
    with pytest.raises(ValueError, match="does not divide"):
        make_local_mesh(model=n + 1)
    with pytest.raises(ValueError, match=">= 1"):
        make_local_mesh(model=0)
    with pytest.raises(ValueError, match="devices"):
        make_local_mesh(data=n + 1, model=1)
    mesh = make_local_mesh()
    assert dict(mesh.shape) == {"data": n, "model": 1}


# ---------------------------------------------------------------------------
# SessionPool grouping on mesh content (the _group_key regression)
# ---------------------------------------------------------------------------
def test_session_pool_splits_same_mesh_different_block_axes():
    """Two sessions on the SAME mesh but different lane-axis bindings used
    to coalesce (the key ignored block_axes) and decode with the lead's
    layout; they must launch separately."""
    mesh = _mesh1(("data", "model"))
    cfg = PBVDConfig(spec=get_code_spec("ccsds"), D=64, L=16, q=8, backend="ref")
    eng_data = DecoderEngine(cfg, mesh=mesh, block_axes=("data",))
    eng_model = DecoderEngine(cfg, mesh=mesh, block_axes=("model",))
    y = np.asarray(_tx("ccsds", 256, 5))
    pool = SessionPool()
    hd, hm = pool.open(eng_data), pool.open(eng_model)
    hd.feed(y)
    hm.feed(y)
    pool.step()
    assert pool.launches == 2
    ref = np.asarray(DecoderEngine(cfg).decode(jnp.asarray(y), 256))
    for h in (hd, hm):
        np.testing.assert_array_equal(np.concatenate([h.take(), h.finish(256)]), ref)


def test_session_pool_coalesces_equal_content_meshes_and_pins_them():
    """Distinct mesh OBJECTS with identical content are one launch group
    (the old ``id(mesh)`` key split them; worse, id reuse after GC could
    merge *different* meshes). The pool pins each pooled mesh strongly."""
    cfg = PBVDConfig(spec=get_code_spec("ccsds"), D=64, L=16, q=8, backend="ref")
    eng_a = DecoderEngine(cfg, mesh=_mesh1())
    eng_b = DecoderEngine(cfg, mesh=_mesh1())  # equal content (JAX may intern)
    y = np.asarray(_tx("ccsds", 256, 6))
    pool = SessionPool()
    ha, hb = pool.open(eng_a), pool.open(eng_b)
    assert len(pool._mesh_refs) == 2  # strong refs held while pooled
    ha.feed(y)
    hb.feed(y)
    pool.step()
    assert pool.launches == 1
    # the mesh is part of the identity: a meshless engine splits the group
    eng_c = DecoderEngine(cfg)
    ha2, hc = pool.open(eng_a), pool.open(eng_c)
    ha2.feed(y)
    hc.feed(y)
    pool.step()
    assert pool.launches == 3
    ref = np.asarray(DecoderEngine(cfg).decode(jnp.asarray(y), 256))
    for h in (ha, hb, ha2, hc):
        np.testing.assert_array_equal(np.concatenate([h.take(), h.finish(256)]), ref)
    assert len(pool._mesh_refs) == 3  # the meshless member pins nothing
    pool.close(ha)
    pool.close(hc)
    assert len(pool._mesh_refs) == 2  # hb's and ha2's meshes still pinned
