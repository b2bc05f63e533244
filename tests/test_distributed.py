"""Multi-device semantics tests.

Each test runs in a subprocess with ``--xla_force_host_platform_device_count=8``
(the main pytest process stays single-device).
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.slow  # subprocess-per-test; excluded from tier-1 runs

SRC = str(Path(__file__).resolve().parents[1] / "src")


def _run(snippet: str, n_dev: int = 8, timeout: int = 600):
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    env["PYTHONPATH"] = SRC
    proc = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(snippet)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
    return proc.stdout


def test_sharded_decode_stream_matches_unsharded():
    """PBVD distributed decode (blocks sharded over data axis) is bit-identical
    to the single-device decode — zero-collective block parallelism."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.pbvd import PBVDConfig, decode_stream, decode_stream_sharded
        from repro.core.encoder import encode_jax, terminate
        from repro.core.channel import transmit
        from repro.core.trellis import CCSDS_27

        code = CCSDS_27
        rng = np.random.default_rng(0)
        n = 8192
        bits = terminate(rng.integers(0, 2, n), code)
        y = transmit(jax.random.PRNGKey(1), encode_jax(jnp.asarray(bits), code), 4.0, code.rate)
        cfg = PBVDConfig(q=8, backend="ref")
        ref = np.asarray(decode_stream(y, n, cfg))
        mesh = jax.make_mesh((8,), ("data",))
        out = np.asarray(decode_stream_sharded(y, n, cfg, mesh))
        assert np.array_equal(ref, out), "sharded decode diverged"
        print("ok")
    """)


def test_mesh_decode_parity_matrix():
    """The acceptance matrix: on 8 host devices, ``decode`` and
    ``decode_batch`` are bit-identical with and without a ``data=8`` mesh,
    across backends × metric modes under shard_map dispatch, for a ragged
    fleet whose block count does not divide the shard count."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.channel import transmit
        from repro.core.codespec import get_code_spec
        from repro.core.encoder import encode_jax, terminate
        from repro.core.engine import DecoderEngine
        from repro.core.pbvd import PBVDConfig
        from repro.launch.mesh import make_decode_mesh

        assert len(jax.devices()) == 8
        spec = get_code_spec("ccsds")

        def tx(n, seed):
            rng = np.random.default_rng(seed)
            bits = terminate(rng.integers(0, 2, n), spec.code)
            return transmit(
                jax.random.PRNGKey(seed),
                encode_jax(jnp.asarray(bits), spec.code), 4.5, spec.rate,
            )

        lens = [96, 190, 96, 250, 128]  # ragged: 10 blocks, not 8-divisible
        ys = [tx(n, 30 + i) for i, n in enumerate(lens)]
        mesh = make_decode_mesh("data=8")
        cases = [("ref", "f32"), ("ref", "i8"), ("pallas", "f32"),
                 ("pallas", "i8"), ("fused", "f32"), ("fused", "i8")]
        for backend, mm in cases:
            cfg = PBVDConfig(spec=spec, D=64, L=16, q=8,
                             backend=backend, metric_mode=mm)
            base = DecoderEngine(cfg)
            refs = [np.asarray(b) for b in base.decode_batch(ys, lens)]
            ref1 = np.asarray(base.decode(ys[1], lens[1]))
            tag = (backend, mm)
            eng = DecoderEngine(cfg, mesh=mesh)
            assert eng.n_shards == 8, tag
            for r, o in zip(refs, eng.decode_batch(ys, lens)):
                assert np.array_equal(r, np.asarray(o)), tag
            assert np.array_equal(ref1, np.asarray(eng.decode(ys[1], lens[1]))), tag
            print("ok", *tag)
    """, timeout=1800)


def test_mesh_pooled_step_parity_and_streaming():
    """Pooled sessions on sharded engines (two mesh sizes, mixed with a
    meshless engine in the same pool) stream bit-identically to the solo
    unsharded decode, under a ragged chunk cadence."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.channel import transmit
        from repro.core.codespec import get_code_spec
        from repro.core.encoder import encode_jax, terminate
        from repro.core.engine import DecoderEngine
        from repro.core.pbvd import PBVDConfig
        from repro.launch.mesh import make_decode_mesh
        from repro.launch.serve_decoder import SessionPool

        spec = get_code_spec("ccsds")
        n = 512
        rng = np.random.default_rng(7)
        bits = terminate(rng.integers(0, 2, n), spec.code)
        y = np.asarray(transmit(
            jax.random.PRNGKey(7), encode_jax(jnp.asarray(bits), spec.code),
            4.5, spec.rate,
        ))
        cfg = PBVDConfig(spec=spec, D=64, L=16, q=8, backend="ref")
        ref = np.asarray(DecoderEngine(cfg).decode(jnp.asarray(y), n))

        mesh = make_decode_mesh("data=8")
        engines = [
            DecoderEngine(cfg),
            DecoderEngine(cfg, mesh=mesh),
            DecoderEngine(cfg, mesh=make_decode_mesh("data=4")),
        ]
        pool = SessionPool()
        handles = [pool.open(e) for e in engines]
        pos, outs = [0] * len(handles), [[] for _ in handles]
        crng = np.random.default_rng(1)
        while any(p < len(y) for p in pos):
            for i, h in enumerate(handles):
                if pos[i] < len(y):
                    step = int(crng.integers(40, 300))
                    h.feed(y[pos[i]:pos[i] + step])
                    pos[i] += step
            pool.step()
            for i, h in enumerate(handles):
                outs[i].append(h.take())
        for i, h in enumerate(handles):
            outs[i].append(h.finish(n))
            got = np.concatenate(outs[i])
            assert np.array_equal(got, ref), f"handle {i} diverged"
        # meshless / data=8 / data=4 are three distinct launch groups
        assert len({pool._group_key(h._session) for h in handles}) == 3
        print("ok", pool.launches)
    """)


def test_mesh_nonpow2_shards_bounded_recompiles():
    """A 6-of-8 device mesh (non-pow2 shard count): sweeping many fleet
    sizes stays within a small, lcm-budgeted set of jit shapes — the old
    pad-after-budget path re-padded per size — and stays bit-exact."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.core.channel import transmit
        from repro.core.codespec import get_code_spec
        from repro.core.encoder import encode_jax, terminate
        from repro.core.engine import DecoderEngine
        from repro.core.pbvd import PBVDConfig
        from repro.kernels.ops import _decode_blocks_jit
        from repro.launch.mesh import make_decode_mesh

        spec = get_code_spec("ccsds")
        cfg = PBVDConfig(spec=spec, D=64, L=16, q=8, backend="ref")
        mesh = make_decode_mesh("data=6")  # submesh of the 8 host devices
        eng = DecoderEngine(cfg, mesh=mesh)
        assert eng.n_shards == 6
        # every budget divides by 6 and fleet sizes collapse to few shapes
        budgets = {k: eng._lane_budget(k) for k in range(1, 33)}
        assert all(b % 6 == 0 for b in budgets.values())
        assert len(set(budgets.values())) <= 6, sorted(set(budgets.values()))

        base = DecoderEngine(cfg)

        def tx(n, seed):
            rng = np.random.default_rng(seed)
            bits = terminate(rng.integers(0, 2, n), spec.code)
            return transmit(
                jax.random.PRNGKey(seed),
                encode_jax(jnp.asarray(bits), spec.code), 4.5, spec.rate,
            )

        fleets = ([96], [96, 190], [96, 190, 250], [96] * 5, [190] * 7)

        def sweep():
            for fleet in fleets:
                ys = [tx(n, 50 + i) for i, n in enumerate(fleet)]
                refs = base.decode_batch(ys, fleet)
                outs = eng.decode_batch(ys, fleet)
                for r, o in zip(refs, outs):
                    assert np.array_equal(np.asarray(r), np.asarray(o)), fleet

        before = _decode_blocks_jit._cache_size()
        sweep()
        grown = _decode_blocks_jit._cache_size() - before
        # one entry per engine per distinct n_real (a static arg) and no
        # more: the sharded pad never forks extra shapes per fleet
        assert grown <= 2 * len(fleets), f"jit cache grew by {grown}"
        # the sweep again, plus a permuted composition with the same total:
        # zero retraces — lcm budgeting keys purely on (shape, n_real)
        sweep()
        ys = [tx(n, 70 + i) for i, n in enumerate([190, 96])]
        eng.decode_batch(ys, [190, 96])
        assert _decode_blocks_jit._cache_size() - before == grown, "retraced"
        print("ok", grown)
    """)


def test_make_local_mesh_invalid_model_raises():
    """``make_local_mesh(model=3)`` on 8 devices used to silently build a
    6-device mesh over a device subset; it must now refuse loudly."""
    _run("""
        import jax
        from repro.launch.mesh import make_decode_mesh, make_local_mesh

        assert len(jax.devices()) == 8
        try:
            make_local_mesh(model=3)
        except ValueError as e:
            assert "does not divide" in str(e), e
        else:
            raise AssertionError("model=3 on 8 devices did not raise")
        m = make_local_mesh(model=2)
        assert dict(m.shape) == {"data": 4, "model": 2}
        m6 = make_decode_mesh("data=6")
        assert dict(m6.shape) == {"data": 6}
        print("ok")
    """)
