"""Multi-device semantics tests.

Each test runs in a subprocess with ``--xla_force_host_platform_device_count=8``
(the main pytest process stays single-device, per the assignment's rule that
only the dry-run sees fake devices).
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


def test_sharded_train_step_matches_single_device():
    """pjit train step on a 4×2 mesh reproduces single-device numerics."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np, dataclasses
        from repro.configs.base import get_config
        from repro._unused.models import lm
        from repro.sharding.rules import axis_rules, tree_shardings
        from repro._unused.train.optimizer import AdamWConfig, adamw_init
        from repro._unused.train.train_step import make_train_step

        cfg = dataclasses.replace(get_config("minitron-8b").reduced(), compute_dtype="float32")
        opt_cfg = AdamWConfig(warmup_steps=1, total_steps=10)
        params = lm.init_params(jax.random.PRNGKey(0), cfg)
        opt = adamw_init(params, opt_cfg)
        rng = np.random.default_rng(0)
        batch = {
            "tokens": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32),
            "labels": jnp.asarray(rng.integers(0, cfg.vocab, (8, 32)), jnp.int32),
        }
        step = make_train_step(cfg, opt_cfg)
        p1, o1, m1 = jax.jit(step)(params, opt, batch)

        mesh = jax.make_mesh((4, 2), ("data", "model"))
        with axis_rules(mesh) as rules:
            paxes = lm.param_axes(cfg)
            pshard = tree_shardings(params, paxes, rules)
            params_s = jax.tree.map(jax.device_put, params, pshard)
            opt_s = adamw_init(params_s, opt_cfg)
            p2, o2, m2 = jax.jit(step)(params_s, opt_s, batch)
        assert abs(float(m1["loss"]) - float(m2["loss"])) < 1e-4, (m1["loss"], m2["loss"])
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4)
        print("ok", float(m1["loss"]))
    """)


def test_pipeline_parallel_matches_sequential():
    """GPipe pipeline over 4 stages == sequential stage composition."""
    _run("""
        import jax, jax.numpy as jnp, numpy as np
        from repro.launch.pp import pipeline_apply, bubble_fraction

        mesh = jax.make_mesh((4,), ("pipe",))
        P, M, mb, d = 4, 6, 2, 16
        rng = np.random.default_rng(0)
        ws = jnp.asarray(rng.normal(size=(P, d, d)).astype(np.float32) * 0.3)
        x = jnp.asarray(rng.normal(size=(M, mb, d)).astype(np.float32))

        def stage(w, h):
            return jnp.tanh(h @ w)

        out = pipeline_apply(stage, ws, x, mesh, axis="pipe")
        ref = x
        for s in range(P):
            ref = jnp.tanh(ref @ ws[s])
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)
        assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
        print("ok")
    """)


def test_dryrun_smoke_tiny_mesh():
    """The dry-run machinery itself (specs → shardings → lower → analyze)
    works on an 8-device mesh with a reduced config."""
    _run("""
        import jax, jax.numpy as jnp
        from repro.configs.base import get_config
        from repro._unused.models import lm
        from repro.sharding.rules import axis_rules, tree_shardings
        from repro.launch.hlo_analysis import analyze_hlo
        from repro._unused.train.optimizer import AdamWConfig, adamw_init, OptState
        from repro._unused.train.train_step import make_train_step

        cfg = get_config("mixtral-8x22b").reduced()
        mesh = jax.make_mesh((4, 2), ("data", "model"))
        with axis_rules(mesh) as rules:
            params_sds = jax.eval_shape(lambda k: lm.init_params(k, cfg), jax.random.PRNGKey(0))
            pshard = tree_shardings(params_sds, lm.param_axes(cfg), rules)
            opt_cfg = AdamWConfig()
            opt_sds = jax.eval_shape(lambda p: adamw_init(p, opt_cfg), params_sds)
            repl = jax.NamedSharding(mesh, jax.sharding.PartitionSpec())
            oshard = OptState(step=repl, m=pshard, v=pshard)
            batch = {
                "tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
                "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32),
            }
            bshard = {k: jax.NamedSharding(mesh, rules.spec(("batch", None))) for k in batch}
            step = make_train_step(cfg, opt_cfg)
            compiled = jax.jit(
                step, in_shardings=(pshard, oshard, bshard),
                out_shardings=(pshard, oshard, None),
            ).lower(params_sds, opt_sds, batch).compile()
            st = analyze_hlo(compiled.as_text())
            assert st.flops > 0
            assert st.total_collective_bytes > 0, "expected collectives on a 4x2 mesh"
            ma = compiled.memory_analysis()
            assert ma is not None
        print("ok", st.flops, st.total_collective_bytes)
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
