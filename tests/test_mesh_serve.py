"""The served path on a ``data=4`` mesh: bits, one launch per lane shape,
and the lane placement's span and counters.

Each test runs in a subprocess with ``--xla_force_host_platform_device_count=4``
(as ``tests/test_distributed.py`` does), so the pytest process stays on one
device. The ``ref`` backend keeps every case to seconds on the CPU.
"""

import os
import subprocess
import sys
import textwrap
from pathlib import Path

import pytest

pytestmark = pytest.mark.tier1

SRC = str(Path(__file__).resolve().parents[1] / "src")

PRELUDE = """
import asyncio, collections, glob, os, sys
import jax, jax.numpy as jnp, numpy as np
from repro.core.channel import transmit
from repro.core.codespec import get_code_spec
from repro.core.encoder import encode_jax, terminate
from repro.core.engine import DecoderEngine
from repro.core.pbvd import PBVDConfig, frame_stream
from repro.kernels.ref import viterbi_classic_np
from repro.launch.mesh import make_decode_mesh
from repro.launch.serve_async import AsyncDecodeService
from repro.launch.serve_decoder import SessionPool
from repro.launch.slab import SymbolSlab

SPEC = get_code_spec("ccsds")
D, L = 64, 42
T = D + 2 * L


def engines(metric_mode="f32"):
    cfg = PBVDConfig(spec=SPEC, D=D, L=L, q=8, backend="ref", metric_mode=metric_mode)
    mesh = make_decode_mesh("data=4")
    return DecoderEngine(cfg, mesh=mesh), DecoderEngine(cfg)


def tx(n_bits, seed, ebn0=5.0):
    rng = np.random.default_rng(seed)
    bits = terminate(rng.integers(0, 2, n_bits), SPEC.code)
    coded = encode_jax(jnp.asarray(bits), SPEC.code)
    return np.asarray(transmit(jax.random.PRNGKey(seed), coded, ebn0, SPEC.rate))


class Events:
    def __init__(self):
        self.n = collections.Counter()
        jax.monitoring.register_event_duration_secs_listener(self.on)

    def on(self, event, duration, **kw):
        self.n[event.rsplit("/", 1)[-1]] += 1

    def traces_and_compiles(self):
        return self.n["jaxpr_trace_duration"], self.n["backend_compile_duration"]
"""


def _run(snippet: str, *args: str, timeout: int = 300) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    env["PYTHONPATH"] = SRC
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", PRELUDE + textwrap.dedent(snippet), *args],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
    )
    assert proc.returncode == 0, f"STDOUT:\n{proc.stdout}\nSTDERR:\n{proc.stderr[-3000:]}"
    return proc.stdout


@pytest.mark.parametrize("metric_mode", ["f32", "i8"])
def test_served_streams_on_a_mesh_match_reference_and_one_chip(metric_mode):
    """Ragged streams through AsyncDecodeService + SymbolSlab + SessionPool
    on a data=4 engine: each stream's bits equal the full-sequence Viterbi
    on the same quantized symbols and the one-chip engine's decode."""
    out = _run("""
        eng4, eng1 = engines(sys.argv[1])
        # 7 + 2 + 5 + 3 + 10 = 27 blocks in a 32-lane launch: the pad lanes
        # fall on the last shard
        sizes = [420, 101, 300, 190, 611]
        ys = [tx(n, 30 + i) for i, n in enumerate(sizes)]

        async def serve():
            slab = SymbolSlab(n_pages=64, page_stages=T, R=2)
            async with AsyncDecodeService(
                slab=slab, max_batch_blocks=64, deadline_ms=2.0
            ) as svc:
                streams = [svc.open(eng4) for _ in ys]
                got = [[] for _ in ys]
                for lo in range(0, max(len(y) for y in ys), 97):
                    for i, (st, y) in enumerate(zip(streams, ys)):
                        if lo < len(y):
                            await st.send(y[lo : lo + 97])
                    await asyncio.sleep(0.01)
                    for i, st in enumerate(streams):
                        got[i].append(st.take())
                for i, (st, n) in enumerate(zip(streams, sizes)):
                    got[i].append(await st.finish(n))
                return [np.concatenate(g) for g in got], svc.metrics()

        outs, m = asyncio.run(serve())
        assert m["launches"] >= 2 and m["mesh_builds"] >= 1, m
        assert m["shard_bytes"] > 0 and m["shard_bytes"] % (T * 2) == 0, m
        cfg = eng4.cfg
        for y, n, got in zip(ys, sizes, outs):
            assert len(got) == n
            one = np.asarray(eng1.decode(jnp.asarray(y), n))
            np.testing.assert_array_equal(got, one)
            q = np.asarray(cfg.quantize(jnp.asarray(y)), np.float64)
            va = viterbi_classic_np(q, SPEC.code, init_state=0, final_state=0)[:n]
            np.testing.assert_array_equal(got, va)
        print("ok", m["launches"], m["mesh_builds"])
    """, metric_mode)
    assert out.startswith("ok")


def test_repeated_lane_shape_reuses_the_mesh_launch():
    """The second launch of a lane shape traces and compiles nothing, and
    the engine and the pool each count one build."""
    _run("""
        eng4, _ = engines()
        ev = Events()
        q = eng4.cfg.quantize(jnp.asarray(tx(32 * D, 3)))
        blocks = frame_stream(q, D, L, 32)
        jax.block_until_ready(eng4._decode_blocks(blocks, (27,), None))
        ev.n.clear()
        jax.block_until_ready(eng4._decode_blocks(blocks, (27,), None))
        assert ev.traces_and_compiles() == (0, 0), ev.n
        assert eng4.mesh_builds == 1

        # the same through a pool: two steps of one lane shape
        eng4, _ = engines()
        pool = SessionPool()
        handles = [pool.open(eng4) for _ in range(3)]
        ys = [tx(8 * D, 40 + i) for i in range(3)]
        for h, y in zip(handles, ys):
            h.feed(y[: 3 * D + L])
        pool.step()
        ev.n.clear()
        for h, y in zip(handles, ys):
            h.feed(y[3 * D + L : 6 * D + L])
        pool.step()
        assert pool.launches == 2 and pool.mesh_builds == 1, pool.mesh_builds
        assert ev.traces_and_compiles() == (0, 0), ev.n
        print("ok")
    """)


def test_lane_placement_span_and_counters(tmp_path):
    """In a recorded trace of one mesh launch, pbvd.shard lies inside
    pbvd.launch on one host line; shard_bytes is the placed lanes' bytes by
    hand, and both counters read 0 on a mesh-less pool."""
    _run("""
        from jax.profiler import ProfileData

        eng4, eng1 = engines()
        y = tx(8 * D, 7)

        def one_launch(eng):
            pool = SessionPool()
            h = pool.open(eng)
            h.feed(y[: 5 * D + L])  # 5 ready blocks: lanes padded to 8
            pool.step()
            return pool

        one_launch(eng4)  # compile outside the trace
        with jax.profiler.trace(sys.argv[1]):
            pool = one_launch(eng4)
        assert pool.launches == 1
        # 8 lanes of T stages, R = 2 int8 symbols each
        assert eng4._lane_budget(5) == 8
        assert pool.shard_bytes == T * 2 * 8 * np.dtype(np.int8).itemsize
        assert pool.mesh_builds == 0  # the launch was built before the trace

        (path,) = glob.glob(os.path.join(sys.argv[1], "**", "*.xplane.pb"), recursive=True)
        hits = []
        for plane in ProfileData.from_file(path).planes:
            if not plane.name.startswith("/host:"):
                continue
            for line in plane.lines:
                evs = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith("pbvd.")
                ]
                launches = [e for e in evs if e[0] == "pbvd.launch"]
                for name, s, e in evs:
                    if name == "pbvd.shard":
                        hits.append(any(a <= s and e <= b for _, a, b in launches))
        assert hits == [True], hits

        flat = one_launch(eng1)
        assert flat.launches == 1
        assert (flat.shard_bytes, flat.mesh_builds) == (0, 0)
        assert (eng1.shard_bytes, eng1.mesh_builds) == (0, 0)

        async def meshless_metrics():
            async with AsyncDecodeService(max_batch_blocks=4, deadline_ms=1.0) as svc:
                st = svc.open(eng1)
                await st.send(y)
                await st.finish(len(y))
                return svc.metrics()

        m = asyncio.run(meshless_metrics())
        assert m["launches"] >= 1 and (m["shard_bytes"], m["mesh_builds"]) == (0, 0), m
        print("ok")
    """, str(tmp_path))
