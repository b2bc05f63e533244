"""The served path's own measurement: its ``pbvd.*`` host spans and the
launch and admission counters that ``AsyncDecodeService.metrics()`` reports.

Counters are checked against hand counts at a tiny geometry (D=64, L=16:
T = 96-stage lanes) on the ``ref`` backend; the spans are recorded under a
real ``jax.profiler`` trace on the CPU and read back from its ``.xplane.pb``.
"""

import asyncio
import glob
import os

import numpy as np
import pytest

import jax
from jax.profiler import ProfileData, TraceAnnotation

from repro.core.codespec import get_code_spec
from repro.core.engine import DecoderEngine, _covered_lane_stages
from repro.core.pbvd import PBVDConfig
from repro.kernels.ops import launched_lanes
from repro.launch.serve_async import AsyncDecodeService
from repro.launch.serve_decoder import SessionPool
from repro.launch.spans import span

D, L, R = 64, 16, 2
T = D + 2 * L


def _engine(backend="ref"):
    return DecoderEngine(
        PBVDConfig(spec=get_code_spec("ccsds"), D=D, L=L, q=8, backend=backend)
    )


def _symbols(n: int, seed: int) -> np.ndarray:
    """``n`` stages of pre-quantized int8 soft symbols."""
    return np.random.default_rng(seed).integers(-127, 128, (n, R)).astype(np.int8)


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def now(self) -> float:
        return self.t


# ---------------------------------------------------------------------------
# span()
# ---------------------------------------------------------------------------
@pytest.mark.tier1
def test_span_is_one_shared_noop_when_the_profiler_is_off():
    assert not TraceAnnotation.is_enabled()
    a, b = span("pbvd.launch", members=2), span("pbvd.frame")
    assert a is b
    assert not isinstance(a, TraceAnnotation)
    with a:
        with b:  # re-entrant: spans nest
            pass


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------
@pytest.mark.tier1
@pytest.mark.parametrize(
    "lo,k,a,b",
    [(-16, 1, 0, 48), (-16, 4, 0, 300), (48, 7, 0, 500), (-16, 3, 0, 10), (112, 5, 0, 1000),
     (-16, 9, 0, 200), (240, 2, 0, 250)],
)
def test_covered_lane_stages_equals_a_stage_by_stage_count(lo, k, a, b):
    brute = sum(
        1 for j in range(k) for t in range(lo + j * D, lo + j * D + T) if a <= t < b
    )
    assert _covered_lane_stages(lo, k, D, T, a, b) == brute


@pytest.mark.tier1
def test_launched_lanes_is_the_backends_lane_tile_padding():
    assert [launched_lanes("ref", n) for n in (1, 6, 129)] == [1, 6, 129]
    assert [launched_lanes("pallas", n) for n in (1, 128, 129)] == [128, 128, 256]
    assert launched_lanes("fused", 3000) == 3072
    # the engine adds its power-of-two budget on top
    pallas = _engine("pallas")
    assert [pallas._launched_lanes(n) for n in (1, 129, 3000, 4096)] == [128, 256, 4096, 4096]
    assert [_engine()._launched_lanes(n) for n in (1, 6, 129)] == [1, 8, 256]


@pytest.mark.tier1
def test_pooled_launch_counters_match_hand_counts():
    """Two members, 300 and 200 int8 stages: 4 and 2 ready blocks."""
    pool = SessionPool()
    eng = _engine()
    a, b = pool.open(eng), pool.open(eng)
    a.feed(_symbols(300, 1))
    b.feed(_symbols(200, 2))
    assert pool.step() == 6
    assert pool.launches == 1
    assert pool.lanes_real == 6
    assert pool.lanes_launched == 8  # pow2 budget; ref runs no lane tile
    # each member's first lane starts at stage -L: 16 zero stages
    # a: lanes [-16, 80) [48, 144) [112, 208) [176, 272) over received [0, 300)
    # b: lanes [-16, 80) [48, 144) over received [0, 200)
    assert pool.stages_real == (80 + 3 * 96) + (80 + 96)
    assert pool.stages_launched == 6 * T
    # framed host windows [-16, 4·64 + 16) and [-16, 2·64 + 16), int8, R = 2
    assert pool.h2d_bytes == (288 + 160) * R
    assert pool.d2h_bytes == 6 * D * 4  # int32 bits back


@pytest.mark.tier1
def test_frame_flush_counters_match_hand_counts():
    """One 40-stage frame finished through the service: one solo one-lane
    launch whose lane [-16, 80) holds 40 received stages."""
    eng = _engine()

    async def scenario():
        svc = AsyncDecodeService(max_batch_blocks=1000, deadline_ms=1e6)
        stream = svc.open(eng)
        await stream.send(_symbols(40, 3))
        bits = await stream.finish(34)
        return bits, svc.metrics()

    bits, m = asyncio.run(scenario())
    assert len(bits) == 34
    assert m["launches"] == 1 and m["admits"] == 1 and m["admit_wait_s"] == 0.0
    assert (m["lanes_real"], m["lanes_launched"]) == (1, 1)
    assert (m["stages_real"], m["stages_launched"]) == (40, T)
    assert m["h2d_bytes"] == T * R  # the whole lane, padding included, int8
    assert m["d2h_bytes"] == D * 4


@pytest.mark.tier1
def test_admit_wait_is_exact_under_a_fake_clock():
    """A sender parked behind the pending-block cap for 0.25 s of the
    service's clock adds exactly 0.25 s; the one never parked adds 0."""
    eng = _engine()
    clk = FakeClock()
    y = _symbols(600, 4)

    async def scenario():
        svc = AsyncDecodeService(
            max_batch_blocks=1000, deadline_ms=0.0, max_pending_blocks=2, clock=clk.now
        )
        stream = svc.open(eng)
        await stream.send(y[:300])  # 4 blocks ready: at the cap
        parked = asyncio.ensure_future(stream.send(y[300:]))
        for _ in range(5):
            await asyncio.sleep(0)
        assert not parked.done()
        clk.t += 0.25
        assert svc.poll() is True  # the dispatch frees the cap
        await asyncio.wait_for(parked, timeout=5)
        return svc.metrics()

    m = asyncio.run(scenario())
    assert m["admits"] == 2
    assert m["admit_wait_s"] == 0.25


# ---------------------------------------------------------------------------
# spans in a recorded trace
# ---------------------------------------------------------------------------
LAUNCH_CHILDREN = ("pbvd.frame", "pbvd.kernel", "pbvd.device_wait", "pbvd.deliver")


def _host_lines(log_dir):
    (path,) = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    lines = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                evs = [
                    (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns)
                    for ev in line.events
                    if ev.name.startswith("pbvd.")
                ]
                if evs:
                    lines.append(evs)
    return lines


@pytest.mark.tier1
def test_served_stream_records_nested_launch_spans(tmp_path):
    eng = _engine()
    y = _symbols(400, 5)

    async def serve():
        async with AsyncDecodeService(max_batch_blocks=2, deadline_ms=1.0) as svc:
            stream = svc.open(eng)
            await stream.send(y[:200])  # 2 blocks ready: the size trigger
            await asyncio.sleep(0.2)  # the dispatcher's pooled step runs
            await stream.send(y[200:])
            return await stream.finish(400)

    asyncio.run(serve())  # compile outside the trace
    with jax.profiler.trace(str(tmp_path)):
        assert isinstance(span("pbvd.frame"), TraceAnnotation)
        bits = asyncio.run(serve())
    assert len(bits) == 400

    lines = _host_lines(str(tmp_path))
    names = {n for evs in lines for n, _, _ in evs}
    assert {"pbvd.ingest", "pbvd.dispatch", "pbvd.launch", "pbvd.finish"} <= names
    assert set(LAUNCH_CHILDREN) <= names
    launches = [(evs, ev) for evs in lines for ev in evs if ev[0] == "pbvd.launch"]
    assert len(launches) >= 2  # at least one pooled step and the finish flush
    for evs, (_, s, e) in launches:
        inside = sorted(
            (ev for ev in evs if ev[0] in LAUNCH_CHILDREN and s <= ev[1] and ev[2] <= e),
            key=lambda ev: ev[1],
        )
        # each child once, in path order, on the launch's own host line
        assert [ev[0] for ev in inside] == list(LAUNCH_CHILDREN)
        for (_, _, end), (_, start, _) in zip(inside, inside[1:]):
            assert end <= start  # siblings do not overlap
