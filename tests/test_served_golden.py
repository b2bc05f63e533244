"""Golden known answers replayed through the served decode path (tier-1).

``tests/test_golden.py`` holds every kernel formulation to the committed
``tests/golden/*.npz`` vectors through the one-shot ``DecoderEngine.decode``.
This suite replays the same vectors through the stack the benchmark builds
(``bench/harness.py``): one ``DecoderEngine`` behind an
``AsyncDecodeService`` whose sessions page their symbols out of a
``SymbolSlab`` of ``D + 2L``-stage pages. That covers what the one-shot path
never runs — the session framing (host window + device gather), the slab's
page copies, the pool's coalesced launches and member slices, the flush of
``finish`` and the pre-quantized int8 passthrough — against byte-stable
answers.

Two paths per vector:

* ``stream`` — three streams send the symbols in three seeded chunkings
  (some not aligned to the puncture period), interleaved; the size trigger
  of the deadline batcher coalesces their ready blocks into shared launches,
  a deadline fire picks up what is left, and ``finish`` flushes each tail.
* ``frame`` — one stream sends everything in one chunk, then ``finish``:
  the solo flush launch of a terminated frame, with no batcher dispatch.

The service is driven under a fake clock without its background task (its
``poll`` is called by hand), so every trigger decision is deterministic.
"""

import asyncio
import json
import zlib
from pathlib import Path

import numpy as np
import pytest

import jax.numpy as jnp

from repro.core.codespec import available_code_specs, get_code_spec
from repro.core.engine import DecoderEngine
from repro.core.pbvd import PBVDConfig
from repro.launch.serve_async import AsyncDecodeService
from repro.launch.slab import SymbolSlab

GOLDEN_DIR = Path(__file__).parent / "golden"
N_STREAMS = 3
MAX_BATCH_BLOCKS = 3  # above one stream's ready blocks: a size fire coalesces
DEADLINE_MS = 10.0


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def now(self) -> float:
        return self.t


def _load(name):
    with np.load(GOLDEN_DIR / (name.replace("/", "_") + ".npz"), allow_pickle=False) as z:
        data = {k: z[k] for k in z.files}
    data["meta"] = json.loads(str(data["meta"]))
    return data


def _chunkings(name: str, n: int) -> list[list[int]]:
    """Three seeded splits of ``n`` wire units into chunk lengths: a fixed
    odd stride, short random chunks, long random chunks."""
    rng = np.random.default_rng([zlib.crc32(name.encode()), n])
    out = []
    for lo, hi in ((7, 7), (1, 40), (20, 90)):
        sizes, left = [], n
        while left:
            sizes.append(min(left, int(rng.integers(lo, hi + 1))))
            left -= sizes[-1]
        out.append(sizes)
    return out


def _split(y: np.ndarray, sizes: list[int]) -> list[np.ndarray]:
    return np.split(y, np.cumsum(sizes)[:-1])


async def _stream_path(svc, clock, engine, y, n_bits, name, spec):
    """Interleave three chunked streams; return each stream's bits and the
    number of batcher dispatches that delivered to two or more streams."""
    chunkings = _chunkings(name, len(y))
    if spec.is_punctured:
        m = spec.kept_per_period
        assert any(s % m for c in chunkings for s in c), "no chunk off the puncture period"
    queues = [_split(y, sizes) for sizes in chunkings]
    streams = [svc.open(engine) for _ in range(N_STREAMS)]
    outs = [[] for _ in streams]
    shared = 0
    while any(queues):
        for i, q in enumerate(queues):
            if not q:
                continue
            await streams[i].send(q.pop(0))
            if svc.poll():
                got = [st.take() for st in streams]
                for o, g in zip(outs, got):
                    o.append(g)
                shared += sum(len(g) > 0 for g in got) >= 2
    # what the size trigger left behind goes out on the deadline
    clock.t += DEADLINE_MS / 1e3
    svc.poll()
    for st, o in zip(streams, outs):
        o.append(st.take())
        o.append(await st.finish(n_bits))
    return [np.concatenate(o) for o in outs], shared


async def _frame_path(svc, engine, y, n_bits):
    st = svc.open(engine)
    await st.send(y)
    return [await st.finish(n_bits)]


@pytest.mark.tier1
@pytest.mark.parametrize("path", ["stream", "frame"])
@pytest.mark.parametrize("wire", ["float32", "int8"])
@pytest.mark.parametrize("metric_mode", ["f32", "i16", "i8"])
@pytest.mark.parametrize("name", available_code_specs())
def test_served_golden(name, metric_mode, wire, path):
    g = _load(name)
    meta = g["meta"]
    D, L = meta["D"], meta["L"]
    spec = get_code_spec(name)
    cfg = PBVDConfig(
        spec=spec, D=D, L=L, q=meta["q"], backend="ref", metric_mode=metric_mode
    )
    engine = DecoderEngine(cfg)
    y = g["y"]
    if wire == "int8":
        y = np.asarray(cfg.quantize(jnp.asarray(y)))
        assert y.dtype == np.int8
    slab = SymbolSlab(n_pages=4 * N_STREAMS, page_stages=D + 2 * L, R=spec.code.R)
    clock = FakeClock()

    async def run():
        # not started: poll() is driven by hand; capacity trouble raises
        # instead of parking a sender that nothing would wake
        svc = AsyncDecodeService(
            slab=slab,
            max_batch_blocks=MAX_BATCH_BLOCKS,
            deadline_ms=DEADLINE_MS,
            clock=clock.now,
            block_on_backpressure=False,
        )
        if path == "stream":
            bits, shared = await _stream_path(svc, clock, engine, y, meta["n_bits"], name, spec)
            assert shared >= 1, "no batcher dispatch coalesced two streams"
        else:
            bits = await _frame_path(svc, engine, y, meta["n_bits"])
            assert svc.metrics()["dispatches"] == 0
        return bits, svc.metrics()

    bits, m = asyncio.run(run())
    expected = g["bits_i8"] if metric_mode == "i8" else g["bits_f32"]
    for i, b in enumerate(bits):
        np.testing.assert_array_equal(
            b, expected, err_msg=f"{name}/{metric_mode}/{wire}/{path} stream {i} "
            "drifted from the golden vector"
        )
    assert slab.pages_free == slab.n_pages, "slab pages leaked"
    assert m["errors_by_class"] == {} and m["quarantined_streams"] == 0
