"""Serving-layer suite: pool lifecycle, paged slabs, async dispatch.

Four layers, matching DESIGN.md §13:

* slab allocator + paged session store (free-list recycling, exhaustion,
  page-boundary reads, bit-exactness of a slab-backed session);
* SessionPool lifecycle (the PR's bugfix sweep): finish-before-step,
  finish folding undrained step() output, pooled-vs-solo finish
  bit-identity for every non-block-aligned tail across the golden CodeSpec
  set × all metric modes, idempotent close, mesh pins that survive id
  reuse after GC;
* deadline-or-size dispatch determinism under a fake clock (no sleeps, no
  background task — the trigger is a pure function of the injected clock);
* admission control: bounded queues block (or raise in non-blocking mode)
  instead of growing, slab exhaustion maps to backpressure, and the
  64-stream Poisson trace decodes bit-exactly vs one-shot ``decode()`` no
  matter how the event loop interleaves it.
"""

import asyncio
import gc

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.core.channel import transmit
from repro.core.codespec import available_code_specs, get_code_spec
from repro.core.encoder import encode_jax, terminate
from repro.core.engine import ArraySessionStore, DecoderEngine
from repro.core.pbvd import PBVDConfig
from repro.launch.serve_async import (
    AsyncDecodeService,
    Backpressure,
    DeadlineBatcher,
    run_poisson_trace,
)
from repro.launch.serve_decoder import SessionPool, _latency_summary, _serve_status
from repro.launch.slab import PagedSessionStore, SlabExhausted, SymbolSlab

GEOM = dict(D=64, L=16, q=8)


def _tx_stream(name: str, n_bits: int, ebn0: float, seed: int):
    spec = get_code_spec(name)
    rng = np.random.default_rng(seed)
    payload = rng.integers(0, 2, n_bits)
    coded = encode_jax(jnp.asarray(terminate(payload, spec.code)), spec.code)
    tx = spec.puncture_stream(coded) if spec.is_punctured else coded
    y = np.asarray(transmit(jax.random.PRNGKey(seed), tx, ebn0, spec.rate))
    return spec, payload, y


def _engine(spec, metric_mode="f32", **overrides):
    kw = dict(GEOM)
    kw.update(overrides)
    return DecoderEngine(
        PBVDConfig(spec=spec, backend="ref", metric_mode=metric_mode, **kw)
    )


class FakeClock:
    def __init__(self):
        self.t = 100.0

    def now(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


# ---------------------------------------------------------------------------
# SymbolSlab + PagedSessionStore
# ---------------------------------------------------------------------------
@pytest.mark.tier1
def test_slab_alloc_free_recycles_lifo_and_zeroes():
    slab = SymbolSlab(n_pages=3, page_stages=4, R=2)
    a, b = slab.alloc(), slab.alloc()
    assert slab.pages_in_use == 2 and slab.high_water == 2
    slab._data[a] = 7.0  # dirty it
    slab.free(a)
    assert slab.pages_free == 2
    c = slab.alloc()  # LIFO: the just-freed page comes back first
    assert c == a
    assert np.all(slab._data[c] == 0.0)  # zeroed on free → BM-neutral alloc
    with pytest.raises(ValueError, match="double free"):
        slab.free(b)
        slab.free(b)
    with pytest.raises(ValueError):
        SymbolSlab(0, 4, 2)


@pytest.mark.tier1
def test_slab_exhaustion_is_explicit():
    slab = SymbolSlab(n_pages=2, page_stages=8, R=2)
    store = slab.open_store()
    store.append(np.ones((16, 2)))  # fills both pages
    with pytest.raises(SlabExhausted):
        store.append(np.ones((1, 2)))
    store.drop_prefix(8)  # retire one page back to the free-list
    store.append(np.ones((8, 2)))  # recycled page absorbs the growth
    assert slab.pages_in_use == 2


@pytest.mark.tier1
def test_paged_store_matches_array_store_reference():
    """Randomized append/grow/scatter/read/drop: the paged store is
    observationally identical to the contiguous reference store."""
    rng = np.random.default_rng(3)
    slab = SymbolSlab(n_pages=64, page_stages=5, R=3)  # odd page size on purpose
    paged, ref = slab.open_store(), ArraySessionStore(3)
    for _ in range(300):
        op = rng.integers(0, 4)
        if op == 0:
            rows = rng.normal(size=(int(rng.integers(0, 12)), 3)).astype(np.float32)
            paged.append(rows)
            ref.append(rows)
        elif op == 1:
            n = int(rng.integers(0, 7))
            paged.grow(n)
            ref.grow(n)
        elif op == 2 and len(ref):
            k = int(rng.integers(1, 5))
            si = rng.integers(0, len(ref), k)
            sj = rng.integers(0, 3, k)
            v = rng.normal(size=k).astype(np.float32)
            paged.scatter(si, sj, v)
            ref.scatter(si, sj, v)
        elif op == 3 and len(ref):
            n = int(rng.integers(0, len(ref) + 1))
            paged.drop_prefix(n)
            ref.drop_prefix(n)
        assert len(paged) == len(ref)
        lo = int(rng.integers(0, len(ref) + 1))
        n = int(rng.integers(0, len(ref) - lo + 3))  # deliberately over-reads
        np.testing.assert_array_equal(paged.read(lo, n), ref.read(lo, n))
    paged.close()
    assert slab.pages_in_use == 0
    with pytest.raises(ValueError, match="closed"):
        paged.append(np.zeros((1, 3)))
    paged.close()  # idempotent


@pytest.mark.tier1
def test_slab_backed_session_bit_exact_and_releases_pages():
    spec, _, y = _tx_stream("ccsds-3/4", 512, 4.5, 9)
    eng = _engine(spec)
    ref = np.asarray(eng.decode(jnp.asarray(y), 512))
    slab = SymbolSlab(n_pages=32, page_stages=GEOM["D"] + 2 * GEOM["L"], R=spec.code.R)
    sess = eng.session(store=slab.open_store())
    rng = np.random.default_rng(0)
    out, pos = [], 0
    while pos < len(y):
        n = int(rng.integers(1, 150))
        out.append(sess.decode(y[pos : pos + n]))
        pos += n
    out.append(sess.finish(512))
    np.testing.assert_array_equal(np.concatenate(out), ref)
    assert slab.high_water > 0
    sess.close()
    assert slab.pages_in_use == 0  # every page back on the free-list


def _slab_state(slab):
    return (list(slab._free), slab._in_use.copy(), slab._data.copy(), slab.high_water)


def _assert_slab_state(slab, state):
    free, in_use, data, high_water = state
    assert slab._free == free
    np.testing.assert_array_equal(slab._in_use, in_use)
    np.testing.assert_array_equal(slab._data, data)
    assert slab.high_water == high_water


@pytest.mark.tier1
def test_slab_single_free_refuses_bad_pages():
    slab = SymbolSlab(n_pages=4, page_stages=3, R=2)
    a = slab.alloc()
    slab._data[a] = 5.0
    state = _slab_state(slab)
    with pytest.raises(ValueError, match="double free"):
        slab.free(2)  # never allocated
    with pytest.raises(ValueError, match="outside slab"):
        slab.free(4)
    with pytest.raises(ValueError, match="outside slab"):
        slab.free(-1)
    _assert_slab_state(slab, state)
    slab.free(a)
    with pytest.raises(ValueError, match="double free"):
        slab.free(a)
    assert slab.pages_free == 4 and slab.pages_in_use == 0


@pytest.mark.tier1
@pytest.mark.parametrize(
    "batch, match",
    [
        ([0, 1, 0], "double free"),  # listed twice in one batch
        ([0, 2, 1], "double free"),  # page 2 was freed before
        ([1, 5], "double free"),  # page 5 was never allocated
        ([0, 6], "outside slab"),
        ([-1, 0], "outside slab"),
    ],
)
def test_slab_free_many_refuses_a_bad_batch_and_changes_nothing(batch, match):
    slab = SymbolSlab(n_pages=6, page_stages=3, R=2)
    pages = [slab.alloc() for _ in range(4)]
    assert pages == [0, 1, 2, 3]
    slab.free(2)
    for p in (0, 1, 3):
        slab._data[p] = p + 1.0
    state = _slab_state(slab)
    with pytest.raises(ValueError, match=match):
        slab.free_many(batch)
    _assert_slab_state(slab, state)
    slab.free_many([3, 0, 1])  # the pages are still held and free normally
    assert slab._free[-3:] == [3, 0, 1] and slab.pages_in_use == 0
    with pytest.raises(ValueError, match="double free"):
        slab.free_many([3])


@pytest.mark.tier1
def test_slab_released_pages_read_zero_on_next_alloc():
    """Pages released by free, free_many, drop_prefix and close all come
    back all-zero from alloc."""
    P = 4
    slab = SymbolSlab(n_pages=12, page_stages=P, R=2)
    single, batch = slab.alloc(), [slab.alloc() for _ in range(3)]
    dropped, closed = slab.open_store(), slab.open_store()
    dropped.append(np.full((3 * P + 1, 2), 3.0))
    closed.append(np.full((2 * P, 2), 4.0))
    slab._data[[single, *batch]] = 9.0
    slab.free(single)
    slab.free_many(batch)
    dropped.drop_prefix(3 * P)  # three pages in one batch, one page kept
    closed.close()
    assert slab.pages_in_use == 1
    np.testing.assert_array_equal(dropped.read(0, 1), [[3.0, 3.0]])
    fresh = [slab.alloc() for _ in range(slab.pages_free)]
    assert len(set(fresh)) == 11
    assert np.all(slab._data[fresh] == 0.0)


class _OldFreeList:
    """The slab's page bookkeeping before batch release: a LIFO list of
    free ids, each page pushed on its own after a scan of the list."""

    def __init__(self, n_pages: int):
        self.n_pages = n_pages
        self.free = list(range(n_pages - 1, -1, -1))
        self.high_water = 0

    def alloc(self) -> int:
        if not self.free:
            raise SlabExhausted("reference slab exhausted")
        page = self.free.pop()
        self.high_water = max(self.high_water, self.n_pages - len(self.free))
        return page

    def release(self, page: int) -> None:
        assert page not in self.free
        self.free.append(page)


class _OldPageList:
    """One store's pages as the per-page loops kept them."""

    def __init__(self, free_list: _OldFreeList, P: int):
        self.fl, self.P = free_list, P
        self.pages, self.head, self.n = [], 0, 0

    def extend(self, n: int) -> None:
        if n <= 0:
            return
        need = -(-(self.head + self.n + n) // self.P)
        while len(self.pages) < need:
            self.pages.append(self.fl.alloc())
        self.n += n

    def drop_prefix(self, n: int) -> None:
        n = min(n, self.n)
        if n <= 0:
            return
        self.head += n
        self.n -= n
        while self.head >= self.P:
            self.fl.release(self.pages.pop(0))
            self.head -= self.P
        if self.n == 0 and self.head == 0 and self.pages:
            for p in self.pages:
                self.fl.release(p)
            self.pages.clear()

    def close(self) -> None:
        for p in self.pages:
            self.fl.release(p)
        self.pages.clear()
        self.head = self.n = 0


@pytest.mark.tier1
@pytest.mark.parametrize("seed, n_pages, P", [(0, 14, 5), (1, 12, 3), (2, 20, 7), (3, 10, 4)])
def test_slab_batch_release_matches_old_per_page_free_list(seed, n_pages, P):
    """Random append/grow/scatter/read/drop_prefix/close over several stores:
    the page ids each store holds, the free-list's order, ``pages_free``,
    ``high_water`` and every read match the per-page reference, exhaustion
    included."""
    rng = np.random.default_rng(seed)
    R = 2
    slab, fl = SymbolSlab(n_pages=n_pages, page_stages=P, R=R), _OldFreeList(n_pages)
    slots = [(slab.open_store(), _OldPageList(fl, P), ArraySessionStore(R)) for _ in range(4)]
    exhausted = 0
    for _ in range(400):
        i = int(rng.integers(len(slots)))
        paged, model, content = slots[i]
        op = int(rng.integers(0, 6))
        if op in (0, 1):
            n = int(rng.integers(0, 3 * P))
            rows = rng.normal(size=(n, R)).astype(np.float32)
            try:
                paged.append(rows) if op == 0 else paged.grow(n)
            except SlabExhausted:
                exhausted += 1
                with pytest.raises(SlabExhausted):
                    model.extend(n)
            else:
                model.extend(n)
                content.append(rows) if op == 0 else content.grow(n)
        elif op == 2 and len(content):
            k = int(rng.integers(1, 4))
            si, sj = rng.integers(0, len(content), k), rng.integers(0, R, k)
            v = rng.normal(size=k).astype(np.float32)
            paged.scatter(si, sj, v)
            content.scatter(si, sj, v)
        elif op in (3, 4):
            n = int(rng.integers(0, len(content) + 2))
            paged.drop_prefix(n)
            model.drop_prefix(n)
            content.drop_prefix(n)
        elif op == 5:
            paged.close()
            model.close()
            slots[i] = (slab.open_store(), _OldPageList(fl, P), ArraySessionStore(R))
        paged, model, content = slots[i]
        for store, ref, _ in slots:
            assert store._pages == ref.pages
        assert slab._free == fl.free
        assert slab.pages_free == len(fl.free)
        assert slab.pages_in_use == n_pages - len(fl.free)
        assert slab.high_water == fl.high_water
        held = np.ones(n_pages, bool)
        held[fl.free] = False
        np.testing.assert_array_equal(slab._in_use, held)
        lo = int(rng.integers(0, len(content) + 1))
        np.testing.assert_array_equal(paged.read(lo, len(content)), content.read(lo, len(content)))
    assert exhausted > 0  # the walk reached the slab's limit
    for paged, model, _ in slots:
        paged.close()
        model.close()
    assert slab._free == fl.free and slab.pages_in_use == 0


class _RowIndexStore(PagedSessionStore):
    """The store as it addressed stages before whole-page copies: a flat
    row index over every stage of a window, then one fancy gather or
    scatter of rows."""

    def _rows(self, lo: int, n: int) -> np.ndarray:
        P = self._slab.page_stages
        g = self._head + lo + np.arange(n)
        return np.asarray(self._pages, np.int64)[g // P] * P + g % P

    def append(self, rows) -> None:
        rows = np.asarray(rows, np.float32)
        if len(rows):
            self._ensure_capacity(self._n + len(rows))
            self._slab._data.reshape(-1, self._slab.R)[self._rows(self._n, len(rows))] = rows
            self._n += len(rows)

    def read(self, lo: int, n: int) -> np.ndarray:
        n = max(0, min(n, self._n - lo))
        return self._slab._data.reshape(-1, self._slab.R)[self._rows(lo, n)]


def _read_window(rng, held: int, head: int, P: int) -> tuple[int, int]:
    """A read of one of the shapes framing and recovery make: random, mid-page
    to mid-page over many pages, exactly one page, empty, or past the end."""
    kind = int(rng.integers(5))
    lo = int(rng.integers(0, held + 1))
    if kind == 1 and held >= 2 * P:  # many pages, starting and ending mid-page
        lo = int(rng.integers(0, P))
        return lo, int(rng.integers(P + 1, held - lo + 1))
    if kind == 2 and held >= 2 * P:  # exactly one whole page
        return (-head) % P + P * int(rng.integers(0, (held - (-head) % P) // P)), P
    if kind == 3:
        return lo, 0
    if kind == 4:  # runs past the held end: clipped
        return lo, held - lo + int(rng.integers(1, 2 * P + 2))
    return lo, int(rng.integers(0, held - lo + 1))


@pytest.mark.tier1
@pytest.mark.parametrize("seed, P", [(0, 1), (1, 3), (2, 596), (3, 1), (4, 3), (5, 596)])
def test_paged_read_append_match_row_index_reference(seed, P):
    """A seeded walk of int8 and float32 appends, prefix drops and reads: the
    whole-page store reads what the per-row index store reads and leaves the
    same slab contents, for page sizes of 1, 3 and 596 stages."""
    rng = np.random.default_rng(seed)
    R, n_pages = 2, 64
    slab, ref_slab = SymbolSlab(n_pages, P, R), SymbolSlab(n_pages, P, R)
    store, ref = slab.open_store(), _RowIndexStore(ref_slab)
    heads = set()
    for _ in range(120):
        if len(store) < (n_pages // 2) * P and rng.integers(3):
            n = int(rng.integers(0, 4 * P + 3))
            if rng.integers(2):
                rows = rng.integers(-127, 128, (n, R)).astype(np.int8)
            else:
                rows = rng.normal(size=(n, R)).astype(np.float32)
            store.append(rows)
            ref.append(rows)
        else:
            n = int(rng.integers(0, len(store) + 1))
            store.drop_prefix(n)
            ref.drop_prefix(n)
        heads.add(store._head)
        assert len(store) == len(ref) and store._pages == ref._pages
        np.testing.assert_array_equal(slab._data, ref_slab._data)
        for _ in range(3):
            lo, n = _read_window(rng, len(store), store._head, P)
            got = store.read(lo, n)
            assert got.dtype == np.float32 and got.shape == (max(0, min(n, len(store) - lo)), R)
            np.testing.assert_array_equal(got, ref.read(lo, n))
    assert P == 1 or len(heads - {0}) > 0  # appends landed after mid-page drops
    store.close()
    assert slab.pages_in_use == 0


@pytest.mark.tier1
@pytest.mark.parametrize("held", [0, 2, 4, 6])
def test_paged_append_refused_mid_chunk_writes_nothing(held):
    """An append that runs out of pages partway through its chunk raises
    SlabExhausted with no symbol written and ``len`` unchanged."""
    P = 4
    slab = SymbolSlab(n_pages=3, page_stages=P, R=2)
    store = slab.open_store()
    store.append(np.full((held, 2), 5.0, np.float32))
    data, before = slab._data.copy(), store.read(0, held)
    with pytest.raises(SlabExhausted):  # one stage more than the whole slab
        store.append(np.arange((3 * P + 1) * 2, dtype=np.int8).reshape(-1, 2) - 50)
    assert len(store) == held
    np.testing.assert_array_equal(slab._data, data)
    np.testing.assert_array_equal(store.read(0, held + 3 * P), before)
    store.append(np.ones((1, 2), np.int8))  # a chunk that fits still lands
    np.testing.assert_array_equal(store.read(held, 1), [[1.0, 1.0]])


@pytest.mark.tier1
@pytest.mark.parametrize("lo, n", [(1, 2), (3, 4), (2, 9)])  # in a page, one page, across pages
def test_paged_read_does_not_alias_slab(lo, n):
    slab = SymbolSlab(n_pages=4, page_stages=4, R=2)
    store = slab.open_store()
    store.append(np.arange(26, dtype=np.float32).reshape(13, 2))
    store.drop_prefix(1)  # head mid-page, so lo=3 reads exactly page 1
    data, first = slab._data.copy(), store.read(lo, n)
    first[...] = -1.0
    np.testing.assert_array_equal(slab._data, data)
    np.testing.assert_array_equal(store.read(lo, n), data.reshape(-1, 2)[1 + lo : 1 + lo + n])


@pytest.mark.tier1
@pytest.mark.parametrize("P, drop, held", [(4, 3, 9), (5, 7, 11), (596, 590, 700)])
def test_paged_snapshot_restore_across_page_boundary(P, drop, held):
    """A window that starts mid-page and crosses a page boundary snapshots
    and restores into a fresh store (head 0) with every stage intact."""
    rng = np.random.default_rng(P)
    rows = rng.integers(-127, 128, (drop + held, 2)).astype(np.int8)
    src = SymbolSlab(n_pages=4, page_stages=P, R=2).open_store()
    src.append(rows)
    src.drop_prefix(drop)
    assert src._head and src._head + held > P
    snap = src.snapshot()
    dst_slab = SymbolSlab(n_pages=4, page_stages=P, R=2)
    dst = dst_slab.open_store()
    dst.restore(snap)
    assert dst._head == 0 and len(dst) == held
    np.testing.assert_array_equal(dst.read(0, held), rows[drop:].astype(np.float32))
    np.testing.assert_array_equal(dst.read(0, held), src.read(0, held))
    snap["rows"][...] = 0.0  # the snapshot is a copy, not a view of either slab
    np.testing.assert_array_equal(dst.read(0, held), rows[drop:].astype(np.float32))


# ---------------------------------------------------------------------------
# SessionPool lifecycle: the finish paths
# ---------------------------------------------------------------------------
@pytest.mark.tier1
@pytest.mark.parametrize("name", available_code_specs())
@pytest.mark.parametrize("metric_mode", ["f32", "i16", "i8"])
def test_pooled_finish_bit_identical_to_solo_ragged_tails(name, metric_mode):
    """Acceptance: PooledSession.finish ≡ DecoderSession.finish for every
    non-block-aligned tail in the golden CodeSpec set, every metric mode."""
    spec, _, y = _tx_stream(name, 300, 4.5, 21)
    eng = _engine(spec, metric_mode=metric_mode)
    D = GEOM["D"]
    for n_bits in (300, 299, 257, 2 * D + 1, 2 * D - 1, 97):
        solo = eng.session()
        solo.ingest(y)
        a = solo.finish(n_bits)
        pool = SessionPool()
        h = pool.open(eng)
        h.feed(y)
        b = h.finish(n_bits)
        np.testing.assert_array_equal(a, b)
        assert len(a) == n_bits
        # and both equal the one-shot decode of the same stream
        np.testing.assert_array_equal(
            a, np.asarray(eng.decode(jnp.asarray(y), n_bits))
        )


@pytest.mark.tier1
def test_pooled_finish_before_step_and_interleaved_steps():
    spec, _, y = _tx_stream("ccsds", 400, 4.5, 4)
    eng = _engine(spec)
    ref = np.asarray(eng.decode(jnp.asarray(y), 400))

    # finish before any step: the flush is the only launch
    pool = SessionPool()
    h = pool.open(eng)
    h.feed(y)
    np.testing.assert_array_equal(h.finish(400), ref)
    assert h.bits_emitted == 400

    # feed/step/feed/finish with takes in between
    pool = SessionPool()
    h = pool.open(eng)
    h.feed(y[:300])
    pool.step()
    part = h.take()
    h.feed(y[300:])
    pool.step()
    out = np.concatenate([part, h.take(), h.finish(400)])
    np.testing.assert_array_equal(out, ref)


@pytest.mark.tier1
def test_pooled_finish_folds_undrained_queue():
    """finish() without a prior take() must deliver the queued step() output
    instead of silently dropping it (the old docstring caveat)."""
    spec, _, y = _tx_stream("ccsds", 400, 4.5, 5)
    eng = _engine(spec)
    ref = np.asarray(eng.decode(jnp.asarray(y), 400))
    pool = SessionPool()
    h = pool.open(eng)
    h.feed(y)
    assert pool.step() > 0  # blocks decoded and queued on the session
    out = h.finish(400)  # NO take() first — finish folds the queue
    np.testing.assert_array_equal(out, ref)
    assert len(h.take()) == 0  # nothing left behind
    assert h.bits_emitted == 400


# ---------------------------------------------------------------------------
# SessionPool lifecycle: open/close
# ---------------------------------------------------------------------------
@pytest.mark.tier1
def test_pool_close_is_idempotent():
    spec, _, y = _tx_stream("ccsds", 128, 5.0, 6)
    eng = _engine(spec)
    pool = SessionPool()
    h = pool.open(eng)
    pool.close(h)
    pool.close(h)  # second close: no ValueError, no state corruption
    assert len(pool) == 0
    h2 = pool.open(eng)
    pool.close(h2)
    pool.close(h)  # stale handle close after reuse: still a no-op
    assert len(pool) == 0 and not pool._mesh_refs


@pytest.mark.tier1
def test_pool_mesh_pin_released_once_and_survives_id_reuse():
    """The mesh pin is keyed by the member OBJECT: a closed member's GC'd
    id being reused by a new member can neither drop nor double-release a
    pin (the old ``id(ps)`` key could)."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    spec = get_code_spec("ccsds")
    eng = DecoderEngine(
        PBVDConfig(spec=spec, backend="ref", **GEOM), mesh=mesh, block_axes=("data",)
    )
    pool = SessionPool()
    h1 = pool.open(eng)
    assert len(pool._mesh_refs) == 1
    pool.close(h1)
    assert len(pool._mesh_refs) == 0
    pool.close(h1)  # double close: pin already released, exactly once
    assert len(pool._mesh_refs) == 0
    del h1
    gc.collect()
    # new members after the old id is reusable: pins track exactly the live
    # membership, keyed by the member objects themselves
    handles = [pool.open(eng) for _ in range(4)]
    assert set(pool._mesh_refs) == set(handles)
    assert all(m is mesh for m in pool._mesh_refs.values())
    for h in handles:
        pool.close(h)
    assert len(pool._mesh_refs) == 0


@pytest.mark.tier1
def test_pool_open_partial_failure_leaves_no_state():
    """A failure while registering a new member rolls the pool back to a
    clean state — no orphan member, no leaked mesh pin."""
    from jax.sharding import Mesh

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    spec = get_code_spec("ccsds")
    eng = DecoderEngine(
        PBVDConfig(spec=spec, backend="ref", **GEOM), mesh=mesh, block_axes=("data",)
    )
    pool = SessionPool()

    class ExplodingDict(dict):
        def __setitem__(self, k, v):
            raise RuntimeError("registration failed")

    pool._mesh_refs = ExplodingDict()
    with pytest.raises(RuntimeError, match="registration failed"):
        pool.open(eng)
    assert len(pool) == 0 and len(pool._mesh_refs) == 0


# ---------------------------------------------------------------------------
# Deadline-or-size dispatch: deterministic under a fake clock
# ---------------------------------------------------------------------------
@pytest.mark.tier1
def test_deadline_batcher_fake_clock_determinism():
    clk = FakeClock()
    b = DeadlineBatcher(max_batch_blocks=4, deadline_s=0.010, clock=clk.now)
    assert not b.due(0) and b.timeout() is None  # nothing pending, nothing armed
    b.note_feed()
    assert b.timeout() == pytest.approx(0.010)
    assert not b.due(1)  # below size, before deadline
    clk.advance(0.0099)
    assert not b.due(3)
    clk.advance(0.0001)
    assert b.due(1)  # exactly at the deadline
    assert b.due(4) and b.due(9)  # size trigger holds regardless
    b.fired()
    assert b.timeout() is None and not b.due(1)  # arm cleared by dispatch
    b.note_feed()
    b.note_feed()  # later feeds do not push the oldest arrival back
    assert b.timeout() == pytest.approx(0.010)
    assert b.due(4)  # size trigger is immediate even with a fresh arm
    with pytest.raises(ValueError):
        DeadlineBatcher(0, 1.0)
    with pytest.raises(ValueError):
        DeadlineBatcher(1, -1.0)


@pytest.mark.tier1
def test_service_dispatch_deadline_determinism_fake_clock():
    """Drive the service's poll() by hand under a fake clock: the dispatch
    sequence and every recorded chunk latency are exact numbers."""
    spec, _, y = _tx_stream("ccsds", 256, 4.5, 8)
    eng = _engine(spec)
    ref = np.asarray(eng.decode(jnp.asarray(y), 256))
    clk = FakeClock()

    async def scenario():
        svc = AsyncDecodeService(
            max_batch_blocks=1000,  # size trigger out of the way
            deadline_ms=10.0,
            max_pending_blocks=10_000,
            clock=clk.now,
        )  # NOT started: poll() is driven manually, no background task
        stream = svc.open(eng)
        await stream.send(y[: len(y) // 2])  # completes ≥ 1 block
        assert svc.poll() is False  # deadline not yet reached
        clk.advance(0.009)
        assert svc.poll() is False
        clk.advance(0.001)
        assert svc.poll() is True  # fires exactly at the 10 ms deadline
        assert svc.dispatches == 1
        assert svc.poll() is False  # nothing ready → no spurious dispatch
        clk.advance(5.0)
        assert svc.poll() is False  # deadline arm was cleared by the fire
        await stream.send(y[len(y) // 2 :])
        clk.advance(0.010)
        assert svc.poll() is True
        clk.advance(0.003)
        # take() was never called, so finish folds both dispatches' queued
        # bits plus the flushed tail — the whole stream comes back here
        return await stream.finish(256), svc

    out, svc = asyncio.run(scenario())
    np.testing.assert_array_equal(out, ref)
    m = svc.metrics()
    assert m["dispatches"] == 2
    assert m["chunks"] == 2
    assert m["p50_ms"] is not None and m["p99_ms"] is not None
    # latencies are exact fake-clock deltas — the accounting is
    # deterministic, not wall-clock-dependent
    lats = sorted(round(t, 6) for t in svc._latencies_s)
    assert lats[0] == pytest.approx(0.013)  # chunk 2: resolved at finish
    assert lats[1] == pytest.approx(5.020)  # chunk 1: idle gap + 2nd deadline


@pytest.mark.tier1
def test_service_metrics_guard_small_samples():
    svc = AsyncDecodeService(max_batch_blocks=1, deadline_ms=1.0)
    m = svc.metrics()
    assert m["chunks"] == 0
    assert m["p50_ms"] is None and m["p99_ms"] is None and m["sustained_mbps"] is None
    assert _latency_summary([]) == "no latency samples"
    assert "p99≈max" in _latency_summary([1.0, 2.0])
    assert "p99≈max" not in _latency_summary(list(range(50)))


# ---------------------------------------------------------------------------
# Backpressure: bounded admission
# ---------------------------------------------------------------------------
@pytest.mark.tier1
def test_backpressure_raises_in_nonblocking_mode():
    spec, _, y = _tx_stream("ccsds", 512, 4.5, 12)
    eng = _engine(spec)

    async def scenario():
        svc = AsyncDecodeService(
            max_batch_blocks=1000,
            deadline_ms=0.0,  # manual poll() is due as soon as anything is pending
            max_pending_blocks=2,
            block_on_backpressure=False,
        )
        stream = svc.open(eng)
        await stream.send(y[:300])  # ≥ 2 blocks ready → at the cap
        assert svc._pool.pending_blocks() >= 2
        with pytest.raises(Backpressure, match="pending-block cap"):
            await stream.send(y[300:])
        # a dispatch drains the pool; admission opens again
        assert svc.poll() is True
        await stream.send(y[300:])
        return np.concatenate([stream.take(), await stream.finish(512)])

    out = asyncio.run(scenario())
    np.testing.assert_array_equal(out, np.asarray(eng.decode(jnp.asarray(y), 512)))


@pytest.mark.tier1
def test_backpressure_blocks_sender_until_dispatch():
    """In blocking mode the bounded queue parks the sender instead of
    growing: the send only completes after a dispatch frees capacity."""
    spec, _, y = _tx_stream("ccsds", 512, 4.5, 13)
    eng = _engine(spec)

    async def scenario():
        svc = AsyncDecodeService(
            max_batch_blocks=1000,
            deadline_ms=0.0,  # manual poll() is due as soon as anything is pending
            max_pending_blocks=2,
        )
        stream = svc.open(eng)
        await stream.send(y[:300])
        blocked = asyncio.ensure_future(stream.send(y[300:]))
        for _ in range(5):
            await asyncio.sleep(0)
        assert not blocked.done()  # parked on the cap, not queued unboundedly
        assert svc.poll() is True  # manual dispatch (service not started)
        await asyncio.wait_for(blocked, timeout=5)
        return np.concatenate([stream.take(), await stream.finish(512)])

    out = asyncio.run(scenario())
    np.testing.assert_array_equal(out, np.asarray(eng.decode(jnp.asarray(y), 512)))


@pytest.mark.tier1
def test_slab_exhaustion_backpressure_and_hopeless_admit():
    spec, _, y = _tx_stream("ccsds", 512, 4.5, 14)
    eng = _engine(spec)
    T = GEOM["D"] + 2 * GEOM["L"]

    async def scenario():
        # 4 pages: exactly one stream's full-slab working set
        slab = SymbolSlab(n_pages=4, page_stages=T, R=spec.code.R)
        svc = AsyncDecodeService(
            max_batch_blocks=1000,
            deadline_ms=0.0,  # manual poll() is due as soon as anything is pending
            slab=slab,
            block_on_backpressure=False,
        )
        stream = svc.open(eng)
        await stream.send(y[: 4 * T])  # fills the slab exactly
        with pytest.raises(Backpressure, match="slab pages"):
            # pages can only come back via a dispatch; non-blocking mode
            # maps the allocator's exhaustion to admission refusal
            await stream.send(y[4 * T :])
        assert svc.poll() is True  # decode → commit → pages freed
        await stream.send(y[4 * T :])  # recycled pages absorb the retry
        bits = np.concatenate([stream.take(), await stream.finish(512)])
        assert slab.pages_in_use == 0  # finish released the stream's pages
        return bits

    out = asyncio.run(scenario())
    np.testing.assert_array_equal(out, np.asarray(eng.decode(jnp.asarray(y), 512)))

    async def hopeless():
        # a chunk bigger than the whole slab can never be admitted: that
        # must raise even in blocking mode rather than deadlock
        slab = SymbolSlab(n_pages=1, page_stages=8, R=spec.code.R)
        svc = AsyncDecodeService(max_batch_blocks=1000, deadline_ms=0.0, slab=slab)
        stream = svc.open(eng)
        with pytest.raises(SlabExhausted):
            await stream.send(y[:300])

    asyncio.run(hopeless())


# ---------------------------------------------------------------------------
# The acceptance trace: 64 Poisson streams, bit-exact
# ---------------------------------------------------------------------------
@pytest.mark.tier1
def test_async_service_64_stream_poisson_bit_exact():
    """64 concurrent streams under Poisson arrivals through the full stack
    (admission → slab paging → deadline dispatch → delivery) decode
    bit-exactly vs per-stream one-shot ``decode()``."""
    S, n_bits = 64, 256
    spec = get_code_spec("ccsds")
    eng = _engine(spec)
    payloads, ys = [], []
    for i in range(S):
        _, p, y = _tx_stream("ccsds", n_bits, 4.5, 40 + i)
        payloads.append(p)
        ys.append(y)
    refs = [np.asarray(eng.decode(jnp.asarray(y), n_bits)) for y in ys]
    T = GEOM["D"] + 2 * GEOM["L"]
    slab = SymbolSlab(n_pages=6 * S, page_stages=T, R=spec.code.R)
    bits, report = asyncio.run(
        run_poisson_trace(
            eng,
            ys,
            [n_bits] * S,
            chunk_symbols=100,
            rate_chunks_per_s=5000.0,
            seed=3,
            slab=slab,
            service_kwargs=dict(max_batch_blocks=64, deadline_ms=2.0),
        )
    )
    for b, r in zip(bits, refs):
        np.testing.assert_array_equal(b, r)
    assert report["chunks"] == sum(-(-len(y) // 100) for y in ys)
    assert report["bits_delivered"] == S * n_bits
    assert report["p50_ms"] is not None
    assert 0 < report["slab_pages_high_water"] <= slab.n_pages
    assert slab.pages_in_use == 0  # every stream's pages returned
    # the dispatcher coalesced: far fewer pool steps than chunks
    assert report["dispatches"] < report["chunks"]


def test_serve_status_fails_on_any_stream_failure():
    """A serve mode exits non-zero when a stream ended in a typed
    DecodeError (or any non-array result) or the pool quarantined one —
    a printed BER never stands in for a failed launch."""
    from repro.launch.faults import DispatchError, StreamError

    ok = [np.zeros(8, np.int32), np.ones(8, np.int32)]
    assert _serve_status(ok, 0) == 0
    assert _serve_status(ok, 1) == 1
    assert _serve_status([ok[0], StreamError("poisoned")], 0) == 1
    assert _serve_status([DispatchError("launch refused"), ok[1]], 0) == 1
    assert _serve_status([ok[0], None], 0) == 1
