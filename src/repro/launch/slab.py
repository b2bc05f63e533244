"""Paged session-state slabs: shared storage for millions of short streams.

A streaming decode session carries soft symbols between chunks — the
inter-block overlap tail plus whatever arrived since the last launch. With
one contiguous ndarray per session (the default
:class:`~repro.core.engine.ArraySessionStore`), a serving layer admitting
millions of short-lived streams churns an allocation per chunk per stream.
This module is the paged alternative, shaped like pie's paged-KV blocks
(ROADMAP item 2): ONE slab of fixed-size pages shared by every live
session, a LIFO free-list so a dying stream's pages are immediately reused
by the next admit, and per-session stores that are *views* onto their page
list rather than owners of memory.

* :class:`SymbolSlab` — the allocator: ``(n_pages, page_stages, R)``
  float32 backing array + free-list, and one in-use flag per page, so the
  range and double-free checks are O(1) whatever the free-list's length.
  Pages are zeroed on release, so a freshly allocated page is always
  all-zero (the BM-neutral erasure value the punctured ingest and the
  zero-padded tail both rely on). ``free_many`` releases a run of pages in
  one call: one check, one zeroing scatter, one extend of the free-list.
* :class:`PagedSessionStore` — one session's buffered-symbol window,
  implementing the :class:`~repro.core.engine.ArraySessionStore` contract
  over a list of slab pages: ``append``/``grow``/``scatter`` fill the tail,
  ``drop_prefix`` retires committed stages and returns the fully consumed
  prefix of pages to the free-list in one batch, ``read`` gathers a stage
  window across page boundaries.

Reads and appends move whole pages: ``read`` copies the pages a window
touches with one gather on the page axis and slices the partial first and
last page off the copy; ``append`` writes at most three pieces (the rest of
the tail page, the whole pages after it in one page-indexed assignment, and
the start of the last page). Their cost grows with the bytes moved, not with
a per-row index. A read never aliases the slab, and an append either writes
every row or, when the slab runs out of pages, none.

Exhaustion is an explicit :class:`SlabExhausted` — the admission layer
(:mod:`repro.launch.serve_async`) maps it to backpressure instead of
letting the slab grow unboundedly.

See DESIGN.md §13 for the layout and the serving-layer contract.
"""

from __future__ import annotations

import numpy as np

from repro.launch.faults import CapacityError

__all__ = ["SlabExhausted", "SymbolSlab", "PagedSessionStore"]


class SlabExhausted(CapacityError):
    """No free pages left in the slab (admission should apply backpressure).

    A :class:`~repro.launch.faults.CapacityError`: the service — not the
    stream or the launch — is out of room, so waiting for a dispatch to
    retire pages (or shedding the admission) is the right response.
    """


class SymbolSlab:
    """A pool of fixed-size symbol pages with a LIFO free-list.

    Parameters
    ----------
    n_pages: total pages in the slab (the hard capacity knob).
    page_stages: full-rate stages per page. The serving layer sizes this to
        the session working set — a session holds at most ``D + L`` stages
        between steps plus whatever arrival jitter buffers on top, so
        ``D + 2L`` (one decode window) is a natural default.
    R: symbols per stage (the mother code rate denominator).
    """

    def __init__(self, n_pages: int, page_stages: int, R: int):
        if n_pages <= 0 or page_stages <= 0 or R <= 0:
            raise ValueError(
                f"slab geometry must be positive, got n_pages={n_pages}, "
                f"page_stages={page_stages}, R={R}"
            )
        self.n_pages = int(n_pages)
        self.page_stages = int(page_stages)
        self.R = int(R)
        self._data = np.zeros((n_pages, page_stages, R), np.float32)
        self._free: list[int] = list(range(n_pages - 1, -1, -1))  # LIFO: pop()
        self._in_use = np.zeros(n_pages, bool)  # O(1) page state for the checks
        self.high_water = 0  # max pages simultaneously in use (for reports)

    # ---- allocation ----------------------------------------------------------------
    @property
    def pages_free(self) -> int:
        return len(self._free)

    @property
    def pages_in_use(self) -> int:
        return self.n_pages - len(self._free)

    def alloc(self) -> int:
        """Take a (zeroed) page id off the free-list."""
        if not self._free:
            raise SlabExhausted(
                f"slab exhausted: all {self.n_pages} pages "
                f"({self.n_pages * self.page_stages} stages) in use"
            )
        page = self._free.pop()
        self._in_use[page] = True
        self.high_water = max(self.high_water, self.pages_in_use)
        return page

    def free(self, page: int) -> None:
        """Return a page; zero it so the next alloc sees BM-neutral zeros."""
        self.free_many((page,))

    def free_many(self, pages) -> None:
        """Return pages in one call, as ``free`` on each in order would.

        Every page is checked before any state changes: a page outside the
        slab, one not in use (never allocated or already free) or one listed
        twice raises ``ValueError`` and leaves the slab as it was. The pages
        are zeroed with one scatter and pushed onto the free-list in order,
        so the next allocs hand them back last-released first.
        """
        pages = np.asarray(pages, np.int64).reshape(-1)
        if not pages.size:
            return
        outside = (pages < 0) | (pages >= self.n_pages)
        if outside.any():
            raise ValueError(f"page {pages[outside.argmax()]} outside slab of {self.n_pages}")
        idle = ~self._in_use[pages]
        if idle.any():
            raise ValueError(f"double free of slab page {pages[idle.argmax()]}")
        if pages.size > 1 and np.unique(pages).size < pages.size:
            raise ValueError("double free: a slab page listed twice in one release")
        self._in_use[pages] = False
        self._data[pages] = 0.0
        self._free.extend(pages.tolist())

    def open_store(self) -> "PagedSessionStore":
        """A fresh (empty) session store over this slab."""
        return PagedSessionStore(self)


class PagedSessionStore:
    """One session's symbol buffer as a window over slab pages.

    Logical stage ``i`` (0 = oldest held stage) lives at page
    ``pages[(head + i) // P]``, row ``(head + i) % P`` where ``head`` is the
    intra-page offset of stage 0 and ``P = slab.page_stages``. ``append``/
    ``grow`` extend the tail (allocating pages on demand), ``drop_prefix``
    advances ``head`` and frees pages the window has fully left — so a
    steady-state stream touches exactly ceil(working set / P) pages no
    matter how many chunks flow through it.

    ``read`` and ``append`` address whole pages, with slices for the partial
    pages at either edge of the window. ``read`` returns a fresh float32
    array (writing to it leaves the slab as it was). ``append`` reserves
    every page it needs before it writes a symbol, so a
    :class:`SlabExhausted` leaves the held stages and ``len`` unchanged.

    Implements the :class:`~repro.core.engine.ArraySessionStore` contract;
    see that class for method semantics.
    """

    def __init__(self, slab: SymbolSlab):
        self._slab = slab
        self._pages: list[int] = []
        self._head = 0  # intra-page offset of logical stage 0
        self._n = 0  # stages held
        self._closed = False

    def __len__(self) -> int:
        return self._n

    # ---- page addressing -----------------------------------------------------------
    def _ensure_capacity(self, n_total: int) -> None:
        """Grow the page list to hold ``n_total`` logical stages."""
        P = self._slab.page_stages
        need_pages = -(-(self._head + n_total) // P)
        while len(self._pages) < need_pages:
            self._pages.append(self._slab.alloc())

    # ---- ArraySessionStore contract ------------------------------------------------
    def append(self, rows: np.ndarray) -> None:
        self._check_open()
        rows = np.asarray(rows)
        n = len(rows)
        if n == 0:
            return
        rows = np.broadcast_to(rows, (n, self._slab.R))  # a bad shape fails before any write
        self._ensure_capacity(self._n + n)  # so does an exhausted slab
        data, P = self._slab._data, self._slab.page_stages
        p, r = divmod(self._head + self._n, P)
        done = 0
        if r:  # the rest of the tail page
            done = min(P - r, n)
            data[self._pages[p], r : r + done] = rows[:done]
            p += 1
        k = (n - done) // P
        if k:  # whole pages, one page-indexed assignment (casts to float32)
            data[self._pages[p : p + k]] = rows[done : done + k * P].reshape(k, P, -1)
            p += k
            done += k * P
        if done < n:  # the start of the last page
            data[self._pages[p], : n - done] = rows[done:]
        self._n += n

    def grow(self, n: int) -> None:
        # pages arrive zeroed from the free-list and the tail past _n was
        # never written (stores only drop from the head), so growing is just
        # capacity + bookkeeping — no memset
        self._check_open()
        if n > 0:
            self._ensure_capacity(self._n + n)
            self._n += n

    def scatter(self, stage_idx, sym_idx, values) -> None:
        self._check_open()
        stage_idx = np.asarray(stage_idx)
        g = self._head + stage_idx
        P = self._slab.page_stages
        pages = np.asarray(self._pages, np.int64)[g // P]
        self._slab._data[pages, g % P, sym_idx] = values

    def read(self, lo: int, n: int) -> np.ndarray:
        self._check_open()
        n = max(0, min(n, self._n - lo))
        if n <= 0:
            return np.zeros((0, self._slab.R), np.float32)
        P = self._slab.page_stages
        g = self._head + lo
        p0, p1 = g // P, -(-(g + n) // P)
        # one gather on the page axis copies the pages; slice the edges off
        pages = np.take(self._slab._data, self._pages[p0:p1], axis=0)
        return pages.reshape(-1, self._slab.R)[g - p0 * P : g - p0 * P + n]

    def drop_prefix(self, n: int) -> None:
        self._check_open()
        n = min(n, self._n)
        if n <= 0:
            return
        self._head += n
        self._n -= n
        P = self._slab.page_stages
        k = self._head // P  # pages the window has fully left
        if k:
            self._slab.free_many(self._pages[:k])
            del self._pages[:k]
            self._head -= k * P
        if self._n == 0 and self._head == 0 and self._pages:
            # fully drained on a page boundary: release the idle tail page too
            self._slab.free_many(self._pages)
            self._pages.clear()

    def snapshot(self) -> dict:
        """Logical content only — a copy of the held rows, never page ids.

        Restoring allocates FRESH pages from whatever slab backs the target
        store (``_head`` restarts at 0); page boundaries shift but every
        logical stage is identical, which is all the session framing reads.
        """
        self._check_open()
        return {"rows": np.array(self.read(0, self._n), np.float32)}

    def restore(self, snap: dict) -> None:
        self._check_open()
        if self._n or self._pages:
            raise ValueError("restore() target store is not empty")
        self.append(np.asarray(snap["rows"], np.float32))

    def close(self) -> None:
        """Return every page to the slab; safe to call repeatedly."""
        if self._closed:
            return
        self._slab.free_many(self._pages)
        self._pages.clear()
        self._head = self._n = 0
        self._closed = True

    # ---- internals -----------------------------------------------------------------
    def _check_open(self) -> None:
        if self._closed:
            raise ValueError("operation on a closed PagedSessionStore")
