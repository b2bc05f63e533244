"""The one traffic generator: clients driven by a mix file.

A mix (``bench/traffic/<name>.json``) gives:

* ``clients_per_chip`` -- concurrent clients, times the cell's chips;
* ``chunk_stages`` -- stages per chunk; every chunk is one terminated
  codeword (``chunk_stages - v`` payload bits, v zero tail bits);
* ``chunks_per_stream`` -- chunks a stream holds before the client
  finishes it and opens the next (null: one stream per client, sent until
  the window closes and finished after it);
* ``finish_bits`` -- ``"payload"`` finishes a stream with its payload bits
  only (the tail bits are not asked for), ``"all"`` with every stage sent;
* ``pool_chunks`` -- distinct noisy codewords made from the seed; clients
  draw chunks from this pool, so host memory does not grow with the window;
* ``ebn0_db`` -- the channel; ``warmup_requests_per_client`` -- chunks each
  client sends, on average, before the measured window opens;
* ``check`` -- which delivered chunks the reference decodes afterwards:
  each chunk is kept with probability ``1/keep_every`` (drawn from the
  seed) and at most ``max_chunks`` of the kept ones are compared (null:
  all);
* ``arrivals`` (optional, default closed) -- when each client's chunks are
  due:

  - ``{"kind": "closed"}``: a chunk is due when the previous ``send`` of
    the client has returned (and, between streams, when the previous
    stream's ``finish`` has);
  - ``{"kind": "poisson", "rate_hz": r}``: an open loop, chunks due at
    exponential gaps of mean 1/r seconds;
  - ``{"kind": "periodic", "period_ms": p}``: an open loop, chunks due
    every p ms from a phase drawn from the seed;
  - ``{"kind": "onoff", "rate_hz": r, "on_ms": a, "off_ms": b}``: an open
    loop, Poisson at rate r during ON periods; ON and OFF periods are
    exponential with means a and b ms, the first phase drawn from the seed.

Closed loop: before each chunk a client yields to the event loop once, as
an asynchronous reader of its next chunk would; it then sends the chunk
and takes whatever bits have been delivered, and finishes a stream right
after its last chunk. Open loop: streams start when their first chunk is
due, whether or not the client's previous stream has finished (a one-chunk
stream, a frame, never waits for another), and a stream sends each chunk
when it is due or, where its previous ``send`` is still parked, as soon as
that returns; while it waits for a due time it takes the bits delivered
meanwhile every millisecond, so a chunk's completion is seen within one.

Every chunk is a request: due, sent (``send`` started), done (its last bit
delivered). Its latency is done - due, and sent - due is how late the
generator sent it. The requests of the window are those due in it; a
request due in the window that ends after it counts with its whole wait,
and an open-loop chunk due in the window but never sent before the close
counts as attempted and not answered.
"""

from __future__ import annotations

import asyncio
import dataclasses
import itertools
import time

import numpy as np

from codes import Code, make_codewords

__all__ = ["Mix", "StreamLog", "Window", "due_times", "make_pool", "payload_bits_in", "run_clients"]

POLL_S = 1e-3  # an open-loop stream waiting for its next due time takes bits this often


@dataclasses.dataclass(frozen=True)
class Mix:
    clients_per_chip: int
    chunk_stages: int
    chunks_per_stream: int | None
    finish_bits: str
    pool_chunks: int
    ebn0_db: float
    warmup_requests_per_client: int
    check: dict
    arrivals: dict = dataclasses.field(default_factory=lambda: {"kind": "closed"})

    @classmethod
    def from_json(cls, d: dict) -> "Mix":
        fields = {f.name: f for f in dataclasses.fields(cls)}
        unknown = set(d) - set(fields)
        if unknown:
            raise ValueError(f"unknown mix keys {sorted(unknown)}")
        mix = cls(**{k: d[k] for k in fields if k in d})
        if mix.arrivals.get("kind") not in ("closed", "poisson", "periodic", "onoff"):
            raise ValueError(f"unknown arrivals {mix.arrivals!r}")
        return mix

    @property
    def open_loop(self) -> bool:
        return self.arrivals["kind"] != "closed"

    @property
    def stream_stages(self) -> int | None:
        """Stages of one whole stream (None: unbounded)."""
        if self.chunks_per_stream is None:
            return None
        return self.chunks_per_stream * self.chunk_stages


def due_times(arrivals: dict, rng: np.random.Generator):
    """Offsets in seconds, from the generator's start, at which one client's
    chunks fall due under an open-loop ``arrivals``; an endless iterator."""
    kind = arrivals["kind"]
    t = 0.0
    if kind == "periodic":
        period = arrivals["period_ms"] / 1e3
        t = float(rng.uniform(0.0, period))
        while True:
            yield t
            t += period
    elif kind == "poisson":
        while True:
            t += float(rng.exponential(1.0 / arrivals["rate_hz"]))
            yield t
    elif kind == "onoff":
        on_s, off_s = arrivals["on_ms"] / 1e3, arrivals["off_ms"] / 1e3
        on = bool(rng.random() < on_s / (on_s + off_s))
        edge = float(rng.exponential(on_s if on else off_s))
        while True:
            if not on:
                t, on = edge, True
                edge = t + float(rng.exponential(on_s))
                continue
            gap = float(rng.exponential(1.0 / arrivals["rate_hz"]))
            if t + gap < edge:
                t += gap
                yield t
            else:
                t, on = edge, False
                edge = t + float(rng.exponential(off_s))
    else:
        raise ValueError(f"no schedule for closed-loop arrivals {arrivals!r}")


def payload_bits_in(p0: int, n: int, S: int, v: int) -> int:
    """Payload positions among stream positions [p0, p0 + n) of a stream made
    of S-stage codewords whose last v stages are tail bits."""

    def upto(p: int) -> int:  # payload positions in [0, p)
        full, rest = divmod(p, S)
        return full * (S - v) + min(rest, S - v)

    return upto(p0 + n) - upto(p0)


@dataclasses.dataclass
class StreamLog:
    """What one stream sent and what came back, for the check afterwards."""

    ids: list  # pool index of each chunk sent, in order
    stages_sent: int = 0
    n_bits: int | None = None  # the length finish() asked for
    delivered: int = 0  # bits delivered so far
    kept: dict = dataclasses.field(default_factory=dict)  # chunk -> bits
    error: str | None = None
    reqs: list = dataclasses.field(default_factory=list)  # [due, sent, done] per chunk
    n_done: int = 0  # chunks whose every bit has been delivered


class Window:
    """Warm-up, then the measured window, then the drain: the phase every
    client reads before each request, and the counts taken in the window.

    The window closes at the first ``poll()`` at or after its end time,
    whoever calls it: a timer task may wake late behind a long turn of the
    event loop, so clients poll too, before each request (a request and
    the bits it takes back fall on one side of the close). ``on_close``
    runs once, at that moment."""

    def __init__(self, warmup_requests: int, on_close=None):
        self.phase = "warmup"
        self.warmup_requests = warmup_requests
        self.warm = asyncio.Event()
        self.closed = asyncio.Event()
        self.on_close = on_close
        self.t0 = self.t_end = self.t1 = None
        self.sent = 0
        # counts of the measured window
        self.payload_bits = 0
        self.blocks = 0
        self.finish_s: list[float] = []
        self.unsent: list[float] = []  # due times of open-loop chunks never sent

    @property
    def measuring(self) -> bool:
        return self.phase == "measure"

    def open(self, seconds: float) -> None:
        self.t0 = time.perf_counter()
        self.t_end = self.t0 + seconds
        self.phase = "measure"

    def poll(self) -> None:
        """Close the window if its time is up."""
        if self.phase == "measure" and time.perf_counter() >= self.t_end:
            self.close()

    def close(self) -> None:
        if self.phase == "drain":
            return
        was_open = self.phase == "measure"
        self.phase = "drain"
        self.t1 = time.perf_counter()
        try:
            if was_open and self.on_close is not None:
                self.on_close()
        finally:
            self.closed.set()

    def note_request(self) -> None:
        self.sent += 1
        if self.sent >= self.warmup_requests:
            self.warm.set()

    def in_window(self, t: float) -> bool:
        return self.t0 is not None and self.t0 <= t < (self.t1 if self.t1 is not None else self.t_end)

    def note_unsent(self, dues) -> None:
        """Keep the due times, among ``dues`` (ascending), that fell before
        the close: open-loop chunks due in the window that the client never
        got to send, because the system held it back until the close."""
        for due in dues:
            if due >= self.t1:
                return
            self.unsent.append(due)

    def requests(self, logs) -> list:
        """[due, sent, done] of every request due in the window (sent and
        done None where it was never sent, done None where its bits never
        all came)."""
        sent = [r for log in logs for r in log.reqs if self.in_window(r[0])]
        return sent + [[due, None, None] for due in self.unsent if self.in_window(due)]


def make_pool(mix: Mix, code: Code, cfg: dict, seed: int) -> np.ndarray:
    """The seed's pool of noisy codewords, (pool_chunks, chunk_stages, R) int8."""
    rng = np.random.default_rng([seed, 0])
    return make_codewords(rng, mix.pool_chunks, mix.chunk_stages, code, mix.ebn0_db, int(cfg["q"]))


async def run_clients(
    svc, engine, mix: Mix, code: Code, cfg: dict, seed: int, n_clients: int,
    win: Window, span, grace_s: float,
) -> list[StreamLog]:
    """Drive ``n_clients`` clients until ``win`` drains; return the log of
    every stream. A stream still waiting ``grace_s`` after the window
    closed is cancelled, and logged as unanswered."""
    S, v, D = mix.chunk_stages, code.v, int(cfg["D"])
    pool = make_pool(mix, code, cfg, seed)
    keep_every = int(mix.check["keep_every"])
    logs: list[StreamLog] = []
    t_gen = time.perf_counter()

    def deliver(log: StreamLog, bits: np.ndarray, *, final: bool) -> None:
        p0, n = log.delivered, len(bits)
        log.delivered += n
        now = time.perf_counter()
        if win.measuring:
            win.payload_bits += payload_bits_in(p0, n, S, v)
            # whole blocks from dispatches; a finish counts its last blocks
            win.blocks += (-(-log.delivered // D) - p0 // D) if final else n // D
        for c in range(p0 // S, -(-(p0 + n) // S)):
            if c in log.kept:
                lo, hi = max(p0, c * S), min(p0 + n, (c + 1) * S)
                log.kept[c][lo - c * S : hi - c * S] = bits[lo - p0 : hi - p0]
        while log.n_done < len(log.reqs) and (
            log.delivered >= (log.n_done + 1) * S or (final and log.delivered >= log.n_bits)
        ):
            log.reqs[log.n_done][2] = now
            log.n_done += 1

    async def wait_due(due: float, stream, log: StreamLog) -> None:
        """Until ``due``, take what is delivered every ``POLL_S``."""
        while (left := due - time.perf_counter()) > 0:
            await asyncio.sleep(min(left, POLL_S))
            win.poll()
            deliver(log, stream.take(), final=False)

    def draws(rng, n=None):
        """(pool index, kept for the check) of each chunk of a stream."""
        for _ in itertools.repeat(None) if n is None else range(n):
            yield int(rng.integers(mix.pool_chunks)), rng.integers(keep_every) == 0

    async def one_stream(chunks, dues) -> StreamLog:
        """Open, send, finish one stream and return its log; ``chunks`` gives
        each chunk's ``draws``, ``dues`` its due time (None: closed loop, due
        when the previous send returned)."""
        log = StreamLog(ids=[])
        logs.append(log)
        try:
            stream = svc.open(engine)
            while True:
                due = None if dues is None else next(dues)
                if due is not None:
                    await wait_due(due, stream, log)
                    if win.phase == "drain" and log.ids:
                        win.note_unsent(itertools.chain([due], dues))
                        break
                sent = time.perf_counter()
                cid, keep = next(chunks)
                if keep:
                    log.kept[len(log.ids)] = np.full(S, -1, np.int8)
                log.ids.append(cid)
                log.reqs.append([sent if due is None else due, sent, None])
                win.note_request()
                with span("bench.send"):
                    await stream.send(pool[cid])
                log.stages_sent += S
                deliver(log, stream.take(), final=False)
                if len(log.ids) == mix.chunks_per_stream:
                    break
                await asyncio.sleep(0)  # the client reads its next chunk
                win.poll()
                if win.phase == "drain":
                    if dues is not None:
                        win.note_unsent(dues)
                    break
            log.n_bits = log.stages_sent - (v if mix.finish_bits == "payload" else 0)
            t_finish = time.perf_counter()
            with span("bench.finish"):
                tail = await stream.finish(log.n_bits)
            if win.in_window(t_finish):
                win.finish_s.append(time.perf_counter() - t_finish)
            deliver(log, tail, final=True)
        except Exception as e:  # noqa: BLE001 - a failed request is counted, not fatal
            log.error = f"{type(e).__name__}: {e}"
        return log

    async def closed_client(i: int) -> None:
        rng = np.random.default_rng([seed, 1, i])
        while True:
            await asyncio.sleep(0)  # the client reads its next chunk
            win.poll()
            if win.phase == "drain":
                return
            if (await one_stream(draws(rng, mix.chunks_per_stream), None)).error is not None:
                return

    async def open_client(i: int, streams: set) -> None:
        rng = np.random.default_rng([seed, 1, i])
        offsets = due_times(mix.arrivals, np.random.default_rng([seed, 3, i]))
        dues = (t_gen + t for t in offsets)
        while True:
            first = next(dues)
            await asyncio.sleep(max(0.0, first - time.perf_counter()))
            win.poll()
            if win.phase == "drain":
                win.note_unsent(itertools.chain([first], dues))
                return

            # a stream's chunks and due times are drawn when it starts, so that
            # streams of one client that overlap in time draw as the seed says
            n = mix.chunks_per_stream
            if n is None:  # one unbounded stream takes every later due time
                chunks, chunk_dues = draws(rng), itertools.chain([first], dues)
            else:
                chunks = iter(list(draws(rng, n)))
                chunk_dues = iter([first] + [next(dues) for _ in range(n - 1)])
            task = asyncio.create_task(one_stream(chunks, chunk_dues))
            streams.add(task)
            task.add_done_callback(streams.discard)
            if n is None:
                return

    streams: set = set()
    if mix.open_loop:
        tasks = [asyncio.create_task(open_client(i, streams)) for i in range(n_clients)]
    else:
        tasks = [asyncio.create_task(closed_client(i)) for i in range(n_clients)]
    await win.closed.wait()
    t_grace = time.perf_counter() + grace_s
    _, late = await asyncio.wait(tasks, timeout=grace_s)
    pending = list(late) + list(streams)  # open-loop clients leave their streams behind
    if pending:
        _, late = await asyncio.wait(pending, timeout=max(0.0, t_grace - time.perf_counter()))
    for t in late:
        t.cancel()
    await asyncio.gather(*tasks, *pending, return_exceptions=True)
    for log in logs:
        if log.error is None and (log.n_bits is None or log.delivered < log.n_bits):
            log.error = f"no answer within {grace_s:g} s of the window's close"
    return logs
