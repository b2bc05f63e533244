"""Serving driver: streaming decode through the DecoderEngine + SessionPool.

    # one stream, one session (the PR-1 shape):
    PYTHONPATH=src python -m repro.launch.serve_decoder --code ccsds-3/4 \
        --chunk-bits 4096 --n-chunks 100 --ebn0 4.0 --backend ref

    # many concurrent streams coalesced into batched launches:
    PYTHONPATH=src python -m repro.launch.serve_decoder --streams 16 \
        --chunk-bits 1024 --n-chunks 50 --backend ref

Modeled on `repro.launch.serve`: a long-lived session object carries the
decoder state (the inter-block overlap tail + puncture phase) across chunks,
so an unbounded symbol stream decodes chunk-by-chunk — the serving shape of
the paper's multi-stream pipelining (§IV-D).

The :class:`SessionPool` is the multi-tenant layer on top: many concurrent
:class:`~repro.core.engine.DecoderSession`s register with the pool, chunks
are *fed* (buffered) per session, and :meth:`SessionPool.step` coalesces
every session's ready blocks — grouped by launch compatibility — into ONE
``pbvd_decode_blocks`` launch per group (DESIGN.md §3). Each session keeps
its own overlap tail and puncture phase; only the kernel launch is shared,
so per-session bits stay bit-exact to a solo session.
"""

from __future__ import annotations

import argparse
import time
from collections import defaultdict

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.channel import transmit
from repro.core.codespec import available_code_specs, get_code_spec
from repro.core.encoder import encode_jax, terminate
from repro.core.engine import DecoderEngine, DecoderSession
from repro.core.pbvd import PBVDConfig
from repro.launch.faults import StreamError
from repro.launch.spans import span
from repro.kernels.ops import (
    DEFAULT_TB_CHUNK,
    available_backends,
    backend_tb_chunk_sensitive,
    resolve_tb_mode,
)

__all__ = ["SessionPool", "PooledSession", "main"]


class PooledSession:
    """One stream's handle inside a :class:`SessionPool`.

    ``feed`` buffers a chunk (no launch); decoded bits arrive on the next
    :meth:`SessionPool.step` and are drained with :meth:`take`. ``finish``
    flushes the zero-padded tail exactly like ``DecoderSession.finish``.
    """

    def __init__(self, pool: "SessionPool", session: DecoderSession):
        self._pool = pool
        self._session = session
        self._queue: list[np.ndarray] = []
        self.bits_emitted = 0
        self.sid: int | None = None  # the serving layer's stream id, for spans

    def feed(self, chunk) -> None:
        """Buffer a chunk of received symbols (same wire formats as
        ``DecoderSession.decode``); decoding happens at ``pool.step()``."""
        self._session.ingest(chunk)

    def take(self) -> np.ndarray:
        """Drain every decoded bit delivered by pool steps so far."""
        if not self._queue:
            return np.zeros((0,), np.int32)
        out = np.concatenate(self._queue)
        self._queue.clear()
        return out

    def finish(self, n_bits: int | None = None) -> np.ndarray:
        """Flush the stream: any undrained step() output first, then the
        remaining blocks (zero-padded tail), trimmed so the session's total
        delivery is ``n_bits``.

        Undelivered step() output is FOLDED into the return value (an
        implicit :meth:`take`), so ``finish`` alone always accounts for every
        decoded bit — the old contract silently dropped queued bits when the
        caller skipped ``take()``. The flush launch itself is framed and
        trimmed by the same ``DecoderSession._finish_plan`` /
        ``_frame_ready`` / ``_pad_lanes`` path as ``DecoderSession.finish``,
        so pooled and solo tails are bit-identical by construction for every
        non-block-aligned ``n_bits``.
        """
        s = self._session
        n_bits, n_blocks, prior = s._finish_plan(n_bits)
        if n_blocks > s._blocks_done:
            # launch BEFORE draining the queue: a failed flush launch then
            # leaves the handle exactly as it was (the launch commits nothing
            # on failure), so the serving layer can retry finish() without
            # losing the undrained step() output
            tail = self._pool._launch([(self, n_blocks)])[0]
        else:
            tail = np.zeros((0,), np.int32)
        head = self.take()  # fold undrained step() output instead of losing it
        tail = tail[: max(0, n_bits - prior)]
        self.bits_emitted += len(tail)
        return np.concatenate([head, tail]) if len(head) else tail

    def _deliver(self, bits: np.ndarray) -> None:
        self._queue.append(bits)
        self.bits_emitted += len(bits)


class SessionPool:
    """Coalesce the ready blocks of many concurrent sessions into batched
    kernel launches.

    Sessions are grouped by *launch compatibility* — the key is
    ``(mother code, D, L, backend, start_policy, metric_mode, tb_mode,
    tb_chunk, window dtype, interpret, mesh identity)``: everything that
    shapes or parameterizes the kernel launch. The mesh identity is
    content-based — axis names, shape, device ids and the engine's
    ``block_axes`` — never ``id(mesh)``.
    Code specs that share a mother code but differ in puncturing land in the
    same group (puncturing only affects ingest, never the launch), as do
    sessions with different payload lengths or chunk cadences.

    One :meth:`step` builds, per group, a single flattened frames × blocks
    lane axis from each member's ready window (``FramedBlocks.frame_counts``
    records the per-session block counts), pads the total to the shared
    power-of-two shape budget, launches once, and scatters the per-frame
    bits back to each session — which then advances its own overlap tail
    exactly as a solo launch would have.
    """

    def __init__(self):
        self._members: list[PooledSession] = []
        # strong refs to each pooled engine's mesh for the membership's
        # lifetime: the group key describes the mesh by CONTENT (axis names,
        # shape, device ids — never ``id()``, whose reuse after GC could
        # falsely coalesce sessions on different meshes), and pinning the
        # object here guarantees no two live members' meshes can alias.
        # Keyed by the PooledSession OBJECT (identity hash): an ``id(ps)``
        # key could alias a closed-and-GC'd member's reused id onto a new
        # member, dropping or double-releasing the wrong mesh pin
        self._mesh_refs: dict[PooledSession, object] = {}
        self.launches = 0  # batched launches issued (for reporting/tests)
        # what the launches carried: real lanes and the lanes the kernels
        # ran (pow2/shard budget and lane tile), lane-stages that hold a
        # received stage and all T = D + 2L of each real lane, bytes of the
        # framed host windows sent to the device and of the bits copied back
        self.lanes_real = 0
        self.lanes_launched = 0
        self.stages_real = 0
        self.stages_launched = 0
        self.h2d_bytes = 0
        self.d2h_bytes = 0
        # on a mesh-bound engine: bytes of framed lanes placed onto the mesh,
        # and the mesh launches traced and built for these launches
        self.shard_bytes = 0
        self.mesh_builds = 0
        # fault-tolerance hooks (DESIGN.md §14): ``fault_hook(entries,
        # isolating)`` is consulted before every launch (the injection point
        # for repro.launch.faults.FaultInjector); quarantined members land in
        # ``quarantined`` as (session, StreamError) pairs for the serving
        # layer to drain
        self.fault_hook = None
        self.quarantined: list[tuple[PooledSession, StreamError]] = []

    # ---- membership ----------------------------------------------------------------
    def open(
        self,
        engine: DecoderEngine,
        *,
        interpret: bool | None = None,
        store=None,
    ) -> PooledSession:
        """Open a pooled streaming session on ``engine``.

        ``store`` is forwarded to :meth:`DecoderEngine.session` (slab-paged
        session state for the async serving layer). Pool state is mutated
        atomically: a partially failed open leaves neither a membership entry
        nor a mesh pin behind.
        """
        ps = PooledSession(self, engine.session(interpret=interpret, store=store))
        try:
            self._members.append(ps)
            if engine.mesh is not None:
                self._mesh_refs[ps] = engine.mesh
        except BaseException:
            if ps in self._members:
                self._members.remove(ps)
            self._mesh_refs.pop(ps, None)
            raise
        return ps

    def close(self, ps: PooledSession) -> None:
        """Remove a session from the pool (it keeps its buffered state).

        Idempotent: closing an already-closed (or never-opened) member is a
        no-op, and the member's mesh pin is released exactly once.
        """
        try:
            self._members.remove(ps)
        except ValueError:
            pass
        self._mesh_refs.pop(ps, None)

    def __len__(self) -> int:
        return len(self._members)

    # ---- scheduling ----------------------------------------------------------------
    def pending_blocks(self) -> int:
        """Blocks decodable right now across every member."""
        return sum(
            ps._session.ready_blocks() - ps._session._blocks_done
            for ps in self._members
        )

    def step(self, *, isolate: bool = False) -> int:
        """Decode every ready block in the pool; returns the block count.

        Sessions with no complete window are skipped; compatible sessions
        share one launch per group. A failed launch commits nothing —
        sessions only advance after their bits exist — so a plain ``step``
        that raises is safely retryable as-is.

        ``isolate=True`` switches to the quarantine protocol: a group whose
        launch raises is bisected until the culprit member(s) are isolated,
        each culprit is removed from the pool with a typed
        :class:`~repro.launch.faults.StreamError` recorded in
        ``self.quarantined``, and every healthy member's relaunch delivers
        bits identical to an undisturbed step (PBVD blocks are mutually
        independent, so batch composition never changes per-stream bits —
        the paper property that makes isolation cheap).
        """
        groups: dict[tuple, list[tuple[PooledSession, int]]] = defaultdict(list)
        for ps in self._members:
            s = ps._session
            b1 = s.ready_blocks()
            if b1 > s._blocks_done:
                groups[self._group_key(s)].append((ps, b1))
        total = 0
        for entries in groups.values():
            if isolate:
                delivered = self._launch_isolated(entries)
            else:
                outs = self._launch(entries)
                delivered = list(zip(entries, outs))
            for (ps, _), bits in delivered:
                ps._deliver(bits)
                total += len(bits) // ps._session.cfg.D
        return total

    # ---- internals -----------------------------------------------------------------
    @staticmethod
    def _group_key(s: DecoderSession) -> tuple:
        cfg = s.cfg
        q = cfg.effective_q  # narrow metric modes force/cap the quantizer
        if s._int_dtype is not None:
            dt = np.dtype(s._int_dtype).str
        elif q is not None:
            dt = "int8" if q <= 8 else "int16"
        else:
            dt = "float32"
        # the mesh enters the key by CONTENT plus the engine's lane-axis
        # binding: two engines on the same mesh but different block_axes
        # compile DIFFERENT launches and must not coalesce, and a
        # content key — unlike the old ``id(mesh)`` — can neither split
        # equal meshes built twice nor falsely merge distinct meshes whose
        # ids collide after GC (the pool additionally pins every pooled
        # mesh in ``_mesh_refs``)
        eng = s.engine
        if eng.mesh is None:
            mesh_key = None
        else:
            mesh_key = (
                tuple(eng.mesh.axis_names),
                tuple((a, int(n)) for a, n in eng.mesh.shape.items()),
                tuple(int(d.id) for d in eng.mesh.devices.flat),
                eng.block_axes,
            )
        # key on the RESOLVED tb mode so an "auto" session coalesces with
        # one that spelled the backend's preferred mode out explicitly
        tb_mode = resolve_tb_mode(cfg.backend, cfg.tb_mode)
        return (
            cfg.code,
            cfg.D,
            cfg.L,
            cfg.backend,
            cfg.start_policy,
            cfg.metric_mode,
            # each acs_impl's inert knob is dropped from the key (mirrors
            # the dispatcher's cache-key normalization), so e.g. matrix
            # sessions coalesce regardless of their butterfly radix
            cfg.acs_impl,
            cfg.acs_radix if cfg.acs_impl == "butterfly" else None,
            cfg.acs_k if cfg.acs_impl == "matrix" else None,
            tb_mode,
            # tb_chunk only parameterizes chunk-sensitive prefix launches
            # (the dispatcher normalizes it out otherwise); keying on it
            # elsewhere would only split coalescable groups
            cfg.tb_chunk
            if tb_mode == "prefix" and backend_tb_chunk_sensitive(cfg.backend)
            else None,
            dt,
            s._interpret,
            mesh_key,
        )

    def _launch(
        self,
        entries: list[tuple[PooledSession, int]],
        *,
        isolating: bool = False,
    ) -> list[np.ndarray]:
        """One batched launch for ``entries`` = [(session, decode-up-to-b1)].

        Returns each entry's decoded bits (whole blocks, forward order) and
        commits each session's overlap tail past the decoded blocks. An
        exception (from the hook or the kernel) commits NOTHING, so the
        identical launch can be rebuilt from session state.
        """
        if self.fault_hook is not None:
            self.fault_hook(entries, isolating)
        lead = entries[0][0]._session
        eng = lead.engine
        counts = [b1 - ps._session._blocks_done for ps, b1 in entries]
        n_real = sum(counts)
        lanes = eng._launched_lanes(n_real)
        args = dict(members=len(entries), lanes_real=n_real, lanes=lanes)
        if len(entries) == 1 and entries[0][0].sid is not None:
            args["sid"] = entries[0][0].sid
        with span("pbvd.launch", **args):
            with span("pbvd.frame"):
                frames, h2d, stages = [], 0, 0
                for (ps, b1), k in zip(entries, counts):
                    s = ps._session
                    w = s._frame_host(b1)
                    h2d += w.nbytes
                    stages += s._received_lane_stages(b1)
                    frames.append(s._frame_device(w, k))
                packed = jnp.concatenate(frames, axis=2) if len(frames) > 1 else frames[0]
                # the lead engine's shard-aware budget (pow2 rounded once to
                # the mesh shard count) — identical for every member, since
                # the group key includes the full mesh identity + block_axes
                packed = eng._pad_lanes(packed)
            with span("pbvd.kernel"):
                placed, builds = eng.shard_bytes, eng.mesh_builds
                bits = eng._decode_blocks(packed, tuple(counts), lead._interpret)
                self.shard_bytes += eng.shard_bytes - placed
                self.mesh_builds += eng.mesh_builds - builds
                subs, lo = [], 0
                for k in counts:  # each member's bits, queued behind the kernel
                    subs.append(jnp.transpose(bits[:, lo : lo + k]))
                    lo += k
            self.launches += 1
            self.lanes_real += n_real
            self.lanes_launched += lanes
            self.stages_real += stages
            self.stages_launched += n_real * (lead.cfg.D + 2 * lead.cfg.L)
            self.h2d_bytes += h2d
            with span("pbvd.device_wait"):  # the first copy waits for the kernel
                outs = [np.asarray(sub, dtype=np.int32).reshape(-1) for sub in subs]
            with span("pbvd.deliver"):
                for (ps, b1), out in zip(entries, outs):
                    self.d2h_bytes += out.nbytes
                    ps._session._commit(b1)
        return outs

    # ---- quarantine ----------------------------------------------------------------
    def _launch_isolated(
        self, entries: list[tuple[PooledSession, int]]
    ) -> list[tuple[tuple[PooledSession, int], np.ndarray]]:
        """Launch ``entries``, bisecting on failure to isolate culprits.

        Healthy members decode bit-exact to the full coalesced launch (block
        independence); members whose SINGLE-lane-group launch still fails are
        quarantined via :meth:`_quarantine` and excluded from the result.
        Worst case this costs O(f·log n) launches for f culprits among n
        members — each bisection level relaunches only the halves that
        contain a failure.
        """
        try:
            outs = self._launch(entries, isolating=True)
            return list(zip(entries, outs))
        except Exception as exc:  # noqa: BLE001 - classify, don't mask
            if len(entries) == 1:
                ps = entries[0][0]
                err = (
                    exc
                    if isinstance(exc, StreamError)
                    else StreamError(
                        f"stream quarantined: its lane-group reproducibly "
                        f"fails the launch ({exc!r})",
                        stream=ps,
                    )
                )
                if err.__cause__ is None and err is not exc:
                    err.__cause__ = exc
                self._quarantine(ps, err)
                return []
            mid = len(entries) // 2
            return self._launch_isolated(entries[:mid]) + self._launch_isolated(
                entries[mid:]
            )

    def _quarantine(self, ps: PooledSession, err: StreamError) -> None:
        """Remove ``ps`` from the pool and record its typed failure.

        The member's buffered session state is left intact — the serving
        layer owns the slab pages and frees them when it fails the stream's
        waiters (``AsyncDecodeService._fail_stream``).
        """
        self.close(ps)
        self.quarantined.append((ps, err))

    def drain_quarantined(self) -> list[tuple[PooledSession, StreamError]]:
        """Hand the accumulated quarantine records to the caller (and reset)."""
        out, self.quarantined = self.quarantined, []
        return out

    def repoint_engine(self, old: DecoderEngine, new: DecoderEngine) -> int:
        """Swap every member bound to engine ``old`` onto ``new`` (mesh-loss
        rescale). Members' ready-but-undecoded blocks replay on the new
        engine at the next step, bit-exact to the uninterrupted run — block
        content is host-side session state and the mesh only places lanes.
        Returns the number of members repointed.
        """
        n = 0
        for ps in self._members:
            s = ps._session
            if s.engine is old:
                s.engine = new
                if new.mesh is not None:
                    self._mesh_refs[ps] = new.mesh
                else:
                    self._mesh_refs.pop(ps, None)
                n += 1
        return n


# ---------------------------------------------------------------------------
# Driver
# ---------------------------------------------------------------------------
def _make_stream(spec, n_bits: int, ebn0: float, seed: int):
    rng = np.random.default_rng(seed)
    payload = rng.integers(0, 2, n_bits)
    coded = encode_jax(jnp.asarray(terminate(payload, spec.code)), spec.code)
    tx = spec.puncture_stream(coded) if spec.is_punctured else coded
    y = np.asarray(transmit(jax.random.PRNGKey(seed), tx, ebn0, spec.rate))
    return payload, y


def _latency_summary(lat_ms) -> str:
    """p50/p99 of a latency sample, guarded for tiny sample counts —
    ``np.percentile`` on an empty array raises, and a p99 quoted from a
    handful of chunks is noise dressed as a tail, so say so."""
    lat = np.asarray(lat_ms, np.float64)
    if lat.size == 0:
        return "no latency samples"
    out = f"p50={np.percentile(lat, 50):.1f} ms p99={np.percentile(lat, 99):.1f} ms"
    if lat.size < 20:  # p99 interpolated from < 20 samples ≈ the max
        out += f" (n={lat.size}: p99≈max)"
    return out


def _serve_status(results, quarantined: int) -> int:
    """The process exit code of a serve mode: 1 when any stream ended
    without decoded bits (a typed ``DecodeError`` in its result slot) or the
    pool quarantined a stream, else 0 — a failed launch never passes as a
    printed BER."""
    failed = [i for i, r in enumerate(results) if not isinstance(r, np.ndarray)]
    if not failed and not quarantined:
        return 0
    first = f" (stream {failed[0]}: {results[failed[0]]!r})" if failed else ""
    print(
        f"[serve_decoder] FAILED: {len(failed)} stream(s) without decoded "
        f"bits{first}; {quarantined} stream(s) quarantined"
    )
    return 1


def _serve_single(engine, spec, cfg, args) -> int:
    n_bits = args.chunk_bits * args.n_chunks
    payload, y = _make_stream(spec, n_bits, args.ebn0, args.seed)
    sess = engine.session()
    bounds = np.linspace(0, len(y), args.n_chunks + 1).astype(int)
    decoded, lat_ms = [], []
    t0 = time.perf_counter()
    for lo, hi in zip(bounds[:-1], bounds[1:]):
        t1 = time.perf_counter()
        decoded.append(sess.decode(y[lo:hi]))
        lat_ms.append((time.perf_counter() - t1) * 1e3)
    # the finish flush decodes the final (often largest) window — leaving it
    # out of lat_ms reported a p99 that omitted the worst chunk
    t1 = time.perf_counter()
    decoded.append(sess.finish(n_bits))
    lat_ms.append((time.perf_counter() - t1) * 1e3)
    dt = time.perf_counter() - t0

    bits = np.concatenate(decoded)
    ber = float(np.mean(bits != payload))
    print(
        f"[serve_decoder] {n_bits} bits in {dt*1e3:.0f} ms → {n_bits/dt/1e6:.2f} Mbps; "
        f"chunk latency {_latency_summary(lat_ms)}"
    )
    print(f"[serve_decoder] BER = {ber:.2e} ({int(ber * n_bits)} errors)")
    return _serve_status([bits], 0)


def _serve_pooled(engine, spec, cfg, args) -> int:
    n_bits = args.chunk_bits * args.n_chunks
    streams = [
        _make_stream(spec, n_bits, args.ebn0, args.seed + i)
        for i in range(args.streams)
    ]
    pool = SessionPool()
    handles = [pool.open(engine) for _ in streams]
    bounds = np.linspace(0, len(streams[0][1]), args.n_chunks + 1).astype(int)
    outs = [[] for _ in streams]
    step_ms = []
    t0 = time.perf_counter()
    for lo, hi in zip(bounds[:-1], bounds[1:]):  # one ingest round, one step
        for (_, y), h in zip(streams, handles):
            h.feed(y[lo:hi])
        t1 = time.perf_counter()
        pool.step()
        step_ms.append((time.perf_counter() - t1) * 1e3)
        for i, h in enumerate(handles):
            outs[i].append(h.take())
    for i, h in enumerate(handles):
        outs[i].append(h.finish(n_bits))
    dt = time.perf_counter() - t0

    total_bits = n_bits * args.streams
    results = [np.concatenate(o) for o in outs]
    errors = sum(int(np.sum(b != p)) for b, (p, _) in zip(results, streams))
    print(
        f"[serve_decoder] {args.streams} streams × {n_bits} bits in {dt*1e3:.0f} ms "
        f"→ aggregate {total_bits/dt/1e6:.2f} Mbps; "
        f"{pool.launches} batched launches "
        f"({args.n_chunks * args.streams} chunks fed); "
        f"step latency {_latency_summary(step_ms)}"
    )
    print(
        f"[serve_decoder] BER = {errors/total_bits:.2e} ({errors} errors "
        f"over {total_bits} bits)"
    )
    return _serve_status(results, len(pool.quarantined))


def _serve_async_durable(engine, spec, cfg, args) -> int:
    """Durable serving drill: journaled admissions, client-side delivered-bit
    persistence, optional mid-trace SIGKILL, and ``recover()`` restart.

    The client protocol per stream ``i``:

    * deliveries are drained with ``take(ack=False)``, appended to
      ``{journal_dir}/delivered-{i}.bits`` (one uint8 byte per bit),
      fsync'd, and only THEN acked — so the service's ack watermark never
      runs ahead of the durable file;
    * sending resumes from ``stream.chunks_admitted`` (the WAL-derived
      cursor), so a chunk lost in the crash gap between ``send()`` and its
      admit record is simply re-sent;
    * on ``--recover``, each file is truncated back to the recovered ack
      watermark — bytes persisted after the last durable ack are exactly
      the bits recovery will redeliver (the no-duplicate invariant).

    Returns a process exit code: 0 = every stream's delivered bits match
    the one-shot reference decode, 1 = mismatch, 3 = ``--kill-at`` was set
    but the trace completed without reaching the kill point.
    """
    import asyncio
    import os
    import signal

    from repro.launch.journal import ChunkJournal
    from repro.launch.serve_async import AsyncDecodeService
    from repro.launch.slab import SymbolSlab

    n_bits = args.chunk_bits * args.n_chunks
    streams = [
        _make_stream(spec, n_bits, args.ebn0, args.seed + i)
        for i in range(args.streams)
    ]
    cs = max(1, len(streams[0][1]) // args.n_chunks)
    chunk_lists = [
        [y[k * cs : (k + 1) * cs] for k in range(-(-len(y) // cs))]
        for _, y in streams
    ]
    slab = SymbolSlab(
        n_pages=args.slab_pages, page_stages=cfg.D + 2 * cfg.L, R=spec.code.R
    )
    journal = ChunkJournal(args.journal_dir)
    service_kwargs = dict(
        max_batch_blocks=args.max_batch_blocks,
        deadline_ms=args.deadline_ms,
        slab=slab,
        journal=journal,
        integrity_rate=args.integrity_rate,
    )
    if args.kill_at is not None:

        def _kill_hook(svc):
            if svc.dispatches >= args.kill_at:
                os.kill(os.getpid(), signal.SIGKILL)  # no cleanup: a real crash

        service_kwargs["on_dispatch"] = _kill_hook

    async def _client(i, stream):
        path = os.path.join(args.journal_dir, f"delivered-{i}.bits")
        if stream is None:  # finished before the crash; its file is complete
            return
        mode = "r+b" if args.recover and os.path.exists(path) else "wb"
        with open(path, mode) as f:
            if mode == "r+b":
                f.seek(0, os.SEEK_END)
                assert f.tell() >= stream.acked_bits, (
                    f"stream {i}: durable file shorter than ack watermark "
                    f"({f.tell()} < {stream.acked_bits})"
                )
                f.truncate(stream.acked_bits)  # un-acked tail gets redelivered
                f.seek(0, os.SEEK_END)

            def persist(bits):
                if len(bits):
                    f.write(np.asarray(bits, np.uint8).tobytes())
                    f.flush()
                    os.fsync(f.fileno())
                stream.ack()

            chunks = chunk_lists[i]
            # paced sends (unlike the ephemeral trace, deterministic spacing
            # not Poisson): the deadline dispatcher must actually run between
            # arrivals or the whole trace would flush inside finish() and a
            # --kill-at dispatch boundary would never be crossed
            gap_s = 1.0 / args.rate_chunks_per_s if args.rate_chunks_per_s else 0.0
            for k in range(stream.chunks_admitted, len(chunks)):
                await stream.send(chunks[k])
                await asyncio.sleep(gap_s)
                persist(stream.take(ack=False))
            persist(await stream.finish(n_bits))

    async def drive():
        if args.recover:
            kw = {k: v for k, v in service_kwargs.items() if k != "journal"}
            svc = AsyncDecodeService.recover(journal, engine, **kw)
        else:
            svc = AsyncDecodeService(**service_kwargs)
        async with svc:
            # sid == stream index by construction: streams open in index
            # order on the fresh run, and sids are stable across recovery
            if args.recover:
                handles = [svc.recovered_streams.get(i) for i in range(args.streams)]
            else:
                handles = [svc.open(engine) for _ in range(args.streams)]
            await asyncio.gather(*(_client(i, h) for i, h in enumerate(handles)))
            return svc.metrics()

    t0 = time.perf_counter()
    m = asyncio.run(drive())
    dt = time.perf_counter() - t0
    journal.close()
    if args.kill_at is not None:
        print(
            f"[serve_decoder] --kill-at {args.kill_at} never reached "
            f"({m['dispatches']} dispatches total)"
        )
        return 3

    bad = 0
    for i, (_, y) in enumerate(streams):
        path = os.path.join(args.journal_dir, f"delivered-{i}.bits")
        got = np.frombuffer(open(path, "rb").read(), np.uint8)
        sess = engine.session()
        ref = np.concatenate([sess.decode(y), sess.finish(n_bits)])
        if len(got) != n_bits or np.any(got != ref):
            bad += 1
            print(f"[serve_decoder] stream {i}: delivered bits != reference")
    print(
        f"[serve_decoder] durable: {args.streams} streams × {n_bits} bits in "
        f"{dt*1e3:.0f} ms ({m['dispatches']} dispatches, "
        f"{m['checkpoints']} checkpoints, journal seq {m['journal_seq']}, "
        f"integrity {m['integrity_flagged']}/{m['integrity_checked']} flagged); "
        f"{'all streams bit-exact vs reference' if not bad else f'{bad} stream(s) MISMATCHED'}"
    )
    return 0 if bad == 0 else 1


def _serve_async(engine, spec, cfg, args) -> int:
    """Drive the asyncio service under a Poisson arrival trace (the
    serving-layer shape: admission → paged slabs → deadline dispatch)."""
    import asyncio

    from repro.launch.serve_async import run_poisson_trace
    from repro.launch.slab import SymbolSlab

    n_bits = args.chunk_bits * args.n_chunks
    streams = [
        _make_stream(spec, n_bits, args.ebn0, args.seed + i)
        for i in range(args.streams)
    ]
    ys = [y for _, y in streams]
    chunk_symbols = max(1, len(ys[0]) // args.n_chunks)
    slab = SymbolSlab(
        n_pages=args.slab_pages,
        page_stages=cfg.D + 2 * cfg.L,
        R=spec.code.R,
    )
    t0 = time.perf_counter()
    bits, report = asyncio.run(
        run_poisson_trace(
            engine,
            ys,
            [n_bits] * len(ys),
            chunk_symbols=chunk_symbols,
            rate_chunks_per_s=args.rate_chunks_per_s,
            seed=args.seed,
            slab=slab,
            service_kwargs=dict(
                max_batch_blocks=args.max_batch_blocks,
                deadline_ms=args.deadline_ms,
            ),
        )
    )
    dt = time.perf_counter() - t0
    total_bits = n_bits * args.streams
    errors = sum(
        int(np.sum(b != p))
        for b, (p, _) in zip(bits, streams)
        if isinstance(b, np.ndarray)
    )
    print(
        f"[serve_decoder] async: {args.streams} streams × {n_bits} bits in "
        f"{dt*1e3:.0f} ms → sustained "
        f"{report['sustained_mbps'] if report['sustained_mbps'] is not None else float('nan'):.2f} Mbps "
        f"({report['dispatches']} dispatches, {report['launches']} launches, "
        f"slab high-water {report['slab_pages_high_water']} pages); "
        f"chunk latency p50={report['p50_ms']:.1f} ms p99={report['p99_ms']:.1f} ms"
    )
    print(
        f"[serve_decoder] BER = {errors/total_bits:.2e} ({errors} errors "
        f"over {total_bits} bits)"
    )
    return _serve_status(bits, report["quarantined_streams"])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--code", default="ccsds", choices=available_code_specs())
    ap.add_argument("--backend", default="ref", choices=available_backends())
    ap.add_argument("--d", type=int, default=512, help="decode block length D")
    ap.add_argument("--l", type=int, default=42, help="traceback depth L")
    ap.add_argument("--q", type=int, default=8, help="quantization bits (0 = float32)")
    ap.add_argument(
        "--metric-mode",
        default="f32",
        choices=["f32", "i16", "i8"],
        help="path-metric pipeline (narrow modes re-cap q to the saturation budget)",
    )
    ap.add_argument(
        "--tb-mode",
        default="auto",
        choices=["auto", "serial", "prefix"],
        help="traceback algorithm (auto = the backend's measured-fastest; "
        "prefix = chunked survivor-map composition)",
    )
    ap.add_argument(
        "--tb-chunk",
        type=int,
        default=DEFAULT_TB_CHUNK,
        help="prefix traceback chunk size (stages composed per chunk map)",
    )
    ap.add_argument(
        "--acs-radix",
        type=int,
        default=2,
        choices=[2, 4],
        help="forward-ACS radix (4 = stage-fused two-stage steps, bit-exact)",
    )
    ap.add_argument(
        "--acs-impl",
        default="butterfly",
        choices=["butterfly", "matrix"],
        help="forward-pass formulation (matrix = k-stage (min,+) tropical "
        "matmul steps, bit-exact)",
    )
    ap.add_argument(
        "--acs-k",
        type=int,
        default=2,
        help="matrix-ACS fusion depth k (stages per tropical matmul step)",
    )
    ap.add_argument(
        "--mesh",
        default=None,
        metavar="AXIS=N[,AXIS=N]",
        help="shard the lane (parallel-block) axis over a device mesh, e.g. "
        "data=8 (CPU rehearsal: XLA_FLAGS=--xla_force_host_platform_"
        "device_count=8; multi-host: the JAX_COORDINATOR_ADDRESS/"
        "JAX_NUM_PROCESSES/JAX_PROCESS_ID env triplet, see repro.launch.mesh)",
    )
    ap.add_argument("--chunk-bits", type=int, default=4096, help="payload bits per chunk")
    ap.add_argument("--n-chunks", type=int, default=100)
    ap.add_argument(
        "--streams",
        type=int,
        default=1,
        help="concurrent streams; >1 coalesces sessions through a SessionPool",
    )
    ap.add_argument("--ebn0", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument(
        "--serve-async",
        action="store_true",
        help="drive the asyncio serving layer (repro.launch.serve_async) "
        "under a Poisson arrival trace instead of the synchronous loop",
    )
    ap.add_argument(
        "--deadline-ms",
        type=float,
        default=5.0,
        help="async dispatch deadline: max age of the oldest undispatched "
        "chunk before a coalesced step fires anyway",
    )
    ap.add_argument(
        "--max-batch-blocks",
        type=int,
        default=32,
        help="async dispatch size trigger: ready blocks that fire a step",
    )
    ap.add_argument(
        "--slab-pages",
        type=int,
        default=1024,
        help="session-state slab capacity (pages of D+2L stages each)",
    )
    ap.add_argument(
        "--rate-chunks-per-s",
        type=float,
        default=1000.0,
        help="per-stream Poisson chunk arrival rate for --serve-async",
    )
    ap.add_argument(
        "--journal-dir",
        default=None,
        help="with --serve-async: write-ahead journal admitted chunks + "
        "checkpoint session state under this directory, and persist each "
        "stream's delivered bits to delivered-<i>.bits (crash-safe serving, "
        "DESIGN.md §15)",
    )
    ap.add_argument(
        "--integrity-rate",
        type=float,
        default=0.0,
        help="fraction of deliveries screened by the re-encode integrity "
        "sentinel (0 = off; 1 = every delivery); flagged streams quarantine "
        "with IntegrityError",
    )
    ap.add_argument(
        "--kill-at",
        type=int,
        default=None,
        help="with --journal-dir: SIGKILL this process the moment the "
        "dispatch counter reaches N (crash drill; exit 3 if never reached)",
    )
    ap.add_argument(
        "--recover",
        action="store_true",
        help="with --journal-dir: rebuild the service from the journal "
        "(checkpoint + replay) instead of starting fresh, resume the trace, "
        "and verify delivered bits against the one-shot reference",
    )
    args = ap.parse_args()
    if (args.kill_at is not None or args.recover) and args.journal_dir is None:
        ap.error("--kill-at/--recover require --journal-dir")
    if args.journal_dir is not None and not args.serve_async:
        ap.error("--journal-dir requires --serve-async")

    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_decode_mesh, maybe_init_distributed

    mesh = None
    if args.mesh:
        maybe_init_distributed()  # no-op unless the multi-host env triplet is set
        mesh = make_decode_mesh(args.mesh)
    # after the distributed init (the helper reads the backend), before any compile
    enable_compile_cache()

    spec = get_code_spec(args.code)
    cfg = PBVDConfig(
        spec=spec,
        D=args.d,
        L=args.l,
        q=args.q or None,
        backend=args.backend,
        metric_mode=args.metric_mode,
        tb_mode=args.tb_mode,
        tb_chunk=args.tb_chunk,
        acs_radix=args.acs_radix,
        acs_impl=args.acs_impl,
        acs_k=args.acs_k,
    )
    engine = DecoderEngine(
        cfg, mesh=mesh, block_axes=None if mesh is not None else ("data",)
    )
    if mesh is not None:
        print(
            f"[serve_decoder] mesh {dict(mesh.shape)} over {mesh.devices.size} "
            f"device(s); lane axis on {engine.block_axes} "
            f"({engine.n_shards} shards, shard_map dispatch)"
        )
    print(
        f"[serve_decoder] {spec.name}: K={spec.code.K}, rate={spec.rate:.3f}, "
        f"D={cfg.D}, L={cfg.L}, q={cfg.effective_q}, backend={cfg.backend}, "
        f"metric_mode={cfg.metric_mode}, tb_mode={cfg.tb_mode} "
        f"(→ {resolve_tb_mode(cfg.backend, cfg.tb_mode)}), "
        f"acs_impl={cfg.acs_impl}"
        f"{f' (k={cfg.acs_k})' if cfg.acs_impl == 'matrix' else f', acs_radix={cfg.acs_radix}'}; "
        f"{args.streams} stream(s) × {args.chunk_bits * args.n_chunks} payload bits "
        f"in {args.n_chunks} chunks at Eb/N0={args.ebn0} dB"
    )
    if args.serve_async and args.journal_dir is not None:
        serve = _serve_async_durable
    elif args.serve_async:
        serve = _serve_async
    elif args.streams > 1:
        serve = _serve_pooled
    else:
        serve = _serve_single
    raise SystemExit(serve(engine, spec, cfg, args))


if __name__ == "__main__":
    main()
