"""The unified decode path: ``DecoderEngine`` + stateful streaming sessions.

One engine method covers what used to be three copy-pasted pipelines
(``decode_stream``, ``decode_stream_sharded`` and the per-backend branches in
``kernels/ops.py``):

* **codes** come from a :class:`~repro.core.codespec.CodeSpec` (mother code +
  optional puncturing) — punctured streams are depunctured with BM-neutral
  zeros and flow through the unchanged framing/kernels;
* **backends** are looked up in the kernel registry
  (:mod:`repro.kernels.registry`) — ``ref``/``pallas``/``fused`` all receive
  the same ``FramedBlocks`` contract;
* **sharding** is a constructor argument (``mesh`` + ``block_axes``), not a
  separate function: the parallel-block axis is sharded across the mesh with
  zero cross-device communication (the PBVD property that makes the decoder
  scale linearly in chips);
* **streaming** is :meth:`DecoderEngine.session`: a session carries the
  inter-block overlap tail (up to ``D + L`` received stages, ``2L`` of which
  overlap the neighbouring blocks) across successive ``decode()`` calls so an
  unbounded stream decodes chunk-by-chunk, bit-exact to the one-shot decode;
* **batching across streams** is :meth:`DecoderEngine.decode_batch`: the
  framed blocks of many independent streams are concatenated along the lane
  axis (a flattened frames × blocks packing, ``FramedBlocks.frame_counts``)
  and decoded in ONE kernel launch — blocks are mutually independent, so the
  per-frame bits are bit-identical to sequential ``decode()`` calls while
  short frames stop wasting the 128-lane tile.

See DESIGN.md §1/§3 for the architecture and the streaming invariants.
"""

from __future__ import annotations

import math

import numpy as np

import jax
import jax.numpy as jnp

from repro.kernels.ops import check_mesh_launch, launched_lanes, pbvd_decode_blocks
from repro.launch.faults import SymbolError, check_finite_symbols
from repro.launch.spans import span
from .codespec import CodeSpec

__all__ = ["ArraySessionStore", "DecoderEngine", "DecoderSession"]


class ArraySessionStore:
    """Default storage for a session's buffered soft symbols: one contiguous
    per-session ndarray.

    A *session store* is the seam that lets a serving layer swap the
    per-session Python buffer for shared, slab-allocated pages
    (:class:`repro.launch.slab.PagedSessionStore`) without the session
    noticing — :class:`DecoderSession` only ever touches its buffer through
    this interface. The contract (all stage indices are LOCAL, i.e. relative
    to the store's first held stage):

    * ``len(store)`` — stages currently held;
    * ``append(rows)`` — append ``(n, R)`` float-convertible symbol rows;
    * ``grow(n)`` — append ``n`` all-zero stages (punctured ingest scatters
      into them afterwards);
    * ``scatter(stage_idx, sym_idx, values)`` — elementwise write;
    * ``read(lo, n)`` — up to ``n`` rows from ``lo`` (short at the tail,
      never padded: framing owns the zero-padding);
    * ``drop_prefix(n)`` — discard the first ``n`` stages (committed blocks);
    * ``close()`` — release backing storage (idempotent);
    * ``snapshot()`` — a picklable dict of the held rows (logical content
      only — paged stores do NOT record page ids, so a snapshot restores
      into any store, slab-backed or not);
    * ``restore(snap)`` — load a snapshot into an EMPTY store.

    ``snapshot``/``restore`` are the durability seam (DESIGN.md §15): the
    serving layer's checkpoint writer snapshots every live session and the
    crash-recovery path restores them into freshly allocated stores.
    """

    def __init__(self, R: int):
        self._a = np.zeros((0, R), np.float32)

    def __len__(self) -> int:
        return len(self._a)

    def append(self, rows: np.ndarray) -> None:
        self._a = np.concatenate([self._a, rows.astype(np.float32)])

    def grow(self, n: int) -> None:
        if n > 0:
            self._a = np.concatenate(
                [self._a, np.zeros((n, self._a.shape[1]), np.float32)]
            )

    def scatter(self, stage_idx, sym_idx, values) -> None:
        self._a[stage_idx, sym_idx] = values

    def read(self, lo: int, n: int) -> np.ndarray:
        return self._a[lo : lo + n]

    def drop_prefix(self, n: int) -> None:
        if n > 0:
            self._a = self._a[n:]

    def close(self) -> None:
        self._a = np.zeros((0, self._a.shape[1]), np.float32)

    def snapshot(self) -> dict:
        return {"rows": self._a.copy()}

    def restore(self, snap: dict) -> None:
        if len(self._a):
            raise ValueError("restore() target store is not empty")
        self._a = np.asarray(snap["rows"], np.float32).copy()


def _pow2_at_least(n: int) -> int:
    """Smallest power of two ≥ n (the shared jit shape budget)."""
    return 1 << max(0, n - 1).bit_length()


def _covered_lane_stages(lo: int, k: int, D: int, T: int, a: int, b: int) -> int:
    """Stages of ``[a, b)`` summed over the ``k`` lanes ``[lo + jD, lo + jD + T)``.

    Lanes wholly inside ``[a, b)`` count ``T`` each; only the lanes cut by
    ``a`` or ``b`` (at most about ``2 + (T - D) / D`` of them at either end)
    are measured one by one, so the cost does not grow with ``k``.
    """
    if k <= 0 or b <= a:
        return 0
    j0 = min(k, max(0, -(-(a - lo) // D)))  # first lane starting at or after a
    j1 = max(j0, min(k, (b - T - lo) // D + 1))  # first lane ending after b
    total = (j1 - j0) * T
    for j in (*range(j0), *range(j1, k)):
        s = lo + j * D
        total += max(0, min(s + T, b) - max(s, a))
    return total


class DecoderEngine:
    """Single entry point for PBVD decoding.

    Parameters
    ----------
    cfg: PBVDConfig — decode geometry (D, L), quantization, backend, code/spec.
    mesh: optional ``jax.sharding.Mesh``; when given, the parallel-block axis
        of every decode is sharded over ``block_axes`` (e.g. ``("pod","data")``
        on the production mesh). Blocks never interact, so the sharded launch
        is collective-free — fleet throughput is N chips of lane throughput.
    block_axes: mesh axes carrying the lane (flattened frames × blocks) axis.
        ``None`` resolves the ``"blocks"`` logical-axis rule of
        :mod:`repro.sharding.rules` against the mesh (``("pod", "data")``
        on a multi-pod mesh, ``("data",)`` otherwise). A mesh-bound launch
        runs under :func:`repro.sharding.smap.lane_shard_map`, each shard
        decoding its local lanes — the Pallas kernels cannot be partitioned
        automatically, and the lanes need no partitioning beyond the split.
        The binding is validated eagerly at construction
        (:func:`repro.kernels.ops.check_mesh_launch`).
    """

    def __init__(
        self,
        cfg=None,
        *,
        mesh=None,
        block_axes: tuple[str, ...] | None = ("data",),
    ):
        from .pbvd import PBVDConfig  # local import: pbvd re-exports the engine

        self.cfg = cfg if cfg is not None else PBVDConfig()
        self.spec: CodeSpec = self.cfg.codespec
        self.mesh = mesh
        if block_axes is None:
            if mesh is None:
                block_axes = ("data",)
            else:
                from repro.sharding.rules import block_mesh_axes

                block_axes = block_mesh_axes(mesh)
        self.block_axes = tuple(block_axes)
        # eager: a bad mesh binding fails when the engine is BUILT, with a
        # clear error naming the axis/backend — never inside a pooled launch
        self.n_shards = (
            check_mesh_launch(mesh, self.block_axes, self.cfg.backend)
            if mesh is not None
            else 1
        )
        # what the mesh launch has cost so far: launches traced and built
        # (misses of its jit cache) and bytes of framed lanes placed on the mesh
        self.mesh_builds = 0
        self.shard_bytes = 0
        if mesh is not None:
            from repro.sharding.smap import lane_shard_map, lane_sharding

            self._lane_sharding = lane_sharding(mesh, self.block_axes, 3)

            def build(blocks, code, launch):
                # runs once per trace: a new lane shape, code or launch knob
                self.mesh_builds += 1
                kw = dict(launch)
                return lane_shard_map(
                    lambda y_local: pbvd_decode_blocks(y_local, code, **kw),
                    mesh=mesh,
                    axes=self.block_axes,
                    in_rank=3,
                    out_rank=2,
                )(blocks)

            self._mesh_launch = jax.jit(build, static_argnums=(1, 2))

    # ------------------------------------------------------------------ one-shot
    def decode(self, y, n_bits: int | None = None, *, interpret: bool | None = None):
        """Decode a soft-symbol stream → (n_bits,) int32 bits.

        ``y`` is either a (n_stages, R) full-rate stream or, for a punctured
        spec, a 1-D stream of received (punctured) symbols, which is
        depunctured with BM-neutral zeros first. ``n_bits`` defaults to the
        number of full-rate stages in the stream.
        """
        blocks, n_blocks, n_bits = self._frame_one(y, n_bits)
        if self.mesh is not None:
            # mesh launches round lanes to the shard-aware budget once, here;
            # pad lanes are zero-symbol blocks beyond frame_counts, trimmed
            blocks = self._pad_lanes(blocks)
        bits = self._decode_blocks(blocks, (n_blocks,), interpret)  # (D, n_blocks)
        return jnp.transpose(bits).reshape(-1)[:n_bits]

    # ------------------------------------------------------------------ batched
    def decode_batch(
        self,
        ys,
        n_bits_list=None,
        *,
        interpret: bool | None = None,
    ) -> list:
        """Decode many independent streams in ONE kernel launch.

        ``ys`` is a sequence of streams, each in any form :meth:`decode`
        accepts; ``n_bits_list`` gives each stream's payload length (or
        ``None`` entries / ``None`` for the stage-count default). Every
        stream is framed exactly like :meth:`decode`, the per-frame block
        axes are concatenated into one flattened frames × blocks lane axis
        (padded to the shared power-of-two shape budget so recurring batch
        geometries reuse jit shapes), and the single launch's output is
        unpacked and trimmed per frame.

        Returns a list of (n_bits_i,) int32 arrays, bit-identical per frame
        to sequential ``decode()`` calls — parallel blocks never interact,
        and pad lanes are zero-symbol blocks the backends trim.
        """
        ys = list(ys)
        if not ys:
            return []
        if n_bits_list is None:
            n_bits_list = [None] * len(ys)
        if len(n_bits_list) != len(ys):
            raise ValueError(
                f"n_bits_list has {len(n_bits_list)} entries for {len(ys)} streams"
            )
        uniform = self._frame_uniform(ys, n_bits_list)
        if uniform is not None:
            packed, frame_counts, bit_counts = uniform
        else:
            framed = [self._frame_one(y, nb) for y, nb in zip(ys, n_bits_list)]
            frame_counts = tuple(k for _, k, _ in framed)
            bit_counts = tuple(nb for _, _, nb in framed)
            packed = jnp.concatenate([b for b, _, _ in framed], axis=2)
        packed = self._pad_lanes(packed)
        bits = self._decode_blocks(packed, frame_counts, interpret)  # (D, total)
        if uniform is not None:  # equal frames: one reshape, not S slices
            S, k, n_bits = len(ys), frame_counts[0], bit_counts[0]
            rows = jnp.transpose(bits.reshape(-1, S, k), (1, 2, 0))
            return list(rows.reshape(S, -1)[:, :n_bits])
        out, lo = [], 0
        for k, n_bits in zip(frame_counts, bit_counts):
            out.append(jnp.transpose(bits[:, lo : lo + k]).reshape(-1)[:n_bits])
            lo += k
        return out

    # ------------------------------------------------------------------ streaming
    def session(
        self, *, interpret: bool | None = None, store=None
    ) -> "DecoderSession":
        """Open a stateful streaming session (see :class:`DecoderSession`).

        ``store`` swaps the session's symbol buffer for an alternative
        :class:`ArraySessionStore`-shaped backend — e.g. a paged slab view
        (:class:`repro.launch.slab.PagedSessionStore`) so millions of
        short-lived streams share one allocation instead of churning
        per-session ndarrays.
        """
        return DecoderSession(self, interpret=interpret, store=store)

    # ------------------------------------------------------------------ internals
    def _lane_budget(self, n: int) -> int:
        """Shared jit lane-shape budget for ``n`` real lanes.

        The power-of-two budget rounded ONCE to the shard count —
        ``lcm(pow2_at_least(n), n_shards)`` — so a mesh-bound launch is both
        evenly shardable over ``block_axes`` and drawn from the same bounded
        shape set as the unsharded path (a post-hoc "pad to a multiple of
        n_shards" after the pow2 pad would mint a fresh shape per fleet size
        for any non-power-of-two shard count and recompile unboundedly under
        streaming). Without a mesh this IS ``_pow2_at_least``.
        """
        budget = _pow2_at_least(n)
        s = self.n_shards
        return budget * s // math.gcd(budget, s)

    def _launched_lanes(self, n: int) -> int:
        """Lanes the kernels run for ``n`` real lanes: the lane budget, each
        shard's share rounded up to the backend's lane tile by the same
        :func:`~repro.kernels.ops.launched_lanes` the backend pads with."""
        per_shard = self._lane_budget(n) // self.n_shards
        return self.n_shards * launched_lanes(self.cfg.backend, per_shard)

    def _pad_lanes(self, blocks):
        """Pad the lane axis to :meth:`_lane_budget` with zero-symbol blocks."""
        total = blocks.shape[2]
        budget = self._lane_budget(total)
        if budget > total:
            blocks = jnp.pad(blocks, ((0, 0), (0, 0), (0, budget - total)))
        return blocks

    def _frame_one(self, y, n_bits: int | None):
        """Depuncture, quantize and frame one stream → (blocks, n_blocks, n_bits)."""
        from .pbvd import frame_stream

        y = self._to_full_rate(y)
        # reject NaN/Inf before framing: a non-finite symbol would corrupt
        # the path metrics of every lane coalesced into the launch, and the
        # f32 metric path never passes through quantize_soft's own check
        check_finite_symbols(y, "DecoderEngine.decode")
        if n_bits is None:
            n_bits = int(y.shape[0])
        cfg = self.cfg
        n_blocks = -(-n_bits // cfg.D)
        if cfg.effective_q is not None and not jnp.issubdtype(y.dtype, jnp.integer):
            y = cfg.quantize(y)  # already-integer inputs are pre-quantized
        return frame_stream(y, cfg.D, cfg.L, n_blocks), n_blocks, n_bits

    def _frame_uniform(self, ys, n_bits_list):
        """Fast path for same-shape stream fleets (the serving common case).

        Stacks the streams, quantizes once, and vmaps the one-stream
        ``frame_stream`` over the fleet — the same framing code path as
        ``decode()``, but O(1) kernel dispatches instead of O(n_streams).
        Returns ``None`` when streams differ in shape/dtype/length (the
        general path handles those).
        """
        from .pbvd import frame_stream

        if len(ys) < 2:
            return None
        shapes = {tuple(np.shape(y)) for y in ys}
        dtypes = {np.dtype(getattr(y, "dtype", np.float64)) for y in ys}
        if len(shapes) != 1 or len(dtypes) != 1 or len(set(n_bits_list)) != 1:
            return None
        for i, y in enumerate(ys):
            check_finite_symbols(y, f"DecoderEngine.decode_batch (stream {i})")
        y0 = jnp.stack([self._to_full_rate(jnp.asarray(y)) for y in ys])  # (S, n, R)
        S, n_sym, R = y0.shape
        n_bits = n_bits_list[0] if n_bits_list[0] is not None else n_sym
        cfg = self.cfg
        k = -(-n_bits // cfg.D)
        if cfg.effective_q is not None and not jnp.issubdtype(y0.dtype, jnp.integer):
            y0 = cfg.quantize(y0)
        blocks = jax.vmap(
            lambda s: frame_stream(s, cfg.D, cfg.L, k)
        )(y0)  # (S, T, R, k)
        T = cfg.D + 2 * cfg.L
        packed = jnp.transpose(blocks, (1, 2, 0, 3)).reshape(T, R, S * k)
        return packed, (k,) * S, (n_bits,) * S

    def _to_full_rate(self, y):
        if y.ndim == 1:
            if not self.spec.is_punctured:
                raise SymbolError(
                    "1-D symbol stream given but the code spec is unpunctured; "
                    "pass (n_stages, R) soft symbols"
                )
            return self.spec.depuncture_stream(jnp.asarray(y))
        if y.shape[-1] != self.spec.code.R:
            raise SymbolError(f"stream rank {y.shape[-1]} != code R {self.spec.code.R}")
        return y

    def _decode_blocks(
        self, blocks, frame_counts: tuple[int, ...], interpret: bool | None
    ):
        """(T, R, B) framed symbols → (D, sum(frame_counts)) bits.

        ``frame_counts`` is the per-frame real-block layout along the lane
        axis (one entry for plain decodes); lanes beyond the real blocks are
        padding the backend trims. With a mesh bound, the lane axis arrives
        pre-padded to :meth:`_lane_budget` (every caller rounds once, before
        launch), is placed on the mesh split over ``block_axes`` (span
        ``pbvd.shard``, counted in ``shard_bytes``), and runs through the
        engine's one jitted ``shard_map`` launch, traced and built once per
        lane shape (``mesh_builds``) — collective-free, since blocks never
        interact.
        """
        cfg = self.cfg
        launch_kwargs = dict(
            decode_start=cfg.L,
            n_decode=cfg.D,
            start_policy=cfg.start_policy,
            backend=cfg.backend,
            interpret=interpret,
            metric_mode=cfg.metric_mode,
            tb_mode=cfg.tb_mode,
            tb_chunk=cfg.tb_chunk,
            acs_radix=cfg.acs_radix,
            acs_impl=cfg.acs_impl,
            acs_k=cfg.acs_k,
        )
        if self.mesh is None:
            return pbvd_decode_blocks(
                blocks, self.spec.code, frame_counts=frame_counts, **launch_kwargs
            )

        B = blocks.shape[2]
        if B % self.n_shards:
            # internal invariant, not a user error: decode/decode_batch/
            # sessions/SessionPool all round lanes via _lane_budget first
            raise ValueError(
                f"lane axis {B} not divisible into {self.n_shards} shards; "
                f"callers must pad to _lane_budget before launch"
            )
        # the jitted launch refuses lanes committed to one device when its
        # mapped axis spans several, so the lanes are placed here, in view
        with span("pbvd.shard", shards=self.n_shards, lanes=B):
            blocks = jax.device_put(blocks, self._lane_sharding)
        self.shard_bytes += blocks.size * blocks.dtype.itemsize
        # each shard decodes its B/n_shards local lanes independently;
        # per-shard outputs must be uniform in shape, so the pad-lane trim
        # happens ONCE on the stitched result (frame_counts stays a host-side
        # concept — the mapped body decodes every local lane)
        launch = tuple(sorted(launch_kwargs.items()))
        bits = self._mesh_launch(blocks, self.spec.code, launch)
        return bits[:, : sum(frame_counts)]


class DecoderSession:
    """Chunk-by-chunk decoding of an unbounded stream.

    The session buffers received symbols (depuncturing incrementally for
    punctured specs) and decodes a parallel block as soon as its full window
    ``[bD - L, bD + D + L)`` is available — exactly the window the one-shot
    framing would build, so the concatenation of all ``decode()`` outputs plus
    ``finish()`` is bit-identical to ``engine.decode`` on the whole stream.

    The carried state between calls is the overlap tail (at most ``D + L``
    stages of soft symbols), the puncture phase, and the block counter.

    Internally the launch is split into three phases so a
    :class:`~repro.launch.serve_decoder.SessionPool` can pack the ready
    blocks of many sessions into one launch: :meth:`ready_blocks` (how far
    the stream can decode), :meth:`_frame_ready` (build the framed window,
    no launch), and :meth:`_commit` (advance the block counter, trim the
    buffer). ``decode()``/``finish()`` compose them with a solo launch.
    """

    def __init__(
        self,
        engine: DecoderEngine,
        *,
        interpret: bool | None = None,
        store=None,
    ):
        self.engine = engine
        self.cfg = engine.cfg
        self.spec = engine.spec
        self._interpret = interpret
        # the buffered-symbol storage backend (see ArraySessionStore for the
        # contract); a serving layer passes a slab-paged store instead
        self._store = store if store is not None else ArraySessionStore(self.spec.code.R)
        self._base = 0  # global stage index of the store's first held stage
        self._blocks_done = 0
        self._kept_seen = 0  # punctured symbols consumed (puncture phase)
        self._int_dtype = None  # set when chunks arrive pre-quantized (integer)
        self._started = False
        self.bits_emitted = 0

    # ---- public API ----------------------------------------------------------------
    def decode(self, chunk) -> np.ndarray:
        """Feed a chunk of received symbols; return newly decodable bits.

        ``chunk`` is (n, R) full-rate soft symbols for unpunctured specs, or
        a 1-D punctured symbol stream for punctured specs (the wire format —
        full-rate chunks would desynchronize the carried puncture phase).
        Integer chunks are treated as pre-quantized (like ``engine.decode``)
        and must not be mixed with float chunks. Returns an int32 array
        (possibly empty): ``D`` bits per parallel block whose window is now
        complete.
        """
        self.ingest(chunk)
        out = self._decode_upto(self.ready_blocks())
        self.bits_emitted += len(out)
        return out

    def finish(self, n_bits: int | None = None) -> np.ndarray:
        """Flush the stream: decode the remaining blocks (zero-padded tail).

        ``n_bits`` is the total payload length of the stream (defaults to the
        number of full-rate stages received); the returned tail makes the
        session's concatenated output equal ``engine.decode(y, n_bits)``.
        """
        n_bits, n_blocks, prior = self._finish_plan(n_bits)
        out = self._decode_upto(n_blocks)
        out = out[: max(0, n_bits - prior)]
        self.bits_emitted += len(out)
        return out

    def close(self) -> None:
        """Release the session's buffered-symbol storage (idempotent).

        Required for slab-backed stores, whose pages return to the shared
        free-list here; a no-op-ish convenience for the default store.
        """
        self._store.close()

    def ingest(self, chunk) -> None:
        """Buffer a chunk without decoding (used by pooled sessions)."""
        self._ingest(np.asarray(chunk))

    def snapshot(self) -> dict:
        """Picklable session state: the buffered-symbol window plus the
        scalars that position it in the stream (overlap base, block counter,
        puncture phase, quantization dtype).  Restoring the snapshot into a
        fresh session continues the stream bit-exact — the checkpoint half
        of the serving layer's crash-recovery contract (DESIGN.md §15)."""
        return dict(
            store=self._store.snapshot(),
            base=self._base,
            blocks_done=self._blocks_done,
            kept_seen=self._kept_seen,
            int_dtype=(
                np.dtype(self._int_dtype).str if self._int_dtype is not None else None
            ),
            started=self._started,
            bits_emitted=self.bits_emitted,
        )

    def restore(self, snap: dict) -> None:
        """Load a :meth:`snapshot` into this (freshly created) session."""
        self._store.restore(snap["store"])
        self._base = int(snap["base"])
        self._blocks_done = int(snap["blocks_done"])
        self._kept_seen = int(snap["kept_seen"])
        self._int_dtype = (
            np.dtype(snap["int_dtype"]) if snap["int_dtype"] is not None else None
        )
        self._started = bool(snap["started"])
        self.bits_emitted = int(snap["bits_emitted"])

    def ready_blocks(self) -> int:
        """Highest block index b1 such that blocks [0, b1) are decodable now."""
        D, L = self.cfg.D, self.cfg.L
        return max(self._blocks_done, (self._stages_complete() - L) // D)

    # ---- internals -----------------------------------------------------------------
    def _finish_plan(self, n_bits: int | None) -> tuple[int, int, int]:
        """The flush arithmetic shared by every finish path.

        Returns ``(n_bits, n_blocks, prior)``: the resolved payload length,
        the total block count to decode, and the bits already covered by
        committed blocks. :meth:`finish` and ``PooledSession.finish`` both
        trim their flush launch with exactly this plan, which is what keeps
        the solo and pooled tails bit-identical by construction for every
        non-block-aligned ``n_bits``.
        """
        D = self.cfg.D
        if n_bits is None:
            n_bits = self._base + len(self._store)
        return n_bits, -(-n_bits // D), self._blocks_done * D

    def _stages_complete(self) -> int:
        """Stages for which every (unpunctured) symbol has been received."""
        if not self.spec.is_punctured:
            return self._base + len(self._store)
        next_slot = int(self.spec.kept_slot_indices(self._kept_seen, 1)[0])
        return next_slot // self.spec.code.R

    def _ingest(self, chunk: np.ndarray) -> None:
        R = self.spec.code.R
        if chunk.size:
            # validate BEFORE buffering: a rejected chunk must leave the
            # session state untouched so the stream (or its quarantine) never
            # sees a half-ingested chunk
            check_finite_symbols(chunk, "session send()")
            # pre-quantized (integer) streams skip the session's quantization,
            # mirroring engine.decode; mixing dtypes would corrupt the buffer
            is_int = np.issubdtype(chunk.dtype, np.integer)
            if not self._started:
                self._int_dtype = chunk.dtype if is_int else None
                self._started = True
            elif is_int != (self._int_dtype is not None):
                raise SymbolError(
                    "cannot mix integer (pre-quantized) and float chunks "
                    "within one session"
                )
        if self.spec.is_punctured:
            if chunk.ndim != 1:
                # a punctured wire format is the 1-D kept-symbol stream; a
                # full-rate chunk would desynchronize the puncture phase
                raise SymbolError(
                    f"punctured sessions take 1-D punctured symbol chunks, "
                    f"got shape {chunk.shape}"
                )
            n = len(chunk)
            if n == 0:
                return
            slots = self.spec.kept_slot_indices(self._kept_seen, n)
            need_stages = int(slots[-1]) // R + 1
            grow = need_stages - (self._base + len(self._store))
            if grow > 0:
                self._store.grow(grow)
            local = slots - self._base * R
            self._store.scatter(local // R, local % R, chunk)
            self._kept_seen += n
        elif chunk.ndim == 2 and chunk.shape[1] == R:
            self._store.append(chunk)
        else:
            raise SymbolError(
                f"chunk shape {chunk.shape} invalid for code R={R} "
                f"(punctured={self.spec.is_punctured})"
            )

    def _frame_ready(self, b1: int) -> jnp.ndarray:
        """Frame blocks [blocks_done, b1) → (T, R, b1 - blocks_done) quantized
        symbols, zero-padding the partial last block past the buffered tail.

        Does NOT advance the session (see :meth:`_commit`). Lane-axis padding
        to the jit shape budget is the caller's job (``engine._pad_lanes``) —
        solo and pooled launches share that mechanism, so pad lanes are
        identical zero-symbol blocks on both paths.
        """
        return self._frame_device(self._frame_host(b1), b1 - self._blocks_done)

    def _frame_host(self, b1: int) -> np.ndarray:
        """The host window of blocks [blocks_done, b1): global stages
        [b0·D − L, b1·D + L), zero where the stream has no symbol, in the
        dtype that goes to the device."""
        b0 = self._blocks_done
        cfg = self.cfg
        D, L, R = cfg.D, cfg.L, self.spec.code.R
        lo = b0 * D - L  # global first stage of the combined window
        hi_pad = b1 * D + L  # exclusive global end incl. padding
        left_pad = max(0, -lo)  # only the very first block reaches stage -L
        s0 = max(lo, 0) - self._base
        need = hi_pad - max(lo, 0)
        window = self._store.read(s0, need)
        parts = []
        if left_pad:
            parts.append(np.zeros((left_pad, R), np.float32))
        parts.append(window)
        right_pad = need - len(window)
        if right_pad > 0:
            parts.append(np.zeros((right_pad, R), np.float32))
        w = np.concatenate(parts) if len(parts) > 1 else parts[0]
        if self._int_dtype is not None:  # pre-quantized stream: exact passthrough
            return w.astype(self._int_dtype)
        return w

    def _frame_device(self, w: np.ndarray, k: int) -> jnp.ndarray:
        """Copy a :meth:`_frame_host` window of ``k`` blocks to the device,
        quantize it, and gather it into (T, R, k) lanes."""
        cfg = self.cfg
        T = cfg.D + 2 * cfg.L
        y = jnp.asarray(w)
        if self._int_dtype is None and cfg.effective_q is not None:
            y = cfg.quantize(y)
        idx = np.arange(T)[:, None] + np.arange(k)[None, :] * cfg.D
        return jnp.transpose(y[idx], (0, 2, 1))  # (T, R, k)

    def _received_lane_stages(self, b1: int) -> int:
        """Lane-stages of blocks [blocks_done, b1) that carry a received
        stage, not the zero padding of :meth:`_frame_host`."""
        D, L = self.cfg.D, self.cfg.L
        b0 = self._blocks_done
        return _covered_lane_stages(
            b0 * D - L, b1 - b0, D, D + 2 * L, 0, self._base + len(self._store)
        )

    def _commit(self, b1: int) -> None:
        """Advance past blocks [blocks_done, b1); trim the consumed buffer."""
        D, L = self.cfg.D, self.cfg.L
        self._blocks_done = b1
        new_base = max(0, b1 * D - L)
        drop = new_base - self._base
        if drop > 0:
            self._store.drop_prefix(min(drop, len(self._store)))
            self._base = new_base

    def _decode_upto(self, b1: int) -> np.ndarray:
        """Decode blocks [blocks_done, b1) in one solo launch; advance."""
        b0 = self._blocks_done
        k = b1 - b0
        if k <= 0:
            return np.zeros((0,), np.int32)
        # pad the block count to the engine's lane budget (power of two,
        # rounded once to the mesh shard count) so chunked streams hit a
        # bounded set of jit shapes; pad-lane bits are trimmed by the backend.
        # _pad_lanes is the SAME mechanism the pooled launch uses, so a solo
        # flush and a pooled flush build identical launches lane for lane
        blocks = self.engine._pad_lanes(self._frame_ready(b1))
        bits = self.engine._decode_blocks(blocks, (k,), self._interpret)  # (D, k)
        out = np.asarray(jnp.transpose(bits), dtype=np.int32).reshape(-1)
        self._commit(b1)
        return out
