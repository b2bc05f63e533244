"""Fused single-kernel PBVD: forward ACS + in-VMEM traceback (beyond-paper).

The paper's two-kernel split exists because a GPU CTA cannot hold the
survivor-path history of a parallel block in shared memory (D+2L = 596
stages × 8 B × 32 blocks/warp ≈ 150 KB > SMEM), so SP must round-trip
through global memory between K1 and K2 — that SP traffic (8 B per stage
per block ≈ 9.3 B per decoded bit) dominates the decoder's memory roofline.

On TPU the VMEM budget is two orders of magnitude larger: a 128-lane block
tile needs only `T×2×4×128 ≈ 610 KB` for the full bit-packed SP history.
This kernel therefore keeps SP in VMEM scratch, runs the traceback in the
same kernel invocation, and emits bit-packed decoded words — HBM traffic
per decoded bit drops from ≈ 11.6 B (int8 symbols + SP out + SP in + bits)
to ≈ (1+2L/D)·R·1 B in + 1/8 B out ≈ 2.5 B:  a ~4.6× memory-roofline win
that the GPU architecture structurally cannot reach.

``tb_mode`` selects the phase-2 traceback: ``"serial"`` walks one stage per
step (stopping at ``decode_start`` — earlier stages emit nothing);
``"prefix"`` runs the chunked survivor-map composition of
:mod:`repro.kernels.traceback` directly from the VMEM SP scratch (the
composed-map and decoded-bit scratches also live in VMEM), keeping the
~2.5 B/bit HBM roofline while cutting the serial chain from T steps to
ceil(T/tb_chunk) — see DESIGN.md §9.

Validated bit-exactly against the two-kernel path and the jnp oracle
(`tests/test_fused_kernel.py`).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.trellis import ConvCode
from .acs import (
    LANE_TILE,
    _min_subtract,
    _pack_plane,
    butterfly_bm_row,
    folded_bm_rows,
    matrix_step,
    radix2_stage,
    radix4_stage_pair,
)
from repro.core.quantize import metric_mode_qmax, norm_interval
from .ref import _acc_dtype_for
from .traceback import DEFAULT_TB_CHUNK, _prefix_traceback_phases, prefix_chunk_geometry

__all__ = ["pbvd_fused_pallas", "DEFAULT_SYM_CHUNK"]

# Stages per double-buffered symbol tile (radix-4 and matrix paths): the HBM
# read of the next tile overlaps the current tile's ACS compute. Big enough
# to amortize the DMA issue cost; the 2× scratch is 2·64·R·TILE symbol bytes
# plus one 32-bit widened tile — see DESIGN.md §10 for the model.
DEFAULT_SYM_CHUNK = 64
# Tile alignment in stages: a tile of 32·j stages is 32·j·R flat symbol rows,
# a multiple of the TPU sublane tiling for 8-, 16- and 32-bit dtypes alike.
_SYM_ALIGN = 32


def _acs_phase(
    y_ref,
    pm_ref,
    sp_write,
    *,
    code: ConvCode,
    n_stages: int,
    acc_dtype,
    norm_every: int,
):
    """Phase 1 (radix 2): forward ACS from VMEM-resident symbols; survivor
    words handed to ``sp_write(s, words)``."""
    tile = pm_ref.shape[-1]

    pm_ref[...] = jnp.zeros_like(pm_ref)

    def acs_body(s, pm):
        y_s = y_ref[pl.ds(s, 1)][0].astype(acc_dtype)  # (R, TILE)
        new_pm, dec = radix2_stage(pm, y_s, code, acc_dtype, tile)
        if norm_every:  # amortized min-subtract (i16/i8 saturation contract)
            new_pm = jax.lax.cond(
                s % norm_every == norm_every - 1, _min_subtract, lambda p: p, new_pm
            )
        sp_write(s, _pack_plane(dec, tile))  # (W, TILE)
        return new_pm

    pm = jax.lax.fori_loop(0, n_stages, acs_body, pm_ref[...], unroll=False)
    pm_ref[...] = pm


def _acs_phase_dbuf(
    y_hbm,  # (T_pad·R, B) stage-major symbol rows, HBM/ANY — in their WIRE dtype
    bt,  # lane-tile index of this program instance
    pm_ref,  # VMEM scratch (N, TILE)
    sp_write,  # per-stage survivor-word writer (trailing T mod k stages)
    sp_write_multi,  # per-step writer: (flat stage, [k packed planes])
    sym_ref,  # VMEM scratch (2, SYM·R, TILE), wire dtype — the double buffer
    wide_ref,  # VMEM scratch (SYM·R, TILE), acc dtype — the widened tile
    sem_ref,  # DMA semaphores (2,)
    *,
    code: ConvCode,
    n_stages: int,
    acc_dtype,
    norm_every: int,
    clip_qmax: int | None,
    sym_chunk: int,
    k: int,
    step,  # (pm, [k (R, TILE) stage rows]) → (new_pm, [k decision planes])
):
    """Phase 1 (k-stage steps): ACS on a double-buffered symbol pipeline.

    Symbols stay in HBM in their quantized wire dtype; while the steps of
    tile c compute, the DMA engine prefetches tile c+1 into the other half
    of the double buffer, so the HBM read overlaps ACS compute. Each landed
    tile is widened (and clipped, narrow modes — see acs_forward_ref) once
    into a 32-bit scratch, from which the steps read their ``k·R`` rows.
    The symbols travel as flat stage-major rows ``(T_pad·R, B)`` and the
    wrapper makes ``sym_chunk`` a multiple of 32 and of k: every DMA slice
    is then aligned to the sublane tiling of any wire dtype (a 3-D
    ``(SYM, R, TILE)`` slice is not, for R=3 or for int8 symbols), steps
    never straddle tiles, and the T mod k trailing stages (radix-2,
    unconditional min-subtract in narrow modes — a uniform budget-safe
    shift) fall in the last tile only, matching the ref scan's split.
    """
    tile = pm_ref.shape[-1]
    R = code.R
    T = n_stages
    n_chunks = -(-T // sym_chunk)

    def dma(c, slot):
        return pltpu.make_async_copy(
            y_hbm.at[pl.ds(c * sym_chunk * R, sym_chunk * R), pl.ds(bt * tile, tile)],
            sym_ref.at[slot],
            sem_ref.at[slot],
        )

    def stage_rows(t, n):  # n (R, TILE) stage rows from tile-local stage t
        rows = wide_ref[pl.ds(t * R, n * R)]
        return [rows[i * R : (i + 1) * R] for i in range(n)]

    pm_ref[...] = jnp.zeros_like(pm_ref)
    pm = pm_ref[...]
    dma(0, 0).start()
    for c in range(n_chunks):  # static chunk count: python-level pipeline
        slot = c % 2
        if c + 1 < n_chunks:
            dma(c + 1, (c + 1) % 2).start()  # prefetch overlaps this chunk
        dma(c, slot).wait()
        y_t = sym_ref[slot].astype(acc_dtype)
        if clip_qmax is not None:
            y_t = jnp.clip(y_t, -clip_qmax, clip_qmax)
        wide_ref[...] = y_t
        lo = c * sym_chunk
        hi = min(lo + sym_chunk, T)
        step_base = lo // k  # sym_chunk is a k-multiple

        def step_body(s, pm, step_base=step_base, lo=lo):
            new_pm, planes = step(pm, stage_rows(k * s, k))
            if norm_every:  # cadence counts GLOBAL k-stage steps
                new_pm = jax.lax.cond(
                    (step_base + s) % norm_every == norm_every - 1,
                    _min_subtract,
                    lambda p: p,
                    new_pm,
                )
            sp_write_multi(lo + k * s, [_pack_plane(d, tile) for d in planes])
            return new_pm

        pm = jax.lax.fori_loop(0, (hi - lo) // k, step_body, pm, unroll=False)
        for t in range(hi - lo - (hi - lo) % k, hi - lo):
            pm, dec = radix2_stage(pm, stage_rows(t, 1)[0], code, acc_dtype, tile)
            if norm_every:
                pm = _min_subtract(pm)
            sp_write(lo + t, _pack_plane(dec, tile))
    pm_ref[...] = pm


def _run_acs_phase(
    y_ref,
    pm_ref,
    sp_write,
    sp_write_multi,
    extra_scratch,
    *,
    code: ConvCode,
    n_stages: int,
    acc_dtype,
    norm_every: int,
    radix: int,
    impl: str,
    k: int,
    clip_qmax: int | None,
    sym_chunk: int,
):
    """Dispatch phase 1: VMEM-resident radix-2, or the double-buffered
    pipeline of stage-fused radix-4 butterflies or k-stage matrix steps."""
    if impl == "butterfly" and radix == 2:
        _acs_phase(
            y_ref,
            pm_ref,
            sp_write,
            code=code,
            n_stages=n_stages,
            acc_dtype=acc_dtype,
            norm_every=norm_every,
        )
        return
    tile = pm_ref.shape[-1]
    if impl == "matrix":

        def step(pm, ys):
            return matrix_step(pm, ys, code, acc_dtype, tile, k)

    else:
        k = 2

        def step(pm, ys):
            new_pm, dec1, dec2 = radix4_stage_pair(pm, ys[0], ys[1], code, acc_dtype, tile)
            return new_pm, [dec1, dec2]

    _acs_phase_dbuf(
        y_ref,
        pl.program_id(0),
        pm_ref,
        sp_write,
        sp_write_multi,
        *extra_scratch,
        code=code,
        n_stages=n_stages,
        acc_dtype=acc_dtype,
        norm_every=norm_every,
        clip_qmax=clip_qmax,
        sym_chunk=sym_chunk,
        k=k,
        step=step,
    )


def _fused_kernel(
    y_ref,  # (T, R, TILE) symbols in VMEM (radix 2) or (T_pad·R, B) in ANY (dbuf)
    start_ref,  # (1, TILE) int32 traceback start state
    bits_ref,  # (n_words, TILE) int32 out: bit-packed decoded bits
    sp_ref,  # VMEM scratch (T, W, TILE) int32 survivor words
    pm_ref,  # VMEM scratch (N, TILE) acc path metrics
    *extra_scratch,  # dbuf: (sym double buffer, widened tile, DMA semaphores)
    code: ConvCode,
    n_stages: int,
    decode_start: int,
    n_decode: int,
    acc_dtype,
    norm_every: int,
    radix: int,
    impl: str,
    k: int,
    clip_qmax: int | None,
    sym_chunk: int,
):
    tile = pm_ref.shape[-1]
    v = code.v
    half = code.n_states // 2
    W = sp_ref.shape[1]

    # ---- phase 1: forward ACS, SP stays in VMEM ---------------------------------
    def sp_write(s, words):
        sp_ref[pl.ds(s, 1)] = words[None]

    def sp_write_multi(s, words):
        # stage-major scratch: all of a fused step's bit-planes land in one
        # contiguous store
        sp_ref[pl.ds(s, len(words))] = jnp.stack(words)

    _run_acs_phase(
        y_ref,
        pm_ref,
        sp_write,
        sp_write_multi,
        extra_scratch,
        code=code,
        n_stages=n_stages,
        acc_dtype=acc_dtype,
        norm_every=norm_every,
        radix=radix,
        impl=impl,
        k=k,
        clip_qmax=clip_qmax,
        sym_chunk=sym_chunk,
    )

    # ---- phase 2: serial traceback from VMEM, emit packed bits -------------------
    def tb_body(i, carry):
        state, word = carry
        s = n_stages - 1 - i  # walk stages T-1 .. decode_start (early exit)
        sp_t = sp_ref[pl.ds(s, 1)][0]  # (W, TILE)
        word_idx = state >> 5
        sel = sp_t[0][None, :]
        if W > 1:
            for wi in range(1, W):
                sel = jnp.where(word_idx == wi, sp_t[wi][None, :], sel)
        bit = (sel >> (state & 31)) & 1
        out_bit = state >> (v - 1)

        b = s - decode_start  # decoded-bit index (valid when 0 ≤ b < n_decode)
        in_region = jnp.logical_and(b >= 0, b < n_decode)
        word = jnp.where(in_region, word | (out_bit << (b & 31)), word)

        # flush the packed word when its lowest bit arrives
        @pl.when(jnp.logical_and(in_region, (b & 31) == 0))
        def _flush():
            bits_ref[pl.ds(b >> 5, 1)] = word

        word = jnp.where(jnp.logical_and(in_region, (b & 31) == 0), jnp.zeros_like(word), word)
        return 2 * (state % half) + bit, word

    state0 = start_ref[...]
    # stages below decode_start feed nothing the emitted words depend on:
    # the last flush fires at s = decode_start (b = 0)
    jax.lax.fori_loop(
        0,
        n_stages - decode_start,
        tb_body,
        (state0, jnp.zeros((1, tile), jnp.int32)),
        unroll=False,
    )


def _fused_prefix_kernel(
    y_ref,  # (T, R, TILE) symbols in VMEM (radix 2) or (T_pad·R, B) in ANY (dbuf)
    start_ref,  # (1, TILE) int32 traceback start state
    bits_ref,  # (n_words, TILE) int32 out: bit-packed decoded bits
    sp_ref,  # VMEM scratch (n_chunks, C, W, TILE) int32 survivor words
    pm_ref,  # VMEM scratch (N, TILE) acc path metrics
    maps_ref,  # VMEM scratch (n_act, N, TILE) int32 composed chunk maps
    entry_ref,  # VMEM scratch (nc_e, TILE) int32 chunk entry states
    tbbits_ref,  # VMEM scratch (nc_e, C, TILE) int32 unpacked decoded bits
    *extra_scratch,  # dbuf: (sym double buffer, widened tile, DMA semaphores)
    code: ConvCode,
    n_stages: int,
    decode_start: int,
    n_decode: int,
    acc_dtype,
    norm_every: int,
    radix: int,
    impl: str,
    k: int,
    clip_qmax: int | None,
    sym_chunk: int,
    C: int,
    P: int,
    n_chunks: int,
    c_lo: int,
    c_hi: int,
):
    tile = pm_ref.shape[-1]

    # ---- phase 1: forward ACS into the chunk-major SP scratch -------------------
    if P:  # pad rows below stage 0 (chunk 0) are inert zero words
        sp_ref[0:1, 0:P] = jnp.zeros_like(sp_ref[0:1, 0:P])

    def sp_write(s, words):
        flat = s + P
        sp_ref[pl.ds(flat // C, 1), pl.ds(flat % C, 1)] = words[None, None]

    def sp_write_multi(s, words):
        # chunk-major scratch: a fused step may straddle a traceback-chunk
        # boundary (C not a step multiple), so the planes store individually
        for i, w in enumerate(words):
            sp_write(s + i, w)

    _run_acs_phase(
        y_ref,
        pm_ref,
        sp_write,
        sp_write_multi,
        extra_scratch,
        code=code,
        n_stages=n_stages,
        acc_dtype=acc_dtype,
        norm_every=norm_every,
        radix=radix,
        impl=impl,
        k=k,
        clip_qmax=clip_qmax,
        sym_chunk=sym_chunk,
    )

    # ---- phase 2: chunked map composition + short walk + expansion --------------
    def emit(row, out_bit):
        tbbits_ref[:, pl.ds(row, 1)] = out_bit

    _prefix_traceback_phases(
        sp_ref,
        start_ref[...],
        emit,
        maps_ref,
        entry_ref,
        code=code,
        C=C,
        n_chunks=n_chunks,
        c_lo=c_lo,
        c_hi=c_hi,
    )

    # ---- phase 3: pack the decode region to output words --------------------------
    # same vectorized pack idiom as the ACS phase: flatten the chunk-major
    # bit scratch, slice the decode window (static bounds), zero-pad bits
    # that overhang T (they don't exist; serial mode leaves them 0 too) and
    # reduce 32 sublanes per word
    ds_local = (decode_start + P) - c_lo * C
    n_window = min(n_decode, n_stages - decode_start)  # bits that exist
    n_words = bits_ref.shape[0]
    flat = tbbits_ref[...].reshape(-1, tile)[ds_local : ds_local + n_window]
    pad = n_words * 32 - n_window
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad, tile), jnp.int32)], axis=0)
    weights = (jnp.int32(1) << jnp.arange(32, dtype=jnp.int32))[None, :, None]
    bits_ref[...] = (flat.reshape(n_words, 32, tile) * weights).sum(
        axis=1, dtype=jnp.int32
    )


@functools.partial(
    jax.jit,
    static_argnames=(
        "code",
        "decode_start",
        "n_decode",
        "interpret",
        "metric_mode",
        "tb_mode",
        "tb_chunk",
        "acs_radix",
        "acs_impl",
        "acs_k",
        "sym_chunk",
    ),
)
def pbvd_fused_pallas(
    y: jnp.ndarray,
    code: ConvCode,
    *,
    decode_start: int,
    n_decode: int,
    start_state: jnp.ndarray | None = None,
    interpret: bool = False,
    metric_mode: str = "f32",
    tb_mode: str = "serial",
    tb_chunk: int = DEFAULT_TB_CHUNK,
    acs_radix: int = 2,
    acs_impl: str = "butterfly",
    acs_k: int = 2,
    sym_chunk: int = DEFAULT_SYM_CHUNK,
) -> jnp.ndarray:
    """One-kernel PBVD decode. y (T, R, B) → packed bits (n_decode/32, B) int32.

    n_decode must be a multiple of 32 (bit-packed output words).
    ``metric_mode`` "i16"/"i8" adds the amortized min-subtract normalization
    (int32 VPU registers — see ``repro.kernels.registry.METRIC_MODES``).
    ``tb_mode="prefix"`` runs the chunked parallel-prefix traceback from the
    VMEM survivor scratch (bit-exact to serial for any ``tb_chunk``).
    ``acs_radix=4`` halves the forward serial chain with stage-fused radix-4
    steps AND moves the symbol read to a double-buffered HBM→VMEM pipeline:
    the symbols stay in their wire dtype in HBM and the next ``sym_chunk``
    stages prefetch while the current ones compute (odd T runs one trailing
    radix-2 step; decoded bits stay bit-identical to radix 2).
    ``acs_impl="matrix"`` runs the k-stage (min,+) tropical-matmul ACS on
    the same double-buffered pipeline (``sym_chunk`` rounds down to a
    multiple of lcm(k, 32), at least one; T mod k trailing stages run
    radix-2; float symbols lower to
    the staged butterfly — see ``acs_forward_pallas``). Decoded bits stay
    bit-identical for every impl/radix/k.
    """
    T, R, B = y.shape
    if n_decode % 32:
        raise ValueError("n_decode must be a multiple of 32")
    if B % LANE_TILE:
        raise ValueError(f"B={B} not a multiple of {LANE_TILE}")
    if tb_mode not in ("serial", "prefix"):
        raise ValueError(f"unknown tb_mode {tb_mode!r}")
    if acs_impl not in ("butterfly", "matrix"):
        raise ValueError(f"acs_impl must be 'butterfly' or 'matrix', got {acs_impl!r}")
    if acs_radix not in (2, 4):
        raise ValueError(f"acs_radix must be 2 or 4, got {acs_radix}")
    if acs_impl == "matrix":
        code.validate_matrix_k(acs_k)
    elif acs_radix == 4 and code.n_states < 4:
        raise ValueError(f"radix-4 ACS needs K >= 3 (got K={code.K})")
    semantic = _acc_dtype_for(y.dtype, metric_mode)
    acc_dtype = jnp.float32 if semantic == jnp.float32 else jnp.int32
    if acs_impl == "matrix" and acc_dtype == jnp.float32:
        # float lowering, as in acs_forward_pallas: the flat k-stage
        # contraction is not IEEE-associative — run the butterfly body
        acs_impl, acs_radix = "butterfly", 2
    if acs_impl == "matrix":
        norm_every = norm_interval(code, metric_mode, stages_per_step=acs_k)
    else:
        norm_every = norm_interval(code, metric_mode, acs_radix)
    clip_qmax = metric_mode_qmax(code, metric_mode) if norm_every else None
    dbuf = acs_impl == "matrix" or acs_radix == 4
    if not dbuf:
        # symbols ride the pallas pipeline into VMEM, widened to the
        # register dtype up front
        y = y.astype(acc_dtype)
        if clip_qmax is not None:
            # saturate out-of-budget pre-quantized symbols (see acs_forward_ref)
            y = jnp.clip(y, -clip_qmax, clip_qmax)
        y_spec = pl.BlockSpec((T, R, LANE_TILE), lambda bt: (0, 0, bt))
    else:
        # symbols stay in HBM in their WIRE dtype (the kernel widens/clips
        # after the VMEM load), as flat stage-major rows. The tile is a
        # multiple of the step depth (steps never straddle tiles) and of 32
        # stages (every DMA row slice is aligned to the sublane tiling of
        # any dtype up to 32 rows); T pads to a tile multiple so every DMA
        # is statically shaped — the pad stages are never computed
        step_k = acs_k if acs_impl == "matrix" else 2
        align = math.lcm(step_k, _SYM_ALIGN)
        sym_chunk = max(align, sym_chunk - sym_chunk % align)
        pad = (-T) % sym_chunk
        if pad:
            y = jnp.pad(y, ((0, pad), (0, 0), (0, 0)))
        y = y.reshape(-1, B)
        y_spec = pl.BlockSpec(memory_space=pltpu.MemorySpace.ANY)

    N = code.n_states
    W = (N + 31) // 32
    n_bt = B // LANE_TILE
    n_words = n_decode // 32

    if start_state is None:
        start_state = jnp.zeros((B,), jnp.int32)

    common = dict(
        code=code,
        n_stages=T,
        decode_start=decode_start,
        n_decode=n_decode,
        acc_dtype=acc_dtype,
        norm_every=norm_every,
        radix=acs_radix,
        impl=acs_impl,
        k=acs_k,
        clip_qmax=clip_qmax,
        sym_chunk=sym_chunk,
    )
    if tb_mode == "serial":
        kernel = functools.partial(_fused_kernel, **common)
        scratch = [
            pltpu.VMEM((T, W, LANE_TILE), jnp.int32),
            pltpu.VMEM((N, LANE_TILE), acc_dtype),
        ]
    else:
        # geometry over the bits that exist: the packed width n_decode may
        # overhang T at ragged D (top word bits stay 0, as in serial mode)
        n_window = min(n_decode, T - decode_start)
        C, P, n_chunks, c_lo, c_hi = prefix_chunk_geometry(
            T, decode_start, n_window, tb_chunk
        )
        kernel = functools.partial(
            _fused_prefix_kernel,
            **common,
            C=C,
            P=P,
            n_chunks=n_chunks,
            c_lo=c_lo,
            c_hi=c_hi,
        )
        scratch = [
            pltpu.VMEM((n_chunks, C, W, LANE_TILE), jnp.int32),
            pltpu.VMEM((N, LANE_TILE), acc_dtype),
            pltpu.VMEM((n_chunks - c_lo, N, LANE_TILE), jnp.int32),
            pltpu.VMEM((c_hi - c_lo + 1, LANE_TILE), jnp.int32),
            pltpu.VMEM((c_hi - c_lo + 1, C, LANE_TILE), jnp.int32),
        ]
    if dbuf:
        scratch = scratch + [
            pltpu.VMEM((2, sym_chunk * R, LANE_TILE), y.dtype),  # double buffer
            pltpu.VMEM((sym_chunk * R, LANE_TILE), acc_dtype),  # widened tile
            pltpu.SemaphoreType.DMA((2,)),
        ]
    packed = pl.pallas_call(
        kernel,
        grid=(n_bt,),
        in_specs=[
            y_spec,
            pl.BlockSpec((1, LANE_TILE), lambda bt: (0, bt)),
        ],
        out_specs=pl.BlockSpec((n_words, LANE_TILE), lambda bt: (0, bt)),
        out_shape=jax.ShapeDtypeStruct((n_words, B), jnp.int32),
        scratch_shapes=scratch,
        interpret=interpret,
        name="pbvd_fused",
    )(y, start_state.reshape(1, B).astype(jnp.int32))
    return packed
