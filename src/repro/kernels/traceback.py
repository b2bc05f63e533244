"""Pallas TPU kernels for the PBVD traceback/decode phase (paper kernel K2).

Two kernels share this module (selected by the ``tb_mode`` backend knob,
see DESIGN.md §9):

**Serial** (``tb_mode="serial"``, the paper's K2): the traceback is
embarrassingly parallel in blocks but strictly serial in stages. On the GPU
the paper assigns one *thread* per block; on TPU we assign one *lane* per
block: the walked state is a ``(1, 128)`` int32 vector, the stage loop is a
``fori_loop`` of ``T - decode_start`` steps (stages below ``decode_start``
emit nothing and are never walked), and each step does

  * a W-way select to fetch the survivor word of the current state
    (W = ceil(N/32) = 2 for the CCSDS code — cheaper than any gather),
  * a per-lane variable bit-shift to extract the decision bit,
  * the state walk ``state' = 2·(state mod N/2) + bit``,
  * emits the decoded bit (the state's MSB) for stages inside the decode
    region.

**Parallel-prefix** (``tb_mode="prefix"``): the serial chain is broken with
chunked survivor-map composition. Each stage's packed survivor words define
a predecessor map ``f_s: state → prev_state`` over the N states; maps
compose associatively, so for chunks of ``C = tb_chunk`` stages the kernel

  1. composes each chunk's C maps into one N-entry chunk map, vectorized
     over **chunks × states on the sublane axis** (the data-dependent
     "gather" ``h ← f_s[h]`` is the same W-way word select + variable shift
     as the serial walk, just on (n_chunks, N, 128) operands — no gathers);
  2. walks the ceil(T/C) composed maps serially from the start state (a
     one-hot sublane reduction per step) to recover every chunk's entry
     state — the ONLY remaining serial chain, T/C steps instead of T;
  3. re-expands all chunks' decoded bits in parallel given their entry
     states (C steps on (n_chunks, 128) operands).

Chunks wholly below ``decode_start`` are never composed, walked or
expanded; chunks above the decode region (the traceback-only tail) are
composed and walked but not expanded. T is padded *below* stage 0 to a
chunk multiple — the walk never depends on stages below the emitted region,
so zero pad words are inert.

Decoded bits are written stage-major ``(T, TILE)`` (serial) or chunk-major
``(nc, C, TILE)`` (prefix; reshaped/sliced by the wrapper) and bit-packed
by the ops wrapper (the paper's U₂ = 1/8 D2H compression).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.trellis import ConvCode
from .acs import LANE_TILE

__all__ = [
    "traceback_pallas",
    "traceback_prefix_pallas",
    "DEFAULT_TB_CHUNK",
    "prefix_chunk_geometry",
]

DEFAULT_TB_CHUNK = 64


def _traceback_kernel(
    sp_ref,  # (T, W, TILE) int32 packed survivor words
    start_ref,  # (1, TILE) int32 traceback start state per block
    bits_ref,  # (D, TILE) int32 out: decoded bits, forward order
    *,
    code: ConvCode,
    n_stages: int,
    decode_start: int,
    n_decode: int,
):
    W = sp_ref.shape[1]
    tile = sp_ref.shape[-1]
    v = code.v
    half = code.n_states // 2

    def step(i, state):
        s = n_stages - 1 - i  # walk stages T-1 .. decode_start
        sp_t = sp_ref[pl.ds(s, 1)][0]  # (W, TILE)
        word_idx = state >> 5
        word = sp_t[0][None, :] if W == 1 else jnp.zeros((1, tile), jnp.int32)
        if W > 1:
            for wi in range(W):
                word = jnp.where(word_idx == wi, sp_t[wi][None, :], word)
        bit = (word >> (state & 31)) & 1
        out_bit = state >> (v - 1)  # MSB = input bit of transition s

        # store decoded bit if s < decode_start + n_decode (the early-exit
        # loop bound already guarantees s >= decode_start)
        in_region = s < decode_start + n_decode
        offset = jnp.clip(s - decode_start, 0, n_decode - 1)

        @pl.when(in_region)
        def _emit():
            bits_ref[pl.ds(offset, 1)] = out_bit.astype(jnp.int32)

        return 2 * (state % half) + bit

    state0 = start_ref[...]  # (1, TILE)
    # stages below decode_start emit nothing and feed nothing: stop the walk
    # at decode_start (saves the M truncation stages, ~8% at Table III)
    jax.lax.fori_loop(0, n_stages - decode_start, step, state0, unroll=False)


@functools.partial(
    jax.jit, static_argnames=("code", "decode_start", "n_decode", "interpret")
)
def traceback_pallas(
    sp: jnp.ndarray,
    start_state: jnp.ndarray,
    code: ConvCode,
    *,
    decode_start: int,
    n_decode: int,
    interpret: bool = False,
) -> jnp.ndarray:
    """Serial traceback/decode. sp: (T, W, B); start_state: (B,) → bits (D, B)."""
    T, W, B = sp.shape
    if B % LANE_TILE:
        raise ValueError(f"B={B} not a multiple of {LANE_TILE}")
    n_bt = B // LANE_TILE
    kernel = functools.partial(
        _traceback_kernel,
        code=code,
        n_stages=T,
        decode_start=decode_start,
        n_decode=n_decode,
    )
    bits = pl.pallas_call(
        kernel,
        grid=(n_bt,),
        in_specs=[
            pl.BlockSpec((T, W, LANE_TILE), lambda bt: (0, 0, bt)),
            pl.BlockSpec((1, LANE_TILE), lambda bt: (0, bt)),
        ],
        out_specs=pl.BlockSpec((n_decode, LANE_TILE), lambda bt: (0, bt)),
        out_shape=jax.ShapeDtypeStruct((n_decode, B), jnp.int32),
        interpret=interpret,
        name="pbvd_traceback",
    )(sp, start_state.reshape(1, B).astype(jnp.int32))
    return bits


# ---------------------------------------------------------------------------
# Parallel-prefix traceback
# ---------------------------------------------------------------------------
def prefix_chunk_geometry(T: int, decode_start: int, n_decode: int, tb_chunk: int):
    """Static chunk geometry of the prefix traceback.

    Returns ``(C, P, n_chunks, c_lo, c_hi)``: the clamped chunk size, the
    below-stage-0 padding that makes ``T + P`` a chunk multiple, the total
    chunk count, and the first/last chunk index touching the decode region
    (after padding). Chunks ``< c_lo`` are skipped entirely; chunks
    ``> c_hi`` are composed/walked but never expanded.
    """
    if tb_chunk < 1:
        raise ValueError(f"tb_chunk must be >= 1, got {tb_chunk}")
    if not 0 <= decode_start <= T - n_decode:
        raise ValueError(
            f"decode region [{decode_start}, {decode_start + n_decode}) "
            f"outside [0, {T})"
        )
    C = min(tb_chunk, T)
    P = (-T) % C
    n_chunks = (T + P) // C
    ds = decode_start + P
    c_lo = ds // C
    c_hi = (ds + n_decode - 1) // C
    return C, P, n_chunks, c_lo, c_hi


def _prefix_traceback_phases(
    spr_ref,  # (n_chunks, C, W, TILE) packed survivor words (chunk-major view)
    start,  # (1, TILE) int32 start state at time T
    emit_bit,  # callback(row k, out_bit (nc_e, 1, TILE)) — write decoded bits
    maps_ref,  # VMEM scratch (n_act, N, TILE) int32 composed chunk maps
    entry_ref,  # VMEM scratch (nc_e, TILE) int32 chunk entry states
    *,
    code: ConvCode,
    C: int,
    n_chunks: int,
    c_lo: int,
    c_hi: int,
):
    """The three prefix phases, shared by the standalone and fused kernels.

    Phase A composes each active chunk's C stage maps into one N-entry map
    (vectorized over chunks × states); phase B serially walks the
    ``n_chunks - c_lo`` composed maps from ``start`` recording each
    expansion chunk's entry state; phase C re-walks the expansion chunks in
    parallel, emitting one decoded-bit row per step via ``emit_bit``.
    """
    N = code.n_states
    half = N // 2
    v = code.v
    W = spr_ref.shape[2]
    tile = spr_ref.shape[-1]
    n_act = n_chunks - c_lo
    nc_e = c_hi - c_lo + 1

    # ---- phase A: compose chunk maps, parallel across chunks × states ----
    maps_ref[...] = jax.lax.broadcasted_iota(jnp.int32, (n_act, N, tile), 1)

    def compose_body(k, _):
        row = C - 1 - k  # stages are applied top-down within each chunk
        sp_k = spr_ref[pl.ds(c_lo, n_act), pl.ds(row, 1)][:, 0]  # (n_act, W, TILE)
        h = maps_ref[...]  # (n_act, N, TILE)
        word_idx = h >> 5
        sel = jnp.broadcast_to(sp_k[:, 0][:, None, :], (n_act, N, tile))
        for wi in range(1, W):
            sel = jnp.where(word_idx == wi, sp_k[:, wi][:, None, :], sel)
        bit = (sel >> (h & 31)) & 1
        maps_ref[...] = 2 * (h % half) + bit
        return 0

    jax.lax.fori_loop(0, C, compose_body, 0, unroll=False)

    # ---- phase B: the ONLY serial chain — ceil(T/C) steps over chunk maps ----
    iota_n = jax.lax.broadcasted_iota(jnp.int32, (N, tile), 0)

    def walk_body(j, state):
        c = n_act - 1 - j  # local chunk index (0 = chunk c_lo), walked top-down

        @pl.when(c < nc_e)
        def _record():  # entry state = walk state at the top of chunk c
            entry_ref[pl.ds(c, 1)] = state

        g = maps_ref[pl.ds(c, 1)][0]  # (N, TILE)
        onehot = iota_n == state  # (N, TILE); state broadcasts from (1, TILE)
        return jnp.sum(jnp.where(onehot, g, 0), axis=0, keepdims=True)

    jax.lax.fori_loop(0, n_act, walk_body, start, unroll=False)

    # ---- phase C: re-expand decoded bits, parallel across chunks ----
    def expand_body(k, state):  # state: (nc_e, TILE)
        row = C - 1 - k
        sp_k = spr_ref[pl.ds(c_lo, nc_e), pl.ds(row, 1)][:, 0]  # (nc_e, W, TILE)
        word_idx = state >> 5
        sel = sp_k[:, 0]
        for wi in range(1, W):
            sel = jnp.where(word_idx == wi, sp_k[:, wi], sel)
        bit = (sel >> (state & 31)) & 1
        emit_bit(row, (state >> (v - 1))[:, None, :])
        return 2 * (state % half) + bit

    jax.lax.fori_loop(0, C, expand_body, entry_ref[...], unroll=False)


def _traceback_prefix_kernel(
    spr_ref,  # (n_chunks, C, W, TILE) int32 packed survivor words
    start_ref,  # (1, TILE) int32 traceback start state per block
    bits_ref,  # (nc_e, C, TILE) int32 out: decoded bits, chunk-major
    maps_ref,  # VMEM scratch (n_act, N, TILE) int32
    entry_ref,  # VMEM scratch (nc_e, TILE) int32
    *,
    code: ConvCode,
    C: int,
    n_chunks: int,
    c_lo: int,
    c_hi: int,
):
    def emit(row, out_bit):
        bits_ref[:, pl.ds(row, 1)] = out_bit

    _prefix_traceback_phases(
        spr_ref,
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


@functools.partial(
    jax.jit,
    static_argnames=("code", "decode_start", "n_decode", "tb_chunk", "interpret"),
)
def traceback_prefix_pallas(
    sp: jnp.ndarray,
    start_state: jnp.ndarray,
    code: ConvCode,
    *,
    decode_start: int,
    n_decode: int,
    tb_chunk: int = DEFAULT_TB_CHUNK,
    interpret: bool = False,
) -> jnp.ndarray:
    """Parallel-prefix traceback. sp: (T, W, B); start: (B,) → bits (D, B).

    Bit-exact to :func:`traceback_pallas` for every chunk size (including
    non-divisors of T and ``tb_chunk >= T``); the serial dependency drops
    from T steps to ceil(T/tb_chunk). VMEM cost: the composed-map scratch is
    ``(ceil(T/C) - c_lo)·N·128·4`` bytes per lane tile — ~320 KB at Table III
    geometry with C=64 (see DESIGN.md §9 for the cost model).
    """
    T, W, B = sp.shape
    if B % LANE_TILE:
        raise ValueError(f"B={B} not a multiple of {LANE_TILE}")
    C, P, n_chunks, c_lo, c_hi = prefix_chunk_geometry(
        T, decode_start, n_decode, tb_chunk
    )
    if P:  # pad BELOW stage 0: the walk never consumes stages under the
        # emitted region, so zero pad words are inert (top stage stays T-1)
        sp = jnp.pad(sp, ((P, 0), (0, 0), (0, 0)))
    spr = sp.reshape(n_chunks, C, W, B)
    n_act = n_chunks - c_lo
    nc_e = c_hi - c_lo + 1
    N = code.n_states
    n_bt = B // LANE_TILE

    kernel = functools.partial(
        _traceback_prefix_kernel,
        code=code,
        C=C,
        n_chunks=n_chunks,
        c_lo=c_lo,
        c_hi=c_hi,
    )
    bits = pl.pallas_call(
        kernel,
        grid=(n_bt,),
        in_specs=[
            pl.BlockSpec((n_chunks, C, W, LANE_TILE), lambda bt: (0, 0, 0, bt)),
            pl.BlockSpec((1, LANE_TILE), lambda bt: (0, bt)),
        ],
        out_specs=pl.BlockSpec((nc_e, C, LANE_TILE), lambda bt: (0, 0, bt)),
        out_shape=jax.ShapeDtypeStruct((nc_e, C, B), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((n_act, N, LANE_TILE), jnp.int32),
            pltpu.VMEM((nc_e, LANE_TILE), jnp.int32),
        ],
        interpret=interpret,
        name="pbvd_traceback_prefix",
    )(spr, start_state.reshape(1, B).astype(jnp.int32))
    # chunk-major (nc_e, C, B) → stage-major rows of the decode region
    ds_local = (decode_start + P) - c_lo * C
    flat = bits.reshape(nc_e * C, B)
    return jax.lax.slice_in_dim(flat, ds_local, ds_local + n_decode, axis=0)
