"""jit'd public wrappers around the PBVD kernels, backend-dispatched.

The three decode backends (``ref`` pure-jnp oracle, ``pallas`` two-kernel
K1/K2 path, ``fused`` single-kernel ACS+traceback) register themselves here
via the :mod:`repro.kernels.registry` decorator, each receiving the common
``FramedBlocks``/``ConvCode`` contract. ``pbvd_decode_blocks`` is the
dispatcher the engine calls; it validates the backend/start-policy pair
eagerly (a ``ValueError`` before any tracing) and contains no per-backend
branches.

Each backend adapter owns its shape plumbing (lane padding to 128, stage
padding to the stage-chunk — end-padding with zero symbols is BM-neutral and
keeps the state-0 walk stable, see tests), the traceback start-state policy,
and the paper's packed-I/O transforms. The lane axis may be a flattened
frames × blocks packing (``FramedBlocks.frame_counts``); backends return
exactly ``blocks.n_real_blocks`` lanes, trimming any pad lanes themselves.

On the CPU backend the Pallas kernels run in interpret mode; on TPU they
compile natively (:func:`default_interpret`; any other platform is an error). ``backend="ref"`` selects the pure-jnp oracle (which is
also the fast path on CPU and the one XLA fuses well — used by the
benchmarks).
"""

from __future__ import annotations

import functools
from typing import Literal

import jax
import jax.numpy as jnp

from repro.core.quantize import norm_interval
from repro.core.trellis import ConvCode
from . import ref as _ref
from .acs import LANE_TILE, DEFAULT_STAGE_CHUNK, acs_forward_pallas
from .registry import (
    ACS_IMPL,
    ACS_RADIX,
    METRIC_MODES,
    TB_MODES,
    FramedBlocks,
    available_backends,
    backend_acs_impl,
    backend_acs_radix,
    backend_metric_modes,
    backend_preferred_tb_mode,
    backend_start_policies,
    backend_tb_chunk_sensitive,
    backend_tb_modes,
    get_backend,
    knob_error,
    launched_lanes,
    register_backend,
    resolve_tb_mode,
)
from .traceback import DEFAULT_TB_CHUNK, traceback_pallas, traceback_prefix_pallas

__all__ = [
    "pbvd_decode_blocks",
    "check_mesh_launch",
    "default_interpret",
    "FramedBlocks",
    "METRIC_MODES",
    "TB_MODES",
    "ACS_RADIX",
    "ACS_IMPL",
    "DEFAULT_TB_CHUNK",
    "DEFAULT_ACS_K",
    "register_backend",
    "get_backend",
    "available_backends",
    "backend_start_policies",
    "backend_metric_modes",
    "backend_tb_modes",
    "backend_tb_chunk_sensitive",
    "backend_acs_radix",
    "backend_acs_impl",
    "backend_preferred_tb_mode",
    "launched_lanes",
    "resolve_tb_mode",
    "knob_error",
]

# Default matrix-ACS fusion depth; also what ``acs_k`` normalizes to when
# ``acs_impl="butterfly"`` leaves it inert (cache-key hygiene, like tb_chunk
# under serial traceback).
DEFAULT_ACS_K = 2


def default_interpret() -> bool:
    """Whether Pallas kernels run in interpret mode on this process's backend.

    ``True`` on the CPU backend (tests and CPU rehearsals), ``False`` on TPU.
    Any other platform raises: the kernels are written for the TPU, and an
    interpreter run on a platform nobody chose would hide that the chip is
    missing while the served path still "works".
    """
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"Pallas decode kernels run on 'tpu' (compiled) or 'cpu' (interpret "
        f"mode, for tests); JAX's default backend is {platform!r}"
    )


def check_mesh_launch(mesh, block_axes, backend: str) -> int:
    """Eagerly validate a mesh × backend decode combination; return n_shards.

    Every failure here is a clear pre-trace ``ValueError`` (or ``KeyError``
    for an unknown backend) instead of a downstream shard_map shape error:
    empty/duplicate ``block_axes``, axes the mesh does not have, and a
    backend name the registry does not know.
    Called by ``DecoderEngine`` at construction, so a bad mesh binding fails
    when the engine is built — never inside a batched launch mid-stream.
    """
    get_backend(backend)  # KeyError names the unknown backend
    axes = tuple(block_axes)
    if not axes:
        raise ValueError("block_axes must name at least one mesh axis")
    if len(set(axes)) != len(axes):
        raise ValueError(f"block_axes {axes} repeats a mesh axis")
    missing = [a for a in axes if a not in mesh.axis_names]
    if missing:
        raise ValueError(
            f"block_axes {missing} not in mesh axes {tuple(mesh.axis_names)}"
        )
    n_shards = 1
    for a in axes:
        n_shards *= int(mesh.shape[a])
    if n_shards < 1:
        raise ValueError(f"mesh shards the lane axis {n_shards} ways: empty mesh?")
    return n_shards


def _pad_axis(x: jnp.ndarray, axis: int, multiple: int) -> jnp.ndarray:
    return _pad_to(x, axis, -(-x.shape[axis] // multiple) * multiple)


def _pad_to(x: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    pad = size - x.shape[axis]
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


# ---------------------------------------------------------------------------
# Backends
# ---------------------------------------------------------------------------
@register_backend(
    "ref",
    metric_modes=("f32", "i16", "i8"),
    tb_modes=("serial", "prefix"),
    tb_chunk_sensitive=False,  # full-depth associative scan — no chunks
    preferred_tb_mode="serial",  # BENCH_pr.json: prefix 0.14-0.39× serial here
    acs_radix=(2, 4),
    acs_impl=("butterfly", "matrix"),
)
def _decode_ref(
    blocks: FramedBlocks,
    code: ConvCode,
    *,
    start_policy: str = "zero",
    stage_chunk: int = DEFAULT_STAGE_CHUNK,
    interpret: bool = False,
    metric_mode: str = "f32",
    tb_mode: str = "serial",
    tb_chunk: int = DEFAULT_TB_CHUNK,
    acs_radix: int = 2,
    acs_impl: str = "butterfly",
    acs_k: int = DEFAULT_ACS_K,
) -> jnp.ndarray:
    """Pure-jnp oracle path (also the XLA-fused fast path on CPU).

    ``tb_mode="prefix"`` uses the ``lax.associative_scan`` state-map
    composition (log-depth, exact); ``tb_chunk`` is a kernel-layout knob and
    is ignored here — the scan composes at full depth either way, and the
    decoded bits are identical for every chunking.
    """
    B = blocks.y.shape[2]
    sp, pm = _ref.acs_forward_ref(
        blocks.y, code, metric_mode=metric_mode, radix=acs_radix,
        impl=acs_impl, matrix_k=acs_k,
    )
    if start_policy == "argmin":
        start = jnp.argmin(pm, axis=0).astype(jnp.int32)
    else:
        start = jnp.zeros((B,), jnp.int32)
    tb = _ref.traceback_prefix_ref if tb_mode == "prefix" else _ref.traceback_ref
    bits = tb(sp, code, blocks.decode_start, blocks.n_decode, start)
    return bits[:, : blocks.n_real_blocks]


@register_backend(
    "pallas",
    metric_modes=("f32", "i16", "i8"),
    tb_modes=("serial", "prefix"),
    # measured-fastest on the committed bench (BENCH_pr.json, acs_radix_sweep
    # / traceback_sweep): the interpret lowering pays ~4× for the prefix
    # composition phases. Flip to "prefix" once a real-TPU bench lands —
    # the declaration IS the auto-resolution, one line per backend.
    preferred_tb_mode="serial",
    acs_radix=(2, 4),
    acs_impl=("butterfly", "matrix"),
    lane_tile=LANE_TILE,
)
def _decode_pallas(
    blocks: FramedBlocks,
    code: ConvCode,
    *,
    start_policy: str = "zero",
    stage_chunk: int = DEFAULT_STAGE_CHUNK,
    interpret: bool = False,
    metric_mode: str = "f32",
    tb_mode: str = "serial",
    tb_chunk: int = DEFAULT_TB_CHUNK,
    acs_radix: int = 2,
    acs_impl: str = "butterfly",
    acs_k: int = DEFAULT_ACS_K,
) -> jnp.ndarray:
    """Two-kernel path (paper K1 ACS + K2 traceback, serial or prefix)."""
    T = blocks.y.shape[0]
    if acs_impl == "matrix":
        # the matrix kernel consumes whole k-stage steps per chunk: round
        # the chunk down to a k-multiple (64 → 63 for k=3); stage padding
        # below then keeps T a chunk multiple as before
        stage_chunk = max(acs_k, stage_chunk - stage_chunk % acs_k)
    y = _pad_to(blocks.y, 2, launched_lanes("pallas", blocks.y.shape[2]))
    y = _pad_axis(y, 0, stage_chunk)  # stage padding (end; BM-neutral zeros)
    Bp = y.shape[2]

    sp, pm = acs_forward_pallas(
        y,
        code,
        stage_chunk=stage_chunk,
        interpret=interpret,
        metric_mode=metric_mode,
        radix=acs_radix,
        impl=acs_impl,
        k=acs_k,
    )
    if start_policy == "argmin":
        # argmin over the padded-final metrics: the zero-BM pad stages only
        # min-merge paths, so the padded walk recovers a true argmin state at
        # stage T and the full padded survivor history must be walked.
        start = jnp.argmin(pm, axis=0).astype(jnp.int32)
    else:
        # state-0 start is defined at the true final stage T: walking the
        # zero-symbol pad stages from state 0 would land on an arbitrary
        # state at T, so drop the pad-stage survivors before the traceback.
        sp = sp[:T]
        start = jnp.zeros((Bp,), jnp.int32)
    if tb_mode == "prefix":
        bits = traceback_prefix_pallas(
            sp,
            start,
            code,
            decode_start=blocks.decode_start,
            n_decode=blocks.n_decode,
            tb_chunk=tb_chunk,
            interpret=interpret,
        )
    else:
        bits = traceback_pallas(
            sp,
            start,
            code,
            decode_start=blocks.decode_start,
            n_decode=blocks.n_decode,
            interpret=interpret,
        )
    return bits[:, : blocks.n_real_blocks]


@register_backend(
    "fused",
    start_policies=("zero",),
    metric_modes=("f32", "i16", "i8"),
    tb_modes=("serial", "prefix"),
    preferred_tb_mode="serial",  # measured-fastest on the committed bench
    # (see the pallas registration note; same TPU re-measure applies here)
    acs_radix=(2, 4),
    acs_impl=("butterfly", "matrix"),
    lane_tile=LANE_TILE,
)
def _decode_fused(
    blocks: FramedBlocks,
    code: ConvCode,
    *,
    start_policy: str = "zero",
    stage_chunk: int = DEFAULT_STAGE_CHUNK,
    interpret: bool = False,
    metric_mode: str = "f32",
    tb_mode: str = "serial",
    tb_chunk: int = DEFAULT_TB_CHUNK,
    acs_radix: int = 2,
    acs_impl: str = "butterfly",
    acs_k: int = DEFAULT_ACS_K,
) -> jnp.ndarray:
    """Single-kernel path (ACS + in-VMEM traceback, bit-packed output) —
    see kernels/fused.py; unpacked here for API compatibility."""
    from .fused import pbvd_fused_pallas

    if start_policy != "zero":
        # direct backend callers bypass the dispatcher's eager check; fail
        # loudly rather than silently decoding from state 0
        raise ValueError(
            "fused backend tracebacks from state 0 (start_policies=('zero',))"
        )
    nd = -(-blocks.n_decode // 32) * 32  # kernel emits 32-bit words
    y = _pad_to(blocks.y, 2, launched_lanes("fused", blocks.y.shape[2]))
    packed = pbvd_fused_pallas(
        y,
        code,
        decode_start=blocks.decode_start,
        n_decode=nd,
        interpret=interpret,
        metric_mode=metric_mode,
        tb_mode=tb_mode,
        tb_chunk=tb_chunk,
        acs_radix=acs_radix,
        acs_impl=acs_impl,
        acs_k=acs_k,
    )
    # unpack only what is kept: trim pad lanes BEFORE the 32× shift-expand
    # and expand the ragged last word to just its live rows, so the
    # intermediate is (n_decode, n_real) instead of (n_words·32, B_padded)
    packed = packed[:, : blocks.n_real_blocks]
    n_full = blocks.n_decode // 32
    rem = blocks.n_decode - n_full * 32
    shifts = jnp.arange(32, dtype=jnp.int32)
    parts = []
    if n_full:
        full = (packed[:n_full, None, :] >> shifts[None, :, None]) & 1
        parts.append(full.reshape(n_full * 32, -1))
    if rem:
        tail = (packed[n_full, None, :] >> shifts[:rem, None]) & 1
        parts.append(tail)
    bits = parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)
    return bits.astype(jnp.int32)


# ---------------------------------------------------------------------------
# Dispatcher
# ---------------------------------------------------------------------------
@functools.partial(
    jax.jit,
    static_argnames=(
        "code",
        "decode_start",
        "n_decode",
        "start_policy",
        "backend",
        "stage_chunk",
        "interpret",
        "n_real",
        "metric_mode",
        "tb_mode",
        "tb_chunk",
        "acs_radix",
        "acs_impl",
        "acs_k",
    ),
)
def _decode_blocks_jit(
    y_blocks: jnp.ndarray,
    code: ConvCode,
    *,
    decode_start: int,
    n_decode: int,
    start_policy: str,
    backend: str,
    stage_chunk: int,
    interpret: bool,
    n_real: int | None,
    metric_mode: str,
    tb_mode: str,
    tb_chunk: int,
    acs_radix: int,
    acs_impl: str,
    acs_k: int,
) -> jnp.ndarray:
    fn = get_backend(backend)
    return fn(
        FramedBlocks(
            y_blocks,
            decode_start,
            n_decode,
            (n_real,) if n_real is not None else None,
        ),
        code,
        start_policy=start_policy,
        stage_chunk=stage_chunk,
        interpret=interpret,
        metric_mode=metric_mode,
        tb_mode=tb_mode,
        tb_chunk=tb_chunk,
        acs_radix=acs_radix,
        acs_impl=acs_impl,
        acs_k=acs_k,
    )


def pbvd_decode_blocks(
    y_blocks: jnp.ndarray,
    code: ConvCode,
    *,
    decode_start: int,
    n_decode: int,
    start_policy: Literal["zero", "argmin"] = "zero",
    backend: str = "pallas",
    stage_chunk: int = DEFAULT_STAGE_CHUNK,
    interpret: bool | None = None,
    frame_counts: tuple[int, ...] | None = None,
    metric_mode: Literal["f32", "i16", "i8"] = "f32",
    tb_mode: Literal["serial", "prefix", "auto"] = "serial",
    tb_chunk: int = DEFAULT_TB_CHUNK,
    acs_radix: int = 2,
    acs_impl: Literal["butterfly", "matrix"] = "butterfly",
    acs_k: int = DEFAULT_ACS_K,
) -> jnp.ndarray:
    """Decode framed parallel blocks via the named backend.

    y_blocks: (T, R, B) soft symbols (float32, or int8/int16 for the exact
        quantized path), framed [trunc M | decode D | traceback L]. The lane
        axis may pack several frames (``frame_counts``, see
        :class:`FramedBlocks`); trailing lanes beyond the real blocks are
        padding.
    ``metric_mode`` selects the path-metric pipeline (:data:`METRIC_MODES`):
        "f32" accumulates unbounded; "i16"/"i8" run the narrow normalized
        pipeline and require pre-quantized integer symbols within the
        saturation budget (the engine quantizes accordingly).
    ``tb_mode`` selects the traceback algorithm (:data:`TB_MODES`): "serial"
        is the paper's stage walk, "prefix" the chunked parallel-prefix
        survivor-map composition (bit-exact; ``tb_chunk`` sizes the chunks
        and is ignored by "serial"), and "auto" resolves — eagerly, before
        the cache key — to the backend's declared measured-fastest mode.
    ``acs_radix`` selects the forward-ACS step (:data:`ACS_RADIX`): 2 is the
        paper's butterfly, 4 the stage-fused two-stage step (bit-exact; odd
        T runs one trailing radix-2 step).
    ``acs_impl`` selects the forward-pass formulation (:data:`ACS_IMPL`):
        "butterfly" is the compare-select trellis at ``acs_radix``,
        "matrix" the k-stage (min,+) tropical-matmul path with fusion depth
        ``acs_k`` (bit-exact; T mod k trailing stages run radix-2). Each
        impl's inert knob (``acs_k`` under butterfly, ``acs_radix`` under
        matrix) is normalized out of the jit cache key.
    Returns (n_decode, n_real_blocks) int32 decoded bits.

    Backend, start-policy, metric-mode, tb-mode, acs-radix and acs-impl are
    validated *before* jit: an unknown backend raises ``KeyError``; an
    unsupported start policy, metric mode, tb mode, radix or impl —
    including a narrow metric mode whose saturation budget cannot absorb
    the radix-4 double-stage (or matrix k-stage) accumulation for this
    code, and an ``acs_k`` outside the structural bounds — raises
    ``ValueError`` eagerly via :func:`repro.kernels.registry.knob_error`'s
    uniform shape (never a trace-time error from inside the kernel
    adapter).

    Only the TOTAL real-lane count enters the jit cache key: lanes are
    mutually independent and per-frame unpacking happens host-side, so the
    per-frame split is collapsed to ``sum(frame_counts)`` at this boundary —
    a pool whose sessions contribute varying block counts reuses one
    compiled launch per padded shape instead of retracing per composition.
    """
    if interpret is None:
        interpret = default_interpret()
    supported = backend_start_policies(backend)  # KeyError for unknown backend
    if start_policy not in supported:
        raise knob_error(backend, "start_policy", start_policy, supported)
    supported_modes = backend_metric_modes(backend)
    if metric_mode not in supported_modes:
        raise knob_error(backend, "metric_mode", metric_mode, supported_modes)
    tb_mode = resolve_tb_mode(backend, tb_mode)  # "auto" → declared fastest
    supported_tb = backend_tb_modes(backend)
    if tb_mode not in supported_tb:
        raise knob_error(backend, "tb_mode", tb_mode, supported_tb)
    if tb_chunk < 1:
        raise ValueError(f"tb_chunk must be >= 1, got {tb_chunk}")
    supported_impl = backend_acs_impl(backend)
    if acs_impl not in supported_impl:
        raise knob_error(backend, "acs_impl", acs_impl, supported_impl)
    if acs_impl == "matrix":
        # structural bounds on the fusion depth, then the narrow-mode budget
        # for k unnormalized stages per step — both eager, pre-jit
        code.validate_matrix_k(acs_k)
        norm_interval(code, metric_mode, stages_per_step=acs_k)
        # the butterfly radix is inert under the matrix impl: normalize it
        # out of the jit cache key (and skip its K>=3 check — a K=2 code
        # can run matrix k=1 regardless of the radix default)
        acs_radix = 2
    else:
        supported_radix = backend_acs_radix(backend)
        if acs_radix not in supported_radix:
            raise knob_error(backend, "acs_radix", acs_radix, supported_radix)
        if acs_radix == 4 and code.n_states < 4:
            raise ValueError(f"acs_radix=4 needs K >= 3 (got K={code.K})")
        # narrow modes: the re-derived normalization cadence must exist at
        # this radix — norm_interval raises a clear ValueError here, pre-jit,
        # when the budget cannot absorb the fused double-stage accumulation
        norm_interval(code, metric_mode, acs_radix)
        acs_k = DEFAULT_ACS_K  # inert under butterfly: one cache key
    if tb_mode == "serial" or not backend_tb_chunk_sensitive(backend):
        # the launch ignores tb_chunk (serial walk, or a chunk-free prefix
        # implementation): normalize it out of the jit cache key so callers
        # sweeping tb_chunk don't recompile identical launches
        tb_chunk = DEFAULT_TB_CHUNK
    return _decode_blocks_jit(
        y_blocks,
        code,
        decode_start=decode_start,
        n_decode=n_decode,
        start_policy=start_policy,
        backend=backend,
        stage_chunk=stage_chunk,
        interpret=interpret,
        n_real=sum(frame_counts) if frame_counts is not None else None,
        metric_mode=metric_mode,
        tb_mode=tb_mode,
        tb_chunk=tb_chunk,
        acs_radix=acs_radix,
        acs_impl=acs_impl,
        acs_k=acs_k,
    )
