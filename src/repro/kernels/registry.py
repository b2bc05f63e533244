"""Backend registry for the PBVD decode kernels.

Every backend is a function with the common contract

    backend(blocks: FramedBlocks, code: ConvCode, *,
            start_policy, stage_chunk, interpret, metric_mode,
            tb_mode, tb_chunk)
        -> (n_decode, B_real) int32 bits

registered under a name via ``@register_backend("name")``. The engine (and
the legacy ``pbvd_decode_blocks`` wrapper) dispatch through :func:`get_backend`
— adding a backend is one decorated function, not another ``if`` branch in
the decode path (DESIGN.md §1).

Contract details (DESIGN.md §3):

* The lane axis of ``FramedBlocks.y`` may be a flattened **frames × blocks**
  packing: the blocks of several independent streams ride one launch,
  concatenated along the lane dimension, with ``frame_counts`` recording how
  many real blocks each frame contributed. Every backend must return exactly
  ``blocks.n_real_blocks`` lanes — trailing pad lanes (power-of-two shape
  budget, lane-tile rounding, shard padding) are the backend's to trim.
* Backends declare which traceback start policies they implement via
  ``register_backend(name, start_policies=...)``; the dispatcher validates
  the policy *before* entering jit so unsupported combinations fail with an
  eager ``ValueError`` instead of a trace-time error.
* Backends likewise declare the **metric modes** they implement
  (``register_backend(name, metric_modes=...)``); the mode semantics are the
  :data:`METRIC_MODES` contract below, validated eagerly the same way.
* Backends declare the **traceback modes** they implement
  (``register_backend(name, tb_modes=...)``); the mode semantics are the
  :data:`TB_MODES` contract below (serial stage walk vs chunked
  parallel-prefix survivor-map composition), validated eagerly the same way.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Protocol

__all__ = [
    "FramedBlocks",
    "DecodeBackend",
    "METRIC_MODES",
    "TB_MODES",
    "ACS_RADIX",
    "ACS_IMPL",
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


# ---------------------------------------------------------------------------
# The quantized-metric contract (DESIGN.md §8)
# ---------------------------------------------------------------------------
# ``metric_mode`` fixes the *semantics* of the path-metric pipeline — symbol
# width, normalization cadence, and saturation budget. Storage width is a
# backend implementation detail: the pure-XLA ``ref`` backend stores PM in
# the narrow dtype (CPU SIMD lanes are 2–4× wider at int16/int8), while the
# Pallas kernels keep 32-bit VPU registers (TPU lanes are 32-bit; the narrow
# win there is HBM symbol traffic, already int8) — bit-identical either way,
# because the budget keeps every value inside the narrow range.
#
# Saturation budget (see ``repro.core.quantize.pm_spread_bound``): with
# min-subtract normalization every k stages and symbols bounded by
# |y| ≤ qmax, every path metric ever formed obeys
# |PM| ≤ (2·v + k)·R·qmax. A mode is well-defined for a code/quantizer pair
# iff that bound fits ``pm_dtype`` — the engine picks the widest symbol
# quantizer that satisfies it at k=1 (``repro.core.quantize.max_symbol_bits``)
# and the kernels spend the remaining headroom on the normalization cadence
# (``repro.core.quantize.norm_interval``; identical k in every backend), so
# the narrow paths can NEVER saturate, regardless of stream length
# (10k-stage adversarial streams are driven against this in
# tests/test_kernels.py).
METRIC_MODES: dict[str, dict[str, Any]] = {
    "f32": dict(
        pm_dtype="float32/int32",
        symbols="float32, or any pre-quantized int (exact int32 accumulation)",
        normalization="none (unbounded accumulation)",
        saturation_budget="int32 headroom: 2^31 / (R·2^q) stages per block",
    ),
    "i16": dict(
        pm_dtype="int16",
        symbols="int8 (q ≤ 8; widest q with the k=1 budget ≤ 32767)",
        normalization="min-subtract every norm_interval(code, 'i16') stages "
        "(per lane; ~100+ for the registered codes)",
        saturation_budget="(2·v+k)·R·qmax ≤ 32767 — hard-decision bit-exact "
        "to f32 on the same symbols",
    ),
    "i8": dict(
        pm_dtype="int8",
        symbols="coarse int (widest q with the k=1 budget ≤ 127; q=3 for "
        "the registered codes)",
        normalization="min-subtract every norm_interval(code, 'i8') stages "
        "(per lane; ~8-9 for the registered codes)",
        saturation_budget="(2·v+k)·R·qmax ≤ 127 — exact vs f32 on the same "
        "coarse symbols; vs q=8 the difference is the quantizer's (≈0.2–0.3 dB "
        "at 3-bit soft decisions)",
    ),
}


# ---------------------------------------------------------------------------
# The traceback-mode contract (DESIGN.md §9)
# ---------------------------------------------------------------------------
# ``tb_mode`` fixes the *algorithm* of the K2 traceback phase; both modes are
# bit-exact for every survivor history (composition of exact predecessor maps
# commutes with the walk), so the choice is purely a latency/VMEM trade:
#
# * ``"serial"`` — the paper's walk: one W-way word select + variable shift
#   per stage, ``T - decode_start`` strictly serial steps on (1, lanes)
#   operands. Minimal memory, maximal dependency chain.
# * ``"prefix"`` — chunked survivor-map composition: each chunk of
#   ``tb_chunk`` stages is composed into one N-entry state map (parallel
#   across chunks × states on the sublane axis, same select idiom), the
#   composed maps are walked in ceil(T/tb_chunk) serial steps, and all
#   chunks' bits re-expand in parallel. ``tb_chunk`` bounds the composed-map
#   scratch: (ceil(T/C) - c_lo)·N·lanes·4 B per lane tile (see DESIGN.md §9
#   for the VMEM cost model and the chunk-size sweet spot).
#
# ``tb_chunk`` is a jit static — changing the chunk size recompiles a
# chunk-sensitive prefix launch, it never re-frames. Where the launch
# ignores it (``tb_mode="serial"``, or a backend registered with
# ``tb_chunk_sensitive=False`` such as ``ref``'s full-depth scan) the
# dispatcher normalizes it out of the cache key.
TB_MODES: dict[str, dict[str, Any]] = {
    "serial": dict(
        serial_steps="T - decode_start (early exit below the decode region)",
        scratch="none beyond the survivor history",
        when="tiny T, VMEM-starved geometries, or as the parity oracle",
    ),
    "prefix": dict(
        serial_steps="ceil(T/tb_chunk) composed-map walk",
        scratch="composed maps (n_active·N·lanes·4 B) + entry states + "
        "(fused) unpacked chunk bits",
        when="where the backend declares it profitable — the last O(T) "
        "chain becomes O(T/C) with sublane-parallel composition/expansion",
    ),
}

# ``tb_mode="auto"`` is not an algorithm: the dispatcher resolves it to the
# backend's declared measured-fastest mode (``register_backend(
# preferred_tb_mode=...)``) BEFORE the tb_modes validation, so callers get
# the per-backend winner without knowing the benchmark table. The
# declarations encode BENCH_pr.json on the platform it was recorded:
# prefix on ``ref`` runs at 0.14-0.39× serial (XLA already fuses the
# serial scan; the associative scan pays gather-composition for nothing on
# CPU), and the Pallas kernels' interpret lowering pays similarly for the
# composition phases. A backend flips its declaration to "prefix" the
# moment a committed bench measures it profitable there (the design case:
# real-TPU runs, where the serial walk is the dependency-chain bottleneck
# the chunked composition removes).


# ---------------------------------------------------------------------------
# The ACS-radix contract (DESIGN.md §10)
# ---------------------------------------------------------------------------
# ``acs_radix`` fixes how many trellis stages one forward-ACS step collapses.
# Both radixes are bit-exact for every input (the radix-4 step emits the two
# STANDARD radix-2 survivor bit-planes, and its compare/select tree
# reproduces the two-stage comparisons exactly — by integer associativity on
# the narrow pipeline, by a staged add order in f32), so the choice is a
# pure serial-chain/bandwidth trade:
#
# * ``2`` — the paper's butterfly: one stage per step, T serial steps.
# * ``4`` — stage-fused: ceil(T/2) steps of 4-way compare-select per state
#   over the collapsed two-stage trellis (4 predecessors, combined 2-symbol
#   labels with only 2^(2R-1) distinct folded metrics per step), one
#   normalization/survivor-emission round amortized over two decoded bits;
#   the fused backend additionally double-buffers the symbol reads
#   (HBM→VMEM prefetch of the next step's tile overlaps the current
#   butterfly). Odd T runs one trailing radix-2 step. Narrow metric modes
#   re-derive the normalization cadence for the doubled per-step
#   accumulation (``quantize.norm_interval(code, mode, radix)``) and reject
#   code/mode pairs whose budget cannot absorb two unnormalized stages —
#   eagerly, before any tracing.
ACS_RADIX: dict[int, dict[str, Any]] = {
    2: dict(
        serial_steps="T butterfly stages",
        metrics_per_step="2^(R-1) folded branch metrics",
        when="the default: tiny codes (K < 3), narrow modes whose budget "
        "cannot absorb two unnormalized stages, and the measured winner on "
        "the ref/CPU backend at small batch (BENCH_pr.json acs_radix_sweep)",
    ),
    4: dict(
        serial_steps="ceil(T/2) stage-fused steps (+1 radix-2 step, odd T)",
        metrics_per_step="2^(2R-1) folded combined two-stage metrics",
        when="the ACS-bound regime (98% of decode time post-PR 4) — halves "
        "the forward serial chain and amortizes normalization/emission "
        "over two bits; fused backend overlaps symbol HBM reads via a "
        "double-buffered VMEM pipeline",
    ),
}


# ---------------------------------------------------------------------------
# The ACS-implementation contract (DESIGN.md §11)
# ---------------------------------------------------------------------------
# ``acs_impl`` fixes the *formulation* of the forward-ACS step. Both are
# bit-exact for every input — the matrix path emits the STANDARD radix-2
# survivor bit-planes per collapsed stage (recovered from its compare
# tournament), so traceback, SP layout and golden vectors are untouched —
# and the choice is a pure compute-unit/arithmetic-intensity trade:
#
# * ``"butterfly"`` — the paper's compare-select butterflies (radix 2, or
#   the PR 5 stage-fused radix 4 under ``acs_radix``), element-wise VPU
#   work throughout.
# * ``"matrix"`` — the tensor-core formulation (arXiv:2011.13579): ``acs_k``
#   consecutive stages collapse into ONE (min,+) matrix-vector product
#   ``new_pm[n'] = min_n (A[n', n] + pm[n])``, ceil(T/acs_k) steps. The
#   k-stage matrix A is assembled from only 2^(kR-1) folded combined
#   metrics (the PR 3 antipodal fold composed over the stage window) — on
#   the Pallas path as ONE dense signed one-hot matmul shaped for the MXU
#   (``ConvCode.matrix_expansion``), with the min-tournament contraction
#   (and per-stage survivor-plane recovery) on the VPU. Integer
#   accumulators take the flat contraction (exact by associativity); f32
#   accumulators lower to the staged radix-2 sequence, because IEEE float
#   addition is not associative and the contract is bit-exactness, not
#   approximate parity. ``acs_k`` is validated eagerly: 1 ≤ k ≤ v,
#   k·R ≤ MATRIX_MAX_LABEL_BITS, and narrow metric modes must absorb k
#   unnormalized stages per step (``quantize.norm_interval(code, mode,
#   stages_per_step=k)`` — config-time rejection, never a silent saturate).
#   When ``acs_impl="matrix"``, ``acs_radix`` is inert and normalized out
#   of the jit cache key (and ``acs_k`` likewise under ``"butterfly"``).
ACS_IMPL: dict[str, dict[str, Any]] = {
    "butterfly": dict(
        serial_steps="T (radix 2) or ceil(T/2) (radix 4) compare-select steps",
        metrics_per_step="2^(R-1) or 2^(2R-1) folded branch metrics",
        when="the default: VPU-bound element-wise ACS, the paper's "
        "formulation, and the measured winner under XLA CPU SIMD",
    ),
    "matrix": dict(
        serial_steps="ceil(T/acs_k) tropical matmul steps "
        "(+ T mod acs_k trailing radix-2 stages)",
        metrics_per_step="2^(acs_k·R-1) folded combined metrics, assembled "
        "by one signed one-hot (2^k·N, 2^(kR-1)) MXU matmul",
        when="MXU-rich hardware where the k-fold shorter serial chain and "
        "the matmul-shaped metric assembly beat the VPU butterflies "
        "(BENCH_pr.json acs_impl_sweep)",
    ),
}


def knob_error(backend: str, knob: str, value: Any, allowed) -> ValueError:
    """The uniform eager knob-validation error.

    Both validation layers — the dispatcher (``pbvd_decode_blocks``) and the
    config (``PBVDConfig``) — raise exactly this shape, naming the backend,
    the offending knob and the allowed values, so a bad knob fails the same
    way no matter which door it came through, always before any jit trace.
    """
    return ValueError(
        f"backend {backend!r} does not support {knob}={value!r}; "
        f"supported {knob} values: {tuple(allowed)}"
    )


@dataclasses.dataclass(frozen=True)
class FramedBlocks:
    """The framed parallel-block batch every backend consumes.

    ``y``: (T, R, B) soft symbols (float32, or int8/int16 for the exact
    quantized path), framed [truncation M | decode D | traceback L].
    ``decode_start``/``n_decode``: the decode region within the T stages.
    ``frame_counts``: when the lane axis packs several frames (independent
    streams), the number of real blocks each frame contributed, in lane
    order; ``None`` means a single frame spanning every lane. Lanes beyond
    ``sum(frame_counts)`` are padding and must be trimmed by the backend.
    """

    y: Any  # jnp.ndarray (possibly a tracer)
    decode_start: int
    n_decode: int
    frame_counts: tuple[int, ...] | None = None

    def __post_init__(self):
        if self.frame_counts is not None:
            if any(k <= 0 for k in self.frame_counts):
                raise ValueError(
                    f"frame_counts must be positive, got {self.frame_counts}"
                )
            if sum(self.frame_counts) > self.y.shape[2]:
                raise ValueError(
                    f"frame_counts {self.frame_counts} sum to "
                    f"{sum(self.frame_counts)} > lane axis {self.y.shape[2]}"
                )

    @property
    def shape(self) -> tuple[int, int, int]:
        return tuple(self.y.shape)

    @property
    def n_frames(self) -> int:
        return 1 if self.frame_counts is None else len(self.frame_counts)

    @property
    def n_real_blocks(self) -> int:
        """Real (non-pad) lanes; what every backend must return."""
        if self.frame_counts is None:
            return int(self.y.shape[2])
        return sum(self.frame_counts)

    def frame_slices(self) -> list[slice]:
        """Lane-axis slice of each packed frame, in order."""
        if self.frame_counts is None:
            return [slice(0, int(self.y.shape[2]))]
        out, lo = [], 0
        for k in self.frame_counts:
            out.append(slice(lo, lo + k))
            lo += k
        return out


class DecodeBackend(Protocol):
    def __call__(
        self,
        blocks: FramedBlocks,
        code: Any,
        *,
        start_policy: str,
        stage_chunk: int,
        interpret: bool,
        metric_mode: str,
        tb_mode: str,
        tb_chunk: int,
        acs_radix: int,
        acs_impl: str,
        acs_k: int,
    ) -> Any: ...


_BACKENDS: dict[str, DecodeBackend] = {}


def register_backend(
    name: str,
    *,
    start_policies: tuple[str, ...] = ("zero", "argmin"),
    metric_modes: tuple[str, ...] = ("f32",),
    tb_modes: tuple[str, ...] = ("serial",),
    tb_chunk_sensitive: bool = True,
    preferred_tb_mode: str = "serial",
    acs_radix: tuple[int, ...] = (2,),
    acs_impl: tuple[str, ...] = ("butterfly",),
    lane_tile: int = 1,
) -> Callable[[DecodeBackend], DecodeBackend]:
    """Decorator: register a decode backend under ``name``.

    ``start_policies`` declares which traceback start policies the backend
    implements; ``metric_modes`` declares which :data:`METRIC_MODES` entries
    it implements; ``tb_modes`` declares which :data:`TB_MODES` traceback
    algorithms it implements; ``acs_radix`` declares which :data:`ACS_RADIX`
    forward-ACS radixes it implements; ``acs_impl`` declares which
    :data:`ACS_IMPL` forward-pass formulations it implements. The dispatcher
    rejects others eagerly (pre-jit). The defaults are the conservative
    ``("f32",)``/``("serial",)``/``(2,)``/``("butterfly",)`` — a backend
    must OPT INTO the narrow pipeline, the prefix traceback, the
    stage-fused ACS and the (min,+) matrix ACS explicitly, otherwise the
    eager check would wave through modes it never implemented.

    ``preferred_tb_mode`` declares the backend's measured-fastest traceback
    mode — what ``tb_mode="auto"`` resolves to (must be in ``tb_modes``).

    ``tb_chunk_sensitive=False`` declares that the backend's prefix
    traceback ignores ``tb_chunk`` (e.g. a full-depth associative scan): the
    dispatcher then normalizes the knob out of the jit cache key, and the
    benchmarks collapse the chunk sweep dimension.

    ``lane_tile`` declares the lane multiple the backend pads a launch to
    (its kernels' lane tile); :func:`launched_lanes` is that padding, which
    the backend applies and the serving layer counts.
    """
    if lane_tile < 1:
        raise ValueError(f"lane_tile must be >= 1, got {lane_tile}")
    unknown = set(metric_modes) - METRIC_MODES.keys()
    if unknown:
        raise ValueError(f"unknown metric modes {sorted(unknown)}")
    unknown_tb = set(tb_modes) - TB_MODES.keys()
    if unknown_tb:
        raise ValueError(f"unknown tb modes {sorted(unknown_tb)}")
    unknown_radix = set(acs_radix) - ACS_RADIX.keys()
    if unknown_radix:
        raise ValueError(f"unknown acs radixes {sorted(unknown_radix)}")
    unknown_impl = set(acs_impl) - ACS_IMPL.keys()
    if unknown_impl:
        raise ValueError(f"unknown acs impls {sorted(unknown_impl)}")
    if preferred_tb_mode not in tb_modes:
        raise ValueError(
            f"preferred_tb_mode {preferred_tb_mode!r} not in tb_modes {tb_modes}"
        )

    def deco(fn: DecodeBackend) -> DecodeBackend:
        if name in _BACKENDS:
            raise ValueError(f"backend {name!r} already registered")
        _BACKENDS[name] = fn
        fn.backend_name = name  # type: ignore[attr-defined]
        fn.start_policies = tuple(start_policies)  # type: ignore[attr-defined]
        fn.metric_modes = tuple(metric_modes)  # type: ignore[attr-defined]
        fn.tb_modes = tuple(tb_modes)  # type: ignore[attr-defined]
        fn.tb_chunk_sensitive = bool(tb_chunk_sensitive)  # type: ignore[attr-defined]
        fn.preferred_tb_mode = str(preferred_tb_mode)  # type: ignore[attr-defined]
        fn.acs_radix = tuple(acs_radix)  # type: ignore[attr-defined]
        fn.acs_impl = tuple(acs_impl)  # type: ignore[attr-defined]
        fn.lane_tile = int(lane_tile)  # type: ignore[attr-defined]
        return fn

    return deco


def get_backend(name: str) -> DecodeBackend:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise KeyError(
            f"unknown decode backend {name!r}; available: {available_backends()}"
        ) from None


def backend_start_policies(name: str) -> tuple[str, ...]:
    """Start policies the named backend supports."""
    return getattr(get_backend(name), "start_policies", ("zero", "argmin"))


def backend_metric_modes(name: str) -> tuple[str, ...]:
    """Metric modes the named backend supports (see :data:`METRIC_MODES`)."""
    return getattr(get_backend(name), "metric_modes", ("f32",))


def backend_tb_modes(name: str) -> tuple[str, ...]:
    """Traceback modes the named backend supports (see :data:`TB_MODES`)."""
    return getattr(get_backend(name), "tb_modes", ("serial",))


def backend_tb_chunk_sensitive(name: str) -> bool:
    """Whether the named backend's prefix traceback depends on ``tb_chunk``."""
    return getattr(get_backend(name), "tb_chunk_sensitive", True)


def backend_acs_radix(name: str) -> tuple[int, ...]:
    """Forward-ACS radixes the named backend supports (see :data:`ACS_RADIX`)."""
    return getattr(get_backend(name), "acs_radix", (2,))


def backend_acs_impl(name: str) -> tuple[str, ...]:
    """Forward-ACS formulations the named backend supports (see :data:`ACS_IMPL`)."""
    return getattr(get_backend(name), "acs_impl", ("butterfly",))


def backend_preferred_tb_mode(name: str) -> str:
    """The named backend's declared measured-fastest traceback mode."""
    return getattr(get_backend(name), "preferred_tb_mode", "serial")


def launched_lanes(name: str, n: int) -> int:
    """Lanes a launch of ``n`` lanes runs on the named backend: ``n`` rounded
    up to the backend's declared lane tile."""
    tile = getattr(get_backend(name), "lane_tile", 1)
    return -(-n // tile) * tile


def resolve_tb_mode(name: str, tb_mode: str) -> str:
    """Resolve ``"auto"`` to the backend's preferred mode; pass others through.

    Eager (pre-jit): the resolved mode is what enters the tb_modes
    validation, the jit cache key and the SessionPool group key, so an
    ``"auto"`` session coalesces with sessions that spelled the mode out.
    """
    return backend_preferred_tb_mode(name) if tb_mode == "auto" else tb_mode


def available_backends() -> list[str]:
    return sorted(_BACKENDS)
