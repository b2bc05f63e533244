"""Parallel Block-based Viterbi Decoder — configuration, framing and the
paper's throughput model (§III-A / eq. 7).

The stream of received soft symbols is framed into ``N_t`` parallel blocks of
decode length ``D``, each extended by ``M = L`` truncation stages on the left
and ``L`` traceback stages on the right (biting length ``2L`` between
adjacent blocks). All blocks decode independently → block-level parallelism
maps to TPU lanes (within a chip, via the Pallas kernels) × chips (via the
``(pod, data)`` mesh axes, `shard_map`/pjit — zero collectives, verified by
the dry-run).

The decode pipelines themselves live in :mod:`repro.core.engine` — a single
:class:`~repro.core.engine.DecoderEngine` parameterized by code spec, kernel
backend and sharding. ``decode_stream``/``decode_stream_sharded`` are kept as
thin wrappers over the engine for the original call sites.
"""

from __future__ import annotations

import dataclasses
from functools import partial
from typing import Literal

import jax
import jax.numpy as jnp

from repro.kernels.traceback import DEFAULT_TB_CHUNK

from .codespec import CodeSpec
from .quantize import (
    max_symbol_bits,
    metric_dtype_max,
    norm_interval,
    quantize_soft,
    u1_bytes,
    u2_bytes,
)
from .trellis import CCSDS_27, ConvCode

__all__ = [
    "PBVDConfig",
    "frame_stream",
    "decode_stream",
    "decode_stream_sharded",
    "throughput_model",
]


@dataclasses.dataclass(frozen=True)
class PBVDConfig:
    """Decoder configuration. Paper defaults: D=512, L=42 (≈6K), M=L.

    ``spec`` selects a :class:`~repro.core.codespec.CodeSpec` (code +
    puncturing); when given it overrides ``code`` (which is kept in sync so
    ``cfg.code`` always names the mother code the kernels run).

    ``metric_mode`` selects the path-metric pipeline (the
    :data:`~repro.kernels.registry.METRIC_MODES` contract): ``"f32"`` is the
    full-precision accumulate; ``"i16"``/``"i8"`` run the narrow normalized
    pipeline — the engine quantizes symbols to the widest width whose
    saturation budget fits the metric dtype (``effective_q``), so the narrow
    paths never saturate.

    ``tb_mode`` selects the traceback algorithm (the
    :data:`~repro.kernels.registry.TB_MODES` contract): ``"serial"`` walks
    one stage per step; ``"prefix"`` composes ``tb_chunk``-stage survivor
    maps in parallel and cuts the serial chain to ceil(T/tb_chunk) steps —
    bit-exact to serial for every chunk size. The default ``"auto"``
    resolves to the backend's declared ``preferred_tb_mode`` in the kernel
    registry (serial on all three backends today), so picking a backend no
    longer requires knowing the benchmark table.

    ``acs_radix`` selects the forward-ACS step (the
    :data:`~repro.kernels.registry.ACS_RADIX` contract): ``2`` is the
    paper's per-stage butterfly; ``4`` collapses two trellis stages into one
    stage-fused 4-way compare-select step — bit-exact decoded bits, half the
    forward serial chain, one normalization/survivor-emission round per two
    bits, and (fused backend) a double-buffered HBM→VMEM symbol pipeline.

    ``acs_impl`` selects the forward-pass formulation (the
    :data:`~repro.kernels.registry.ACS_IMPL` contract): ``"butterfly"`` is
    the compare-select trellis at ``acs_radix``; ``"matrix"`` collapses
    ``acs_k`` stages into one (min,+) tropical matmul step — bit-exact
    decoded bits, a k-fold shorter forward serial chain, and (Pallas paths)
    the 2^(kR-1) folded combined metrics assembled by one MXU-shaped
    matmul. ``acs_k`` is validated here at config time: structural bounds
    (1 ≤ k ≤ v, k·R ≤ 8) and, for narrow metric modes, the k-stage
    saturation budget — over-deep fusion fails with a ``ValueError``, never
    a silent in-kernel saturate.
    """

    code: ConvCode = CCSDS_27
    D: int = 512  # decode block length
    L: int = 42  # traceback depth (= truncation length M)
    q: int | None = 8  # soft-symbol quantization bits; None → float32
    start_policy: Literal["zero", "argmin"] = "zero"
    backend: Literal["pallas", "ref", "fused"] = "pallas"
    spec: CodeSpec | None = None
    metric_mode: Literal["f32", "i16", "i8"] = "f32"
    tb_mode: Literal["serial", "prefix", "auto"] = "auto"
    tb_chunk: int = DEFAULT_TB_CHUNK  # prefix traceback chunk size
    acs_radix: Literal[2, 4] = 2  # forward-ACS stages fused per step (radix/2)
    acs_impl: Literal["butterfly", "matrix"] = "butterfly"
    acs_k: int = 2  # matrix-ACS fusion depth (stages per tropical matmul)

    @property
    def T(self) -> int:  # stages per parallel block
        return self.D + 2 * self.L

    @property
    def codespec(self) -> CodeSpec:
        """The effective CodeSpec (wrapping ``code`` when none was given)."""
        if self.spec is not None:
            return self.spec
        return CodeSpec(name=f"(2,1,{self.code.K})" if self.code.R == 2 else "custom",
                        code=self.code)

    @property
    def effective_q(self) -> int | None:
        """Quantizer width the engine actually applies to float symbols.

        ``f32`` keeps ``q`` as configured; the narrow metric modes quantize
        unconditionally (int PMs need int symbols) and cap the width at the
        widest q whose worst-case metric fits the mode's dtype
        (:func:`~repro.core.quantize.max_symbol_bits`).
        """
        if self.metric_mode == "f32":
            return self.q
        # cap at the width the kernels' normalization cadence assumes
        # (metric_mode_qmax) — a wider engine-side q would void the budget
        cap = max_symbol_bits(self.code, metric_dtype_max(self.metric_mode))
        return min(self.q or 8, cap)

    def quantize(self, y):
        """Quantize float soft symbols per the configured metric mode.

        ``f32``/``i16`` use the quantizer's default 4σ-ish dynamic range. The
        coarse ``i8`` quantizer (q=3 for the registered codes) maps |y| = 2
        to full scale instead — burning two of three bits on ±4 headroom
        collapses the soft information (measured: rate-3/4 BER 0.21 → 0.009
        at 4.5 dB), while full scale at ±2 keeps the classic ≈0.2 dB 3-bit
        soft-decision loss.
        """
        q = self.effective_q
        if q is None:
            return y
        scale = ((1 << (q - 1)) - 1) / 2.0 if self.metric_mode == "i8" else None
        return quantize_soft(y, q, scale)

    def __post_init__(self):
        # knob validation mirrors the dispatcher's eager checks and raises
        # the SAME uniform error shape (repro.kernels.registry.knob_error:
        # backend, knob, allowed values) — a bad knob fails identically
        # whether it enters through the config or pbvd_decode_blocks, always
        # before any jit trace
        from repro.kernels.ops import (
            backend_acs_impl,
            backend_acs_radix,
            backend_metric_modes,
            backend_tb_modes,
            knob_error,
        )

        if self.D <= 0 or self.L < 0:
            raise ValueError("D must be positive, L non-negative")
        if self.metric_mode not in backend_metric_modes(self.backend):
            raise knob_error(
                self.backend, "metric_mode", self.metric_mode,
                backend_metric_modes(self.backend),
            )
        tb_allowed = (*backend_tb_modes(self.backend), "auto")
        if self.tb_mode not in tb_allowed:
            raise knob_error(self.backend, "tb_mode", self.tb_mode, tb_allowed)
        if self.tb_chunk < 1:
            raise ValueError(f"tb_chunk must be >= 1, got {self.tb_chunk}")
        if self.acs_impl not in backend_acs_impl(self.backend):
            raise knob_error(
                self.backend, "acs_impl", self.acs_impl,
                backend_acs_impl(self.backend),
            )
        if self.acs_radix not in backend_acs_radix(self.backend):
            raise knob_error(
                self.backend, "acs_radix", self.acs_radix,
                backend_acs_radix(self.backend),
            )
        if self.spec is not None and self.spec.code is not self.code:
            # keep cfg.code authoritative for kernel callers
            object.__setattr__(self, "code", self.spec.code)
        if self.acs_impl == "matrix":
            # structural bounds on the fusion depth, then the narrow-mode
            # budget for k unnormalized stages per matrix step — fail at
            # CONFIG time, not by silent saturation in-kernel
            self.code.validate_matrix_k(self.acs_k)
            norm_interval(self.code, self.metric_mode, stages_per_step=self.acs_k)
        elif self.acs_radix == 4:
            if self.code.n_states < 4:
                raise ValueError(f"acs_radix=4 needs K >= 3 (got K={self.code.K})")
            # narrow modes: the saturation budget must absorb the fused
            # step's two unnormalized stages — fail at CONFIG time, with
            # norm_interval's ValueError, not by silent saturation in-kernel
            norm_interval(self.code, self.metric_mode, self.acs_radix)


@partial(jax.jit, static_argnames=("D", "L", "n_blocks"))
def frame_stream(y: jnp.ndarray, D: int, L: int, n_blocks: int) -> jnp.ndarray:
    """Frame a symbol stream into overlapping parallel blocks.

    y: (n_sym, R) soft symbols → (T, R, N_t) with T = D + 2L. Block b covers
    global stages [bD - L, bD + D + L); out-of-range stages are zero
    (BM-neutral).
    """
    n_sym, R = y.shape
    T = D + 2 * L
    pad_tail = n_blocks * D + L - n_sym
    yp = jnp.pad(y, ((L, max(pad_tail, 0)), (0, 0)))
    # gather block windows: index matrix (T, N_t)
    idx = jnp.arange(T)[:, None] + jnp.arange(n_blocks)[None, :] * D
    blocks = yp[idx]  # (T, N_t, R)
    return jnp.transpose(blocks, (0, 2, 1))  # (T, R, N_t)


def decode_stream(
    y: jnp.ndarray,
    n_bits: int,
    cfg: PBVDConfig = PBVDConfig(),
    *,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Decode a soft-symbol stream. y: (n_sym, R) → (n_bits,) int32 bits.

    Thin wrapper over :class:`~repro.core.engine.DecoderEngine`.
    """
    from .engine import DecoderEngine

    return DecoderEngine(cfg).decode(y, n_bits, interpret=interpret)


def decode_stream_sharded(
    y: jnp.ndarray,
    n_bits: int,
    cfg: PBVDConfig,
    mesh: jax.sharding.Mesh,
    *,
    block_axes: tuple[str, ...] | None = ("data",),
    interpret: bool | None = None,
) -> jnp.ndarray:
    """Distributed stream decode: thin wrapper over a mesh-bound engine.

    ``block_axes=None`` resolves the ``"blocks"`` logical-axis rule against
    the mesh (see :class:`~repro.core.engine.DecoderEngine`).
    """
    from .engine import DecoderEngine

    engine = DecoderEngine(cfg, mesh=mesh, block_axes=block_axes)
    return engine.decode(y, n_bits, interpret=interpret)


def throughput_model(
    *,
    D: int,
    L: int,
    R: int,
    q: int | None,
    packed_out: bool,
    s_kernel_mbps: float,
    n_streams: int = 3,
    bandwidth_gbps: float = 8.0,
) -> float:
    """Paper eq. (7): decoding throughput in Mbps given kernel throughput S_k.

    ``bandwidth_gbps`` is the host↔device link (PCIe 2.0 ≈ 8 GB/s in the
    paper's GTX580 setup; a TPU host-DMA link is similar in spirit).

    Derived from first principles (the paper's eq. 7 with the bandwidth
    factored consistently):

      T/P [bit/s] = N_s / ((1 + 2L/D)·U₁/B + N_s/S_k + U₂/B)

    with U in bytes/bit, B in bytes/s, S_k in bit/s.
    """
    B = bandwidth_gbps * 1e9  # bytes/s
    s_k = s_kernel_mbps * 1e6  # bit/s
    u1 = u1_bytes(R, q)
    u2 = u2_bytes(packed_out)
    denom = (1.0 + 2.0 * L / D) * u1 / B + n_streams / s_k + u2 / B
    return n_streams / denom / 1e6  # Mbps
