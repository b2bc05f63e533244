"""Pallas TPU kernel for the PBVD forward ACS phase (paper kernel K1).

TPU mapping (see DESIGN.md §2):

* parallel blocks live on the **lane axis** (tiles of ``LANE_TILE = 128``);
  the trellis states live on sublanes — ``PM`` is a ``(N, 128)`` VMEM-resident
  matrix per program instance (for the CCSDS code: 64×128×4 B = 32 KiB).
* the stage loop is tiled by the second grid dimension; ``PM`` persists in a
  VMEM scratch across stage-chunks (grid iterates stage-chunks innermost) and
  is re-zeroed at chunk 0 — this is the TPU analogue of the GPU kernel
  keeping PM in shared memory for the whole block.
* **symmetry-folded branch metrics**: the correlation metric is antipodal in
  the label (BM(~c) = -BM(c)), so only ``2^(R-1)`` folded metrics exist per
  stage — half the paper's ``2^R`` group metrics. The folded rows are built
  with static add/sub chains (the ±1 signs are trace-time constants — zero
  multiplies), and the four per-butterfly metric rows (α/γ/β/θ) are expanded
  with **static sign selects**: each butterfly's row is ``±`` one of the
  folded entries, negated in-register. No gathers, no warp shuffles.
* the butterfly read ``PM[2j], PM[2j+1]`` is a free sublane reshape
  ``(N, T) → (N/2, 2, T)``; the write-back is a concat of the top/bottom
  halves. No shared-memory banking concerns exist on TPU.
* survivor decisions are bit-packed on the fly to ``ceil(N/32)`` int32 words
  per stage (weighted sublane reduction), giving the paper's
  ``SP[T][words][blocks]`` layout with fully coalesced (lane-contiguous)
  stores — and 32× less HBM traffic than byte-per-state.

The same kernel body runs the float32 path and the exact integer path.
``metric_mode`` selects the path-metric pipeline semantics (see
``repro.kernels.registry.METRIC_MODES``): ``"f32"`` accumulates unbounded
(int32 for integer symbols), ``"i16"``/``"i8"`` add the amortized
min-subtract normalization (every ``norm_interval(code, mode)`` stages,
counted in GLOBAL stage indices so stage-chunking cannot move the
normalization points) whose saturation budget bounds every metric within
int16/int8 range. The TPU VPU computes on 32-bit lanes either way, so
the kernel keeps int32 registers — the narrow dtypes are a *storage/traffic*
contract (symbols arrive int8 over HBM; the pure-XLA ``ref`` backend stores
PM natively narrow) and the normalized values here are bit-identical to the
narrow-dtype arithmetic because they never leave the narrow range.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quantize import metric_mode_qmax, norm_interval
from repro.core.trellis import ConvCode
from .ref import _acc_dtype_for

__all__ = [
    "acs_forward_pallas",
    "folded_matrix_bm_rows",
    "matrix_step",
    "LANE_TILE",
    "DEFAULT_STAGE_CHUNK",
]

LANE_TILE = 128
DEFAULT_STAGE_CHUNK = 64


def folded_bm_rows(y_s, code: ConvCode, acc_dtype):
    """(R, TILE) stage symbols → 2·2^(R-1) rows [+folded, -folded], (1, TILE) each.

    Static add/sub chains over the fold representatives' ±1 signs (trace-time
    constants — no multiplies, no table input); the negated set is the
    in-register sign application the expansion selects from.
    """
    fsv = code.folded_codeword_signs  # (2^(R-1), R) static ±1
    pos, neg = [], []
    for k in range(code.n_folded):
        acc = None
        for r in range(code.R):
            term = y_s[r] if fsv[k, r] > 0 else -y_s[r]
            acc = term if acc is None else acc + term
        row = acc.astype(acc_dtype)[None, :]
        pos.append(row)
        neg.append(-row)
    return pos, neg


def expand_run_rows(pos, neg, idx, sgn, tile: int):
    """Expand static (index, sign) tables over ±folded rows to a metric row.

    ``pos``/``neg`` are lists of (1, TILE) folded rows and their negations;
    ``idx``/``sgn`` are STATIC int arrays (trace-time constants). The
    expansion is a run-length concat of broadcast ±folded rows — no captured
    constants, no gathers — and exactly equals the gather-based form.
    """
    runs: list[tuple[tuple[int, int], int]] = []
    for i, s in zip(idx.tolist(), sgn.tolist()):
        if runs and runs[-1][0] == (i, s):
            runs[-1] = (runs[-1][0], runs[-1][1] + 1)
        else:
            runs.append(((i, s), 1))
    parts = [
        jnp.broadcast_to(pos[k] if s > 0 else neg[k], (cnt, tile))
        for (k, s), cnt in runs
    ]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0)


def butterfly_bm_row(pos, neg, code: ConvCode, key: str, tile: int, acc_dtype):
    """Expand the folded rows to a (n_butterflies, TILE) per-butterfly row.

    ``key`` ∈ {te, to, be, bo} names the α/γ/β/θ codeword column. Each
    butterfly's metric is ± one folded entry; the (index, sign) tables are
    static, so the expansion is a static run-length concat of broadcast
    ±folded rows (no captured constants, no gathers) — cheaper than the
    4·nb·R multiply-adds of the unfolded form and exactly equal to it.
    """
    tabs = code.folded_acs_tables
    return expand_run_rows(
        pos, neg, tabs["fold_cw_" + key], tabs["fold_sgn_" + key], tile
    )


def folded_radix4_bm_rows(y0, y1, code: ConvCode, acc_dtype):
    """Stage-pair symbols → 2^(2R-1) combined folded rows [+, −], (1, TILE) each.

    The combined two-stage label stays antipodal (BM2(~cc) = −BM2(cc)), so
    one static add/sub chain per fold representative covers all 2^(2R)
    combined metrics — the PR 3 fold composed over the stage pair.
    """
    fsv = code.folded_radix4_codeword_signs  # (2^(2R-1), 2R) static ±1
    R = code.R
    pos, neg = [], []
    for k in range(code.n_folded4):
        acc = None
        for r in range(2 * R):
            y_r = y0[r] if r < R else y1[r - R]
            term = y_r if fsv[k, r] > 0 else -y_r
            acc = term if acc is None else acc + term
        row = acc.astype(acc_dtype)[None, :]
        pos.append(row)
        neg.append(-row)
    return pos, neg


def folded_matrix_bm_rows(ys, code: ConvCode, k: int, acc_dtype):
    """k stage symbol rows → 2^(kR-1) combined folded rows [+, −], (1, TILE) each.

    The k-stage combined label stays antipodal (BM_k(~cc) = −BM_k(cc)), so
    one static add/sub chain per fold representative covers all 2^(kR)
    combined metrics — the PR 3 fold composed over the k-stage window
    (radix-4's two-stage fold generalized). ``ys`` is a list of k (R, TILE)
    stage rows, stage t first.
    """
    fsv = code.folded_matrix_codeword_signs(k)  # (2^(kR-1), kR) static ±1
    R = code.R
    pos, neg = [], []
    for m in range(code.n_folded_matrix(k)):
        acc = None
        for r in range(k * R):
            y_r = ys[r // R][r % R]
            term = y_r if fsv[m, r] > 0 else -y_r
            acc = term if acc is None else acc + term
        row = acc.astype(acc_dtype)[None, :]
        pos.append(row)
        neg.append(-row)
    return pos, neg


def matrix_step(pm, ys, code: ConvCode, acc_dtype, tile: int, k: int, e=None):
    """One k-stage (min,+) matrix ACS step on (N, TILE) operands.

    Mirrors :func:`repro.kernels.ref._matrix_step` (integer accumulators
    only — the wrappers lower float to the staged butterfly): the k-stage
    transition metrics A[c, j, u] are assembled from the 2^(kR-1) folded
    combined rows, then ceil-log2(2^k) suffix-min tournament rounds reduce
    the 2^k candidates per target while emitting the k STANDARD radix-2
    survivor bit-planes (round i's decisions, laid out over the canonical
    covering c < 2^(i+1) — exact because later-round terms are common
    additive offsets under integer min).

    Two assembly modes:

    * ``e=None`` — static (index, sign) run-length expansion over the ±folded
      rows per (c, j) (the VPU form; no gathers, like the butterfly path).
    * ``e`` given — the (2^k·N, 2^(kR-1)) signed one-hot expansion operand:
      ONE dense matmul ``E @ folded`` produces every transition metric — the
      MXU-shaped form. Exact: one ±1 per row, and |BM_k| ≤ kR·qmax ≪ 2^24 is
      below f32's integer-exact range, so the f32 accumulate round-trips to
      int losslessly.

    Returns (new_pm, planes): time-(t+k) metrics plus k (N, TILE) decision
    planes, stage t first.
    """
    N = code.n_states
    U = N >> k
    nk = 1 << k
    pos, neg = folded_matrix_bm_rows(ys, code, k, acc_dtype)
    if e is not None:
        folded = jnp.concatenate(pos, axis=0).astype(jnp.float32)
        a = jnp.dot(e, folded, preferred_element_type=jnp.float32)
        a = a.astype(acc_dtype).reshape(nk, nk, U, tile)

        def bm(c, j):
            return a[c, j]

    else:
        tabs = code.matrix_acs_tables(k)

        def bm(c, j):
            return expand_run_rows(
                pos, neg, tabs["fold_idx"][c, j], tabs["fold_sgn"][c, j], tile
            )

    pmk = pm.reshape(U, nk, tile)
    levels = {c: [pmk[:, j] + bm(c, j) for j in range(nk)] for c in range(nk)}
    planes = []
    for i in range(k):
        n_c = 1 << (i + 1)
        parts, nxt = [], {}
        for c in range(nk):
            cur = levels[c]
            d = [
                (cur[2 * h + 1] < cur[2 * h]).astype(jnp.int32)
                for h in range(len(cur) // 2)
            ]
            nxt[c] = [
                jnp.minimum(cur[2 * h], cur[2 * h + 1]) for h in range(len(cur) // 2)
            ]
            if c < n_c:
                parts.append(
                    d[0]
                    if len(d) == 1
                    else jnp.stack(d, axis=1).reshape(len(d) * U, tile)
                )
        levels = nxt
        planes.append(parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=0))
    new_pm = jnp.concatenate([levels[c][0] for c in range(nk)], axis=0)
    return new_pm, planes


def _pack_plane(dec, tile: int):
    """(N, TILE) {0,1} decisions → (ceil(N/32), TILE) int32 packed words."""
    pad = (-dec.shape[0]) % 32
    if pad:
        dec = jnp.concatenate([dec, jnp.zeros((pad, tile), jnp.int32)], axis=0)
    d = dec.reshape(-1, 32, tile)
    weights = (jnp.int32(1) << jnp.arange(32, dtype=jnp.int32))[None, :, None]
    return (d * weights).sum(axis=1, dtype=jnp.int32)


def radix4_stage_pair(pm, y0, y1, code: ConvCode, acc_dtype, tile: int, combine: bool = False):
    """One stage-fused radix-4 ACS step on (N, TILE) operands.

    Mirrors :func:`repro.kernels.ref._radix4_step` with the Pallas row
    idiom: the metric tables are expanded by static run-length concats of
    ±folded rows (no gathers). The default (staged) form shares the first
    tournament round between the two target groups with the same stage-t
    input bit and fixes the add order to the two-stage accumulation — the
    identical op sequence as two radix-2 stages (bit-exact even in IEEE
    float), fused into one step body: one symbol fetch, one normalization
    round and one survivor-emission round per two decoded bits.

    ``combine=True`` (integer accumulators only) adds the combined
    2^(2R-1)-folded two-stage metric once per candidate instead — exact by
    integer associativity, one fewer dependent add round at the cost of N
    extra compare/selects (the measured alternative; see DESIGN.md §10).

    Returns (new_pm, dec1, dec2): the time-(t+2) metrics plus the two
    STANDARD radix-2 survivor bit-planes of stages t and t+1.
    """
    N = code.n_states
    Q = N // 4
    tabs = code.radix4_acs_tables
    pm4 = pm.reshape(Q, 4, tile)
    if combine and jnp.issubdtype(acc_dtype, jnp.integer):
        pos2, neg2 = folded_radix4_bm_rows(y0, y1, code, acc_dtype)
        d1, l1 = {}, {}
        for k in range(4):
            cand = [
                pm4[:, j]
                + expand_run_rows(
                    pos2, neg2, tabs["fold_cc_idx"][k, j], tabs["fold_cc_sgn"][k, j], tile
                )
                for j in range(4)
            ]
            for bm_bit in (0, 1):
                even, odd = cand[2 * bm_bit], cand[2 * bm_bit + 1]
                d1[k, bm_bit] = (odd < even).astype(jnp.int32)
                l1[k, bm_bit] = jnp.minimum(even, odd)
    else:
        pos_a, neg_a = folded_bm_rows(y0, code, acc_dtype)
        pos_b, neg_b = folded_bm_rows(y1, code, acc_dtype)
        mu, d1v = {}, {}
        for x1 in range(2):
            a = [
                pm4[:, j]
                + expand_run_rows(
                    pos_a, neg_a, tabs["fold_c1_idx"][x1, j], tabs["fold_c1_sgn"][x1, j], tile
                )
                for j in range(4)
            ]
            for bm_bit in (0, 1):
                even, odd = a[2 * bm_bit], a[2 * bm_bit + 1]
                d1v[x1, bm_bit] = (odd < even).astype(jnp.int32)
                mu[x1, bm_bit] = jnp.minimum(even, odd)
        d1, l1 = {}, {}
        for k in range(4):
            for bm_bit in (0, 1):
                d1[k, bm_bit] = d1v[k & 1, bm_bit]
                l1[k, bm_bit] = mu[k & 1, bm_bit] + expand_run_rows(
                    pos_b, neg_b, tabs["fold_c2_idx"][k, bm_bit], tabs["fold_c2_sgn"][k, bm_bit], tile
                )
    outs, d2 = [], []
    for k in range(4):
        d2.append((l1[k, 1] < l1[k, 0]).astype(jnp.int32))
        outs.append(jnp.minimum(l1[k, 0], l1[k, 1]))
    new_pm = jnp.concatenate(outs, axis=0)
    # stage-t plane from groups k=0/1 (intermediates [0, N/2)/[N/2, N));
    # the interleave is a free sublane reshape, like the butterfly read
    dec1 = jnp.concatenate(
        [
            jnp.stack([d1[0, 0], d1[0, 1]], axis=1).reshape(N // 2, tile),
            jnp.stack([d1[1, 0], d1[1, 1]], axis=1).reshape(N // 2, tile),
        ],
        axis=0,
    )
    dec2 = jnp.concatenate(d2, axis=0)
    return new_pm, dec1, dec2


def radix2_stage(pm, y_s, code: ConvCode, acc_dtype, tile: int):
    """One radix-2 butterfly stage on (N, TILE) operands → (new_pm, dec).

    Symmetry-folded branch metrics: 2^(R-1) folded rows once per stage
    (static add/sub chains), then the four α/γ/β/θ rows by in-register sign
    selects; the butterfly read is a free sublane reshape (the TPU analogue
    of the GPU shared-memory shuffle).
    """
    nb = code.n_butterflies
    pos, neg = folded_bm_rows(y_s, code, acc_dtype)
    bm_te = butterfly_bm_row(pos, neg, code, "te", tile, acc_dtype)
    bm_to = butterfly_bm_row(pos, neg, code, "to", tile, acc_dtype)
    bm_be = butterfly_bm_row(pos, neg, code, "be", tile, acc_dtype)
    bm_bo = butterfly_bm_row(pos, neg, code, "bo", tile, acc_dtype)

    pairs = pm.reshape(nb, 2, tile)
    pm_even, pm_odd = pairs[:, 0], pairs[:, 1]

    m_te = pm_even + bm_te
    m_to = pm_odd + bm_to
    dec_top = (m_to < m_te).astype(jnp.int32)
    pm_top = jnp.minimum(m_te, m_to)

    m_be = pm_even + bm_be
    m_bo = pm_odd + bm_bo
    dec_bot = (m_bo < m_be).astype(jnp.int32)
    pm_bot = jnp.minimum(m_be, m_bo)

    new_pm = jnp.concatenate([pm_top, pm_bot], axis=0)  # (N, TILE)
    dec = jnp.concatenate([dec_top, dec_bot], axis=0)  # (N, TILE)
    return new_pm, dec


def _min_subtract(pm):
    return pm - jnp.min(pm, axis=0, keepdims=True)


def _acs_kernel(
    y_ref,  # (SC, R, TILE) soft symbols for this stage chunk
    sp_ref,  # (SC, W, TILE) int32 out: packed survivor words
    pm_out_ref,  # (N, TILE) out: final path metrics (last chunk's write wins)
    pm_ref,  # scratch (N, TILE) acc_dtype: path metrics, persists across chunks
    *,
    code: ConvCode,
    stage_chunk: int,
    acc_dtype,
    norm_every: int,
    radix: int,
):
    tile = pm_ref.shape[-1]
    # global stage base of this chunk — hoisted out of the stage loop
    # (program_id is only available at kernel top level)
    chunk_base = pl.program_id(1) * stage_chunk

    @pl.when(pl.program_id(1) == 0)
    def _init():
        pm_ref[...] = jnp.zeros_like(pm_ref)

    def maybe_norm(pm, step_idx):
        if not norm_every:
            return pm
        # amortized min-subtract (i16/i8 saturation contract); cadence counts
        # GLOBAL steps so chunking can't change the normalization points
        return jax.lax.cond(
            step_idx % norm_every == norm_every - 1, _min_subtract, lambda p: p, pm
        )

    if radix == 2:

        def stage_body(s, pm):
            y_s = y_ref[pl.ds(s, 1)][0].astype(acc_dtype)  # (R, TILE)
            new_pm, dec = radix2_stage(pm, y_s, code, acc_dtype, tile)
            new_pm = maybe_norm(new_pm, chunk_base + s)
            sp_ref[pl.ds(s, 1)] = _pack_plane(dec, tile)[None]
            return new_pm

        n_steps = stage_chunk
    else:
        # radix 4: two trellis stages per step; the wrapper guarantees an
        # even stage_chunk, so pairs never straddle a chunk boundary
        step_base = chunk_base // 2

        def stage_body(s, pm):
            y0 = y_ref[pl.ds(2 * s, 1)][0].astype(acc_dtype)
            y1 = y_ref[pl.ds(2 * s + 1, 1)][0].astype(acc_dtype)
            new_pm, dec1, dec2 = radix4_stage_pair(pm, y0, y1, code, acc_dtype, tile)
            new_pm = maybe_norm(new_pm, step_base + s)
            words = jnp.stack([_pack_plane(dec1, tile), _pack_plane(dec2, tile)])
            sp_ref[pl.ds(2 * s, 2)] = words  # two radix-2 bit-planes per step
            return new_pm

        n_steps = stage_chunk // 2

    pm = pm_ref[...]
    pm = jax.lax.fori_loop(0, n_steps, stage_body, pm, unroll=False)
    pm_ref[...] = pm
    pm_out_ref[...] = pm


def _acs_matrix_kernel(
    y_ref,  # (SC, R, TILE) soft symbols for this stage chunk
    e_ref,  # (2^k·N, 2^(kR-1)) f32 expansion operand (whole array, all chunks)
    sp_ref,  # (SC, W, TILE) int32 out: packed survivor words
    pm_out_ref,  # (N, TILE) out: final path metrics (last chunk's write wins)
    pm_ref,  # scratch (N, TILE) acc_dtype: path metrics, persists across chunks
    *,
    code: ConvCode,
    stage_chunk: int,
    acc_dtype,
    norm_every: int,
    k: int,
):
    """Matrix-ACS chunk body: ``stage_chunk // k`` tropical matmul steps.

    The wrapper guarantees ``stage_chunk % k == 0``, so k-stage steps never
    straddle a chunk boundary; each step emits its k standard radix-2
    survivor planes contiguously (one lane-coalesced store). The expansion
    operand E rides in as a real kernel input with a constant index map — it
    is the matmul's left operand, resident for every grid instance.
    """
    tile = pm_ref.shape[-1]
    chunk_base = pl.program_id(1) * stage_chunk
    step_base = chunk_base // k

    @pl.when(pl.program_id(1) == 0)
    def _init():
        pm_ref[...] = jnp.zeros_like(pm_ref)

    e = e_ref[...]

    def maybe_norm(pm, step_idx):
        if not norm_every:
            return pm
        # cadence counts GLOBAL k-stage steps (matching the ref scan), so
        # chunking can't move the normalization points
        return jax.lax.cond(
            step_idx % norm_every == norm_every - 1, _min_subtract, lambda p: p, pm
        )

    def step_body(s, pm):
        ys = y_ref[pl.ds(k * s, k)].astype(acc_dtype)  # (k, R, TILE)
        new_pm, planes = matrix_step(
            pm, [ys[i] for i in range(k)], code, acc_dtype, tile, k, e=e
        )
        new_pm = maybe_norm(new_pm, step_base + s)
        sp_ref[pl.ds(k * s, k)] = jnp.stack([_pack_plane(d, tile) for d in planes])
        return new_pm

    pm = pm_ref[...]
    pm = jax.lax.fori_loop(0, stage_chunk // k, step_body, pm, unroll=False)
    pm_ref[...] = pm
    pm_out_ref[...] = pm


@functools.partial(
    jax.jit,
    static_argnames=(
        "code", "stage_chunk", "interpret", "metric_mode", "radix", "impl", "k"
    ),
)
def acs_forward_pallas(
    y: jnp.ndarray,
    code: ConvCode,
    *,
    stage_chunk: int = DEFAULT_STAGE_CHUNK,
    interpret: bool = False,
    metric_mode: str = "f32",
    radix: int = 2,
    impl: str = "butterfly",
    k: int = 2,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Forward ACS over parallel blocks. y: (T, R, B) → (sp (T, W, B), pm (N, B)).

    T must be a multiple of ``stage_chunk`` and B a multiple of 128 (the ops
    wrapper pads). Float32 and integer (int8/int16/int32) inputs supported;
    integer inputs run the exact integer path. ``metric_mode`` "i16"/"i8"
    adds the amortized min-subtract normalization (int32 VPU registers; the
    values stay bit-identical to narrow-dtype arithmetic by the saturation
    budget — see ``repro.kernels.registry.METRIC_MODES``).
    ``radix=4`` runs the stage-fused two-stage ACS (stage_chunk must be
    even): half the serial chain, two radix-2 survivor bit-planes per step —
    ``sp`` is bit-identical to the radix-2 history.
    ``impl="matrix"`` runs the k-stage (min,+) tropical-matmul ACS
    (stage_chunk must be a k-multiple): the transition matrix is assembled
    as ONE dense MXU matmul against the signed one-hot expansion operand,
    and each step emits k standard radix-2 bit-planes — ``sp`` stays
    bit-identical. Float symbols lower to the staged butterfly (the flat
    k-stage contraction is not IEEE-associative; integers are exact).
    """
    T, R, B = y.shape
    if R != code.R:
        raise ValueError(f"symbol rank {R} != code R {code.R}")
    if T % stage_chunk:
        raise ValueError(f"T={T} not a multiple of stage_chunk={stage_chunk}")
    if B % LANE_TILE:
        raise ValueError(f"B={B} not a multiple of {LANE_TILE}")
    if impl not in ("butterfly", "matrix"):
        raise ValueError(f"impl must be 'butterfly' or 'matrix', got {impl!r}")
    if radix not in (2, 4):
        raise ValueError(f"radix must be 2 or 4, got {radix}")
    if impl == "matrix":
        code.validate_matrix_k(k)
    else:
        if radix == 4 and stage_chunk % 2:
            raise ValueError(f"radix-4 needs an even stage_chunk, got {stage_chunk}")
        if radix == 4 and code.n_states < 4:
            raise ValueError(f"radix-4 ACS needs K >= 3 (got K={code.K})")
    # semantic dtype check (raises for float symbols with i16/i8); registers
    # stay 32-bit wide on the VPU
    semantic = _acc_dtype_for(y.dtype, metric_mode)
    acc_dtype = jnp.float32 if semantic == jnp.float32 else jnp.int32
    if impl == "matrix" and acc_dtype == jnp.float32:
        # IEEE float + is not associative: the flat k-stage contraction would
        # drift from the staged butterfly. Lower to the butterfly radix-2
        # body — the identical op sequence, so still bit-exact to "matrix"
        # semantics (which only promise butterfly-equal decisions).
        impl, radix = "butterfly", 2
    if impl == "matrix":
        if stage_chunk % k:
            raise ValueError(
                f"matrix ACS needs stage_chunk divisible by k={k}, got {stage_chunk}"
            )
        norm_every = norm_interval(code, metric_mode, stages_per_step=k)
    else:
        norm_every = norm_interval(code, metric_mode, radix)
    y = y.astype(acc_dtype)
    if norm_every:
        # saturate out-of-budget pre-quantized symbols (see acs_forward_ref)
        qm = metric_mode_qmax(code, metric_mode)
        y = jnp.clip(y, -qm, qm)

    N = code.n_states
    W = (N + 31) // 32
    n_bt = B // LANE_TILE
    n_sc = T // stage_chunk

    if impl == "matrix":
        kernel = functools.partial(
            _acs_matrix_kernel,
            code=code,
            stage_chunk=stage_chunk,
            acc_dtype=acc_dtype,
            norm_every=norm_every,
            k=k,
        )
        # the expansion matrix is a REAL kernel operand (no captured
        # constants): whole-array block, constant index map — every grid
        # instance sees the same resident E
        e = jnp.asarray(code.matrix_expansion(k), jnp.float32)
        in_specs = [
            pl.BlockSpec((stage_chunk, R, LANE_TILE), lambda bt, sc: (sc, 0, bt)),
            pl.BlockSpec(e.shape, lambda bt, sc: (0, 0)),
        ]
        operands = (y, e)
    else:
        kernel = functools.partial(
            _acs_kernel,
            code=code,
            stage_chunk=stage_chunk,
            acc_dtype=acc_dtype,
            norm_every=norm_every,
            radix=radix,
        )
        in_specs = [
            pl.BlockSpec((stage_chunk, R, LANE_TILE), lambda bt, sc: (sc, 0, bt)),
        ]
        operands = (y,)
    sp, pm = pl.pallas_call(
        kernel,
        grid=(n_bt, n_sc),
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((stage_chunk, W, LANE_TILE), lambda bt, sc: (sc, 0, bt)),
            # PM written out on every chunk; only the last chunk's value is
            # meaningful (same block for all sc → last write wins).
            pl.BlockSpec((N, LANE_TILE), lambda bt, sc: (0, bt)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((T, W, B), jnp.int32),
            jax.ShapeDtypeStruct((N, B), acc_dtype),
        ],
        scratch_shapes=[pltpu.VMEM((N, LANE_TILE), acc_dtype)],
        interpret=interpret,
        name="pbvd_acs_forward",
    )(*operands)
    return sp, pm
