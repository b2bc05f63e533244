"""Per-kernel allclose tests: Pallas (interpret mode) vs the pure-jnp oracle.

Sweeps shapes, dtypes and stage-chunkings per the assignment requirements.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.trellis import CCSDS_27, ConvCode
from repro.kernels.acs import acs_forward_pallas
from repro.kernels.ops import pbvd_decode_blocks
from repro.kernels.ref import (
    acs_forward_ref,
    pbvd_decode_ref,
    traceback_prefix_ref,
    traceback_ref,
    viterbi_classic_np,
)
from repro.kernels.traceback import (
    prefix_chunk_geometry,
    traceback_pallas,
    traceback_prefix_pallas,
)

CODE_25 = ConvCode(polys=((1, 0, 1, 1, 1), (1, 1, 1, 0, 1)))  # (2,1,5), N=16
CODE_37 = ConvCode(polys=((1, 1, 1, 1, 0, 0, 1), (1, 0, 1, 1, 0, 1, 1), (1, 1, 0, 1, 1, 0, 1)))


def _rand_y(rng, T, R, B, dtype):
    y = rng.normal(size=(T, R, B)).astype(np.float32)
    if dtype == np.float32:
        return jnp.asarray(y)
    scale = 31.75 if dtype == np.int8 else 8191.0
    return jnp.asarray(np.clip(np.round(y * scale), -127, 127).astype(dtype))


@pytest.mark.parametrize("code", [CCSDS_27, CODE_25, CODE_37], ids=["217", "215", "317"])
@pytest.mark.parametrize("dtype", [np.float32, np.int8, np.int16], ids=["f32", "i8", "i16"])
@pytest.mark.parametrize("T,B,chunk", [(64, 128, 32), (128, 128, 64), (96, 256, 32)])
def test_acs_pallas_matches_ref(code, dtype, T, B, chunk):
    rng = np.random.default_rng(hash((code.K, T, B)) % 2**31)
    y = _rand_y(rng, T, code.R, B, dtype)
    sp_r, pm_r = acs_forward_ref(y, code)
    sp_p, pm_p = acs_forward_pallas(y, code, stage_chunk=chunk, interpret=True)
    assert jnp.array_equal(sp_r, sp_p)
    if dtype == np.float32:
        np.testing.assert_allclose(np.asarray(pm_r), np.asarray(pm_p), rtol=1e-6)
    else:
        assert jnp.array_equal(pm_r, pm_p)  # integer path is exact


@pytest.mark.parametrize("code", [CCSDS_27, CODE_25], ids=["217", "215"])
@pytest.mark.parametrize("start_mode", ["zero", "argmin", "random"])
def test_traceback_pallas_matches_ref(code, start_mode):
    rng = np.random.default_rng(5)
    T, B, D, L = 128, 128, 64, 32
    y = _rand_y(rng, T, code.R, B, np.float32)
    sp, pm = acs_forward_ref(y, code)
    if start_mode == "zero":
        start = jnp.zeros((B,), jnp.int32)
    elif start_mode == "argmin":
        start = jnp.argmin(pm, axis=0).astype(jnp.int32)
    else:
        start = jnp.asarray(rng.integers(0, code.n_states, B), jnp.int32)
    b_r = traceback_ref(sp, code, L, D, start)
    b_p = traceback_pallas(sp, start, code, decode_start=L, n_decode=D, interpret=True)
    assert jnp.array_equal(b_r, b_p)


# ---------------------------------------------------------------------------
# parallel-prefix traceback: chunked survivor-map composition
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("code", [CCSDS_27, CODE_25], ids=["217", "215"])
@pytest.mark.parametrize("start_mode", ["zero", "argmin", "random"])
def test_traceback_prefix_ref_matches_serial(code, start_mode):
    rng = np.random.default_rng(11)
    T, B, D, L = 96, 8, 48, 24
    y = _rand_y(rng, T, code.R, B, np.float32)
    sp, pm = acs_forward_ref(y, code)
    start = {
        "zero": jnp.zeros((B,), jnp.int32),
        "argmin": jnp.argmin(pm, axis=0).astype(jnp.int32),
        "random": jnp.asarray(rng.integers(0, code.n_states, B), jnp.int32),
    }[start_mode]
    b_r = traceback_ref(sp, code, L, D, start)
    b_s = traceback_prefix_ref(sp, code, L, D, start)
    assert jnp.array_equal(b_r, b_s)


@pytest.mark.tier1
@pytest.mark.parametrize("code", [CCSDS_27, CODE_25], ids=["217", "215"])
@pytest.mark.parametrize("tb_chunk", [1, 7, 32, 64, 128, 200], ids=str)
def test_traceback_prefix_pallas_matches_ref(code, tb_chunk):
    """Bit-exact across divisor, non-divisor and >=T chunk sizes."""
    rng = np.random.default_rng(13)
    T, B, D, L = 128, 128, 64, 32  # decode region [32, 96): decode_start > 0
    y = _rand_y(rng, T, code.R, B, np.float32)
    sp, pm = acs_forward_ref(y, code)
    start = jnp.argmin(pm, axis=0).astype(jnp.int32)
    b_r = traceback_ref(sp, code, L, D, start)
    b_p = traceback_prefix_pallas(
        sp, start, code, decode_start=L, n_decode=D, tb_chunk=tb_chunk, interpret=True
    )
    assert jnp.array_equal(b_r, b_p)


def test_prefix_chunk_geometry_skips_dead_chunks():
    # T=128, decode region [32, 96), C=24 → pad 16, chunks of flat stages
    # [0,24) [24,48) … ; flat decode region [48, 112) → c_lo=2, c_hi=4
    C, P, n_chunks, c_lo, c_hi = prefix_chunk_geometry(128, 32, 64, 24)
    assert (C, P, n_chunks) == (24, 16, 6)
    assert (c_lo, c_hi) == (2, 4)
    # serial chain shrinks to the active-chunk walk
    assert n_chunks - c_lo == 4
    with pytest.raises(ValueError):
        prefix_chunk_geometry(128, 32, 64, 0)  # tb_chunk < 1
    with pytest.raises(ValueError):
        prefix_chunk_geometry(64, 40, 32, 16)  # decode region outside T


def test_tb_mode_eager_validation():
    y = jnp.zeros((16, 2, 4), jnp.float32)
    with pytest.raises(ValueError, match="tb_mode"):
        pbvd_decode_blocks(
            y, CCSDS_27, decode_start=4, n_decode=8, backend="ref", tb_mode="magic"
        )
    with pytest.raises(ValueError, match="tb_chunk"):
        pbvd_decode_blocks(
            y, CCSDS_27, decode_start=4, n_decode=8, backend="ref",
            tb_mode="prefix", tb_chunk=0,
        )


def test_composed_decode_pallas_matches_ref_aligned():
    """Full two-kernel decode: pallas == ref when T is chunk-aligned."""
    rng = np.random.default_rng(9)
    code = CCSDS_27
    D, L = 96, 16  # T = 128, aligned to chunk 64
    y = _rand_y(rng, D + 2 * L, code.R, 128, np.int8)
    ref = pbvd_decode_blocks(y, code, decode_start=L, n_decode=D, backend="ref")
    pal = pbvd_decode_blocks(y, code, decode_start=L, n_decode=D, backend="pallas", interpret=True)
    assert jnp.array_equal(ref, pal)


def test_lane_padding_path():
    """B not a multiple of 128 exercises the wrapper's lane padding."""
    rng = np.random.default_rng(11)
    code = CCSDS_27
    D, L = 64, 32
    y = _rand_y(rng, D + 2 * L, code.R, 40, np.float32)
    ref = pbvd_decode_blocks(y, code, decode_start=L, n_decode=D, backend="ref")
    pal = pbvd_decode_blocks(y, code, decode_start=L, n_decode=D, backend="pallas", interpret=True)
    assert pal.shape == (D, 40)
    assert jnp.array_equal(ref, pal)


@given(st.integers(0, 2**31 - 1), st.sampled_from([CCSDS_27, CODE_25]))
@settings(max_examples=8, deadline=None)
def test_property_noiseless_roundtrip(seed, code):
    """Property: on a noiseless channel, block decode recovers any payload."""
    from repro.core.encoder import encode_np, terminate

    rng = np.random.default_rng(seed)
    D, L = 64, 6 * code.K
    n = D
    bits = terminate(rng.integers(0, 2, n - code.v), code)
    coded = encode_np(bits, code)
    y = (1.0 - 2.0 * coded).astype(np.float32)  # noiseless BPSK
    yb = np.zeros((D + 2 * L, code.R, 1), np.float32)
    yb[L : L + n, :, 0] = y
    out = np.asarray(pbvd_decode_ref(jnp.asarray(yb), code, D, L))[:, 0]
    assert np.array_equal(out[:n], bits)


def test_block_decode_agrees_with_classic_va():
    """PBVD (windowed) agrees with the full-sequence VA at moderate SNR."""
    from repro.core.channel import transmit
    from repro.core.encoder import encode_jax, terminate

    code = CCSDS_27
    rng = np.random.default_rng(3)
    n = 1024
    bits = terminate(rng.integers(0, 2, n), code)
    coded = encode_jax(jnp.asarray(bits), code)
    y = transmit(jax.random.PRNGKey(0), coded, 4.0, code.rate)

    from repro.core.pbvd import PBVDConfig, decode_stream

    dec = np.asarray(decode_stream(y, n, PBVDConfig(q=None, backend="ref")))
    va = viterbi_classic_np(np.asarray(y), code, init_state=0, final_state=0)[:n]
    assert np.array_equal(dec, va)


def test_integer_path_exactness():
    """int8 and int16 quantizations of the same symbols give identical
    survivor paths when the quantized values are equal — the integer ACS
    path is bit-exact (no float reassociation)."""
    rng = np.random.default_rng(17)
    code = CCSDS_27
    y8 = _rand_y(rng, 64, code.R, 128, np.int8)
    y16 = y8.astype(jnp.int16)
    sp8, pm8 = acs_forward_ref(y8, code)
    sp16, pm16 = acs_forward_ref(y16, code)
    assert jnp.array_equal(sp8, sp16)
    assert jnp.array_equal(pm8, pm16)


# ---------------------------------------------------------------------------
# Symmetry-folded branch metrics (DESIGN.md §8)
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "name",
    [
        "ccsds", "ccsds-2/3", "ccsds-3/4", "ccsds-5/6",
        "is95-k9", "is95-k9-2/3", "is95-k9-3/4", "is95-k9-5/6",
        "lte-1/3",
    ],
)
def test_folded_bm_equals_full_under_sign_expansion(name):
    """Per-stage folded table == full table for every CodeSpec, punctured
    rates included (erased symbols are exact zeros and stay BM-neutral)."""
    import zlib

    from repro.core.codespec import get_code_spec
    from repro.kernels.ref import (
        branch_metric_table,
        expand_folded_bm,
        folded_branch_metric_table,
    )

    rng = np.random.default_rng(zlib.adler32(name.encode()))
    spec = get_code_spec(name)
    code = spec.code
    T = 12
    y_punct = rng.normal(size=spec.n_symbols_for(T)).astype(np.float32)
    y = spec.depuncture_stream(jnp.asarray(y_punct), T)  # (T, R), zeros erased
    full = branch_metric_table(y, code)
    folded = folded_branch_metric_table(y, code)
    assert folded.shape[-1] == code.n_folded == (1 << (code.R - 1))
    assert jnp.array_equal(expand_folded_bm(folded, code), full)
    # erased (zero) symbols are BM-neutral: flipping an erased codeword bit
    # cannot change any metric
    if spec.is_punctured:
        full_np = np.asarray(full)
        y_np = np.asarray(y)
        erased = np.nonzero(y_np == 0.0)  # (t, r) erased slots
        for t, r in zip(*erased):
            bit = 1 << (code.R - 1 - r)
            for c in range(1 << code.R):
                assert full_np[t, c] == full_np[t, c ^ bit]


@pytest.mark.parametrize("code", [CCSDS_27, CODE_25, CODE_37], ids=["217", "215", "317"])
@pytest.mark.parametrize("dtype", [np.float32, np.int8], ids=["f32", "i8"])
def test_folded_acs_bit_exact_vs_full(code, dtype):
    """The folded ACS path (the hot path) is bit-exact to the full-BM path."""
    rng = np.random.default_rng(23)
    y = _rand_y(rng, 96, code.R, 128, dtype)
    sp_f, pm_f = acs_forward_ref(y, code, fold=True)
    sp_u, pm_u = acs_forward_ref(y, code, fold=False)
    assert jnp.array_equal(sp_f, sp_u)
    assert jnp.array_equal(pm_f, pm_u)  # exact even in f32: ± rounding symmetry


@pytest.mark.parametrize("start_policy", ["zero", "argmin"])
def test_folded_decode_bit_exact_vs_full_decode(start_policy):
    """Composed decode through the folded kernels == decode on the full
    table (ref fold=False ACS + shared traceback), per start policy."""
    rng = np.random.default_rng(29)
    code = CCSDS_27
    D, L = 64, 32
    y = _rand_y(rng, D + 2 * L, code.R, 96, np.float32)
    sp, pm = acs_forward_ref(y, code, fold=False)
    if start_policy == "argmin":
        start = jnp.argmin(pm, axis=0).astype(jnp.int32)
    else:
        start = jnp.zeros((y.shape[2],), jnp.int32)
    full_bits = traceback_ref(sp, code, L, D, start)
    for backend in ["ref", "pallas"] + (["fused"] if start_policy == "zero" else []):
        got = pbvd_decode_blocks(
            y, code, decode_start=L, n_decode=D, backend=backend,
            start_policy=start_policy, interpret=True,
        )
        assert jnp.array_equal(got, full_bits), backend


# ---------------------------------------------------------------------------
# Narrow metric pipeline: the saturation contract (registry.METRIC_MODES)
# ---------------------------------------------------------------------------
def _normalized_acs_max_transient(y, code, norm_every):
    """Numpy shadow of the normalized integer ACS (int64 — cannot wrap);
    returns the largest |metric| ever formed across all stages, normalizing
    at the same cadence the production kernels use."""
    T, R, B = y.shape
    signs = code.codeword_signs.astype(np.int64)
    cw = code.butterfly_codewords
    pm = np.zeros((code.n_states, B), np.int64)
    max_abs = 0
    for t in range(T):
        bm = signs @ y[t].astype(np.int64)
        pe, po = pm[0::2], pm[1::2]
        m_te, m_to = pe + bm[cw[:, 0]], po + bm[cw[:, 2]]
        m_be, m_bo = pe + bm[cw[:, 1]], po + bm[cw[:, 3]]
        max_abs = max(
            max_abs,
            int(np.abs(np.concatenate([m_te, m_to, m_be, m_bo])).max()),
        )
        pm = np.concatenate([np.minimum(m_te, m_to), np.minimum(m_be, m_bo)])
        if t % norm_every == norm_every - 1:
            pm = pm - pm.min(axis=0, keepdims=True)
    return max_abs


def _adversarial_stream(rng, T, R, B, qmax):
    """Worst-case-seeking stream: extreme ±qmax symbols (random, constant
    runs, and alternating runs — the patterns that pump the PM spread)."""
    thirds = T // 3
    a = rng.choice([-qmax, qmax], size=(thirds, R, B))
    b = np.full((thirds, R, B), qmax)
    c = np.tile(
        np.array([qmax, -qmax]).repeat(R * B).reshape(2, R, B),
        (T - 2 * thirds + 1) // 2 + 1,
    ).reshape(-1, R, B)[: T - 2 * thirds]
    return np.concatenate([a, b, c]).astype(np.int64)


@pytest.mark.parametrize(
    "metric_mode,dtype_max", [("i16", 32767), ("i8", 127)], ids=["i16", "i8"]
)
@pytest.mark.parametrize("code", [CCSDS_27, CODE_37], ids=["217", "317"])
def test_narrow_pm_never_saturates_10k_stages(code, metric_mode, dtype_max):
    """10k adversarial stages: every transient metric stays within the
    documented budget (< dtype max), and the narrow jnp path's decisions
    stay bit-exact to unbounded int32 accumulation — saturation never
    occurred."""
    from repro.core.quantize import (
        max_symbol_bits,
        metric_mode_qmax,
        norm_interval,
        pm_spread_bound,
    )

    q = max_symbol_bits(code, dtype_max)
    qmax = (1 << (q - 1)) - 1
    assert qmax == metric_mode_qmax(code, metric_mode)
    k = norm_interval(code, metric_mode)
    budget = pm_spread_bound(code, qmax, k)
    assert budget <= dtype_max  # the contract is satisfiable at this (q, k)

    rng = np.random.default_rng(41)
    T, B = 10_000, 2
    y = _adversarial_stream(rng, T, code.R, B, qmax)

    # numpy shadow tracks the true transient maximum over all 10k stages at
    # the production cadence
    max_abs = _normalized_acs_max_transient(y, code, k)
    assert max_abs <= budget, f"transient {max_abs} exceeds budget {budget}"

    # the narrow jnp pipeline agrees with unbounded int32 accumulation
    yj = jnp.asarray(y.astype(np.int8 if qmax <= 127 else np.int16))
    sp_narrow, pm_narrow = acs_forward_ref(yj, code, metric_mode=metric_mode)
    sp_wide, _ = acs_forward_ref(yj.astype(jnp.int32), code, metric_mode="f32")
    assert jnp.array_equal(sp_narrow, sp_wide)
    assert int(jnp.max(jnp.abs(pm_narrow))) <= budget


# ---------------------------------------------------------------------------
# Stage-fused radix-4 ACS (DESIGN.md §10, registry.ACS_RADIX)
# ---------------------------------------------------------------------------
def test_radix4_trellis_tables():
    """Collapsed two-stage tables vs brute-force transition enumeration, and
    the combined-label fold identity BM2(cc) = BM(c1) + BM(c2)."""
    for code in (CCSDS_27, CODE_25, CODE_37):
        N, half, Q, v = code.n_states, code.n_states // 2, code.n_states // 4, code.v
        tabs = code.radix4_acs_tables
        for n in range(N):
            k, q = n // Q, n % Q
            assert (k >> 1, k & 1) == (n >> (v - 1), (n >> (v - 2)) & 1)
            for bm in (0, 1):
                m = 2 * (n % half) + bm
                assert ((n >> (v - 1)) << (v - 1)) | (m >> 1) == n  # m → n valid
                assert tabs["c2"][k, bm, q] == code.output_int(m, n >> (v - 1))
                for bp in (0, 1):
                    p = 2 * (m % half) + bp
                    assert p == code.radix4_preds[n, 2 * bm + bp]
                    c1 = code.output_int(p, k & 1)
                    assert tabs["c1"][k & 1, 2 * bm + bp, q] == c1
                    cc = (c1 << code.R) | tabs["c2"][k, bm, q]
                    assert tabs["cc"][k, 2 * bm + bp, q] == cc
        # fold identity over random symbols
        rng = np.random.default_rng(code.K)
        y2 = rng.normal(size=2 * code.R).astype(np.float32)
        bm2f = code.folded_radix4_codeword_signs @ y2
        bm2 = code.fold_sign4 * bm2f[code.fold_index4]
        bm_t = code.codeword_signs @ y2[: code.R]
        bm_t1 = code.codeword_signs @ y2[code.R :]
        assert code.n_folded4 == 1 << (2 * code.R - 1)
        for cc in range(1 << (2 * code.R)):
            np.testing.assert_allclose(
                bm2[cc], bm_t[cc >> code.R] + bm_t1[cc & ((1 << code.R) - 1)],
                rtol=1e-6, atol=1e-6,
            )


@pytest.mark.parametrize("code", [CCSDS_27, CODE_25, CODE_37], ids=["217", "215", "317"])
@pytest.mark.parametrize("dtype,metric_mode", [(np.float32, "f32"), (np.int8, "f32"), (np.int8, "i16")], ids=["f32", "int", "i16"])
@pytest.mark.parametrize("T", [96, 77], ids=["evenT", "oddT"])
def test_acs_radix4_ref_matches_radix2(code, dtype, metric_mode, T):
    """Survivor bit-planes are bit-identical between radixes; f32 path
    metrics are bit-identical too (same IEEE op sequence); narrow-mode
    metrics differ only by a per-lane uniform shift (argmin-invariant)."""
    rng = np.random.default_rng(hash((code.K, T)) % 2**31)
    y = _rand_y(rng, T, code.R, 8, dtype)
    sp2, pm2 = acs_forward_ref(y, code, metric_mode=metric_mode, radix=2)
    sp4, pm4 = acs_forward_ref(y, code, metric_mode=metric_mode, radix=4)
    assert jnp.array_equal(sp2, sp4)
    if metric_mode == "f32":
        assert jnp.array_equal(pm2, pm4)
    else:
        shift = np.asarray(pm4 - pm2)
        assert (shift == shift[0:1]).all()  # uniform per lane
        assert (np.argmin(np.asarray(pm2), 0) == np.argmin(np.asarray(pm4), 0)).all()


@pytest.mark.parametrize("code", [CCSDS_27, CODE_37], ids=["217", "317"])
def test_acs_radix4_combined_formulation_exact(code):
    """The combined 2^(2R-1)-folded-metric form of the fused step (integer
    accumulators) is bit-identical to the staged form and to radix 2 — in
    BOTH implementations: the jnp gather idiom (ref) and the Pallas
    run-length-row idiom (radix4_stage_pair(combine=True))."""
    from repro.kernels.acs import radix4_stage_pair

    rng = np.random.default_rng(31)
    y = _rand_y(rng, 64, code.R, 8, np.int8)
    sp2, pm2 = acs_forward_ref(y, code, radix=2)
    sp4s, pm4s = acs_forward_ref(y, code, radix=4, r4_combine=False)
    sp4c, pm4c = acs_forward_ref(y, code, radix=4, r4_combine=True)
    assert jnp.array_equal(sp2, sp4s) and jnp.array_equal(sp2, sp4c)
    assert jnp.array_equal(pm2, pm4s) and jnp.array_equal(pm2, pm4c)

    # the Pallas row idiom is a pure jnp function — drive both its forms
    # step by step against the staged reference
    B = 8
    pm = jnp.zeros((code.n_states, B), jnp.int32)
    for t in range(0, 8, 2):
        y0 = y[t].astype(jnp.int32)
        y1 = y[t + 1].astype(jnp.int32)
        pm_s, d1_s, d2_s = radix4_stage_pair(pm, y0, y1, code, jnp.int32, B, combine=False)
        pm_c, d1_c, d2_c = radix4_stage_pair(pm, y0, y1, code, jnp.int32, B, combine=True)
        assert jnp.array_equal(pm_s, pm_c)
        assert jnp.array_equal(d1_s, d1_c) and jnp.array_equal(d2_s, d2_c)
        pm = pm_s


@pytest.mark.parametrize("code", [CCSDS_27, CODE_37], ids=["217", "317"])
@pytest.mark.parametrize("dtype,metric_mode", [(np.float32, "f32"), (np.int8, "i8")], ids=["f32", "i8"])
def test_acs_pallas_radix4_matches_ref(code, dtype, metric_mode):
    rng = np.random.default_rng(hash((code.K, 4)) % 2**31)
    T, B, chunk = 96, 128, 32
    y = _rand_y(rng, T, code.R, B, dtype)
    sp_r, pm_r = acs_forward_ref(y, code, metric_mode=metric_mode, radix=4)
    sp_p, pm_p = acs_forward_pallas(
        y, code, stage_chunk=chunk, interpret=True, metric_mode=metric_mode, radix=4
    )
    assert jnp.array_equal(sp_r, sp_p)
    if dtype == np.float32:
        np.testing.assert_allclose(np.asarray(pm_r), np.asarray(pm_p), rtol=1e-6)
    else:
        assert jnp.array_equal(pm_r, pm_p)  # same global step cadence → exact


def test_acs_radix4_eager_validation():
    """Unsupported radixes and geometries fail pre-jit with clear errors."""
    y = jnp.zeros((16, 2, 4), jnp.float32)
    with pytest.raises(ValueError, match="acs_radix"):
        pbvd_decode_blocks(y, CCSDS_27, decode_start=4, n_decode=8, backend="ref", acs_radix=3)
    with pytest.raises(ValueError, match="even stage_chunk"):
        acs_forward_pallas(
            jnp.zeros((66, 2, 128), jnp.float32), CCSDS_27, stage_chunk=33, radix=4,
            interpret=True,
        )
    tiny = ConvCode(polys=((1, 1), (1, 0)))  # K=2: no radix-4 trellis
    with pytest.raises(ValueError, match="K >= 3"):
        pbvd_decode_blocks(y, tiny, decode_start=4, n_decode=8, backend="ref", acs_radix=4)


def test_norm_interval_radix_budget_validation():
    """A code/mode pair whose budget cannot absorb two unnormalized stages
    is rejected at config time (norm_interval ValueError), not saturated."""
    from repro.core.quantize import norm_interval, pm_spread_bound, metric_mode_qmax
    from repro.core.pbvd import PBVDConfig

    # K=11, R=2: i8's widest q is 3 (qmax 3) and (2v+1)·R·qmax = 126 ≤ 127
    # but (2v+2)·R·qmax = 132 > 127 — radix-2 legal, radix-4 impossible
    k11 = ConvCode(polys=(tuple([1] * 11), tuple([1] + [0] * 9 + [1])))
    qmax = metric_mode_qmax(k11, "i8")
    assert pm_spread_bound(k11, qmax, 1) <= 127 < pm_spread_bound(k11, qmax, 2)
    assert norm_interval(k11, "i8") == 1  # radix-2 cadence exists
    with pytest.raises(ValueError, match="acs_radix=4"):
        norm_interval(k11, "i8", 4)
    with pytest.raises(ValueError, match="acs_radix=4"):
        PBVDConfig(code=k11, metric_mode="i8", acs_radix=4)  # config time
    with pytest.raises(ValueError, match="acs_radix=4"):
        pbvd_decode_blocks(
            jnp.zeros((16, 2, 4), jnp.int8), k11, decode_start=4, n_decode=8,
            backend="ref", metric_mode="i8", acs_radix=4,
        )
    # the same code/mode at radix 2 passes every gate
    PBVDConfig(code=k11, metric_mode="i8", acs_radix=2)


@pytest.mark.parametrize(
    "metric_mode,dtype_max", [("i16", 32767), ("i8", 127)], ids=["i16", "i8"]
)
@pytest.mark.parametrize("code", [CCSDS_27, CODE_37], ids=["217", "317"])
def test_narrow_pm_never_saturates_radix4_cadence(code, metric_mode, dtype_max):
    """10k adversarial stages at the RE-DERIVED radix-4 cadence: the doubled
    per-step accumulation stays within the documented budget, and the narrow
    radix-4 path's decisions stay bit-exact to unbounded accumulation."""
    from repro.core.quantize import metric_mode_qmax, norm_interval, pm_spread_bound

    qmax = metric_mode_qmax(code, metric_mode)
    k_steps = norm_interval(code, metric_mode, 4)  # cadence in FUSED steps
    budget = pm_spread_bound(code, qmax, 2 * k_steps)  # 2 stages per step
    assert budget <= dtype_max  # the re-derived cadence satisfies the bound

    rng = np.random.default_rng(43)
    T, B = 10_000, 2
    y = _adversarial_stream(rng, T, code.R, B, qmax)

    # numpy shadow at the radix-4 normalization points (stage cadence 2k,
    # firing after the second stage of every k-th fused step)
    max_abs = _normalized_acs_max_transient(y, code, 2 * k_steps)
    assert max_abs <= budget, f"transient {max_abs} exceeds budget {budget}"

    yj = jnp.asarray(y.astype(np.int8 if qmax <= 127 else np.int16))
    sp_narrow, pm_narrow = acs_forward_ref(yj, code, metric_mode=metric_mode, radix=4)
    sp_wide, _ = acs_forward_ref(yj.astype(jnp.int32), code, metric_mode="f32", radix=2)
    assert jnp.array_equal(sp_narrow, sp_wide)
    assert int(jnp.max(jnp.abs(pm_narrow))) <= budget


def test_tb_mode_auto_resolution():
    """tb_mode="auto" resolves to each backend's declared fastest mode, the
    resolved decode is bit-exact to spelling the mode out, and the registry
    rejects a preferred mode outside tb_modes."""
    from repro.kernels.ops import (
        backend_preferred_tb_mode,
        register_backend,
        resolve_tb_mode,
    )

    for backend in ("ref", "pallas", "fused"):
        preferred = backend_preferred_tb_mode(backend)
        assert resolve_tb_mode(backend, "auto") == preferred
        assert resolve_tb_mode(backend, "prefix") == "prefix"  # pass-through

    rng = np.random.default_rng(53)
    y = _rand_y(rng, 128, CCSDS_27.R, 40, np.float32)
    for backend in ("ref", "pallas", "fused"):
        auto = pbvd_decode_blocks(
            y, CCSDS_27, decode_start=32, n_decode=64, backend=backend,
            tb_mode="auto", interpret=True,
        )
        explicit = pbvd_decode_blocks(
            y, CCSDS_27, decode_start=32, n_decode=64, backend=backend,
            tb_mode=backend_preferred_tb_mode(backend), interpret=True,
        )
        assert jnp.array_equal(auto, explicit), backend

    with pytest.raises(ValueError, match="preferred_tb_mode"):
        register_backend("bogus-auto", tb_modes=("serial",), preferred_tb_mode="prefix")(
            lambda *a, **k: None
        )


def test_narrow_pm_rejects_float_symbols():
    """i16/i8 need pre-quantized integers; float symbols fail loudly."""
    y = jnp.zeros((8, 2, 4), jnp.float32)
    with pytest.raises(ValueError, match="pre-quantized"):
        acs_forward_ref(y, CCSDS_27, metric_mode="i16")


def test_narrow_pm_saturates_out_of_budget_symbols():
    """Pre-quantized symbols beyond the mode's budget are CLIPPED on
    ingestion, not wrapped: q=8 symbols through i8 decode like the exact
    path on the clipped (±qmax) symbols — degraded, never garbage."""
    from repro.core.quantize import metric_mode_qmax

    rng = np.random.default_rng(47)
    code = CCSDS_27
    y8 = _rand_y(rng, 64, code.R, 128, np.int8)  # |y| up to 127 ≫ budget (3)
    qm = metric_mode_qmax(code, "i8")
    sp_i8, pm_i8 = acs_forward_ref(y8, code, metric_mode="i8")
    sp_ref, _ = acs_forward_ref(jnp.clip(y8, -qm, qm), code, metric_mode="f32")
    assert jnp.array_equal(sp_i8, sp_ref)
    assert int(jnp.max(jnp.abs(pm_i8))) <= 127  # no wrap


@pytest.mark.parametrize(
    "platform, expected", [("cpu", True), ("tpu", False), ("gpu", None), ("rocm", None)]
)
def test_default_interpret_only_on_cpu(monkeypatch, platform, expected):
    """Interpret mode is the CPU's alone: the TPU compiles the kernels, and a
    platform the kernels were not written for is an error, not a silent
    interpreter run."""
    from repro.kernels import ops

    monkeypatch.setattr(ops.jax, "default_backend", lambda: platform)
    if expected is None:
        with pytest.raises(RuntimeError, match=platform):
            ops.default_interpret()
    else:
        assert ops.default_interpret() is expected
