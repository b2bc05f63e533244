#!/usr/bin/env python3
"""On-chip smoke test of the served PBVD decode path.

    python chip_smoke.py             # one TPU chip
    python chip_smoke.py --chips 4   # the data=4 mesh path and its reference

Requests enter through the path a user calls — ``DecoderEngine`` →
``SessionPool`` → ``AsyncDecodeService`` with a paged ``SymbolSlab`` — at
the paper's geometry (CCSDS K=7, D=512, L=42, q=8) on both Pallas backends
(``pallas``, ``fused``), and every output is checked:

* golden replay: every ``tests/golden/*.npz`` (made by the ``ref`` backend
  on CPU by ``tools/regen_golden.py``) through both backends in metric
  modes f32 and i8; i8 must be bit-exact, f32 falls back to a BER check
  (printed) only where it is not;
* served trace: 64 streams × 262,144 payload bits at 4 dB Eb/N0 plus one
  noiseless stream, Poisson arrivals, launches of up to 4096 blocks; every
  stream bit-exact to the ``ref`` backend's one-shot decode on the same
  chip, the noiseless one error-free, and zero retries, errors, sheds and
  quarantines in the service metrics;
* one-shot: ``DecoderEngine.decode`` of a 4 Mbit stream (8192 blocks).

``--chips 4`` runs only the mesh path: a ``data=4`` engine per backend,
one-shot (16,384 blocks) and served trace, each bit-exact to the one-chip
meshless decode, with a quarter of the lanes on each device.

Wall times printed along the way are smoke timings, not benchmark
results. The last stdout line is one JSON object, printed only when every
phase passed: ``{"ok": true, "device": {"platform": "tpu", "kind": ...,
"count": N}}``. Without a TPU the script exits non-zero before any phase.
"""

from __future__ import annotations

import argparse
import asyncio
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
BACKENDS = ("pallas", "fused")
EBN0_DB = 4.0
SEED = 20261016


@dataclasses.dataclass(frozen=True)
class Sizes:
    """Problem sizes of the phases (full width by default)."""

    D: int = 512
    L: int = 42
    streams: int = 64
    stream_bits: int = 262_144
    chunk_bits: int = 32_768
    max_batch_blocks: int = 4096
    deadline_ms: float = 100.0
    rate_chunks_per_s: float = 50.0
    oneshot_blocks: int = 8192
    mesh_oneshot_blocks: int = 16_384
    placement_lanes: int = 4096


class SmokeFailure(Exception):
    """A phase produced a wrong or incomplete result."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"[chip_smoke] {msg}", flush=True)


def _cfg(spec, backend: str, sizes: Sizes, **kw):
    from repro.core.pbvd import PBVDConfig

    return PBVDConfig(spec=spec, D=sizes.D, L=sizes.L, q=8, backend=backend, **kw)


def _timed(fn):
    """(result, seconds) of ``fn()`` with every output array waited on."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def make_streams(spec, sizes: Sizes, seed: int = SEED):
    """``sizes.streams`` noisy streams plus one noiseless stream, made in
    bulk on the device: one batched encode and one channel draw.

    Returns (payloads, ys): (n_bits,) payload bits and (n_bits + v, R)
    float32 soft symbols per stream; the last stream is noiseless.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.channel import bpsk, transmit
    from repro.core.encoder import encode_jax

    rng = np.random.default_rng(seed)
    payload = rng.integers(0, 2, (sizes.streams, sizes.stream_bits))
    flushed = np.pad(payload, ((0, 0), (0, spec.code.v)))  # K-1 zero tail each
    coded = encode_jax(jnp.asarray(flushed), spec.code)
    noisy = np.asarray(transmit(jax.random.PRNGKey(seed), coded, EBN0_DB, spec.rate))
    clean = np.asarray(bpsk(coded[0]))
    return list(payload) + [payload[0]], list(noisy) + [clean]


def concat_stream(ys, n_bits: int):
    """One long stream from whole terminated streams (a valid codeword:
    each ends in state 0) covering at least ``n_bits`` stages."""
    import numpy as np

    parts, have = [], 0
    for y in ys:
        parts.append(y)
        have += len(y)
        if have >= n_bits:
            return np.concatenate(parts)
    raise SmokeFailure(f"streams hold {have} stages, fewer than {n_bits}")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------
def check_compiled_kernels(spec, sizes: Sizes) -> None:
    """The launch the served path makes compiles to a Mosaic kernel call,
    not the interpreter: ``default_interpret()`` resolved to False. The
    compile times show whether the persistent compile cache was warm."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.ops import default_interpret, pbvd_decode_blocks

    interpret = default_interpret()
    check(interpret is False, f"interpret resolved to {interpret!r} on this device")
    T = sizes.D + 2 * sizes.L
    y = jax.ShapeDtypeStruct((T, spec.code.R, 1024), jnp.int8)
    secs = {}
    for backend in BACKENDS:
        launch = jax.jit(
            lambda y, b=backend: pbvd_decode_blocks(
                y, spec.code, decode_start=sizes.L, n_decode=sizes.D, backend=b
            )
        )
        t0 = time.perf_counter()
        text = launch.lower(y).compile().as_text()
        secs[backend] = time.perf_counter() - t0
        check("tpu_custom_call" in text, f"{backend} launch has no Mosaic kernel call")
    log(
        f"interpret={interpret}; pallas and fused compile to tpu_custom_call; "
        "smoke timing: compile of a 1024-lane launch "
        + ", ".join(f"{b} {s:.2f} s" for b, s in secs.items())
    )


def golden_replay(backends=BACKENDS) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.codespec import get_code_spec
    from repro.core.engine import DecoderEngine
    from repro.core.pbvd import PBVDConfig

    files = sorted((ROOT / "tests" / "golden").glob("*.npz"))
    check(bool(files), "no golden vectors under tests/golden")
    n_exact, f32_by_ber = 0, []
    for path in files:
        with np.load(path, allow_pickle=False) as z:
            g = {k: z[k] for k in z.files}
        meta = json.loads(str(g["meta"]))
        spec = get_code_spec(meta["spec"])
        n_bits = meta["n_bits"]
        for backend in backends:
            for mode in ("f32", "i8"):
                cfg = PBVDConfig(
                    spec=spec, D=meta["D"], L=meta["L"], q=meta["q"],
                    backend=backend, metric_mode=mode,
                )
                bits = np.asarray(DecoderEngine(cfg).decode(jnp.asarray(g["y"]), n_bits))
                expected = g[f"bits_{mode}"]
                diff = np.flatnonzero(bits != expected)
                tag = f"{meta['spec']}/{backend}/{mode}"
                if diff.size == 0:
                    n_exact += 1
                    continue
                check(mode == "f32", f"golden {tag}: bits {diff[:16].tolist()} differ")
                errs = int(np.sum(bits != g["payload"]))
                ref_errs = int(np.sum(expected != g["payload"]))
                log(
                    f"golden {tag}: NOT bit-exact at bits {diff[:16].tolist()}; "
                    f"{errs} payload errors vs {ref_errs} in the golden decode"
                )
                check(
                    errs <= ref_errs + max(2, n_bits // 100),
                    f"golden {tag}: BER {errs}/{n_bits} exceeds the golden "
                    f"decode's {ref_errs}/{n_bits}",
                )
                f32_by_ber.append(tag)
    log(
        f"golden replay: {len(files)} specs x {len(backends)} backends x f32/i8: "
        f"{n_exact} bit-exact, f32 checked by BER: {f32_by_ber or 'none'}"
    )


def served_trace(engine, ys, refs, sizes: Sizes, label: str) -> dict:
    """One Poisson trace of every stream through the async service; each
    stream's bits must equal ``refs`` and the service must report no
    failure of any kind. Returns the service metrics."""
    import numpy as np

    from repro.launch.serve_async import run_poisson_trace
    from repro.launch.slab import SymbolSlab

    spec = engine.spec
    page_stages = sizes.D + 2 * sizes.L
    chunk = sizes.chunk_bits  # unpunctured: one received stage per payload bit
    # every stream holding a full decode window plus one chunk of jitter
    pages_per_stream = 2 + 2 * -(-chunk // page_stages)
    slab = SymbolSlab(
        n_pages=pages_per_stream * len(ys), page_stages=page_stages, R=spec.code.R
    )
    n_bits = [sizes.stream_bits] * len(ys)
    bits, m = asyncio.run(
        run_poisson_trace(
            engine,
            ys,
            n_bits,
            chunk_symbols=chunk,
            rate_chunks_per_s=sizes.rate_chunks_per_s,
            seed=11,
            slab=slab,
            service_kwargs=dict(
                max_batch_blocks=sizes.max_batch_blocks, deadline_ms=sizes.deadline_ms
            ),
        )
    )
    failed = [(i, b) for i, b in enumerate(bits) if not isinstance(b, np.ndarray)]
    check(not failed, f"{label}: streams ended without bits: {failed[:3]}")
    for i, (b, r) in enumerate(zip(bits, refs)):
        if not np.array_equal(b, r):
            bad = np.flatnonzero(b != r) if b.shape == r.shape else b.shape
            raise SmokeFailure(f"{label}: stream {i} differs from its reference at {bad}")
    check(
        m["retries"] == 0
        and not m["errors_by_class"]
        and m["quarantined_streams"] == 0
        and m["shed_blocks"] == 0,
        f"{label}: service reported failures: retries={m['retries']} "
        f"errors={m['errors_by_class']} quarantined={m['quarantined_streams']} "
        f"shed={m['shed_blocks']}",
    )
    check(slab.pages_in_use == 0, f"{label}: {slab.pages_in_use} slab pages leaked")
    return m


def served_phase(engine, ys, payloads, refs, sizes: Sizes, label: str) -> None:
    """The served trace twice (the first compiles every launch shape) and
    the noiseless stream's error count."""
    import numpy as np

    m, cold_s = _timed(lambda: served_trace(engine, ys, refs, sizes, label))
    m, warm_s = _timed(lambda: served_trace(engine, ys, refs, sizes, label))
    errors = int(np.sum(refs[-1] != payloads[-1]))
    check(errors == 0, f"{label}: noiseless stream decoded with {errors} errors")
    total = sizes.stream_bits * len(ys)
    log(
        f"{label}: {len(ys)} streams x {sizes.stream_bits} bits bit-exact; "
        f"{m['dispatches']} dispatches, {m['launches']} launches, retries 0, "
        f"errors 0, quarantined 0; noiseless stream 0 errors; smoke timing: "
        f"first trace {cold_s:.2f} s, second {warm_s:.2f} s "
        f"({total / warm_s / 1e6:.1f} Mb/s decoded)"
    )


def oneshot_phase(engine, ref_engine, y, n_bits: int, label: str):
    """One-shot decode vs the reference engine; prints first and second
    call times (the first includes compilation, or a compile-cache hit)."""
    import numpy as np

    out, first_s = _timed(lambda: engine.decode(y, n_bits))
    out, warm_s = _timed(lambda: engine.decode(y, n_bits))
    ref = np.asarray(ref_engine.decode(y, n_bits))
    got = np.asarray(out)
    check(
        np.array_equal(got, ref),
        f"{label}: {int(np.sum(got != ref))} of {n_bits} bits differ from the reference",
    )
    log(
        f"{label}: {n_bits} bits bit-exact; smoke timing: first call "
        f"{first_s:.2f} s (compile + run), warm {warm_s:.3f} s "
        f"({n_bits / warm_s / 1e6:.1f} Mb/s decoded)"
    )
    return got


def run_one_chip(sizes: Sizes = Sizes()) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.codespec import get_code_spec
    from repro.core.engine import DecoderEngine

    spec = get_code_spec("ccsds")
    check_compiled_kernels(spec, sizes)

    t0 = time.perf_counter()
    golden_replay()
    log(f"golden replay took {time.perf_counter() - t0:.1f} s")

    payloads, ys = make_streams(spec, sizes)
    ref_engine = DecoderEngine(_cfg(spec, "ref", sizes))
    refs = [np.asarray(ref_engine.decode(jnp.asarray(y), sizes.stream_bits)) for y in ys]
    long_bits = sizes.oneshot_blocks * sizes.D
    long_y = jnp.asarray(concat_stream(ys, long_bits))
    for backend in BACKENDS:
        engine = DecoderEngine(_cfg(spec, backend, sizes))
        served_phase(engine, ys, payloads, refs, sizes, f"served/{backend}")
        oneshot_phase(engine, ref_engine, long_y, long_bits, f"one-shot/{backend}")


def run_four_chips(sizes: Sizes = Sizes()) -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.core.codespec import get_code_spec
    from repro.core.engine import DecoderEngine
    from repro.core.pbvd import frame_stream
    from repro.launch.mesh import make_decode_mesh

    spec = get_code_spec("ccsds")
    mesh = make_decode_mesh("data=4")
    payloads, ys = make_streams(spec, sizes)
    long_bits = sizes.mesh_oneshot_blocks * sizes.D
    long_y = jnp.asarray(concat_stream(ys, long_bits))
    for backend in BACKENDS:
        one_chip = DecoderEngine(_cfg(spec, backend, sizes))
        engine = DecoderEngine(_cfg(spec, backend, sizes), mesh=mesh)
        check(engine.n_shards == 4, f"data=4 engine has {engine.n_shards} shards")

        # placement: the launch leaves a quarter of the lanes on each device
        n_lanes = sizes.placement_lanes
        y0 = engine.cfg.quantize(jnp.asarray(ys[0]))
        blocks = frame_stream(y0, sizes.D, sizes.L, n_lanes)
        bits = engine._decode_blocks(blocks, (n_lanes,), None)
        shards = bits.addressable_shards
        lanes = sorted((s.device.id, s.data.shape[1]) for s in shards)
        check(
            len({d for d, _ in lanes}) == 4 and all(n == n_lanes // 4 for _, n in lanes),
            f"{backend}: lanes per device {lanes}, expected {n_lanes // 4} on each of 4",
        )
        log(f"mesh/{backend}: lanes per device (id, lanes) {lanes}")

        oneshot_phase(engine, one_chip, long_y, long_bits, f"mesh one-shot/{backend}")
        refs = [np.asarray(one_chip.decode(jnp.asarray(y), sizes.stream_bits)) for y in ys]
        m, secs = _timed(lambda: served_trace(engine, ys, refs, sizes, f"mesh served/{backend}"))
        errors = int(np.sum(refs[-1] != payloads[-1]))
        check(errors == 0, f"mesh served/{backend}: noiseless stream has {errors} errors")
        log(
            f"mesh served/{backend}: {len(ys)} streams bit-exact to the one-chip "
            f"decode; {m['dispatches']} dispatches, retries 0, errors 0, "
            f"quarantined 0; smoke timing: {secs:.2f} s incl. compilation"
        )


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument(
        "--chips",
        type=int,
        default=1,
        choices=[1, 4],
        help="1: golden replay, served trace and one-shot on one chip; "
        "4: only the data=4 mesh path and its one-chip reference",
    )
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"[chip_smoke] no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(
            f"[chip_smoke] no TPU found: JAX's first device is {dev.platform!r} "
            f"({dev.device_kind}); the Pallas kernels are not run in interpret mode",
            file=sys.stderr,
        )
        return 1
    cache_dir = Path(enable_compile_cache())  # before the first compile
    cached = len(list(cache_dir.iterdir())) if cache_dir.is_dir() else 0
    log(f"device {dev.device_kind} x {len(devices)} ({dev.platform}); compile cache "
        f"{cache_dir} ({cached} entries at start)")
    if len(devices) < args.chips:
        print(f"[chip_smoke] --chips {args.chips} needs {args.chips} devices, "
              f"found {len(devices)}", file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    try:
        if args.chips == 4:
            run_four_chips()
        else:
            run_one_chip()
    except SmokeFailure as e:
        print(f"[chip_smoke] FAILED: {e}", file=sys.stderr)
        return 1
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s")
    result = {
        "ok": True,
        "device": {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)},
    }
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
