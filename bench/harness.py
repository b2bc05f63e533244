"""One run of one cell: build the deployment, drive its traffic through the
program's served path, measure the window, check the bits, report.

The timed path is the one users call: ``DecoderEngine`` ->
``AsyncDecodeService.open / AsyncStream.send / take / finish`` (with a
``SymbolSlab`` behind ``SessionPool``). Everything else here -- symbols,
traffic, reference decoder, trace reduction, work and peaks -- belongs to
the benchmark.
"""

from __future__ import annotations

import asyncio
import contextlib
import dataclasses
import importlib.util
import json
import shutil
import statistics
import sys
import time
from pathlib import Path

import numpy as np

from codes import code_from_config, viterbi_blocks
from generator import Mix, Window, make_pool, run_clients
from roofline import block_work, peaks_for
from xplane import find_xplane, reduce_trace

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
CACHE = ROOT / ".bench_cache"  # compile cache and trace scratch, at a fixed path
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def load_json(path: Path) -> dict:
    return json.loads(Path(path).read_text())


def load_cell(name: str, root: Path = ROOT):
    """(benchmark, workload entry, configuration, mix) for workload ``name``."""
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(cells)}")
    cell = cells[name]
    conf = {c["name"]: c for c in bench["configs"]}[cell["config"]]
    config = load_json(root / conf["file"])
    return bench, cell, config, load_mix(cell["traffic"])


def load_mix(name: str) -> dict:
    """The parameters of traffic mix ``name`` (``bench/traffic/<name>.json``),
    which the one generator reads (``generator.Mix``)."""
    return load_json(BENCH / "traffic" / f"{name}.json")


def metric_reader(name: str):
    """The ``read(run)`` function of ``bench/metrics/<name>.py``."""
    path = BENCH / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def read_metric(name: str, run) -> tuple:
    """(value, note) of a metric for a run: a reader returns a value, or a
    (value, note) pair whose note says how to read it (for a roofline share,
    which term binds); a value of None means nothing to read."""
    out = metric_reader(name)(run)
    return out if isinstance(out, tuple) else (out, None)


def metrics_of(bench: dict, cell: str, trace: bool) -> list[dict]:
    """The cell's end-to-end metrics (``trace`` false) or per-layer ones."""
    group = bench["per_layer"] if trace else bench["end_to_end"]
    return [m for m in group if cell in m.get("workloads", [cell])]


def build_engine(config: dict, **overrides):
    """The program's engine for a configuration; ``overrides`` are extra
    ``PBVDConfig`` fields (the control's lower-precision path, or a small
    backend for tests on the CPU)."""
    from repro.core.codespec import get_code_spec
    from repro.core.engine import DecoderEngine
    from repro.core.pbvd import PBVDConfig

    cfg = PBVDConfig(
        spec=get_code_spec(config["code_spec"]),
        D=int(config["D"]), L=int(config["L"]), q=int(config["q"]), **overrides,
    )
    mesh = None
    if config.get("mesh"):
        from repro.launch.mesh import make_decode_mesh

        mesh = make_decode_mesh(config["mesh"])
    return DecoderEngine(cfg, mesh=mesh)


def slab_pages(config: dict, mix: Mix, n_clients: int) -> int:
    """Slab pages enough for the admission cap plus one chunk and a decode
    window per client, so the pending-block cap, not the slab, is what
    applies backpressure."""
    D, L = int(config["D"]), int(config["L"])
    svc = config["service"]
    cap = svc.get("max_pending_blocks") or 4 * svc.get("max_batch_blocks", 32)
    chunk_blocks = -(-mix.chunk_stages // D)
    stages = (cap + n_clients * (chunk_blocks + 2)) * D
    return -(-stages // (D + 2 * L)) + 2 * n_clients


@dataclasses.dataclass
class RunRecord:
    """What the metric readers read."""

    window_s: float
    setup_s: float
    payload_bits: int
    blocks: int
    launches: int
    compiles: int
    latencies_s: list  # done - due of every request due in the window that was answered
    late_s: list  # sent - due of every request due in the window that was sent
    finish_s: list
    chips: int
    work: tuple  # (operations, bytes) per block
    peaks: dict | None
    trace: dict | None


class CompileCounter:
    """Counts the executables JAX builds or loads (its backend-compile
    events, persistent-cache hits included)."""

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **kw) -> None:
        if event == BACKEND_COMPILE:
            self.n += 1


def _window_for(log, b: int, pool, S: int, D: int, L: int, R: int):
    """(key, symbols) of block b's decode window in a logged stream; equal
    keys mean equal windows."""
    lo, hi = b * D - L, b * D + D + L
    t0, t1 = max(lo, 0), min(hi, log.stages_sent)
    out = np.zeros((hi - lo, R), np.int8)
    c0, c1 = t0 // S, -(-t1 // S)
    for c in range(c0, c1):
        s, e = max(t0, c * S), min(t1, (c + 1) * S)
        out[s - lo : e - lo] = pool[log.ids[c]][s - c * S : e - c * S]
    return (lo - c0 * S, tuple(log.ids[c0:c1]), t1 - c0 * S), out


def check_streams(logs, mix: Mix, config: dict, seed: int) -> dict:
    """Compare the kept chunks' delivered bits with the reference decoder,
    and every stream's delivered length with what it asked for."""
    code = code_from_config(config)
    D, L, S = int(config["D"]), int(config["L"]), mix.chunk_stages
    pool = make_pool(mix, code, config, seed)
    failed = sum(1 for log in logs if log.error is not None)
    missing = sum(
        abs(log.n_bits - log.delivered) for log in logs if log.error is None and log.n_bits is not None
    )
    targets = [(log, c) for log in logs if log.error is None for c in sorted(log.kept)]
    limit = mix.check.get("max_chunks")
    if limit is not None and len(targets) > limit:
        pick = np.random.default_rng([seed, 2]).choice(len(targets), limit, replace=False)
        targets = [targets[i] for i in sorted(pick)]
    keys, windows, wanted = {}, [], []
    for log, c in targets:
        a, e = c * S, min((c + 1) * S, log.n_bits)
        if e <= a:
            continue
        idx = []
        for b in range(a // D, -(-e // D)):
            key, w = _window_for(log, b, pool, S, D, L, code.R)
            if key not in keys:
                keys[key] = len(windows)
                windows.append(w)
            idx.append(keys[key])
        wanted.append((log.kept[c][: e - a], a - (a // D) * D, idx))
    mismatched = 0
    if windows:
        ref = viterbi_blocks(np.stack(windows), code, L, D)
        for got, off, idx in wanted:
            exp = ref[idx].reshape(-1)[off : off + len(got)]
            have = got >= 0
            missing += int(np.sum(~have))
            mismatched += int(np.sum(have & (got != exp)))
    return {
        "mismatched_bits": {"value": mismatched, "max": 0},
        "missing_bits": {"value": missing, "max": 0},
        "failed_streams": {"value": failed, "max": 0},
        "checked_blocks": {"value": sum(len(i) for _, _, i in wanted), "min": 1},
    }


def checks_pass(checks: dict) -> bool:
    return all(
        c["value"] <= c["max"] if "max" in c else c["value"] >= c["min"] for c in checks.values()
    )


def _memory_peak(devices) -> int:
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
    return max(peaks) if peaks else 0


def run_cell(
    bench: dict, cell: dict, config: dict, mix_json: dict, *, seed: int, seconds: float,
    trace: bool, t_start: float, keep_trace: str | None = None,
    warmup_timeout_s: float = 900.0, grace_s: float = 60.0, record: dict | None = None,
    **engine_overrides,
) -> dict:
    """Run one cell and return the result object the command prints.
    ``record``, where given, receives the run's ``RunRecord`` under ``run``
    and its window's requests ([due, sent, done]) under ``requests``.

    The window opens once the mix's warm-up requests are sent, or after
    ``warmup_timeout_s`` if they never are; clients still waiting
    ``grace_s`` after it closes are cancelled and count as unanswered."""
    import jax

    mix = Mix.from_json(mix_json)
    chips = int(cell["chips"])
    devices = jax.devices()[:chips]
    code = code_from_config(config)
    n_clients = mix.clients_per_chip * chips
    compiles = CompileCounter()
    engine = build_engine(config, **engine_overrides)

    from repro.launch.serve_async import AsyncDecodeService
    from repro.launch.slab import SymbolSlab

    D, L = int(config["D"]), int(config["L"])
    slab = SymbolSlab(n_pages=slab_pages(config, mix, n_clients), page_stages=D + 2 * L, R=code.R)
    span = jax.profiler.TraceAnnotation if trace else (lambda name: contextlib.nullcontext())
    trace_dir = CACHE / "trace"
    marks = {}
    svc = None

    def on_close():
        marks["t1"] = time.perf_counter()
        marks["launches1"], marks["compiles1"] = svc.metrics()["launches"], compiles.n
        if trace:
            marks["window_span"].__exit__(None, None, None)
            jax.profiler.stop_trace()

    win = Window(mix.warmup_requests_per_client * n_clients, on_close=on_close)

    async def drive():
        nonlocal svc
        svc = AsyncDecodeService(slab=slab, **config["service"])
        async with svc:
            async def controller():
                try:
                    try:
                        await asyncio.wait_for(win.warm.wait(), warmup_timeout_s)
                    except asyncio.TimeoutError:
                        pass
                    if trace:
                        shutil.rmtree(trace_dir, ignore_errors=True)
                        jax.profiler.start_trace(str(trace_dir))
                        marks["window_span"] = jax.profiler.TraceAnnotation("bench.window")
                        marks["window_span"].__enter__()
                    marks["t0"] = time.perf_counter()
                    marks["launches0"], marks["compiles0"] = svc.metrics()["launches"], compiles.n
                    win.open(seconds)
                    await asyncio.sleep(seconds)
                    win.poll()
                finally:
                    win.close()

            ctl = asyncio.create_task(controller())
            logs = await run_clients(
                svc, engine, mix, code, config, seed, n_clients, win, span, grace_s
            )
            await ctl
        return logs

    logs = asyncio.run(drive())
    t_drained = time.perf_counter()
    memory_peak = _memory_peak(devices)
    reduced = None
    if trace:
        xplane = find_xplane(str(trace_dir))
        if keep_trace:
            shutil.copyfile(xplane, keep_trace)
        reduced = reduce_trace(xplane)
        shutil.rmtree(trace_dir, ignore_errors=True)
    del engine, slab
    kind = devices[0].device_kind
    reqs = win.requests(logs)
    run = RunRecord(
        window_s=marks["t1"] - marks["t0"],
        setup_s=marks["t0"] - t_start,
        payload_bits=win.payload_bits,
        blocks=win.blocks,
        launches=marks["launches1"] - marks["launches0"],
        compiles=marks["compiles1"] - marks["compiles0"],
        latencies_s=[r[2] - r[0] for r in reqs if r[2] is not None],
        late_s=[r[1] - r[0] for r in reqs if r[1] is not None],
        finish_s=win.finish_s,
        chips=chips,
        work=block_work(code.K, code.R, D, L, int(config["q"]), mix.stream_stages),
        peaks=peaks_for(kind) if devices[0].platform == "tpu" else None,
        trace=reduced,
    )
    if record is not None:
        record.update(run=run, requests=reqs)
    metrics, notes = {}, {}
    for m in metrics_of(bench, cell["name"], trace):
        value, note = read_metric(m["name"], run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        if note:
            notes[m["name"]] = note
            print(f"[bench] {m['name']}: {note}", file=sys.stderr)
    if trace:  # what the traced run would have reported: the tracing cost
        for m in metrics_of(bench, cell["name"], False):
            v, _ = read_metric(m["name"], run)
            print(f"[bench] traced run, untraced metric {m['name']}: {v}", file=sys.stderr)
    t_check = time.perf_counter()
    checks = check_streams(logs, mix, config, seed)
    print(
        f"[bench] seconds: setup {marks['t0'] - t_start:.2f}, window {marks['t1'] - marks['t0']:.2f}, "
        f"drain {t_drained - marks['t1']:.2f}, trace reduction {t_check - t_drained:.2f}, "
        f"reference check {time.perf_counter() - t_check:.2f}",
        file=sys.stderr,
    )
    device = {
        "platform": devices[0].platform,
        "kind": kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": memory_peak,
    }
    result = {
        "correct": checks_pass(checks),
        "attempted": len(reqs),
        "failed": sum(1 for r in reqs if r[2] is None),
        "metrics": metrics,
        "device": device,
    }
    if reduced is not None:
        device["busy_s"] = statistics.fmean(reduced["busy_s"][:chips])
        device["window_s"] = reduced["window_s"]
        result["breakdown"] = {
            "device_ops": reduced["device_ops"],
            "idle_gaps": reduced["idle_gaps"],
        }
    if notes:
        result["notes"] = notes
    result["checks"] = checks
    return result
