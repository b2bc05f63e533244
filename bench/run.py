"""On-chip benchmark of the served Viterbi decode path.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

Runs one cell of ``BENCHMARK.json`` in this process, on the chips the cell
asks for, and prints one JSON object as the last line of standard output:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end
metrics, or with ``--trace 1`` its per-layer ones), ``device``, with
``--trace 1`` a ``breakdown`` and ``notes`` (how to read a metric, such as
which term binds a roofline share), and last ``checks``: each number
compared with its limit. The same checks are the last lines on standard
error.

It exits non-zero and prints no result where JAX finds no TPU, fewer chips
than the cell asks for, or no program under ``src/``.

``--control i8`` runs the program's int8 path-metric pipeline in place of
the configured one (the control that the comparison must reject); the
benchmark's own runs never pass it. ``--keep-trace PATH`` copies the raw
profiler trace of a ``--trace 1`` run to PATH for inspection.
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def fail(msg: str) -> int:
    print(f"[bench] {msg}", file=sys.stderr)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("i8",), default=None)
    ap.add_argument("--keep-trace", default=None, help="copy the raw trace (.xplane.pb) here")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(BENCH))
    import harness

    try:
        bench, cell, config, mix = harness.load_cell(args.workload)
    except (OSError, KeyError, ValueError) as e:
        return fail(f"cannot load workload {args.workload!r}: {e}")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: JAX's first device is {devices[0].platform!r}")
    if len(devices) < int(cell["chips"]):
        return fail(f"workload needs {cell['chips']} chips, JAX found {len(devices)}")
    # the persistent compile cache lives at a fixed path inside the checkout
    jax.config.update("jax_compilation_cache_dir", str(harness.CACHE / "jax"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)

    overrides = {"metric_mode": args.control} if args.control else {}
    result = harness.run_cell(
        bench, cell, config, mix, seed=args.seed, seconds=args.seconds,
        trace=bool(args.trace), t_start=T_START, keep_trace=args.keep_trace, **overrides,
    )
    for name, c in result["checks"].items():
        bound = f"<= {c['max']}" if "max" in c else f">= {c['min']}"
        print(f"[bench] check {name}: {c['value']} (limit {bound})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
