"""Benchmark harness entry point — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines per the repo convention.

  table3_throughput — paper Table III (orig vs optimized decoder, T/P model)
  kernel_scaling    — paper Table III S_k column (K1/K2 split vs N_t)
  fig4_ber          — paper Fig. 4 (BER vs Eb/N0 for L ∈ {14,28,42})
  table4_comparison — paper Table IV (cross-work TNDC normalization)
  punctured_sweep   — beyond-paper: BER/throughput across punctured rates
  batched_throughput — beyond-paper: multi-stream aggregate Mb/s
                       (sequential vs decode_batch vs SessionPool)
  metric_sweep      — beyond-paper: folded-vs-full BM + f32/i16/i8
                       metric-mode decoded-bits/s (writes BENCH_*.json)
  traceback_sweep   — beyond-paper: serial vs parallel-prefix traceback
                       decoded-bits/s per tb_chunk + the ACS-vs-traceback
                       phase timing split (merges into BENCH_*.json)
  acs_radix_sweep   — beyond-paper: stage-fused radix-4 vs radix-2 ACS
                       decoded-bits/s per backend + the per-radix ACS
                       phase split (merges into BENCH_*.json)
  acs_matrix_sweep  — beyond-paper: k-stage (min,+) matrix ACS vs the
                       butterfly decoded-bits/s per backend × fusion depth
                       + the per-impl phase split (merges into BENCH_*.json)

``--metric-mode`` runs ONLY the metric sweep (the folded/quantized
hot-path numbers); ``--tb-mode serial prefix`` runs ONLY the traceback
sweep (``--tb-chunk`` sizes the prefix chunks); ``--acs-radix`` runs ONLY
the radix sweep; ``--acs-impl`` runs ONLY the matrix-vs-butterfly sweep
(``--acs-k`` sets the fusion depths). The CI benchmark-smoke job runs all
four into one artifact, then gates it with tools/bench_compare.py:

    python benchmarks/run.py --metric-mode --out BENCH_pr.json --smoke
    python benchmarks/run.py --tb-mode serial prefix --out BENCH_pr.json --smoke
    python benchmarks/run.py --acs-radix --out BENCH_pr.json --smoke
    python benchmarks/run.py --acs-impl --out BENCH_pr.json --smoke

Roofline shares on the chip come from the on-chip benchmark (``bench/run.py``
with ``--trace 1``, reduced by ``bench/roofline.py``).
"""

from __future__ import annotations

import argparse
import importlib
import sys
import time
from pathlib import Path


def _sibling(name: str):
    """Import a sibling benchmark module whether run as a script or -m."""
    if __package__:
        return importlib.import_module(f".{name}", __package__)
    sys.path.insert(0, str(Path(__file__).resolve().parent))
    return importlib.import_module(name)


def _run_all() -> None:
    for mod in (
        _sibling("table3_throughput"),
        _sibling("kernel_scaling"),
        _sibling("fig4_ber"),
        _sibling("table4_comparison"),
        _sibling("punctured_sweep"),
        _sibling("batched_throughput"),
        _sibling("metric_sweep"),
        _sibling("traceback_sweep"),
        _sibling("acs_radix_sweep"),
        _sibling("acs_matrix_sweep"),
    ):
        t0 = time.perf_counter()
        mod.main()
        print(
            f"# {mod.__name__.split('.')[-1]} finished in {time.perf_counter()-t0:.1f}s",
            file=sys.stderr,
        )


def main(argv=None) -> None:
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument(
        "--metric-mode",
        action="store_true",
        help="run only the metric-pipeline sweep (folded BM + f32/i16/i8)",
    )
    ap.add_argument(
        "--tb-mode",
        nargs="+",
        choices=("serial", "prefix"),
        default=None,
        metavar="MODE",
        help="run only the traceback sweep with these tb modes (reports the "
        "serial-vs-prefix decoded-bits/s and the ACS-vs-traceback phase split)",
    )
    ap.add_argument(
        "--tb-chunk",
        type=int,
        nargs="+",
        default=None,
        metavar="C",
        help="prefix chunk sizes for the traceback sweep (default: 32 64 128)",
    )
    ap.add_argument(
        "--acs-radix",
        action="store_true",
        help="run only the ACS-radix sweep (stage-fused radix-4 vs radix-2)",
    )
    ap.add_argument(
        "--acs-impl",
        action="store_true",
        help="run only the ACS-impl sweep (k-stage matrix vs butterfly)",
    )
    ap.add_argument(
        "--acs-k",
        type=int,
        nargs="+",
        default=None,
        metavar="K",
        help="matrix fusion depths for the ACS-impl sweep (default: 2 3)",
    )
    ap.add_argument(
        "--out", default=None, help="write/merge BENCH_*.json (sweep modes only)"
    )
    ap.add_argument(
        "--smoke",
        action="store_true",
        help="tiny geometry for CI: fewer blocks/reps, same code paths",
    )
    args = ap.parse_args(argv)

    selected = args.metric_mode or args.tb_mode or args.acs_radix or args.acs_impl
    if (args.out or args.smoke) and not selected:
        ap.error(
            "--out/--smoke only apply to the sweeps; add "
            "--metric-mode/--tb-mode/--acs-radix/--acs-impl"
        )
    if args.tb_chunk and not args.tb_mode:
        ap.error("--tb-chunk only applies to the traceback sweep; add --tb-mode")
    if args.acs_k and not args.acs_impl:
        ap.error("--acs-k only applies to the ACS-impl sweep; add --acs-impl")
    # ALL sweep runs (smoke and full) use reps>=5 medians: the smoke rows
    # feed the CI regression gate — one noisy sample on a shared runner must
    # not trip the 15% threshold — and the committed full-geometry artifact
    # records the perf trajectory, which single-sample timings would smear
    reps = 5
    if args.metric_mode:
        metric_sweep = _sibling("metric_sweep")

        n_blocks = (8,) if args.smoke else (64, 512)
        rows = metric_sweep.run(n_blocks, reps=reps)
        for r in rows:
            print("metric_sweep," + ",".join(f"{k}={v}" for k, v in r.items()))
        if args.out:
            metric_sweep.write_bench_json(rows, args.out)
            print(f"# wrote {args.out}", file=sys.stderr)
    if args.tb_mode:
        traceback_sweep = _sibling("traceback_sweep")

        n_blocks = (8,) if args.smoke else (64, 512)
        tb_chunks = tuple(args.tb_chunk) if args.tb_chunk else (32, 64, 128)
        rows = traceback_sweep.run(
            n_blocks,
            tb_chunks=tb_chunks,
            tb_modes=tuple(args.tb_mode),
            reps=reps,
        )
        for r in rows:
            print("traceback_sweep," + ",".join(f"{k}={v}" for k, v in r.items()))
        if args.out:
            traceback_sweep.merge_bench_json(rows, args.out)
            print(f"# merged into {args.out}", file=sys.stderr)
    if args.acs_radix:
        acs_radix_sweep = _sibling("acs_radix_sweep")

        n_blocks = (8,) if args.smoke else (64, 256)
        rows = acs_radix_sweep.run(n_blocks, reps=reps)
        for r in rows:
            print("acs_radix_sweep," + ",".join(f"{k}={v}" for k, v in r.items()))
        if args.out:
            acs_radix_sweep.merge_bench_json(rows, args.out)
            print(f"# merged into {args.out}", file=sys.stderr)
    if args.acs_impl:
        acs_matrix_sweep = _sibling("acs_matrix_sweep")

        n_blocks = (8,) if args.smoke else (64, 256)
        ks = tuple(args.acs_k) if args.acs_k else (2, 3)
        rows = acs_matrix_sweep.run(n_blocks, ks=ks, reps=reps)
        for r in rows:
            print("acs_matrix_sweep," + ",".join(f"{k}={v}" for k, v in r.items()))
        if args.out:
            acs_matrix_sweep.merge_bench_json(rows, args.out)
            print(f"# merged into {args.out}", file=sys.stderr)
    if not selected:
        _run_all()


if __name__ == "__main__":
    main()
