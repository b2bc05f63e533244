import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 " + os.environ.get("XLA_FLAGS", "")
)

"""Multi-pod dry-run of the PBVD decoder.

Lowers + compiles the decoder's lane launch against the production meshes
— 16×16 (single pod, 256 chips) and 2×16×16 (two pods, 512 chips) — using
ShapeDtypeStruct inputs only (no allocation), then records memory analysis
and the HLO-derived roofline terms.

Usage:
  python -m repro.launch.dryrun --viterbi                       # every cell
  python -m repro.launch.dryrun --shape stream_16m_int8 [--multi-pod] [--out F]

``--viterbi`` writes one JSON report per cell under reports/dryrun/.
"""

import argparse
import json
import time
from pathlib import Path

REPORTS = Path(__file__).resolve().parents[3] / "reports" / "dryrun"
VARIANTS = {"stream_16m_int8": 32768, "stream_4m_f32": 8192}  # cell → lanes


def _cell_report_path(shape: str, multi_pod: bool) -> Path:
    mesh = "pod2x16x16" if multi_pod else "pod16x16"
    return REPORTS / f"viterbi-ccsds__{shape}__{mesh}.json"


def run_viterbi_cell(variant: str, multi_pod: bool) -> dict:
    """Dry-run the PBVD decoder's lane launch on a production mesh."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.core.trellis import CCSDS_27
    from repro.kernels.ops import pbvd_decode_blocks
    from repro.launch.hlo_analysis import analyze_hlo
    from repro.launch.mesh import make_production_mesh
    from repro.sharding.rules import block_mesh_axes

    t0 = time.time()
    mesh = make_production_mesh(multi_pod=multi_pod)
    D, L = 512, 42
    T = D + 2 * L
    n_blocks = VARIANTS[variant]
    dtype = jnp.int8 if variant.endswith("int8") else jnp.float32

    lanes = block_mesh_axes(mesh)

    def step(blocks):
        return pbvd_decode_blocks(
            blocks, CCSDS_27, decode_start=L, n_decode=D, backend="ref"
        )

    jitted = jax.jit(
        step,
        in_shardings=(NamedSharding(mesh, P(None, None, lanes)),),
        out_shardings=NamedSharding(mesh, P(None, lanes)),
    )
    sds = jax.ShapeDtypeStruct((T, CCSDS_27.R, n_blocks), dtype)
    compiled = jitted.lower(sds).compile()

    st = analyze_hlo(compiled.as_text())
    report = {
        "arch": "viterbi-ccsds",
        "shape": variant,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_chips": int(mesh.devices.size),
        "kind": "decode_stream",
        "bits_per_step": D * n_blocks,
        "hlo": {
            "flops_per_device": st.flops,
            "bytes_per_device": st.bytes_accessed,
            "collective_bytes_per_device": st.collective_bytes,
            "collective_counts": st.collective_counts,
        },
        "total_s": round(time.time() - t0, 2),
    }
    try:
        ma = compiled.memory_analysis()
        if ma is not None:
            report["memory"] = {
                k: int(getattr(ma, k))
                for k in ("temp_size_in_bytes", "argument_size_in_bytes", "output_size_in_bytes")
                if getattr(ma, k, None) is not None
            }
    except Exception:  # noqa: BLE001
        pass
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description="Multi-pod dry-run of the PBVD decoder.")
    ap.add_argument("--viterbi", action="store_true", help="dry-run every cell")
    ap.add_argument("--shape", choices=list(VARIANTS), help="dry-run one cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--out")
    args = ap.parse_args()

    if args.viterbi:
        for mp in (False, True):
            for variant in VARIANTS:
                r = run_viterbi_cell(variant, mp)
                p = _cell_report_path(variant, mp)
                p.parent.mkdir(parents=True, exist_ok=True)
                p.write_text(json.dumps(r, indent=2))
                print(f"[ok] viterbi × {variant} × {r['mesh']} ({r['total_s']}s)", flush=True)
        return
    if args.shape is None:
        ap.error("give --viterbi or --shape")

    report = run_viterbi_cell(args.shape, args.multi_pod)
    text = json.dumps(report, indent=2)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(text)
    print(text)


if __name__ == "__main__":
    main()
