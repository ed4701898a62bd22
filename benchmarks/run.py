"""Benchmark harness — one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (plus # section headers).

  fig6a — exec time per accelerator (paper Fig. 6a)
  fig6b — multi-core scaling (paper Fig. 6b)
  fig7  — GEMM vs non-GEMM breakdown (paper Fig. 7)
  fig8  — memory accesses per level (paper Fig. 8)
  conversion — RWMA<->BWMA conversion overhead (paper §3.2)
  kernel_report — Pallas DMA-contiguity / VMEM structure (TPU adaptation)
  backend_parity — blocked encoder through each execution backend
  roofline — summary of dry-run roofline terms, if artifacts exist
"""
import argparse
import os


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--scale", type=float, default=1.0,
                    help="<1.0 shrinks the memmodel workload (CI speed)")
    ap.add_argument("--only", nargs="*", default=None)
    args, _ = ap.parse_known_args()

    from repro.launch.compile_cache import use_compile_cache

    use_compile_cache()

    from benchmarks import (
        backend_parity,
        conversion_overhead,
        fig6a_accelerators,
        fig6b_cores,
        fig7_breakdown,
        fig8_memaccess,
        kernel_report,
        serve_throughput,
    )

    sections = {
        "fig6a": fig6a_accelerators.run,
        "fig6b": fig6b_cores.run,
        "fig7": fig7_breakdown.run,
        "fig8": fig8_memaccess.run,
        "conversion": conversion_overhead.run,
        "kernel_report": kernel_report.run,
        "backend_parity": backend_parity.run,
        "serve_throughput": serve_throughput.run,
    }
    for name, fn in sections.items():
        if args.only and name not in args.only:
            continue
        fn(scale=args.scale)

    # roofline summary (reads dry-run artifacts when present)
    if (args.only is None or "roofline" in args.only) and os.path.isdir(
        "experiments/dryrun"
    ):
        from repro.analysis import roofline as R

        recs = R.load_all("experiments/dryrun")
        rows = [a for a in (R.analyze_record(r) for r in recs) if a]
        print(f"# roofline: {len(rows)} compiled cells")
        for r in sorted(rows, key=lambda x: x["roofline_fraction"]):
            print(
                f"roofline/{r['arch']}/{r['shape']}/{r['mesh']},0.0,"
                f"dom={r['dominant']} frac={r['roofline_fraction']:.3f} "
                f"useful={r['useful_ratio']:.2f}"
            )


if __name__ == "__main__":
    main()
