"""Benchmark harness entry point: one benchmark per paper table/figure
plus the kernel report.

  fig1   — Fig. 1 + §V-C: non-i.i.d. degree metric vs WD / label-ratio,
           least-squares fit R^2
  fig3   — Fig. 3: FedAvg / DSL / Multi-DSL / M-DSL accuracy under
           iid / non-iid I / non-iid II
  comm   — §IV-C: uploaded parameters per round, rounds-to-accuracy
  kernels  — Pallas kernel correctness + VMEM/roofline accounting

`python -m benchmarks.run` runs everything in quick mode (CPU-sized);
`--full` uses the paper's settings (50 workers, 20/40 rounds);
`--only fig3,comm` selects a subset.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list of fig1,fig3,comm,kernels")
    ap.add_argument("--dataset", default="mnist_like",
                    choices=["mnist_like", "cifar_like"])
    args = ap.parse_args()
    quick = not args.full
    sel = set(args.only.split(",")) if args.only else {
        "fig1", "fig3", "comm", "kernels"}

    t0 = time.time()
    if "kernels" in sel:
        from benchmarks import kernel_bench
        kernel_bench.run()
    if "fig1" in sel:
        from benchmarks import fig1_metric
        fig1_metric.run(quick=quick, dataset=args.dataset)
    if "comm" in sel:
        from benchmarks import comm_efficiency
        comm_efficiency.run(quick=quick, dataset=args.dataset)
    if "fig3" in sel:
        from benchmarks import fig3_accuracy
        fig3_accuracy.run(quick=quick, dataset=args.dataset)
    print(f"\nbenchmarks done in {time.time() - t0:.0f}s "
          f"({'quick' if quick else 'full'} mode)")


if __name__ == "__main__":
    main()
