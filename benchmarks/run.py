"""Benchmark driver: one function per paper table/figure.
Prints ``name,us_per_call,derived`` CSV rows + writes results/bench.json.

  PYTHONPATH=src python -m benchmarks.run            # full suite
  PYTHONPATH=src python -m benchmarks.run --quick    # smaller graphs
  PYTHONPATH=src python -m benchmarks.run --tiny --tag smoke   # CI smoke
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

RESULTS = Path(__file__).resolve().parents[1] / "results"


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--tiny", action="store_true",
                    help="CI smoke profile: scale-8 graphs, k=4, core "
                         "suites only (seconds, not minutes)")
    ap.add_argument("--only", default=None)
    ap.add_argument("--tag", default=None,
                    help="also write results/BENCH_<tag>.json")
    args = ap.parse_args()
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    scale = 11 if args.quick else 12

    from . import bench_partitioning as bp
    from .bench_pagerank import (fig8_pagerank, layout_build_bench,
                                 program_matrix_bench)
    from .bench_kernels import kernels_microbench

    if args.tiny:
        suites = {
            "fig3_rf_web": lambda: bp.fig3_rf_vs_partitions(
                scale=8, ks=(4,)),
            "fig7_runtime": lambda: bp.fig7_runtime_vs_k(
                scale=8, ks=(4,)),
            # backend sweep incl. the sharded-backend smoke (runs on the
            # CI job's 8 virtual devices; skips itself when too few)
            "fig12_runtime": lambda: bp.fig12_runtime_vs_k(
                scale=8, ks=(4,), nodes=4, repeats=1),
            "fig8_pagerank": lambda: fig8_pagerank(scale=8, k=4, iters=10),
            # one row per GAS program (modelled bytes per exchange +
            # oracle error) and the fused-vs-separate ratio column
            "program_matrix": lambda: program_matrix_bench(
                scale=8, k=4, iters=10),
            "layout_build": lambda: layout_build_bench(scale=8, k=4),
        }
        run_suites(suites, args)
        return

    suites = {
        "fig3_rf_web": lambda: bp.fig3_rf_vs_partitions(scale=scale),
        "fig4_social": lambda: bp.fig4_social(scale=scale),
        "fig5_size": lambda: bp.fig5_graph_size(
            scales=tuple(range(scale - 2, scale + 1))),
        "fig6_space": lambda: bp.fig6_space(scale=scale),
        "fig7_runtime": lambda: bp.fig7_runtime_vs_k(scale=scale),
        "fig8_pagerank": lambda: fig8_pagerank(scale=scale - 1),
        "program_matrix": lambda: program_matrix_bench(scale=scale - 2),
        "layout_build": lambda: layout_build_bench(scale=scale),
        "fig9_ablation": lambda: bp.fig9_ablation(scale=scale),
        "fig10_parallel": lambda: bp.fig10_parallelization(scale=scale),
        "fig12_runtime": lambda: bp.fig12_runtime_vs_k(
            scale=scale, ks=(16, 64), nodes=4),
        "fig11_weight": lambda: bp.fig11_weight_and_balance(scale=scale),
        "kernels": kernels_microbench,
    }
    run_suites(suites, args)


def run_suites(suites: dict, args) -> None:
    if args.only:
        suites = {k: v for k, v in suites.items() if args.only in k}

    all_rows = []
    print("name,us_per_call,derived")
    for name, fn in suites.items():
        t0 = time.time()
        try:
            rows = fn()
        except Exception as e:  # noqa: BLE001
            print(f"{name},ERROR,{type(e).__name__}:{e}", file=sys.stderr)
            raise
        dt = time.time() - t0
        all_rows.extend(rows)
        for r in rows:
            derived = ";".join(f"{k}={v}" for k, v in r.items()
                               if k != "bench")
            print(f"{r.get('bench', name)},"
                  f"{r.get('us_per_edge', round(1e6 * dt / max(len(rows), 1), 1))},"
                  f"{derived}")
    RESULTS.mkdir(exist_ok=True)
    (RESULTS / "bench.json").write_text(json.dumps(all_rows, indent=1))
    if args.tag:
        (RESULTS / f"BENCH_{args.tag}.json").write_text(
            json.dumps(all_rows, indent=1))


if __name__ == "__main__":
    main()
