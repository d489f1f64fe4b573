"""Benchmark runner: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV (one line per measurement).
"""
from __future__ import annotations

import sys
import time


def main() -> None:
    from benchmarks import (
        bench_cluster_throughput,
        bench_collocation,
        bench_layer_scalability,
        bench_multiplex_ablation,
        bench_planner,
        bench_scaling,
    )

    modules = [
        ("table3_planner_search", bench_planner),
        ("fig1_3_scaling_strategies", bench_scaling),
        ("fig5_layer_scalability", bench_layer_scalability),
        ("fig9_10_cluster_throughput", bench_cluster_throughput),
        ("fig11_multiplex_ablation", bench_multiplex_ablation),
        ("fig12_collocation", bench_collocation),
    ]
    print("name,us_per_call,derived")
    for name, mod in modules:
        t0 = time.perf_counter()
        try:
            rows = mod.run()
        except Exception as e:  # a failing bench must not hide the others
            print(f"{name},0.0,ERROR {e!r}")
            continue
        dt = time.perf_counter() - t0
        for r in rows:
            derived = str(r["derived"]).replace(",", ";")
            print(f"{r['name']},{r['us_per_call']:.1f},{derived}")
        print(f"{name}/wall,{dt*1e6:.0f},bench module wall time", flush=True)


if __name__ == "__main__":
    main()
