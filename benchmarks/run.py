"""Benchmark aggregator — one entry per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--full] [--only NAME]

Prints a ``name,us_per_call,derived`` CSV line per microbench plus one
summary line per table artifact. ``--full`` uses the larger dataset and
longer training (the headline numbers recorded in EXPERIMENTS.md).
"""
from __future__ import annotations

import argparse
import json
import sys
import time

from repro.compile_cache import enable_compile_cache


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--n-graphs", type=int, default=None)
    ap.add_argument("--epochs", type=int, default=None)
    args = ap.parse_args()

    n_graphs = args.n_graphs or (1200 if args.full else 240)
    epochs = args.epochs or (60 if args.full else 25)

    from . import (accuracy_mape, chaos_resilience, engine_throughput,
                   fig3_mig_memory, fig4_scatter, fused_mp, microbench,
                   packed_batching, roofline_report, serving_fleet,
                   serving_latency, sparse_mp, table2_dataset, table4_gnn,
                   table5_mig, train_throughput)

    jobs = {
        "microbench": lambda: microbench.run(),
        "engine": lambda: engine_throughput.run(),
        "train": lambda: train_throughput.run(),
        "sparse_mp": lambda: sparse_mp.run(),
        "packed_batching": lambda: packed_batching.run(),
        "fused_mp": lambda: fused_mp.run(),
        "serving_latency": lambda: serving_latency.run(),
        "serving_fleet": lambda: serving_fleet.run(),
        "chaos": lambda: chaos_resilience.run(),
        "table2": lambda: table2_dataset.run(n_graphs=n_graphs),
        "accuracy_mape": lambda: accuracy_mape.run(full=args.full),
        "table4": lambda: table4_gnn.run(n_graphs=n_graphs, epochs=epochs),
        "table5": lambda: table5_mig.run(n_graphs=n_graphs,
                                         epochs=max(epochs, 12)),
        "fig3": lambda: fig3_mig_memory.run(),
        "fig4": lambda: fig4_scatter.run(n_graphs=n_graphs,
                                         epochs=max(epochs, 12)),
        "roofline_single": lambda: roofline_report.run("single"),
        "roofline_multi": lambda: roofline_report.run("multi"),
    }
    if args.only:
        jobs = {k: v for k, v in jobs.items() if k == args.only}

    print("name,us_per_call,derived")
    failed = []
    for name, job in jobs.items():
        t0 = time.perf_counter()
        try:
            out = job()
        except Exception as e:  # pragma: no cover
            print(f"{name},ERROR,{type(e).__name__}: {e}")
            failed.append(name)
            continue
        dt = time.perf_counter() - t0
        if name == "microbench":
            for r in out["rows"]:
                print(f"{r['name']},{r['us_per_call']},{r['derived']}")
            continue
        derived = {k: v for k, v in out.items()
                   if k not in ("rows", "artifact")}
        print(f"{name},{round(dt * 1e6)},"
              f"\"{json.dumps(derived, default=str)[:160]}\"")
    if failed:
        sys.exit(f"jobs that raised: {', '.join(failed)}")


if __name__ == "__main__":
    main()
