#!/usr/bin/env python3
"""Offer an open-loop traffic mix at several fixed rates on one chip, to
find the highest rate it sustains (its knee). Not part of a benchmark
run: a cell of that traffic then records 0.8 of the rate found in its
traffic file.

    python3 bench/sweep.py --config <configuration> --traffic <mix> \\
        --seed <n> --seconds 8 --rates 20,30,40,50

``--config`` names an entry of ``BENCHMARK.json``'s ``configs`` and
``--traffic`` a file ``bench/traffic/<mix>.json``; the pair need not be
a cell yet.

One set-up, then one window per rate. Each prints a JSON line: the rate
offered and completed, the latency median and 95th percentile, the
generator's lag, and the 95th percentile of the first and the last third
of the window's requests, which part when a backlog grows.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

import run
import window


def rate_row(w, rate: float) -> dict:
    lat = window.latencies_ms(w)
    third = max(len(lat) // 3, 1)
    return {
        "rate_per_s": rate,
        "offered_per_s": w.n_due / w.seconds,
        "completed_per_s": len(window.completed_in_window(w)) / w.seconds,
        "p50_ms": window.percentile(lat, 50),
        "p95_ms": window.percentile(lat, 95),
        "p95_first_third_ms": window.percentile(lat[:third], 95),
        "p95_last_third_ms": window.percentile(lat[-third:], 95),
        "lag_p95_ms": window.percentile(window.generator_lag_ms(w), 95),
        "graphs_per_bin": window.graphs_per_bin(w),
        "compiles": w.deltas["recompiles"],
        "failed": len(w.requests.errors),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        sess = run.Session({"name": f"{args.config}.{args.traffic}",
                            "config": args.config, "traffic": args.traffic,
                            "chips": 1}, args.seed)
    except run.NoChip as e:
        run.log(f"no result: {e}")
        return 1
    try:
        if sess.traffic["loop"] != "open":
            raise SystemExit(f"{args.traffic} is not an open loop")
        for k, rate in enumerate(float(r) for r in args.rates.split(",")):
            w = sess.window(args.seconds, stream=10 + k,
                            traffic=dict(sess.traffic, rate_per_s=rate))
            print(json.dumps(rate_row(w, rate)), flush=True)
    finally:
        sess.close()
    return 0


if __name__ == "__main__":
    np.seterr(all="ignore")
    sys.exit(main())
