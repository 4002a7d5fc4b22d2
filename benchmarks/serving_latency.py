"""Serving gate: micro-batched request stream vs per-request loop.

The ROADMAP north star is serving heavy concurrent traffic; PR 5's
``repro.serve.PredictionService`` exists to make a *stream of
single-graph requests* ride the packed engine bins a bulk sweep gets.
This gate drives a *Poisson arrival stream* of single-graph requests at
the service — open-loop, arrivals faster than the per-request baseline
can drain, so the micro-batcher has to coalesce to keep up — and pins:

* **Throughput** — the service sustains ≥ 3× the predictions/s of a
  sequential per-request ``predict_graph`` loop over the same graphs
  (on a single-core host the batcher thread and the arrival loop share
  one CPU, which compresses the ratio — the gate drops to ≥ 1.5× there
  and records ``gate_tier`` in the artifact, same policy as
  ``serving_fleet``).
* **Equivalence** — every streamed result matches the per-request
  ``predict_graph`` prediction to ≤ 1e-5.
* **FIFO** — futures resolve in submission order.

Also reports queue/occupancy/padding and p50/p99 request latency from
:class:`~repro.serve.ServeStats`. Emits ``BENCH_serving_latency.json``.

    PYTHONPATH=src python -m benchmarks.serving_latency
"""
from __future__ import annotations

import sys
import time

from repro.compile_cache import enable_compile_cache

from .common import timed, write_json


def _request_graphs(n: int, seed: int = 0):
    """Mixed-size chain DAGs (8–64 nodes) — the single-model probes a
    design-space explorer fires at a shared predictor. Small on purpose:
    a lone small graph still pays the engine's smallest 256-node-slot
    rung, which is exactly the per-request waste micro-batching
    reclaims."""
    import numpy as np
    from repro.core.ir import OpGraph, OpNode

    rng = np.random.default_rng(seed)
    ops = ["dense", "conv", "relu", "add", "norm", "pool"]
    graphs = []
    for gi in range(n):
        nn = int(rng.integers(8, 64))
        nodes = [OpNode(i, ops[int(rng.integers(0, len(ops)))],
                        (int(rng.integers(1, 16)), int(rng.integers(1, 64))),
                        flops=float(rng.integers(1, 10_000)),
                        macs=float(rng.integers(1, 5_000)))
                 for i in range(nn)]
        edges = [(i, i + 1) for i in range(nn - 1)]
        graphs.append(OpGraph(nodes=nodes, edges=edges,
                              meta={"req": gi, "n": nn}))
    return graphs


def run(n_requests: int = 256, hidden: int = 128, rate_mult: float = 24.0,
        max_wait_ms: float = 15.0, max_batch_graphs: int = 160,
        seed: int = 0):
    import jax
    import numpy as np
    from repro.core import DIPPM, PMGNSConfig, pmgns_init

    cfg = PMGNSConfig(hidden=hidden, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    graphs = _request_graphs(n_requests, seed=seed)
    # warm the memoized canonical fingerprints outside the timed stream:
    # a real client pays the WL hash once when the graph is traced, not
    # per submit — this gate measures micro-batching, not hashing (the
    # cache-vs-cold economics are serving_fleet's job)
    for g in graphs:
        g.fingerprint()

    # -- baseline: sequential per-request predict_graph loop ---------------
    base = DIPPM.from_params(params, cfg)
    [base.predict_graph(g) for g in graphs[:8]]       # warm compiled rungs
    loop_preds, t_loop = timed(
        lambda: [base.predict_graph(g) for g in graphs], repeats=1)
    loop_rate = n_requests / t_loop

    # -- service under an open-loop Poisson arrival stream -----------------
    dippm = DIPPM.from_params(params, cfg)
    svc = dippm.serve(max_wait_ms=max_wait_ms,
                      max_batch_graphs=max_batch_graphs)
    rungs = svc.warmup()
    rng = np.random.default_rng(seed)
    # absolute-time schedule: a late submit catches up instead of
    # pushing every later arrival back (sleep() overshoot would
    # otherwise cap the offered rate well below the intended one)
    arrivals = np.cumsum(
        rng.exponential(1.0 / (rate_mult * loop_rate), n_requests))
    order = []
    futs = []
    t0 = time.perf_counter()
    for i, g in enumerate(graphs):
        dt = t0 + arrivals[i] - time.perf_counter()
        if dt > 0:
            time.sleep(dt)
        fut = svc.submit(g)
        fut.add_done_callback(lambda f, i=i: order.append(i))
        futs.append(fut)
    svc.flush()
    serve_preds = [f.result(timeout=300) for f in futs]
    t_serve = time.perf_counter() - t0
    serve_rate = n_requests / t_serve
    stats = svc.stats
    svc.close()

    max_diff = max(
        max(abs(a.latency_ms - b.latency_ms),
            abs(a.energy_j - b.energy_j),
            abs(a.memory_mb - b.memory_mb))
        for a, b in zip(loop_preds, serve_preds))

    res = {
        "n_requests": n_requests,
        "warmup_rungs": rungs,
        "loop_pred_per_s": round(loop_rate, 2),
        "serve_pred_per_s": round(serve_rate, 2),
        "speedup": round(serve_rate / loop_rate, 2),
        "arrival_rate_mult": rate_mult,
        "fifo": order == sorted(order),
        "max_abs_diff": float(max_diff),
        "batches": stats.batches,
        "batch_occupancy": stats.batch_occupancy,
        "queue_peak": stats.queue_peak,
        "padding_waste_frac": round(stats.padding_waste_frac, 4),
        "latency_ms_p50": round(stats.latency_ms_p50, 2),
        "latency_ms_p99": round(stats.latency_ms_p99, 2),
        # all-unique stream: every request should miss the prediction
        # cache (hits here would mean fingerprint collisions)
        "cache_hits": stats.cache_hits,
        "cache_misses": stats.cache_misses,
        "hit_rate": stats.hit_rate,
        "shed_count": stats.shed_count,
    }
    # single-core hosts timeshare the batcher thread, the engine and
    # the Poisson submit loop on one CPU, compressing serve/loop to
    # ~2x (PR-5 code measures 2.1-2.4x on a 1-core box vs its recorded
    # 3.6-4.7x multi-core) — tier the bar honestly like serving_fleet
    import os
    cores = os.cpu_count() or 1
    if cores >= 2:
        res["gate_tier"], min_speedup = "multi-core", 3.0
    else:
        res["gate_tier"], min_speedup = "single-core", 1.5
    res["min_speedup"] = min_speedup
    res["ok"] = bool(res["speedup"] >= min_speedup and res["fifo"]
                     and max_diff <= 1e-5)
    res["artifact"] = write_json("BENCH_serving_latency.json", res)
    return res


def main():
    enable_compile_cache()
    res = run()
    print(f"loop   : {res['loop_pred_per_s']:8.2f} pred/s  (sequential "
          f"predict_graph, {res['n_requests']} requests)")
    print(f"serve  : {res['serve_pred_per_s']:8.2f} pred/s  speedup "
          f"{res['speedup']:.2f}x  (Poisson stream at "
          f"{res['arrival_rate_mult']:.0f}x loop rate)")
    print(f"batch  : {res['batches']} drains, occupancy "
          f"{res['batch_occupancy']:.1f} graphs/drain, queue peak "
          f"{res['queue_peak']}, padding {res['padding_waste_frac']:.1%}")
    print(f"latency: p50 {res['latency_ms_p50']:.1f} ms  p99 "
          f"{res['latency_ms_p99']:.1f} ms  (warmed {res['warmup_rungs']} "
          f"rungs)")
    print(f"cache  : {res['cache_hits']} hits / {res['cache_misses']} "
          f"misses (hit rate {res['hit_rate']:.1%}, all-unique stream), "
          f"shed {res['shed_count']}")
    print(f"equiv  : max |diff| vs predict_graph = "
          f"{res['max_abs_diff']:.2e}  fifo={res['fifo']}")
    print("PASS" if res["ok"] else "FAIL",
          f"(targets [{res['gate_tier']}]: ≥{res['min_speedup']:.1f}x "
          f"pred/s vs per-request loop, equiv ≤1e-5, FIFO resolution)")
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
