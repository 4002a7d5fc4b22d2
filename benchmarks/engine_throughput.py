"""Engine throughput: batched ``predict_many`` vs the per-graph loop.

The predictor's own throughput is the product metric for design-space
exploration (PerfSAGE / PerfSeer both report it): a zoo sweep scores
hundreds of candidate graphs, so predictions/sec — not single-graph
latency — decides how fast the search runs.

Sweeps a 64-model zoo grid (4 families × 16 variants), times

* **eager**  — an un-jitted batch-of-1 apply per graph (what
  ``predict_graph`` did before the engine existed; kept inline here as
  the historical baseline the ≥3x gate is pinned against),
* **loop**   — ``[dippm.predict_graph(g) for g in graphs]`` (today's
  facade: each call a submit/flush round trip through the shared
  serving path onto compiled engine bins), and
* **engine** — ``dippm.predict_many(graphs)`` (bucketed, batched, one
  compiled apply per padded shape),

and checks all paths produce identical predictions (max |Δ| ≤ 1e-5 on
latency/energy/memory). Tracing the 64 graphs is *not* timed — all
paths consume the same pre-built ``OpGraph`` list.

    PYTHONPATH=src python -m benchmarks.engine_throughput
"""
from __future__ import annotations

from repro.compile_cache import enable_compile_cache

from .common import timed, write_json


def _sweep_graphs():
    """64 zoo graphs: 4 families × (4 shape points × 4 batch sizes)."""
    from repro.zoo.families import trace_family, variant_grid
    grids = {
        "mobilenet": variant_grid("mobilenet", {
            "width": [0.35, 0.5, 0.75, 1.0], "batch": [1, 4, 16, 64],
            "res": [128]}),
        "mnasnet": variant_grid("mnasnet", {
            "width": [0.35, 0.5, 0.75, 1.0], "batch": [1, 4, 16, 64],
            "res": [128]}),
        "resnet": variant_grid("resnet", {
            "width": [0.5, 1.0], "bottleneck": [False, True],
            "batch": [1, 4, 16, 64], "res": [128]}),
        "vit": variant_grid("vit", {
            "dim": [192, 384], "depth": [6, 12], "batch": [1, 4, 16, 64],
            "res": [224], "patch": [32]}),
    }
    graphs = []
    for fam, grid in grids.items():
        graphs.extend(trace_family(fam, cfg) for cfg in grid)
    return graphs


def _eager_predict(dippm, g):
    """The pre-engine ``predict_graph``: un-jitted batch-of-1 apply."""
    import jax.numpy as jnp
    import numpy as np
    from repro.core.batching import collate, sample_from_graph
    from repro.core.gnn import decode_targets, pmgns_apply
    from repro.core.predictor import make_prediction

    batch = collate([sample_from_graph(g)])
    jb = {k: jnp.asarray(v) for k, v in batch.items() if k != "y"}
    y = decode_targets(pmgns_apply(dippm.params, dippm.cfg, jb,
                                   train=False))
    return make_prediction(np.asarray(y)[0], meta=dict(g.meta))


def run(n_graphs: int = 64, hidden: int = 128, repeats: int = 3):
    import jax
    import numpy as np
    from repro.core import DIPPM, PMGNSConfig, pmgns_init

    graphs = _sweep_graphs()[:n_graphs]
    cfg = PMGNSConfig(hidden=hidden)
    dippm = DIPPM.from_params(pmgns_init(jax.random.PRNGKey(0), cfg), cfg)

    eager_out, eager_s = timed(
        lambda: [_eager_predict(dippm, g) for g in graphs], repeats=repeats)
    loop_out, loop_s = timed(
        lambda: [dippm.predict_graph(g) for g in graphs], repeats=repeats)
    dippm.predict_many(graphs)          # warm the compiled-fn cache
    st = dippm.engine().stats
    compiles, batches0 = st.cache_misses, st.batches_run
    many_out, many_s = timed(
        lambda: dippm.predict_many(graphs), repeats=repeats)
    batches_per_sweep = (st.batches_run - batches0) // repeats
    stats = dippm.engine().stats.snapshot()    # counters of the timed runs

    diffs = [
        max(abs(a.latency_ms - b.latency_ms), abs(a.energy_j - b.energy_j),
            abs(a.memory_mb - b.memory_mb))
        for ref, out in ((eager_out, many_out), (loop_out, many_out))
        for a, b in zip(ref, out)
    ]
    res = {
        "n_graphs": len(graphs),
        "eager_pred_per_s": round(len(graphs) / eager_s, 2),
        "loop_pred_per_s": round(len(graphs) / loop_s, 2),
        "engine_pred_per_s": round(len(graphs) / many_s, 2),
        "speedup": round(eager_s / many_s, 2),
        "loop_speedup": round(eager_s / loop_s, 2),
        "max_abs_diff": float(np.max(diffs)),
        "batches_per_sweep": batches_per_sweep,
        "compiles": compiles,
        "cache_entries": stats.cache_entries,
        "recompiles": stats.recompiles,
        "padding_waste_frac": round(stats.padding_waste_frac, 4),
        "precision": stats.precision,
        "bf16_max_abs_delta": stats.bf16_max_abs_delta,
    }
    res["artifact"] = write_json("engine_throughput.json", res)
    return res


def main():
    enable_compile_cache()
    res = run()
    print(f"eager  : {res['eager_pred_per_s']:9.2f} predictions/s "
          f"(pre-engine batch-of-1 baseline)")
    print(f"loop   : {res['loop_pred_per_s']:9.2f} predictions/s "
          f"(predict_graph via the serving path, "
          f"{res['loop_speedup']:.2f}x eager)")
    print(f"engine : {res['engine_pred_per_s']:9.2f} predictions/s "
          f"({res['compiles']} compiles, {res['batches_per_sweep']} "
          f"batched calls/sweep)")
    print(f"stats  : {res['cache_entries']} cache entries, "
          f"{res['recompiles']} recompiles, "
          f"{res['padding_waste_frac']:.1%} of node rows padding")
    delta = res["bf16_max_abs_delta"]
    print(f"precis : policy {res['precision']}"
          + (f", bf16 warmup |Δ| vs f32 = {delta:.2e}"
             if delta is not None else
             " (bf16 drift probe runs only under precision='bf16')"))
    print(f"speedup: {res['speedup']:.2f}x   "
          f"max |diff| = {res['max_abs_diff']:.2e}")
    ok = res["speedup"] >= 3.0 and res["max_abs_diff"] <= 1e-5
    print("PASS" if ok else "FAIL", "(target: ≥3x, |diff| ≤ 1e-5)")
    return 0 if ok else 1


if __name__ == "__main__":
    import sys
    sys.exit(main())
