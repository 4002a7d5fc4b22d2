"""Packed block-diagonal batching gate: flat node axis vs padded buckets.

The padded-sparse path (PR 3) killed the O(N²) adjacency but still pads
every graph to its (node bucket, edge bucket) and compiles per
(N, E, B) shape — a mixed-size zoo therefore wastes most of its device
rows on bucket quantization and batch-pow2 phantom rows, and fragments
into one small dispatch per bucket. The packed layout
(``PMGNSConfig(layout="packed")``) bin-packs mixed-size graphs onto one
flat ``x [P, F]`` axis under a token budget, so padding exists only at
each bin's tail and the whole engine compiles a handful of ``(P, Q, G)``
budget shapes. This gate pins four claims on a realistic mixed-size zoo
(DIPPM-like size mix: mostly small DAGs, a heavy tail up to ~700 nodes):

* **Throughput** — packed engine predictions/sec ≥ 2× padded-sparse.
* **Compile cache** — packed compiled-shape entries ≤ ⅕ of the
  padded-sparse engine's at equal coverage (same graphs predicted).
* **Equivalence** — packed, sparse, and dense predictions agree to
  ≤ 1e-5 for all five layer variants.
* **Trainer parity** — a packed scan-trainer epoch reproduces the
  padded-sparse epoch loss to ≤ 1e-4 relative (dropout disabled: the
  packed layout changes activation *shapes*, so train-mode dropout
  draws a different mask stream; disabling it isolates layout numerics).

Emits one aggregate ``BENCH_packed_batching.json`` artifact for CI.

    PYTHONPATH=src python -m benchmarks.packed_batching
"""
from __future__ import annotations

import sys

from repro.compile_cache import enable_compile_cache

from .common import timed, write_json

VARIANTS = ("graphsage", "gcn", "gat", "gin", "mlp")


def _mixed_zoo(n_graphs: int, seed: int = 0):
    """DIPPM-like mixed-size sample zoo: 60 % small (8–40 nodes), 30 %
    medium (50–200), 10 % large (300–700) — spans every node bucket so
    the padded path pays its full bucket × batch shape cross-product."""
    from repro.dataset.builder import synthetic_samples
    n_small = int(0.6 * n_graphs)
    n_med = int(0.3 * n_graphs)
    n_large = n_graphs - n_small - n_med
    return (synthetic_samples(n_small, seed=seed, n_min=8, n_max=40)
            + synthetic_samples(n_med, seed=seed + 1, n_min=50, n_max=200)
            + synthetic_samples(n_large, seed=seed + 2, n_min=300,
                                n_max=700))


def _equivalence_deltas(samples, hidden: int):
    """max |Δ| of decoded predictions across all three layouts, per
    variant (worst pairing of packed/sparse/dense)."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.batching import collate, collate_packed, group_by_bucket
    from repro.core.gnn import PMGNSConfig, pmgns_infer, pmgns_init

    deltas = {}
    for variant in VARIANTS:
        cfg_d = PMGNSConfig(variant=variant, hidden=hidden)
        cfg_s = PMGNSConfig(variant=variant, hidden=hidden, sparse_mp=True)
        cfg_p = PMGNSConfig(variant=variant, hidden=hidden, layout="packed")
        params = pmgns_init(jax.random.PRNGKey(0), cfg_d)
        yd = np.zeros((len(samples), 3), np.float32)
        ys = np.zeros_like(yd)
        for _, members in group_by_bucket(samples).items():
            chunk = [samples[j] for j in members]
            bd = {k: jnp.asarray(v) for k, v in collate(chunk).items()
                  if k != "y"}
            bs = {k: jnp.asarray(v)
                  for k, v in collate(chunk, sparse=True).items()
                  if k != "y"}
            yd[members] = np.asarray(pmgns_infer(params, cfg_d, bd))
            ys[members] = np.asarray(pmgns_infer(params, cfg_s, bs))
        bp = {k: jnp.asarray(v) for k, v in collate_packed(samples).items()
              if k not in ("y", "wt")}
        yp = np.asarray(pmgns_infer(params, cfg_p, bp))[:len(samples)]
        deltas[variant] = float(max(np.abs(yd - ys).max(),
                                    np.abs(yd - yp).max(),
                                    np.abs(ys - yp).max()))
    return deltas


def _throughput(samples, hidden: int, repeats: int, request_size: int):
    """Packed vs padded-sparse engine over the mixed-size zoo.

    Two traffic shapes, same coverage: one **bulk** sweep (the whole zoo
    in a single ``predict_samples`` call — the offline design-space
    scan) and a **request stream** (the zoo arriving as shuffled
    ``request_size``-graph calls — the serving shape the ROADMAP's
    heavy-traffic north star actually sees). The stream is where padded
    buckets hurt most: every small request fragments across ~6 node
    buckets into pow2-padded mini-batches, while the packed engine runs
    it as one flat bin. The ≥2× gate is on the stream; the bulk number
    is reported for the crossover table.
    """
    import jax
    import numpy as np
    from repro.core.engine import PredictionEngine
    from repro.core.gnn import PMGNSConfig, pmgns_init

    cfg_s = PMGNSConfig(hidden=hidden, sparse_mp=True)
    cfg_p = PMGNSConfig(hidden=hidden, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg_s)
    eng_s = PredictionEngine(params, cfg_s)
    eng_p = PredictionEngine(params, cfg_p)

    rng = np.random.default_rng(0)
    order = rng.permutation(len(samples))
    # serving requests come in assorted sizes (a single variant probe, a
    # family grid, a page of candidates) — cycle ½×/1×/2× around the
    # nominal request size so the stream carries that variety
    sizes, requests, i = (max(1, request_size // 2), request_size,
                          2 * request_size), [], 0
    while i < len(order):
        k = sizes[len(requests) % len(sizes)]
        requests.append([samples[j] for j in order[i:i + k]])
        i += k

    def stream(eng):
        for req in requests:
            eng.predict_samples(req)

    ys = eng_s.predict_samples(samples)          # warm compiled fns
    yp = eng_p.predict_samples(samples)
    stream(eng_s)
    stream(eng_p)
    # interleave sparse/packed rounds and keep each engine's best time:
    # shared-runner load shifts hit both paths alike, so min-of-N keeps
    # the *ratio* stable where a median would wander with the machine
    t_s = t_p = r_s = r_p = float("inf")
    for _ in range(repeats):
        _, t = timed(lambda: eng_s.predict_samples(samples), repeats=1)
        t_s = min(t_s, t)
        _, t = timed(lambda: eng_p.predict_samples(samples), repeats=1)
        t_p = min(t_p, t)
        _, t = timed(lambda: stream(eng_s), repeats=1)
        r_s = min(r_s, t)
        _, t = timed(lambda: stream(eng_p), repeats=1)
        r_p = min(r_p, t)
    return {
        "bulk": {
            "sparse_pred_per_s": round(len(samples) / t_s, 2),
            "packed_pred_per_s": round(len(samples) / t_p, 2),
            "speedup": round(t_s / t_p, 2),
        },
        "stream": {
            "request_size": request_size,
            "sparse_pred_per_s": round(len(samples) / r_s, 2),
            "packed_pred_per_s": round(len(samples) / r_p, 2),
            "speedup": round(r_s / r_p, 2),
        },
        "max_abs_diff": float(np.abs(ys - yp).max()),
        "sparse_cache_entries": eng_s.stats.cache_entries,
        "packed_cache_entries": eng_p.stats.cache_entries,
        "cache_ratio": round(eng_s.stats.cache_entries
                             / max(eng_p.stats.cache_entries, 1), 1),
        "sparse_padding_waste_frac": round(
            eng_s.stats.padding_waste_frac, 4),
        "packed_padding_waste_frac": round(
            eng_p.stats.padding_waste_frac, 4),
    }


def _trainer_epoch_match(n_samples: int, hidden: int):
    """Packed vs padded-sparse scan epochs — identical batch schedule by
    construction, dropout off so the RNG stream is shape-independent."""
    from repro.core.gnn import PMGNSConfig
    from repro.dataset.builder import synthetic_samples
    from repro.train.gnn_trainer import TrainConfig, train_pmgns

    samples = synthetic_samples(n_samples, seed=7)
    common = dict(epochs=2, batch_size=8, lr=1e-3, seed=0, scan_steps=16)
    _, h_s = train_pmgns(
        PMGNSConfig(hidden=hidden, sparse_mp=True, dropout=0.0),
        samples, (), TrainConfig(mode="scan", **common))
    _, h_p = train_pmgns(
        PMGNSConfig(hidden=hidden, layout="packed", dropout=0.0),
        samples, (), TrainConfig(mode="scan", **common))
    rel = max(
        abs(a["train_loss"] - b["train_loss"])
        / max(abs(a["train_loss"]), 1e-12)
        for a, b in zip(h_s, h_p))
    return {"epochs": len(h_p), "steps": h_p[0]["steps"],
            "loss_rel_diff": float(rel)}


def run(n_graphs: int = 192, hidden: int = 64, repeats: int = 4,
        request_size: int = 8):
    import numpy as np

    samples = _mixed_zoo(n_graphs)
    thr = _throughput(samples, hidden, repeats, request_size)
    deltas = _equivalence_deltas(samples[:8] + samples[-4:], hidden)
    trainer = _trainer_epoch_match(64, 16)

    res = {
        "n_graphs": len(samples),
        "node_count_min": int(min(s.n_nodes for s in samples)),
        "node_count_max": int(max(s.n_nodes for s in samples)),
        "node_count_mean": round(
            float(np.mean([s.n_nodes for s in samples])), 1),
        **thr,
        "equivalence_max_abs_diff": deltas,
        "trainer": trainer,
    }
    res["ok"] = bool(
        thr["stream"]["speedup"] >= 2.0
        and thr["cache_ratio"] >= 5.0
        and thr["max_abs_diff"] <= 1e-5
        and all(d <= 1e-5 for d in deltas.values())
        and trainer["loss_rel_diff"] <= 1e-4)
    res["artifact"] = write_json("BENCH_packed_batching.json", res)
    return res


def main():
    enable_compile_cache()
    res = run()
    st, bk = res["stream"], res["bulk"]
    print(f"stream : sparse {st['sparse_pred_per_s']:8.2f}/s  packed "
          f"{st['packed_pred_per_s']:8.2f}/s  speedup "
          f"{st['speedup']:.2f}x  ({st['request_size']}-graph requests)")
    print(f"bulk   : sparse {bk['sparse_pred_per_s']:8.2f}/s  packed "
          f"{bk['packed_pred_per_s']:8.2f}/s  speedup "
          f"{bk['speedup']:.2f}x")
    print(f"cache  : sparse {res['sparse_cache_entries']} entries vs packed "
          f"{res['packed_cache_entries']} ({res['cache_ratio']:.0f}x fewer)")
    print(f"waste  : sparse {res['sparse_padding_waste_frac']:.1%} of node "
          f"rows padding vs packed {res['packed_padding_waste_frac']:.1%}")
    worst = max(res["equivalence_max_abs_diff"].items(), key=lambda kv: kv[1])
    print(f"equiv  : worst variant {worst[0]} |diff| = {worst[1]:.2e}  "
          f"(all 5 layouts×variants ≤ 1e-5 required)")
    print(f"trainer: {res['trainer']['epochs']} packed scan epochs, "
          f"loss rel diff = {res['trainer']['loss_rel_diff']:.2e}")
    print("PASS" if res["ok"] else "FAIL",
          "(targets: ≥2x stream pred/s, ≥5x fewer cache entries, "
          "equiv ≤1e-5, trainer ≤1e-4)")
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
