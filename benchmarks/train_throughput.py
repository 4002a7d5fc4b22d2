"""Training throughput: scan-compiled trainer vs the eager reference loop.

The paper trains PMGNS on 10,508 graphs for up to 500 epochs; at that
scale the trainer's steps/sec is the product metric (PerfSeer / PerfSAGE
make the same argument — a predictor is only cheap if training it is).
The eager loop pays one jitted dispatch for the gradient, one for the
update, a host→device transfer, and a blocking ``float(loss)`` sync
*per step*; the scan path stacks each bucket's batches into
``[num_steps, B, ...]`` device arrays and fuses loss+grad+update into one
``jax.lax.scan`` dispatch per segment with donated ``(params, opt_state)``.

Times ``TrainConfig(mode="eager")`` vs ``mode="scan"`` on the same
synthetic sample set (same seed → same schedule, keys, and numerics),
skipping each mode's first epoch (compile). Also reports the
sparse-until-collate storage win: host bytes for the sample set's edge
lists vs the dense ``[N, N]`` adjacencies they replace.

Gates (CI fails otherwise): scan ≥ 3× eager steps/sec, per-epoch train
loss matching within 1e-3 relative, edge-list storage < 10 % of dense.

    PYTHONPATH=src python -m benchmarks.train_throughput
"""
from __future__ import annotations

import sys

from repro.compile_cache import enable_compile_cache

from .common import write_json


def run(n_samples: int = 512, hidden: int = 16, batch_size: int = 4,
        epochs: int = 4):
    """Deliberately dispatch-bound: a small model and small batches make
    per-step compute cheap, so the timing isolates the per-step host
    overhead (dispatches, transfers, loss syncs) that step fusion
    removes — the overhead that also throttles paper-scale runs, where
    10k graphs × 500 epochs is ~160k eager dispatches."""
    import numpy as np
    from repro.core import PMGNSConfig
    from repro.dataset.builder import synthetic_samples
    from repro.train.gnn_trainer import TrainConfig, train_pmgns

    if epochs < 2:
        raise ValueError("epochs must be ≥ 2: the first epoch is the "
                         "compile warmup and is excluded from timing")

    samples = synthetic_samples(n_samples)
    edge_bytes = sum(s.edges.nbytes for s in samples)
    dense_bytes = sum(s.x.shape[0] ** 2 * 4 for s in samples)

    cfg = PMGNSConfig(hidden=hidden)
    # scan_steps must match across modes: it sets the segment boundaries,
    # and the epoch schedule shuffles at segment granularity
    common = dict(epochs=epochs, batch_size=batch_size, lr=1e-3, seed=0,
                  scan_steps=64)
    _, hist_e = train_pmgns(cfg, samples, (),
                            TrainConfig(mode="eager", **common))
    _, hist_s = train_pmgns(cfg, samples, (),
                            TrainConfig(mode="scan", **common))

    steps = hist_s[0]["steps"]
    eager_s = min(h["seconds"] for h in hist_e[1:])   # skip compile epoch
    scan_s = min(h["seconds"] for h in hist_s[1:])
    loss_rel = max(
        abs(a["train_loss"] - b["train_loss"]) / max(abs(a["train_loss"]),
                                                     1e-12)
        for a, b in zip(hist_e, hist_s))
    res = {
        "n_samples": n_samples,
        "steps_per_epoch": steps,
        "eager_steps_per_s": round(steps / eager_s, 2),
        "scan_steps_per_s": round(steps / scan_s, 2),
        "speedup": round(eager_s / scan_s, 2),
        "max_epoch_loss_rel_diff": float(loss_rel),
        "edge_list_bytes": edge_bytes,
        "dense_adj_bytes_replaced": dense_bytes,
        "storage_ratio": round(edge_bytes / dense_bytes, 4),
    }
    res["artifact"] = write_json("train_throughput.json", res)
    return res


def main():
    enable_compile_cache()
    res = run()
    print(f"eager : {res['eager_steps_per_s']:9.2f} steps/s")
    print(f"scan  : {res['scan_steps_per_s']:9.2f} steps/s")
    print(f"speedup: {res['speedup']:.2f}x   "
          f"max epoch-loss rel diff = {res['max_epoch_loss_rel_diff']:.2e}")
    print(f"storage: edge lists {res['edge_list_bytes'] / 1e3:.1f} kB vs "
          f"dense adjacency {res['dense_adj_bytes_replaced'] / 1e3:.1f} kB "
          f"({res['storage_ratio']:.3f}x)")
    ok = (res["speedup"] >= 3.0
          and res["max_epoch_loss_rel_diff"] <= 1e-3
          and res["storage_ratio"] < 0.1)
    print("PASS" if ok else "FAIL",
          "(targets: ≥3x steps/s, loss rel diff ≤ 1e-3, storage < 0.1x)")
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
