"""Rapid design-space exploration with DIPPM (paper §1: "helps to perform
rapid design-space exploration for the inference performance of a model").

Sweeps a ViT family over (depth × width × batch) with the **batched
prediction engine** (``DIPPM.predict_zoo``): all 27 candidates are traced,
bin-packed onto packed bins, and scored in a handful of jit-compiled
batched apply calls — no candidate is ever executed. Prints the Pareto-optimal
configurations under a memory budget plus engine throughput stats.

    PYTHONPATH=src python examples/design_space_exploration.py
"""
from repro.core import DIPPM, PMGNSConfig
from repro.dataset.builder import (build_dataset, records_to_samples,
                                   split_dataset)
from repro.train.gnn_trainer import TrainConfig, train_pmgns
from repro.zoo.families import variant_grid


def main():
    recs = build_dataset(n_graphs=150, seed=1)
    sp = split_dataset(recs, seed=1)
    cfg = PMGNSConfig(hidden=128)
    params, _ = train_pmgns(
        cfg, records_to_samples(sp["train"]),
        records_to_samples(sp["val"]),
        TrainConfig(epochs=8, batch_size=16, lr=5e-3))
    dippm = DIPPM.from_params(params, cfg)

    budget_mb = 5 * 1024.0       # must fit a 1g.5gb MIG instance
    grid = variant_grid("vit", {"depth": [6, 8, 12],
                                "dim": [192, 384, 768],
                                "batch": [1, 8, 32],
                                "res": [224]})
    points = [((c["depth"], c["dim"], c["batch"]), p)
              for c, p in dippm.predict_zoo("vit", grid)]
    st = dippm.engine().stats
    print(f"engine: {st.graphs_predicted} graphs in {st.batches_run} "
          f"batched calls ({st.cache_misses} compiles)\n")

    feasible = [(k, p) for k, p in points if p.memory_mb < budget_mb]
    # pareto: lowest latency per (depth·dim) capacity proxy
    feasible.sort(key=lambda kp: kp[1].latency_ms)
    print(f"{len(feasible)}/{len(points)} configs fit under "
          f"{budget_mb:.0f} MB (1g.5gb)\n")
    print("depth dim  batch   latency_ms  memory_mb  mig       tpu_slice")
    pareto_cap = 0
    for (d, w, b), p in feasible:
        cap = d * w
        if cap > pareto_cap:     # larger model at this latency rank
            pareto_cap = cap
            print(f"{d:4d} {w:5d} {b:4d}   {p.latency_ms:9.3f} "
                  f"{p.memory_mb:9.1f}  {str(p.mig):8s} {p.tpu_slice}")


if __name__ == "__main__":
    main()
