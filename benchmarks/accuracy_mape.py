"""Accuracy gate: factory dataset → train to convergence → MAPE vs baseline.

The throughput gates catch "the engine got slower"; nothing so far
caught "the predictor got worse". This gate runs the paper's accuracy
protocol end-to-end at CI scale and fails on regression, the same
contract as every other gate:

1. **Dataset** — a CI-scale factory build (zoo families + held-out
   convnext + two LLM tracings from ``repro.configs``), sharded and
   checksum-verified under ``artifacts/datasets`` keyed by plan hash.
   CI caches the directory on that hash, so warm runs skip tracing; a
   second ``build()`` call in-process must reuse every shard (the
   resume property is re-certified on every CI run). Built/planned
   coverage is gated at ≥ 95 % so structured skips can't silently
   shrink the dataset.
2. **Training** — ``repro.train.accuracy.run_accuracy``: Table 3/4
   protocol (hidden 512, Huber, Adam, fingerprint-stable 70/15/15 +
   family holdout), chunked early-stopping driver.
3. **Gate** — per-head MAPE (latency / energy / memory) on the test
   split *and* the unseen family holdout must stay within the
   checked-in baseline (``benchmarks/baselines/accuracy_mape.json``)
   times its tolerance. Per-family holdout MAPE for all three heads is
   asserted present and recorded in the artifact.

Emits ``BENCH_accuracy_mape.json`` plus a copy of the dataset manifest
for artifact upload.

    PYTHONPATH=src python -m benchmarks.accuracy_mape
    PYTHONPATH=src python -m benchmarks.accuracy_mape --full   # 2k graphs
"""
from __future__ import annotations

import json
import os
import shutil
import sys

from repro.compile_cache import enable_compile_cache

from .common import DATASETS_DIR, write_json

BASELINE_PATH = os.path.join(os.path.dirname(__file__), "baselines",
                             "accuracy_mape.json")

#: gate scale — small enough for CI, large enough that per-family MAPE
#: on the holdout is measured over several graphs per head
CI_N_GRAPHS = 320
FULL_N_GRAPHS = 2000
LM_ARCHS = ("qwen2.5-3b", "mamba2-370m")
MIN_COVERAGE = 0.95


def _factory_config(n_graphs: int, seed: int = 0):
    from repro.dataset.factory import FactoryConfig
    return FactoryConfig(
        n_graphs=n_graphs, seed=seed, shard_size=64,
        extra_families=("convnext",), lm_archs=LM_ARCHS)


def _gate_mape(measured: dict, baseline: dict, tol: dict) -> dict:
    """Per-head comparison: measured ≤ max(base·rel, base+abs)."""
    checks = {}
    for head in ("mape_latency", "mape_energy", "mape_memory", "mape"):
        base = float(baseline[head])
        bound = max(base * float(tol["rel"]), base + float(tol["abs"]))
        got = float(measured[head])
        checks[head] = {"measured": round(got, 4),
                        "baseline": round(base, 4),
                        "bound": round(bound, 4),
                        "ok": bool(got <= bound)}
    return checks


def run(n_graphs: int = 0, max_epochs: int = 0, workers: int = 0,
        seed: int = 0, full: bool = False):
    from repro.dataset.factory import build, plan_hash, read_manifest
    from repro.train.accuracy import AccuracyProtocol, run_accuracy

    n_graphs = n_graphs or (FULL_N_GRAPHS if full else CI_N_GRAPHS)
    workers = workers or int(os.environ.get("REPRO_BUILD_WORKERS", "1"))
    cfg = _factory_config(n_graphs, seed)
    ph = plan_hash(cfg)
    out_dir = os.path.join(DATASETS_DIR, f"accuracy-{ph[:16]}")

    res = build(out_dir, cfg, workers=workers, progress=True)
    # resume property, certified every run: a second build must verify
    # checksums and reuse every shard without tracing anything
    res2 = build(out_dir, cfg, workers=workers)
    assert res2.shards_built == 0 and res2.shards_reused == res.n_shards, \
        f"resume reused {res2.shards_reused}/{res.n_shards} shards"
    coverage = res.n_built / max(res.n_planned, 1)
    assert coverage >= MIN_COVERAGE, (
        f"dataset coverage {coverage:.3f} < {MIN_COVERAGE} — "
        f"skips: {res.skips_by_family}")

    proto = AccuracyProtocol(seed=seed,
                             **({"max_epochs": max_epochs}
                                if max_epochs else {}))
    report = run_accuracy(out_dir, proto)
    report.pop("params")

    with open(BASELINE_PATH) as f:
        baseline = json.load(f)
    tol = baseline["tolerance"]
    gates = {split: _gate_mape(report[split], baseline[split], tol)
             for split in ("test", "unseen")}

    # per-family holdout MAPE for all three heads must be reported
    unseen_fams = report["per_family"]["unseen"]
    assert unseen_fams, "no per-family holdout metrics reported"
    for fam, m in unseen_fams.items():
        for head in ("mape_latency", "mape_energy", "mape_memory"):
            assert head in m, f"holdout family {fam} missing {head}"

    failed = [f"{split}.{head}" for split, checks in gates.items()
              for head, c in checks.items() if not c["ok"]]

    out = {
        "n_graphs": n_graphs,
        "plan_hash": ph,
        "dataset": {"n_planned": res.n_planned, "n_built": res.n_built,
                    "n_skipped": res.n_skipped, "n_shards": res.n_shards,
                    "coverage": round(coverage, 4),
                    "shards_reused_on_resume": res2.shards_reused,
                    "skips_by_family": res.skips_by_family,
                    "peak_worker_rss_mb": round(res.max_rss_kb / 1024, 1)},
        "report": report,
        "gates": gates,
        "gates_failed": failed,
    }
    out["artifact"] = write_json("BENCH_accuracy_mape.json", out)
    # surface the dataset manifest next to the bench artifacts for upload
    shutil.copyfile(os.path.join(out_dir, "manifest.json"),
                    write_json("accuracy_dataset_manifest.json",
                               read_manifest(out_dir)))

    assert not failed, f"MAPE regression vs baseline: {failed}\n" + \
        json.dumps(gates, indent=1)
    return out


def main() -> None:
    enable_compile_cache()
    full = "--full" in sys.argv
    if "--print-plan-hash" in sys.argv:
        # CI uses this as the actions/cache key for artifacts/datasets so
        # the config definition lives in exactly one place
        from repro.dataset.factory import plan_hash
        n = FULL_N_GRAPHS if full else CI_N_GRAPHS
        print(plan_hash(_factory_config(n)))
        return
    out = run(full=full)
    rep = out["report"]
    print(f"[accuracy_mape] dataset {out['dataset']['n_built']}"
          f"/{out['dataset']['n_planned']} graphs "
          f"({out['dataset']['n_shards']} shards, plan "
          f"{out['plan_hash'][:12]}), trained {rep['epochs_trained']} "
          f"epochs (converged={rep['converged']})")
    for split in ("val", "test", "unseen"):
        m = rep.get(split)
        if m:
            print(f"  {split:7s} mape={m['mape']:.4f} "
                  f"lat={m['mape_latency']:.4f} "
                  f"enr={m['mape_energy']:.4f} mem={m['mape_memory']:.4f} "
                  f"(n={m['n']})")
    for fam, m in rep["per_family"]["unseen"].items():
        print(f"  holdout {fam}: lat={m['mape_latency']:.4f} "
              f"enr={m['mape_energy']:.4f} mem={m['mape_memory']:.4f}")
    print(f"PASS accuracy_mape (all heads within baseline tolerance) "
          f"→ {out['artifact']}")


if __name__ == "__main__":
    main()
