"""Shared benchmark utilities: dataset cache + timing helpers."""
from __future__ import annotations

import json
import os
import time
from typing import Dict, List, Optional

ART = os.environ.get("REPRO_BENCH_DIR", "artifacts/bench")


def bench_hardware():
    """Roofline envelope for this run: the device's published peaks, or
    on a CPU the nominal ``CPU_HOST`` envelope, asked for by name."""
    import jax

    from repro.roofline.analysis import CPU_HOST, default_hardware
    return CPU_HOST if jax.default_backend() == "cpu" else default_hardware()


def art_path(name: str) -> str:
    os.makedirs(ART, exist_ok=True)
    return os.path.join(ART, name)


def timed(fn, *args, repeats: int = 3, **kw):
    """(result, seconds_per_call) — median of ``repeats``."""
    ts = []
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        ts.append(time.perf_counter() - t0)
    ts.sort()
    return out, ts[len(ts) // 2]


_DATASET_CACHE: Dict[str, list] = {}

DATASETS_DIR = os.environ.get("REPRO_DATASETS_DIR", "artifacts/datasets")


def bench_factory_config(n_graphs: int = 240, seed: int = 0):
    """The shared benchmark dataset recipe (convnext held out)."""
    from repro.dataset.factory import FactoryConfig
    return FactoryConfig(n_graphs=n_graphs, seed=seed,
                         shard_size=max(32, min(256, n_graphs // 4)),
                         extra_families=("convnext",))


def bench_dataset(n_graphs: int = 240, seed: int = 0):
    """Build (or reuse) the benchmark dataset via the sharded factory.

    The dataset lives on disk under ``REPRO_DATASETS_DIR`` keyed by its
    plan hash, so repeat runs (and CI, which caches the directory on the
    same hash) verify shard checksums and skip tracing entirely.
    """
    key = f"{n_graphs}-{seed}"
    if key in _DATASET_CACHE:
        return _DATASET_CACHE[key]
    from repro.dataset.factory import build, iter_records
    cfg = bench_factory_config(n_graphs, seed)
    from repro.dataset.factory import plan_hash as _ph
    out_dir = os.path.join(DATASETS_DIR, f"bench-{_ph(cfg)[:16]}")
    build(out_dir, cfg, workers=int(os.environ.get("REPRO_BUILD_WORKERS",
                                                   "1")))
    recs = list(iter_records(out_dir))
    _DATASET_CACHE[key] = recs
    return recs


def write_json(name: str, obj) -> str:
    p = art_path(name)
    with open(p, "w") as f:
        json.dump(obj, f, indent=1, default=str)
    return p


def write_csv(name: str, rows: List[Dict]) -> str:
    p = art_path(name)
    if rows:
        cols = list(rows[0].keys())
        with open(p, "w") as f:
            f.write(",".join(cols) + "\n")
            for r in rows:
                f.write(",".join(str(r.get(c, "")) for c in cols) + "\n")
    return p
