#!/usr/bin/env python3
"""Build the frozen traffic pool: Table-2 zoo graphs as OpGraph JSON.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/pool/build_pool.py

Writes ``bench/pool/zoo_table2.jsonl.gz``: one ``repro.opgraph.v1``
document per line, ``POOL_SIZE`` of them. Families are allotted in the
paper's Table-2 shares (largest remainder, so every share is within one
graph of exact); each graph is a ``family_variants`` draw traced with
``trace_family`` and serialised with ``to_json``. A draw over
``MAX_NODES`` nodes is redrawn for the same family and counted: the
serving path truncates such graphs today, so they stay out of the pool.

The pool is built once, offline, with a fixed seed and checked in, so a
later change to the tracer or the zoo cannot move the yardstick. The
benchmark's ``--seed`` only decides which pool graphs are sent, in what
order and when.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
POOL_FILE = HERE / "zoo_table2.jsonl.gz"
POOL_SEED = 20230320
POOL_SIZE = 512
MAX_NODES = 1024


def plain(v):
    """``v`` with numpy scalars and arrays turned into JSON types."""
    if isinstance(v, dict):
        return {str(k): plain(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [plain(x) for x in v]
    if isinstance(v, np.ndarray):
        return plain(v.tolist())
    if isinstance(v, np.bool_):
        return bool(v)
    if isinstance(v, np.integer):
        return int(v)
    if isinstance(v, np.floating):
        return float(v)
    return v


def allot(fractions: dict, n: int) -> dict:
    """Whole counts per family summing to ``n``, by largest remainder."""
    total = sum(fractions.values())
    raw = {f: n * w / total for f, w in fractions.items()}
    counts = {f: int(r) for f, r in raw.items()}
    rest = sorted(raw, key=lambda f: (counts[f] - raw[f], f))
    for f in rest[:n - sum(counts.values())]:
        counts[f] += 1
    return counts


def build(n: int = POOL_SIZE, seed: int = POOL_SEED):
    """Returns ``(docs, redrawn)``: the pool documents in send order and
    how many draws were over ``MAX_NODES`` nodes."""
    from repro.zoo.families import (TABLE2_FRACTIONS, family_variants,
                                    trace_family)
    rng = np.random.default_rng(seed)
    families = [f for f, c in sorted(allot(TABLE2_FRACTIONS, n).items())
                for _ in range(c)]
    rng.shuffle(families)
    docs, redrawn = [], 0
    for fam in families:
        while True:
            g = trace_family(fam, family_variants(fam, rng))
            if g.num_nodes <= MAX_NODES:
                break
            redrawn += 1
        doc = plain(g.to_json())
        doc["meta"]["family"] = fam
        docs.append(doc)
    return docs, redrawn


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(POOL_FILE))
    ap.add_argument("--n", type=int, default=POOL_SIZE)
    args = ap.parse_args()
    t0 = time.perf_counter()
    docs, redrawn = build(args.n)
    with gzip.open(args.out, "wt", compresslevel=9) as f:
        for d in docs:
            f.write(json.dumps(d, separators=(",", ":")) + "\n")
    nodes = [len(d["nodes"]) for d in docs]
    edges = [len(d["edges"]) for d in docs]
    print(f"{len(docs)} graphs in {time.perf_counter() - t0:.1f} s; "
          f"{redrawn} draws over {MAX_NODES} nodes redrawn; nodes "
          f"{min(nodes)}-{max(nodes)} (mean {np.mean(nodes):.1f}); "
          f"edges/node {sum(edges) / sum(nodes):.3f}; "
          f"{Path(args.out).stat().st_size} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    sys.exit(main())
