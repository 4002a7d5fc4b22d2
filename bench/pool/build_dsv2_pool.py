#!/usr/bin/env python3
"""Build the DeepSeek-V2 prefill pool: whole LLM graphs as OpGraph JSON.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 bench/pool/build_dsv2_pool.py

Writes ``bench/pool/deepseek_v2_prefill.jsonl.gz``: one
``repro.opgraph.v1`` document per line, for batch {1, 4} × sequence
{1,024, 2,048, 4,096}. Each is ``from_jax(lm.forward)`` of the repo's
``deepseek_v2_236b`` configuration at its published widths and all 60
layers, traced abstractly (``ShapeDtypeStruct``): no weights exist and
nothing is downloaded. Each document's ``meta`` holds the source, the
batch and sequence, its node and edge counts and the departures from
the published configuration.

The pool is built once, offline, and checked in, so a later change to
the tracer or the model code cannot move the yardstick. The build is
deterministic: the same tree writes the same bytes.
"""
from __future__ import annotations

import argparse
import gzip
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
POOL_FILE = HERE / "deepseek_v2_prefill.jsonl.gz"
ARCH = "deepseek_v2_236b"
SOURCE = "https://huggingface.co/deepseek-ai/DeepSeek-V2/blob/main/config.json"
BATCHES = (1, 4)
SEQS = (1024, 2048, 4096)
DEPARTURES = (
    "rope_scaling (YaRN, factor 40) is not modelled: it changes the rope "
    "constants, not the operator graph",
    "routed experts run as the repo's capacity-based dispatch (capacity "
    "factor 1.25) over the published routing; the release gathers every "
    "routed token",
    "attention is the repo's blockwise streaming-softmax scan, the same "
    "contraction as the release's softmax attention",
    "seq_aux (a training loss) is not traced: prefill only",
)


def document(batch: int, seq: int) -> dict:
    """One prefill graph at ``batch`` × ``seq`` as a JSON document."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct

    from build_pool import plain
    from repro.configs import get_config
    from repro.core.frontends import from_jax
    from repro.models import lm

    acfg = get_config(ARCH)

    def forward(params, tokens):
        logits, _ = lm.forward(params, acfg, {"tokens": tokens})
        return logits

    g = from_jax(forward, lm.param_specs(acfg),
                 ShapeDtypeStruct((batch, seq), jnp.int32),
                 meta={"family": acfg.name, "batch": batch, "seq": seq})
    doc = plain(g.to_json())
    doc["meta"].update(source=SOURCE, nodes=len(doc["nodes"]),
                       edges=len(doc["edges"]), departures=list(DEPARTURES))
    return doc


def line(doc: dict) -> str:
    return json.dumps(doc, separators=(",", ":")) + "\n"


def build() -> list:
    return [document(b, s) for b in BATCHES for s in SEQS]


def write(docs: list, path: Path) -> None:
    with open(path, "wb") as raw, gzip.GzipFile(
            fileobj=raw, filename="", mode="wb", compresslevel=9,
            mtime=0) as f:
        for d in docs:
            f.write(line(d).encode())


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--out", default=str(POOL_FILE))
    args = ap.parse_args()
    t0 = time.perf_counter()
    docs = build()
    write(docs, Path(args.out))
    for d in docs:
        m = d["meta"]
        print(f"batch {m['batch']} seq {m['seq']}: {m['nodes']} nodes, "
              f"{m['edges']} edges", file=sys.stderr)
    print(f"{len(docs)} graphs in {time.perf_counter() - t0:.1f} s; "
          f"{Path(args.out).stat().st_size} bytes", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.path.insert(0, str(HERE))
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    sys.exit(main())
