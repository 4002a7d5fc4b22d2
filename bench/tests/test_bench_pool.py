"""The frozen traffic pool: parseable, in Table-2 shares, within size."""
import gzip
import json
import sys
from collections import Counter
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH / "pool"))
sys.path.insert(0, str(BENCH))

import build_pool  # noqa: E402
import loadgen  # noqa: E402


@pytest.fixture(scope="module")
def docs():
    with gzip.open(build_pool.POOL_FILE, "rt") as f:
        return [json.loads(line) for line in f]


def test_pool_size(docs):
    assert len(docs) == build_pool.POOL_SIZE


def test_every_document_parses(docs):
    from repro.core.frontends import from_json
    for d in docs:
        g = from_json(d)
        assert g.num_nodes == len(d["nodes"])
        assert g.num_nodes >= 1


def test_family_shares_follow_table2(docs):
    from repro.zoo.families import TABLE2_FRACTIONS
    got = Counter(d["meta"]["family"] for d in docs)
    want = build_pool.allot(TABLE2_FRACTIONS, len(docs))
    assert set(got) == set(TABLE2_FRACTIONS)
    for fam, frac in TABLE2_FRACTIONS.items():
        assert got[fam] == want[fam]
        assert abs(got[fam] - frac * len(docs)) <= 1


def test_no_graph_over_max_nodes(docs):
    assert max(len(d["nodes"]) for d in docs) <= build_pool.MAX_NODES


def test_graph_size_matches_served_graph(docs):
    from repro.core.batching import sample_from_graph
    from repro.core.frontends import from_json
    for d in docs[:16]:
        s = sample_from_graph(from_json(d))
        assert loadgen.graph_size(json.dumps(d)) == (s.n_nodes, s.n_edges)


def test_allot_sums_and_rounds():
    counts = build_pool.allot({"a": 0.5, "b": 0.3, "c": 0.2001}, 7)
    assert sum(counts.values()) == 7
    assert all(abs(counts[k] - w * 7 / 1.0001) < 1
               for k, w in {"a": 0.5, "b": 0.3, "c": 0.2001}.items())


def test_plain_converts_numpy_types():
    import numpy as np
    out = build_pool.plain({"a": np.int64(3), "b": np.array([1, 2]),
                            "c": [np.float32(0.5), (np.bool_(True),)]})
    assert json.loads(json.dumps(out)) == {"a": 3, "b": [1, 2],
                                           "c": [0.5, [True]]}
