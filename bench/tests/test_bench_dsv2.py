"""The DeepSeek-V2 prefill pool and the readers of the serving record."""
import gzip
import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH / "pool"))
sys.path.insert(0, str(BENCH))

import build_dsv2_pool  # noqa: E402
import loadgen  # noqa: E402
import run  # noqa: E402

#: Nodes and edges of each prefill graph by sequence, whatever the batch.
COUNTS = {1024: (9381, 12718), 2048: (10821, 15178), 4096: (19881, 30178)}


@pytest.fixture(scope="module")
def lines():
    with gzip.open(build_dsv2_pool.POOL_FILE, "rt") as f:
        return [line for line in f.read().splitlines() if line.strip()]


def test_six_documents_with_their_recorded_counts(lines):
    docs = [json.loads(line) for line in lines]
    assert sorted((d["meta"]["batch"], d["meta"]["seq"]) for d in docs) == \
        sorted((b, s) for b in (1, 4) for s in (1024, 2048, 4096))
    for d in docs:
        m = d["meta"]
        assert (m["nodes"], m["edges"]) == COUNTS[m["seq"]]
        assert (len(d["nodes"]), len(d["edges"])) == COUNTS[m["seq"]]
        assert m["source"] == build_dsv2_pool.SOURCE
        assert m["departures"] == list(build_dsv2_pool.DEPARTURES)


def test_every_document_parses_fingerprints_and_featurises(lines):
    from repro.core.batching import sample_from_graph
    from repro.core.frontends import from_json
    prints = set()
    for line in lines:
        g = from_json(json.loads(line))
        prints.add(g.fingerprint())
        s = sample_from_graph(g)
        assert loadgen.graph_size(line) == (s.n_nodes, s.n_edges)
        assert s.n_nodes == g.num_nodes == json.loads(line)["meta"]["nodes"]
    assert len(prints) == len(lines)


def test_builder_rebuilds_the_smallest_document_byte_for_byte(lines):
    assert build_dsv2_pool.line(build_dsv2_pool.document(1, 1024)) == \
        lines[0] + "\n"


# -- readers of the serving record --------------------------------------------

class Done:
    """A settled future whose prediction has the fields ``pred``."""

    def __init__(self, **pred):
        self.pred = pred

    def result(self, timeout=None):
        return types.SimpleNamespace(meta={}, **self.pred)


def fake_run(served, sizes=((100, 150), (50, 60))):
    """A window in which request ``k`` of pool graph ``k % 2`` spent
    ``k + 1`` ms in submit and settled with ``served[k]`` as its serving
    record (``None``: a prediction without one)."""
    rec = loadgen.Requests()
    for k, s in enumerate(served):
        i = rec.add(k % 2, 0.5)
        rec.sent[i], rec.submitted[i] = 1.0, 1.0 + 1e-3 * (k + 1)
        rec.done[i] = 2.0
        rec.futures[i] = Done() if s is None else Done(serving=s)
    return types.SimpleNamespace(requests=rec, t0=0.0, t1=10.0,
                                 seconds=10.0, n_due=len(served),
                                 sizes=list(sizes))


def record(nodes, wait):
    return {"nodes": nodes, "edges": 0, "bin": (4096, 6656, 256),
            "queue_wait_ms": wait}


READERS = ("nodes_served_pct.dsv2", "submit_us_per_node.dsv2",
           "queue_wait_ms.poisson")


def test_readers_read_the_serving_record():
    w = fake_run([record(100, 2.0), record(50, 4.0), record(40, 6.0)])
    read = {name: run.load_reader(name) for name in READERS}
    assert read["nodes_served_pct.dsv2"](w) == pytest.approx(
        100.0 * 190 / 250)
    assert read["submit_us_per_node.dsv2"](w) == pytest.approx(
        1e6 * 6e-3 / 190)
    assert read["queue_wait_ms.poisson"](w) == pytest.approx(4.0)


@pytest.mark.parametrize("served", [[None, None], [record(100, 1.0), None],
                                    []])
def test_readers_return_none_without_a_serving_record(served):
    """A program whose predictions carry no serving record, or a window
    with nothing completed, gives every reader nothing to read."""
    w = fake_run(served)
    for name in READERS:
        assert run.load_reader(name)(w) is None
