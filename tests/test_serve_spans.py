"""Trace spans of the serving path (``repro.core.spans``): what a
profiler session records, and that recording changes no answer."""
import glob

import jax
import numpy as np
import pytest

from repro.core import DIPPM, PMGNSConfig, PredictionEngine, pmgns_init
from repro.core import spans as span_names
from repro.core.batching import sample_from_graph
from repro.core.ir import OpGraph, OpNode


def _graph(n_nodes, seed=0):
    rng = np.random.default_rng(seed)
    ops = ["dense", "conv", "relu", "add"]
    nodes = [OpNode(i, ops[i % len(ops)],
                    (int(rng.integers(1, 16)), int(rng.integers(1, 64))),
                    flops=float(rng.integers(1, 10_000)),
                    macs=float(rng.integers(1, 5_000)))
             for i in range(n_nodes)]
    edges = [(i, i + 1) for i in range(n_nodes - 1)]
    return OpGraph(nodes=nodes, edges=edges, meta={"seed": seed})


GRAPHS = [_graph(n, seed=i) for i, n in enumerate([5, 40, 100, 7, 60, 12])]


@pytest.fixture(scope="module")
def packed_dippm():
    cfg = PMGNSConfig(hidden=32, layout="packed")
    return DIPPM.from_params(pmgns_init(jax.random.PRNGKey(0), cfg), cfg)


def traced(fn, tmp_path):
    """Run ``fn`` under a profiler session; returns its result and the
    ``dippm.*`` host events as ``(name, line, start_ns, dur_ns,
    stats)``."""
    from jax.profiler import ProfileData
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "plugins" / "profile" / "*"
                         / "*.xplane.pb"))[-1]
    events, line = [], 0
    for pl in ProfileData.from_file(path).planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith("dippm."):
                    events.append((e.name, line, e.start_ns, e.duration_ns,
                                   dict(e.stats)))
            line += 1
    return out, events


def _serve_all(dippm):
    """Every graph through ``submit_json``, then three through
    ``submit_many``: two drains, each held open until its flush, so the
    bins do not depend on timing."""
    with dippm.serve(max_wait_ms=60_000.0, cache_size=None) as svc:
        futs = [svc.submit_json(g.to_json()) for g in GRAPHS]
        svc.flush()
        out = [f.result(60) for f in futs]
        futs = svc.submit_many(GRAPHS[:3])
        svc.flush()
        return out + [f.result(60) for f in futs]


def test_answers_are_bit_identical_with_the_profiler_on(packed_dippm,
                                                        tmp_path):
    off = _serve_all(packed_dippm)
    on, events = traced(lambda: _serve_all(packed_dippm), tmp_path)
    assert events
    assert on == off


def test_one_submit_span_per_request_with_its_parts(packed_dippm, tmp_path):
    _, events = traced(lambda: _serve_all(packed_dippm), tmp_path)
    names = [e[0] for e in events]
    n_json, n_many = len(GRAPHS), 3
    assert names.count(span_names.SUBMIT) == n_json     # not one more
    assert names.count(span_names.PARSE) == n_json
    for name in (span_names.FINGERPRINT, span_names.FEATURISE):
        assert names.count(name) == n_json + n_many
    assert names.count(span_names.ENQUEUE) == n_json + 1
    reqs = sorted(e[4]["req"] for e in events if e[0] == span_names.SUBMIT)
    assert reqs == list(range(n_json))
    drains = [e[4] for e in events if e[0] == span_names.DRAIN]
    assert [d["requests"] for d in drains] == [n_json, n_many]
    assert min(d["req_first"] for d in drains) == 0
    assert max(d["req_last"] for d in drains) == n_json + n_many - 1
    assert all(d["queue_wait_ms"] >= 0 for d in drains)
    resolved = [e[4]["requests"] for e in events
                if e[0] == span_names.RESOLVE]
    assert sum(resolved) == n_json + n_many


@pytest.mark.parametrize("layout", ["packed", "sparse", "dense"])
def test_first_call_of_a_shape_is_a_compile_span(layout, tmp_path):
    cfg = PMGNSConfig(hidden=16, layout=layout)
    eng = PredictionEngine(pmgns_init(jax.random.PRNGKey(1), cfg), cfg)
    chunk = [sample_from_graph(g, buckets=eng.engine_cfg.buckets)
             for g in (GRAPHS[0], GRAPHS[3])]

    def twice():
        return eng.run_bin(chunk), eng.run_bin(chunk)

    (a, b), events = traced(twice, tmp_path)
    np.testing.assert_array_equal(a, b)
    names = [e[0] for e in events]
    assert names == [span_names.STAGE, span_names.COMPILE, span_names.FETCH,
                     span_names.STAGE, span_names.RUN, span_names.FETCH]
    stage, compile_, run = events[0], events[1], events[4]
    plan = {"kernel_layers": 0, "segment_layers": 0}    # no Pallas here
    assert stage[4]["graphs"] == 2 and run[4] == {"graphs": 2, **plan}
    assert {k: compile_[4][k] for k in plan} == plan
    # every layout is served on packed bins
    assert set(stage[4]) == set(compile_[4]) - set(plan) | {"graphs"} \
        == {"graphs", "p", "q", "g"}
    fetch = events[5]
    assert run[2] <= fetch[2] and \
        fetch[2] + fetch[3] <= run[2] + run[3]     # fetch inside run


def test_submit_spans_carry_nodes(packed_dippm, tmp_path):
    _, events = traced(lambda: _serve_all(packed_dippm), tmp_path)
    sizes = [g.num_nodes for g in GRAPHS]
    for name, want in ((span_names.PARSE, sizes),
                       (span_names.FINGERPRINT, sizes + sizes[:3]),
                       (span_names.FEATURISE, sizes + sizes[:3])):
        assert [e[4]["nodes"] for e in events if e[0] == name] == want


def test_predictions_carry_their_serving_record(packed_dippm):
    with packed_dippm.serve(max_wait_ms=60_000.0) as svc:
        futs = [svc.submit_json(g.to_json()) for g in GRAPHS]
        svc.flush()
        preds = [f.result(60) for f in futs]
        hit = svc.submit(GRAPHS[0]).result(60)         # from the cache
        samples = [sample_from_graph(g) for g in GRAPHS]
        bins = {i: tuple(svc.engine.bin_shape([samples[j] for j in b]))
                for b in svc.engine.plan_bins(samples) for i in b}
    for i, (g, pred) in enumerate(zip(GRAPHS, preds)):
        served = pred.serving
        assert served["nodes"] == g.num_nodes
        assert served["edges"] == len(set(g.edges))
        assert served["bin"] == bins[i]
        assert served["queue_wait_ms"] >= 0
        assert "serving" not in pred.meta
    assert hit.serving is None and hit == preds[0]


def test_parse_span_names_its_path_and_the_counters_agree(packed_dippm,
                                                          tmp_path):
    """A canonical v1 document takes the array path; a v1 document with
    its nodes out of id order and one with a dtype that is not a string
    take the object path and count as fallbacks; a raw exporter list
    takes the object path and counts in neither counter."""
    shuffled = GRAPHS[1].to_json()
    shuffled["nodes"].reverse()
    raw = GRAPHS[2].to_json()
    del raw["schema"]
    odd_dtype = GRAPHS[3].to_json()
    odd_dtype["nodes"][0]["dtype"] = 4
    docs = [GRAPHS[0].to_json(), shuffled, raw, odd_dtype]

    def serve():
        with packed_dippm.serve(max_wait_ms=60_000.0, cache_size=None) as svc:
            futs = [svc.submit_json(d) for d in docs]
            svc.flush()
            for f in futs:
                f.result(60)
            return svc.stats

    st, events = traced(serve, tmp_path)
    parses = [e[4] for e in events if e[0] == span_names.PARSE]
    assert [p["path"] for p in parses] == ["arrays", "objects", "objects",
                                           "objects"]
    assert [p["nodes"] for p in parses] == [g.num_nodes for g in GRAPHS[:4]]
    assert (st.submitted, st.completed) == (4, 4)
    assert (st.array_parses, st.array_parse_fallbacks) == (1, 2)
