"""The program's spans in traces: a traced run of a small-model cell on
the CPU, a small recorded trace of the service (``data/``), synthetic
lines, and the harness's own reduction of its recorded trace, which
these spans must leave as it was.

Re-record ``data/cpu_spans.xplane.pb`` (six pool documents through a
width-8 SAGE service, Python tracer off) from the checkout's root::

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 \\
        bench/tests/test_bench_program_spans.py
"""
import json
import math
import sys
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

HERE = Path(__file__).resolve().parent
BENCH = HERE.parent
sys.path.insert(0, str(BENCH))

import program_spans  # noqa: E402
import trace_reduce  # noqa: E402

SPANS_TRACE = HERE / "data" / "cpu_spans.xplane.pb"
SPAN_STATS = {
    "dippm.submit": {"req"}, "dippm.parse": set(),
    "dippm.fingerprint": set(), "dippm.featurise": set(),
    "dippm.enqueue": set(), "dippm.batcher.wait": set(),
    "dippm.drain": {"requests", "queue_wait_ms", "req_first", "req_last"},
    "dippm.plan": {"bins"}, "dippm.stage": {"graphs", "p", "q", "g"},
    "dippm.run": {"graphs"}, "dippm.fetch": set(),
    "dippm.resolve": {"requests"},
}
CLIENT = ("dippm.submit", "dippm.parse", "dippm.fingerprint",
          "dippm.featurise", "dippm.enqueue")


def planes_of(path):
    from jax.profiler import ProfileData
    return list(ProfileData.from_file(str(path)).planes)


# -- a traced window of a cell --------------------------------------------

@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Program spans of a traced 1.5 s window of ``sage-zoo-saturate``
    at width 32, traced by the harness's own tracer."""
    import glob

    import run
    sess = run.Session("sage-zoo-saturate", 2**31 + 11, require_chip=False,
                       model_overrides={"hidden": 32})
    out = tmp_path_factory.mktemp("trace")
    try:
        sess.window(1.5, stream=2, tracer=trace_reduce.Tracer(out, 1.5))
    finally:
        sess.close()
    path = sorted(glob.glob(str(out / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    planes = planes_of(path)
    return program_spans.reduce(planes), trace_reduce.reduce_planes(planes)


def test_every_span_is_recorded_with_its_stats(traced):
    spans = traced[0]["program_spans"]
    seen = {}
    for name, _, _, _, stats in spans:
        seen.setdefault(name, set()).update(stats)
    for name, keys in SPAN_STATS.items():
        assert name in seen, name
        assert keys <= seen[name], (name, seen[name])
    assert "dippm.compile" not in seen          # every shape was warmed


def test_client_and_batcher_spans_sit_on_two_lines(traced):
    spans = traced[0]["program_spans"]
    client = {sp[1] for sp in spans if sp[0] in CLIENT}
    batcher = {sp[1] for sp in spans if sp[0] not in CLIENT}
    assert len(client) == 1 and len(batcher) == 1
    assert client != batcher


def test_request_ids_link_submit_to_drain(traced):
    spans = traced[0]["program_spans"]
    reqs = [sp[4]["req"] for sp in spans if sp[0] == "dippm.submit"]
    drains = [(sp[4]["req_first"], sp[4]["req_last"]) for sp in spans
              if sp[0] == "dippm.drain"]
    assert any(a <= r <= b for r in reqs for a, b in drains)


def test_every_reading_is_finite(traced):
    spans = traced[0]["program_spans"]
    for name, read in program_spans.READINGS.items():
        v = read(spans)
        assert v is not None and math.isfinite(v) and v >= 0, name


def test_idle_split_adds_up_in_a_traced_window(traced):
    mine, harness = traced
    idle = sum(v for _, v in mine["idle_by_program_span"])
    assert idle == pytest.approx(harness["window_s"] - harness["busy_s"],
                                 rel=0.01)


# -- the recorded trace of the service -------------------------------------

@pytest.fixture(scope="module")
def recorded():
    planes = planes_of(SPANS_TRACE)
    return planes, program_spans.reduce(planes), \
        trace_reduce.reduce_planes(planes)


def test_recorded_idle_split_adds_up(recorded):
    _, mine, harness = recorded
    split = mine["idle_by_program_span"]
    assert sum(v for _, v in split) == pytest.approx(
        harness["window_s"] - harness["busy_s"], rel=0.01)
    labels = {k.rsplit(" (", 1)[0] for k, _ in split}
    assert "dippm.batcher.wait" in labels
    assert labels <= set(SPAN_STATS) | {"dippm.compile", "no span"}
    assert not labels & set(CLIENT)             # only the feeding line


def test_recorded_self_time_excludes_children(recorded):
    spans = recorded[1]["program_spans"]
    own = program_spans.self_times(spans)
    for sp, t in zip(spans, own):
        inside = [c for c in spans if c is not sp and c[1] == sp[1]
                  and sp[2] <= c[2] and c[2] + c[3] <= sp[2] + sp[3]]
        children = [c for c in inside if not any(
            o is not c and o[2] <= c[2] and c[2] + c[3] <= o[2] + o[3]
            for o in inside)]
        assert t == pytest.approx(sp[3] - sum(c[3] for c in children))
    runs = [t for sp, t in zip(spans, own) if sp[0] == "dippm.run"]
    assert runs and all(t < sp[3] for sp, t in zip(
        [s for s in spans if s[0] == "dippm.run"], runs))


def test_recorded_readings(recorded):
    spans = recorded[1]["program_spans"]
    drains = [sp for sp in spans if sp[0] == "dippm.drain"]
    want = (sum(sp[4]["queue_wait_ms"] for sp in drains)
            / sum(sp[4]["requests"] for sp in drains))
    assert program_spans.READINGS["queue_wait_ms.saturate"](spans) == \
        pytest.approx(want)
    parses = [sp[3] / 1e6 for sp in spans if sp[0] == "dippm.parse"]
    assert program_spans.READINGS["parse_ms.saturate"](spans) == \
        pytest.approx(sum(parses) / len(parses))


def test_spans_outside_the_window_are_left_out(recorded):
    every = program_spans.program_spans(recorded[0], -math.inf, math.inf)
    mid = sorted(sp[2] for sp in every)[len(every) // 2]
    later = program_spans.program_spans(recorded[0], mid, math.inf)
    assert later == [sp for sp in every if sp[2] >= mid]
    assert 0 < len(later) < len(every)


def test_no_program_spans_reads_none():
    planes = [NS(name="/host:CPU", stats=[], lines=[NS(name="python",
              events=[NS(name="bench.submit", start_ns=0, duration_ns=5,
                         stats=[])])])]
    assert program_spans.program_spans(planes, 0, 10) == []
    assert all(read([]) is None for read in program_spans.READINGS.values())


# -- synthetic lines ---------------------------------------------------------

def sp(name, line, s, d):
    return (name, line, s, d, {})


def test_innermost_names_each_instant():
    line = [sp("dippm.drain", 0, 0, 100), sp("dippm.run", 0, 20, 50),
            sp("dippm.fetch", 0, 40, 20), sp("dippm.resolve", 0, 80, 10)]
    assert program_spans.innermost(line) == [
        (0, 20, "dippm.drain"), (20, 40, "dippm.run"),
        (40, 60, "dippm.fetch"), (60, 70, "dippm.run"),
        (70, 80, "dippm.drain"), (80, 90, "dippm.resolve"),
        (90, 100, "dippm.drain")]
    assert program_spans.self_times(line) == [40, 30, 20, 10]


def test_fleet_lines_take_the_instant_furthest_down_the_path():
    spans = [sp("dippm.drain", 0, 0, 100),          # the batcher
             sp("dippm.run", 1, 10, 30),            # replica 0
             sp("dippm.stage", 2, 30, 15),          # replica 1
             sp("dippm.run", 2, 45, 15),
             sp("dippm.submit", 3, 0, 100)]         # a client: not feeding
    assert program_spans.feeding_pieces(spans) == [
        (0, 10, "dippm.drain"), (10, 30, "dippm.run"),
        (30, 40, "dippm.run"), (40, 45, "dippm.stage"),
        (45, 60, "dippm.run"), (60, 100, "dippm.drain")]


# -- the harness's reduction of its own recorded trace, as it was ------------

def test_harness_reduction_reads_as_before():
    r = trace_reduce.reduce_xplane(str(HERE / "data" / "cpu_window.xplane.pb"))
    assert r["window_s"] == 0.020461399
    assert r["busy_s"] == 0.000586667
    assert (r["devices"], r["spans"], len(r["ops"])) == (1, 9, 12)
    assert r["breakdown"] == {
        "device_ops": [["dot_general.1", 0.000492721],
                       ["wrapped_reduce-window", 4.997e-05],
                       ["wrapped_tanh", 3.5668e-05],
                       ["wrapped_reduce", 8.307999999999999e-06]],
        "idle_gaps": [["bench.submit (3 gaps)", 0.017680099999999997],
                      ["bench.wait (1 gaps)", 0.002184978],
                      ["bench.run_bin (9 gaps)", 9.654e-06]]}
    assert [label for label, _ in r["gaps"]].count("bench.submit") == 3


def record(path: Path = SPANS_TRACE) -> None:
    """Trace six pool documents through a width-8 SAGE service."""
    import glob
    import shutil
    import tempfile
    import time

    import jax

    import loadgen
    import weights
    from repro.core.gnn import PMGNSConfig
    from repro.core.predictor import DIPPM
    with open(BENCH / "configs" / "pmgns-sage-512.json") as f:
        model = dict(json.load(f)["model"], hidden=8)
    pool = sorted(loadgen.load_pool(
        BENCH.parent / "bench" / "pool" / "zoo_table2.jsonl.gz"), key=len)
    svc = DIPPM.from_params(weights.make_params(7, model),
                            PMGNSConfig(**model)).serve(cache_size=None,
                                                        node_budget=1024)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    out = Path(tempfile.mkdtemp())
    try:
        svc.warmup()
        svc.submit_json(json.loads(pool[0])).result(60)
        jax.profiler.start_trace(str(out), profiler_options=opts)
        futs = []
        for doc in pool[2:8]:
            futs.append(svc.submit_json(json.loads(doc)))
            time.sleep(0.004)
        for f in futs:
            f.result(60)
        jax.profiler.stop_trace()
        shutil.copy(glob.glob(str(out / "plugins" / "profile" / "*"
                                  / "*.xplane.pb"))[-1], path)
    finally:
        svc.close()
        shutil.rmtree(out, ignore_errors=True)


if __name__ == "__main__":
    record()
