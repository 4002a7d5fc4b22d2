#!/usr/bin/env python3
"""The program's own spans (``dippm.*``, ``repro.core.spans``) in a
profiler trace, and the per-layer readings they give.

:func:`reduce` takes a trace's planes and returns:

- ``program_spans``: every host event named ``dippm.*`` that lies wholly
  inside the traced window, as ``(name, line, start_ns, dur_ns,
  stats)``; ``line`` numbers the host lines (one per Python thread);
- ``idle_by_program_span``: the device's idle time split over the
  innermost program span open on the feeding thread at each instant,
  ``"no span"`` where none is open, in the format of
  ``trace_reduce``'s ``idle_gaps`` (``"<span> (<n> gaps)"``, seconds
  averaged over the devices). The feeding thread is the host line that
  carries ``dippm.drain``; on a replica fleet the lines that carry
  ``dippm.run`` or ``dippm.compile`` join it, and where several lines
  have a span open the one furthest down the path (:data:`DOWNSTREAM`)
  takes the instant.

:data:`READINGS` holds the per-layer readings, each a function of
``program_spans`` that returns milliseconds, or ``None`` where the
trace has none of its span (a program without spans).

The window, the device operations and their idle stretches are
``trace_reduce``'s own, so the idle split adds up to its
``window_s - busy_s``.

Run as a script, it sets up one cell as ``bench/run.py`` does and
traces two windows of it: one with the harness's own tracer, as a
``--trace 1`` run records it, and one with the profiler's Python tracer
off. For each it prints these readings and the idle split beside the
harness's ``submit_ms`` and ``idle_gaps`` of the same window, the
spans per request and each span's total self time; then a span's cost
in ns with the profiler off and on, all as one JSON line::

    python3 bench/program_spans.py --workload <cell> --seed <n> --seconds 20
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import shutil
import sys
import time
from collections import defaultdict
from pathlib import Path
from typing import Callable, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import trace_reduce  # noqa: E402

PREFIX = "dippm."
#: Where several feeding lines have a span open at once, the first of
#: these among them labels the instant.
DOWNSTREAM = ("dippm.fetch", "dippm.compile", "dippm.run", "dippm.stage",
              "dippm.plan", "dippm.resolve", "dippm.drain",
              "dippm.batcher.wait")


def program_spans(planes, lo: float, hi: float) -> List[tuple]:
    """Host events named ``dippm.*`` wholly inside ``[lo, hi]``:
    ``(name, line, start_ns, dur_ns, stats)``."""
    out, line = [], 0
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if (e.name.startswith(PREFIX) and e.start_ns >= lo
                        and e.start_ns + e.duration_ns <= hi):
                    out.append((e.name, line, e.start_ns, e.duration_ns,
                                trace_reduce._stats(e)))
            line += 1
    return out


def _by_line(spans: List[tuple]) -> Dict[int, List[tuple]]:
    """Spans per line, outer before inner: by start, the longer first."""
    lines: Dict[int, List[tuple]] = defaultdict(list)
    for sp in spans:
        lines[sp[1]].append(sp)
    for v in lines.values():
        v.sort(key=lambda sp: (sp[2], -sp[3]))
    return lines


def self_times(spans: List[tuple]) -> List[float]:
    """Each span's duration less what its child spans on the same line
    cover, in ns, in the order of ``spans``."""
    child = [0.0] * len(spans)
    pos = {id(sp): i for i, sp in enumerate(spans)}
    for line in _by_line(spans).values():
        stack: List[tuple] = []
        for sp in line:
            while stack and stack[-1][2] + stack[-1][3] <= sp[2]:
                stack.pop()
            if stack:
                child[pos[id(stack[-1])]] += sp[3]
            stack.append(sp)
    return [sp[3] - c for sp, c in zip(spans, child)]


def innermost(spans: List[tuple]) -> List[tuple]:
    """Disjoint ``(start, end, name)`` pieces of one line's spans, each
    named by the innermost span open there."""
    out: List[tuple] = []

    def emit(a, b, name):
        if b > a:
            out.append((a, b, name))

    stack: List[tuple] = []            # (name, end)
    cur = None
    for name, _, s, d, _ in spans:
        while stack and stack[-1][1] <= s:
            n, end = stack.pop()
            emit(cur, end, n)
            cur = end
        if stack:
            emit(cur, s, stack[-1][0])
        stack.append((name, s + d))
        cur = s
    while stack:
        n, end = stack.pop()
        emit(cur, end, n)
        cur = end
    return out


def feeding_pieces(spans: List[tuple]) -> List[tuple]:
    """Sorted, disjoint ``(start, end, name)``: the innermost program
    span open on the feeding lines at each instant."""
    lines = _by_line(spans)
    feed = [ln for ln, v in lines.items()
            if any(sp[0] in ("dippm.drain", "dippm.run", "dippm.compile")
                   for sp in v)]
    pieces = [p for ln in feed for p in innermost(lines[ln])]
    if len(feed) <= 1:
        return sorted(pieces)
    rank = {n: i for i, n in enumerate(DOWNSTREAM)}
    cuts = sorted({t for a, b, _ in pieces for t in (a, b)})
    out: List[tuple] = []
    open_: Dict[str, int] = defaultdict(int)
    events = sorted([(a, 1, n) for a, _, n in pieces]
                    + [(b, -1, n) for _, b, n in pieces])
    k = 0
    for a, b in zip(cuts, cuts[1:]):
        while k < len(events) and events[k][0] <= a:
            open_[events[k][2]] += events[k][1]
            k += 1
        names = [n for n, c in open_.items() if c > 0]
        if names:
            out.append((a, b, min(names, key=lambda n: rank.get(n, 99))))
    return out


def reduce(planes, n_devices: Optional[int] = None) -> Dict:
    """``program_spans`` and ``idle_by_program_span`` of one trace, on
    ``trace_reduce.reduce_planes``'s window and devices."""
    ops = trace_reduce.device_ops(planes)
    lo, hi = trace_reduce._window(planes, ops,
                                  trace_reduce.host_spans(planes))
    devices = sorted(ops)[:n_devices] if n_devices else sorted(ops)
    spans = program_spans(planes, lo, hi)
    pieces = feeding_pieces(spans)
    starts = [p[0] for p in pieces]
    idle: Dict[str, float] = defaultdict(float)
    count: Dict[str, int] = defaultdict(int)
    for dev in devices:
        u = trace_reduce._union([(s, s + d) for _, s, d in ops[dev]], lo, hi)
        edges = [lo] + [t for iv in u for t in iv] + [hi]
        for gs, ge in zip(edges[::2], edges[1::2]):
            if ge <= gs:
                continue
            covered: Dict[str, float] = defaultdict(float)
            i = max(bisect.bisect_right(starts, gs) - 1, 0)
            while i < len(pieces) and pieces[i][0] < ge:
                a, b, name = pieces[i]
                o = min(b, ge) - max(a, gs)
                if o > 0:
                    covered[name] += o
                i += 1
            covered["no span"] += (ge - gs) - sum(covered.values())
            for name, o in covered.items():
                if o > 0:
                    idle[name] += o
                    count[name] += 1
    n = max(len(devices), 1)
    return {"program_spans": spans, "idle_by_program_span": [
        [f"{k} ({count[k]} gaps)", v / n / 1e9]
        for k, v in sorted(idle.items(), key=lambda kv: -kv[1])]}


# -- the per-layer readings ---------------------------------------------------

def _mean_ms(name: str) -> Callable:
    def read(spans):
        d = [sp[3] for sp in spans if sp[0] == name]
        return sum(d) / len(d) / 1e6 if d else None
    return read


def _per_request_ms(name: str, numerator: Callable) -> Callable:
    def read(spans):
        mine = [sp for sp in spans if sp[0] == name]
        n = sum(sp[4].get("requests", 0) for sp in mine)
        return sum(numerator(sp) for sp in mine) / n if n else None
    return read


#: Metric name -> reading (ms) of ``program_spans``.
READINGS: Dict[str, Callable] = {
    "parse_ms.saturate": _mean_ms("dippm.parse"),
    "fingerprint_ms.saturate": _mean_ms("dippm.fingerprint"),
    "featurise_ms.saturate": _mean_ms("dippm.featurise"),
    "queue_wait_ms.saturate": _per_request_ms(
        "dippm.drain", lambda sp: sp[4].get("queue_wait_ms", 0.0)),
    "resolve_ms.saturate": _per_request_ms(
        "dippm.resolve", lambda sp: sp[3] / 1e6),
    "stage_ms.saturate": _mean_ms("dippm.stage"),
    "run_ms.saturate": _mean_ms("dippm.run"),
}


# -- traced windows on the chip ----------------------------------------------

class QuietTracer(trace_reduce.Tracer):
    """``trace_reduce.Tracer`` with the profiler's Python tracer off:
    spans and device operations only, no event per Python call."""

    def arm(self, t0: float) -> None:
        import threading

        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        shutil.rmtree(self.dir, ignore_errors=True)

        def body():
            try:
                time.sleep(max(0.0, t0 + self.lead - time.perf_counter()))
                jax.profiler.start_trace(str(self.dir),
                                         profiler_options=opts)
                time.sleep(max(0.0, t0 + self.lead + self.length
                               - time.perf_counter()))
                jax.profiler.stop_trace()
            except Exception as e:               # noqa: BLE001
                self.error = e
        self._thread = threading.Thread(target=body, name="bench-tracer")
        self._thread.start()


def span_cost_ns(out_dir: Path, n: int = 200_000) -> Dict[str, float]:
    """ns per span with two stats, with no profiler session running and
    with one running (Python tracer off)."""
    import jax
    from repro.core.spans import TraceAnnotation

    def per_span():
        t = time.perf_counter()
        for i in range(n):
            with TraceAnnotation("dippm.cost", req=i, graphs=1):
                pass
        return (time.perf_counter() - t) / n * 1e9

    off = per_span()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(out_dir), profiler_options=opts)
    try:
        on = per_span()
    finally:
        jax.profiler.stop_trace()
        shutil.rmtree(out_dir, ignore_errors=True)
    return {"span_ns_off": off, "span_ns_on": on}


def traced_window(sess, tracer, seconds: float, stream: int) -> Dict:
    """One traced window of ``sess``: the readings and idle split of its
    trace beside the harness's own numbers for the same window."""
    import window
    from jax.profiler import ProfileData
    w = sess.window(seconds, stream=stream, tracer=tracer)
    chips = sess.cell["chips"]
    path = sorted(glob.glob(str(tracer.dir / "plugins" / "profile" / "*"
                                / "*.xplane.pb")))[-1]
    planes = list(ProfileData.from_file(path).planes)
    harness = trace_reduce.reduce_planes(planes, chips)
    mine = reduce(planes, chips)
    shutil.rmtree(tracer.dir / "plugins", ignore_errors=True)
    spans = mine["program_spans"]
    names = [sp[0] for sp in spans]
    own = list(zip(names, self_times(spans)))
    readings = {k: f(spans) for k, f in READINGS.items()}
    submits = names.count("dippm.submit")
    return {
        "pred_per_s": len(window.completed_in_window(w)) / w.seconds,
        "submit_ms": window.submit_ms(w),
        **readings,
        "parse_fingerprint_featurise_ms": sum(
            readings[k] or 0.0 for k in ("parse_ms.saturate",
                                         "fingerprint_ms.saturate",
                                         "featurise_ms.saturate")),
        "span_counts": {n: names.count(n) for n in sorted(set(names))},
        "self_ms": {n: sum(t for m, t in own if m == n) / 1e6
                    for n in sorted(set(names))},
        "spans_per_request": len(spans) / submits if submits else None,
        "window_s": harness["window_s"], "busy_s": harness["busy_s"],
        "idle_by_program_span": mine["idle_by_program_span"],
        "idle_gaps": harness["breakdown"]["idle_gaps"],
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--out", default="chiprun_out/program_spans")
    args = ap.parse_args(argv)
    import run

    out = Path(args.out) / f"{args.workload}-{args.seed}"
    sess = run.Session(args.workload, args.seed)
    try:
        line = {
            "workload": args.workload, "seed": args.seed,
            "device": sess.devs[0].device_kind,
            "harness_tracer": traced_window(
                sess, trace_reduce.Tracer(out, args.seconds),
                args.seconds, 2),
            "python_tracer_off": traced_window(
                sess, QuietTracer(out, args.seconds), args.seconds, 3),
            **span_cost_ns(out / "cost"),
        }
    finally:
        sess.close()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
