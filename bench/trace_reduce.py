"""Profiler traces of a run's window, reduced to what the metrics read.

:class:`Tracer` records ``jax.profiler`` traces of a few seconds in the
middle of the window. :func:`reduce_xplane` reads one back with nothing
but JAX (``jax.profiler.ProfileData``) and returns:

- ``window_s``: the length of the traced window;
- ``busy_s``: the seconds in which an operation ran on a device (the
  union of its operations' intervals), averaged over the devices;
- ``ops``: every device operation as ``(device, name, start_s,
  seconds)``;
- ``gaps``: every idle stretch of every device, labelled with the
  benchmark's host span (``bench.*``) that overlapped it most, or
  ``"no span"``;
- ``breakdown``: the ten device operations that took most time and the
  idle time by host span, as the result line carries them.

A device operation is an event on a device plane's ``XLA Ops`` line
(TPU), or on the host an event that names its HLO operation (the CPU
backend, which the tests use).
"""
from __future__ import annotations

import bisect
import glob
import json
import shutil
import threading
import time
from collections import defaultdict
from pathlib import Path
from typing import Dict, List, Optional, Tuple

#: Host spans, most specific first: a gap that several overlap takes
#: the first of these among the longest overlaps.
SPAN_PRIORITY = ("bench.run_bin", "bench.plan_bins", "bench.submit",
                 "bench.wait")


def _stats(event) -> Dict:
    try:
        return {k: v for k, v in event.stats}
    except Exception:                            # noqa: BLE001
        return {}


def _union(intervals: List[Tuple[float, float]], lo: float, hi: float):
    """Disjoint, sorted union of ``intervals`` clipped to ``[lo, hi]``."""
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def device_ops(planes) -> Dict[str, List[tuple]]:
    """Device operations by device: ``(name, start_ns, dur_ns)``."""
    out: Dict[str, List[tuple]] = defaultdict(list)
    for pl in planes:
        if pl.name.startswith("/device:"):
            for ln in pl.lines:
                if ln.name != "XLA Ops":
                    continue
                for e in ln.events:
                    out[pl.name].append((e.name, e.start_ns,
                                         e.duration_ns))
    if out:
        return out
    for pl in planes:                            # CPU backend
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                st = _stats(e)
                if "hlo_op" in st:
                    dev = f"cpu:{st.get('device_ordinal', 0)}"
                    out[dev].append((e.name, e.start_ns, e.duration_ns))
    return out


def host_spans(planes, prefix: str = "bench.") -> List[tuple]:
    """The benchmark's host spans: ``(name, start_ns, end_ns)``."""
    out = []
    for pl in planes:
        if not pl.name.startswith("/host:"):
            continue
        for ln in pl.lines:
            for e in ln.events:
                if e.name.startswith(prefix):
                    out.append((e.name, e.start_ns,
                                e.start_ns + e.duration_ns))
    return out


def _window(planes, ops, spans) -> Tuple[float, float]:
    """The traced window on the events' own clock: the profiler
    session's start and stop where a plane carries them on that clock
    (they hold every event), else the first to the last device
    operation or benchmark span."""
    times = [t for v in ops.values() for _, s, d in v for t in (s, s + d)]
    times += [t for _, s, e in spans for t in (s, e)]
    if not times:
        return 0.0, 0.0
    lo, hi = min(times), max(times)
    for pl in planes:
        st = _stats(pl)
        a, b = st.get("profile_start_time"), st.get("profile_stop_time")
        if a is not None and b is not None and a <= lo and hi <= b:
            return float(a), float(b)
    return float(lo), float(hi)


class SpanIndex:
    """Host spans sorted by start, for overlap queries."""

    def __init__(self, spans: List[tuple]) -> None:
        self.spans = sorted(spans, key=lambda sp: sp[1])
        self.starts = [sp[1] for sp in self.spans]
        self.longest = max((e - s for _, s, e in spans), default=0)

    def overlapping(self, lo: float, hi: float):
        i = bisect.bisect_left(self.starts, hi)
        while i > 0 and self.starts[i - 1] >= lo - self.longest:
            i -= 1
            name, s, e = self.spans[i]
            o = min(e, hi) - max(s, lo)
            if o > 0:
                yield name, o


def label_gap(lo: float, hi: float, spans) -> str:
    """The host span that overlapped ``[lo, hi]`` most; among near-ties
    the most specific (:data:`SPAN_PRIORITY`); ``"no span"`` if none."""
    index = spans if isinstance(spans, SpanIndex) else SpanIndex(spans)
    overlap: Dict[str, float] = defaultdict(float)
    for name, o in index.overlapping(lo, hi):
        overlap[name] += o
    if not overlap:
        return "no span"
    best = max(overlap.values())
    top = [n for n, o in overlap.items() if o >= 0.999 * best]
    ranked = sorted(top, key=lambda n: (SPAN_PRIORITY.index(n)
                                        if n in SPAN_PRIORITY else 99, n))
    return ranked[0]


def describe(planes, per_line: int = 3) -> List[Dict]:
    """Planes, their lines and a few events with their stats: what a
    reader needs to see once to know how a backend names things."""
    out = []
    for pl in planes:
        lines = []
        for ln in pl.lines:
            evs = list(ln.events)
            lines.append({"line": ln.name, "events": len(evs), "first": [
                [e.name, e.start_ns, e.duration_ns,
                 {k: str(v)[:400] for k, v in _stats(e).items()}]
                for e in evs[:per_line]]})
        out.append({"plane": pl.name, "lines": lines})
    return out


def reduce_xplane(path: str, n_devices: Optional[int] = None) -> Dict:
    from jax.profiler import ProfileData
    return reduce_planes(list(ProfileData.from_file(str(path)).planes),
                         n_devices)


def reduce_planes(planes, n_devices: Optional[int] = None) -> Dict:
    """What :func:`reduce_xplane` returns, from the trace's planes."""
    ops = device_ops(planes)
    spans = host_spans(planes)
    lo, hi = _window(planes, ops, spans)
    devices = sorted(ops)[:n_devices] if n_devices else sorted(ops)
    index = SpanIndex(spans)
    busy, gaps, flat = [], [], []
    per_name: Dict[str, float] = defaultdict(float)
    for dev in devices:
        evs = ops[dev]
        u = _union([(s, s + d) for _, s, d in evs], lo, hi)
        busy.append(sum(e - s for s, e in u))
        edges = [lo] + [t for iv in u for t in iv] + [hi]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                gaps.append((label_gap(s, e, index), (e - s) / 1e9))
        for name, s, d in evs:
            if s + d > lo and s < hi:
                flat.append((dev, name, (s - lo) / 1e9, d / 1e9))
                per_name[name] += d / 1e9
    if flat and not sum(busy):
        raise RuntimeError("device operations were traced but none lies "
                           "inside the traced window")
    n = max(len(devices), 1)
    idle_by_span: Dict[str, float] = defaultdict(float)
    count_by_span: Dict[str, int] = defaultdict(int)
    for label, sec in gaps:
        idle_by_span[label] += sec / n
        count_by_span[label] += 1
    top_ops = sorted(per_name.items(), key=lambda kv: -kv[1])[:10]
    top_idle = sorted(idle_by_span.items(), key=lambda kv: -kv[1])[:10]
    return {
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / n / 1e9,
        "devices": len(devices),
        "ops": flat,
        "gaps": gaps,
        "spans": len(spans),
        "layout": describe(planes),
        "breakdown": {
            "device_ops": [[k, v / n] for k, v in top_ops],
            "idle_gaps": [[f"{k} ({count_by_span[k]} gaps)", v]
                          for k, v in top_idle],
        },
    }


class Tracer:
    """Traces ``length`` seconds of the window, starting ``lead``
    seconds in, from a thread of its own; :meth:`reduce` reads the
    trace back and removes it, keeping a summary beside it."""

    def __init__(self, out_dir: Path, seconds: float) -> None:
        self.dir = Path(out_dir)
        self.lead = min(2.0, 0.2 * seconds)
        self.length = min(4.0, 0.4 * seconds)
        self._thread: Optional[threading.Thread] = None
        self.error: Optional[BaseException] = None

    def arm(self, t0: float) -> None:
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)

        def body():
            try:
                time.sleep(max(0.0, t0 + self.lead - time.perf_counter()))
                jax.profiler.start_trace(str(self.dir))
                time.sleep(max(0.0, t0 + self.lead + self.length
                               - time.perf_counter()))
                jax.profiler.stop_trace()
            except Exception as e:               # noqa: BLE001
                self.error = e
        self._thread = threading.Thread(target=body, name="bench-tracer")
        self._thread.start()

    def disarm(self) -> None:
        if self._thread is not None:
            self._thread.join()
        if self.error is not None:
            raise RuntimeError(f"trace failed: {self.error!r}")

    def reduce(self, n_devices: Optional[int] = None) -> Dict:
        paths = sorted(glob.glob(str(self.dir / "plugins" / "profile" / "*"
                                     / "*.xplane.pb")))
        if not paths:
            raise RuntimeError(f"no trace under {self.dir}")
        out = reduce_xplane(paths[-1], n_devices)
        shutil.rmtree(self.dir / "plugins", ignore_errors=True)
        summary = {k: v for k, v in out.items() if k not in ("ops", "gaps")}
        summary["ops_head"] = out["ops"][:40]
        with open(self.dir / "summary.json", "w") as f:
            json.dump(summary, f, indent=1)
        return out
