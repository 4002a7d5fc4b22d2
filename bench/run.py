#!/usr/bin/env python3
"""Run one benchmark cell of DIPPM serving on the chips of this machine.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are looked up
by name in ``BENCHMARK.json``; everything that belongs to one of them
sits in a file of its own (``bench/configs/``, ``bench/traffic/``,
``bench/metrics/<metric>.py``), so a new cell or metric is new files,
not an edit here.

A run: checks for the chips (none, or too few, exits 1 with no result);
sets up the prediction service with weights made from ``--seed`` (and
the configuration's ``"weights"`` calibration, if any), warms every
program shape the traffic can reach and sends the traffic untimed
until no new shape compiles; then sends it for ``--seconds`` seconds and
measures. With ``--trace 1`` it also records a profiler trace of part of
the window and reports the per-layer metrics instead of the end-to-end
ones. After the window it compares a seeded sample of the finished
predictions with the plain reference that the configuration file names
(its ``"reference"``, ``bench/reference.py`` for both PMGNS
configurations), at any graph size, and prints
each compared number beside its limit, last on standard error and under
``checks`` in the result. The last line of standard output is the
result, one JSON object. Each window also logs, on standard error, its
completions per second, the longest gap between two completions, and
the process's CPU and garbage-collection seconds, so that a machine
that stood still can be told from a slower program.

``--control 1`` puts the control, the reference at a lower precision,
in the program's place in that comparison, so that the run comes out
not correct; the served predictions' gap on the same sample is logged
beside it (the benchmark's own runs leave it off).
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse                                              # noqa: E402
import gc                                                    # noqa: E402
import importlib.util                                        # noqa: E402
import json                                                  # noqa: E402
import math                                                  # noqa: E402
import os                                                    # noqa: E402
import sys                                                   # noqa: E402
import types                                                 # noqa: E402
from pathlib import Path                                     # noqa: E402
from typing import Callable, Dict, List, Optional           # noqa: E402

import numpy as np                                           # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import counts                                                # noqa: E402
import loadgen                                               # noqa: E402
import peaks as peaks_mod                                    # noqa: E402
import trace_reduce                                          # noqa: E402
import weights                                               # noqa: E402

#: Seconds a due request may still take after the window closes.
SETTLE_S = 60.0
#: Finished predictions compared with the reference in each run.
SAMPLE = 256
#: Untimed passes of the cell's traffic in set-up, at most.
WARM_PASSES = 3
#: Keys of a configuration's ``model`` block that ``PMGNSConfig`` takes.
MODEL_KEYS = ("variant", "node_feat_dim", "static_dim", "hidden",
              "n_gnn_blocks", "n_fc_blocks", "n_targets", "readout",
              "layout", "use_pallas", "precision")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class NoChip(RuntimeError):
    """JAX finds no accelerator of a known kind, or too few of them."""


def load_spec(root: Path = ROOT) -> Dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def find(entries: List[Dict], name: str, what: str) -> Dict:
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit(f"no {what} named {name!r} in BENCHMARK.json")


def cell_metrics(spec: Dict, cell: str, trace: bool) -> List[Dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics, or
    with a trace its per-layer ones."""
    entries = spec["per_layer" if trace else "end_to_end"]
    return [m for m in entries if cell in m.get("workloads", [cell])]


def load_module(path: Path, prefix: str, name: str) -> types.ModuleType:
    mod_spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(mod_spec)
    mod_spec.loader.exec_module(mod)
    return mod


def load_reader(name: str) -> Callable:
    return load_module(BENCH / "metrics" / f"{name}.py", "bench_metric_",
                       name).read


def load_reference(root: Path, config: Dict,
                   name: str) -> types.ModuleType:
    """The plain reference module at the configuration's ``"reference"``
    path, relative to ``root``: its ``featurise``, ``forward_log`` and
    ``served_gap`` decide ``correct``."""
    if "reference" not in config:
        raise SystemExit(f"configuration {name!r} names no \"reference\"")
    return load_module(root / config["reference"], "bench_reference_", name)


def check_chips(chips: int, require_chip: bool = True):
    """The devices to report, after checking that they are accelerators
    of a kind with published peaks, and enough of them."""
    import jax
    devs = jax.devices()
    if require_chip:
        if devs[0].platform != "tpu":
            raise NoChip(f"no TPU: JAX's first device is "
                         f"{devs[0].platform!r}")
        try:
            peaks_mod.peaks_for(devs[0].device_kind)
        except KeyError as e:
            raise NoChip(str(e)) from None
        if len(devs) < chips:
            raise NoChip(f"the cell needs {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs


class Instruments:
    """The benchmark's own spans around the program's public entry
    points. A missing entry point is noted and skipped."""

    def __init__(self, svc) -> None:
        import jax
        self.annotate = jax.profiler.TraceAnnotation
        eng = svc.engine
        self._wrap(eng, "plan_bins", "bench.plan_bins")
        for e in list(getattr(eng, "replicas", None) or [eng]):
            self._wrap(e, "run_bin", "bench.run_bin")

    def _wrap(self, obj, attr: str, span: str) -> None:
        orig = getattr(obj, attr, None)
        if orig is None:
            log(f"note: {type(obj).__name__}.{attr} not found; "
                f"no {span} spans")
            return

        def wrapped(*a, **kw):
            with self.annotate(span):
                return orig(*a, **kw)
        setattr(obj, attr, wrapped)

    def span(self, name: str):
        return self.annotate(name)


def counters(svc) -> Dict[str, float]:
    """The program's cumulative counters that the metrics read as
    deltas across the window."""
    st, est = svc.stats, svc.engine.stats
    return {"completed": st.completed, "bins": st.bins,
            "recompiles": est.recompiles,
            "node_slots_total": est.node_slots_total,
            "node_slots_real": est.node_slots_real}


def warm_escapes(svc, pool: List[str], sizes: List[tuple]) -> List[tuple]:
    """Compile, on every engine, each bin shape whose edge count
    escalates past its rung's edge budget and that the pool can fill.

    ``warmup(rungs="all")`` compiles the ladder of typical-density
    shapes only; a bin of dense graphs (DenseNet's concatenations) on a
    rung escalates its edge axis to the budget and compiles on first
    sight. For each rung this packs the pool's densest graphs up to the
    rung's node count, as a bin of them would, and runs that bin on
    every engine when its edges overflow the rung. Returns the shapes
    run."""
    from repro.core.batching import (packed_rung_ladder, packed_shape,
                                     resolve_packed_budgets,
                                     sample_from_graph)
    from repro.core.frontends import from_json
    ec = svc.engine.engine_cfg
    nb, eb, gb = resolve_packed_budgets(ec.node_budget, ec.edge_budget,
                                        ec.graph_budget)
    engines = list(getattr(svc.engine, "replicas", None) or [svc.engine])
    densest = sorted(range(len(pool)),
                     key=lambda i: -sizes[i][1] / max(sizes[i][0], 1))
    shapes, below = [], 0
    for p, q, g in packed_rung_ladder(nb, eb, gb):
        chosen, tn, te = [], 0, 0
        for i in densest:
            n, e = sizes[i]
            if tn + n <= p and te + e <= eb and len(chosen) < g:
                chosen.append(i)
                tn, te = tn + n, te + e
        lo, below = below, p
        if tn <= lo or te <= q:
            continue
        samples = [sample_from_graph(from_json(json.loads(pool[i])),
                                     buckets=ec.buckets,
                                     extended_static=ec.extended_static)
                   for i in chosen]
        for e in engines:
            e.run_bin(samples)
        shapes.append(packed_shape(samples, nb, eb, gb))
    return shapes


def warm_lone_bins(svc, pool: List[str], sizes: List[tuple],
                   known: List[tuple]) -> List[tuple]:
    """Compile, on every engine, each shape that a bin of one pool graph
    takes outside the rung ladder and ``known`` (the escapes run).

    A graph over a budget runs as a lone bin padded to powers of two,
    which neither ``warmup`` nor :func:`warm_escapes` compiles. The
    shape comes from the program's own sample of the graph, so it is
    right whether or not the program cuts the graph. A graph that the
    program keeps whole (no more nodes than its largest node bucket)
    has no more nodes or edges in its sample than in its document, so
    where the document's own shape is known its bin's is too, and it is
    not featurised. Returns the shapes run."""
    from repro.core.batching import (packed_rung_ladder, packed_shape,
                                     resolve_packed_budgets,
                                     sample_from_graph)
    from repro.core.frontends import from_json
    ec = svc.engine.engine_cfg
    nb, eb, gb = resolve_packed_budgets(ec.node_budget, ec.edge_budget,
                                        ec.graph_budget)
    known = set(packed_rung_ladder(nb, eb, gb)) | set(known)
    engines = list(getattr(svc.engine, "replicas", None) or [svc.engine])
    lone: Dict[tuple, object] = {}
    for line, (n, e) in zip(pool, sizes):
        whole = types.SimpleNamespace(n_nodes=n, n_edges=e)
        if n <= ec.buckets[-1] and packed_shape([whole], nb, eb, gb) in known:
            continue
        sample = sample_from_graph(from_json(json.loads(line)),
                                   buckets=ec.buckets,
                                   extended_static=ec.extended_static)
        shape = packed_shape([sample], nb, eb, gb)
        if shape not in known:
            lone.setdefault(shape, sample)
    for sample in lone.values():
        for e in engines:
            e.run_bin([sample])
    return list(lone)


class GcClock:
    """Seconds the interpreter spent collecting garbage while it runs."""

    def __init__(self) -> None:
        self.seconds, self.count, self._t = 0.0, 0, None
        gc.callbacks.append(self._tick)

    def _tick(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.seconds += time.perf_counter() - self._t
            self.count += 1

    def stop(self) -> None:
        gc.callbacks.remove(self._tick)


def settle(rec: loadgen.Requests, upto: int, deadline: float) -> None:
    """Wait until the first ``upto`` requests have settled, or until
    ``deadline``."""
    for i in range(upto):
        fut = rec.futures[i]
        if fut is not None and not fut.done():
            left = deadline - time.perf_counter()
            if left <= 0:
                return
            try:
                fut.exception(left)
            except TimeoutError:
                return


def sample_finished(rec: loadgen.Requests, idx: List[int], sizes,
                    rng: np.random.Generator) -> List[int]:
    """A seeded sample of the finished requests, with the largest graph
    among them always in it."""
    if len(idx) <= SAMPLE:
        return list(idx)
    largest = max(idx, key=lambda i: sizes[rec.pool_idx[i]][0])
    pick = set(rng.choice(idx, size=SAMPLE - 1, replace=False).tolist())
    pick.add(largest)
    return sorted(pick)


def compare(reference: types.ModuleType, rec: loadgen.Requests,
            sample: List[int], pool: List[str], params, model: Dict,
            control: bool) -> np.ndarray:
    """Per-sample widest log1p gaps against ``reference`` of the served
    predictions or, with ``control``, of the control's answers in their
    place (the served gap is then logged beside it). Each distinct pool
    document of the sample is featurised and computed once."""
    if not sample:
        return np.zeros(0)
    docs = sorted({rec.pool_idx[i] for i in sample})
    at = {d: k for k, d in enumerate(docs)}
    row = np.array([at[rec.pool_idx[i]] for i in sample])
    t = time.perf_counter()
    feats = [reference.featurise(json.loads(pool[d])) for d in docs]
    served = np.array([[p.latency_ms, p.energy_j, p.memory_mb]
                       for p in (rec.futures[i].result(0) for i in sample)])
    ref = reference.forward_log(params, model["variant"], feats)[row]
    log(f"reference over {len(docs)} distinct graphs in "
        f"{time.perf_counter() - t:.3f} s; outputs: {float(ref.min())} "
        f"to {float(ref.max())}")
    gaps = reference.served_gap(served, ref)
    log_gaps(reference, "served", served, ref)
    if not control:
        return gaps
    log(f"served max_log_gap: {float(gaps.max())}")
    low = np.expm1(reference.forward_log(params, model["variant"], feats,
                                         control=True)[row]
                   .astype(np.float64))
    log_gaps(reference, "control", low, ref)
    return reference.served_gap(low, ref)


def log_gaps(reference: types.ModuleType, name: str, phys: np.ndarray,
             ref: np.ndarray) -> None:
    """Log how the per-graph gaps of ``phys`` spread, and each target's
    widest gap."""
    gaps = reference.served_gap(phys, ref)
    with np.errstate(divide="ignore", invalid="ignore"):
        per_target = np.abs(np.log1p(phys) - ref).max(0)
    log(f"{name} gaps: median {float(np.median(gaps))} p90 "
        f"{float(np.quantile(gaps, 0.9))} max {float(gaps.max())}; "
        f"widest per target {per_target.tolist()}")


class Session:
    """A cell's service, set up once; :meth:`window` sends its traffic
    for a while and returns what happened, as many times as asked.
    ``cell`` is a workload's name in ``BENCHMARK.json`` or an entry of
    the same form.

    ``model_overrides`` and ``fault`` serve the tests: a smaller model,
    and a hook that may break the service underneath before any traffic.
    """

    def __init__(self, cell, seed: int, *, require_chip=True,
                 root: Path = ROOT, model_overrides: Optional[Dict] = None,
                 fault: Optional[Callable] = None) -> None:
        spec = load_spec(root)
        self.spec = spec
        self.cell = (find(spec["workloads"], cell, "workload")
                     if isinstance(cell, str) else cell)
        conf_entry = find(spec["configs"], self.cell["config"], "config")
        with open(root / conf_entry["file"]) as f:
            self.config = json.load(f)
        self.reference = load_reference(root, self.config,
                                        conf_entry["name"])
        with open(root / BENCH.name / "traffic"
                  / f"{self.cell['traffic']}.json") as f:
            self.traffic = json.load(f)
        self.model = {**self.config["model"], **(model_overrides or {})}
        self.seed, self.root = seed, root
        self.devs = check_chips(self.cell["chips"], require_chip)
        self.peaks = (peaks_mod.peaks_for(self.devs[0].device_kind)
                      if require_chip else None)
        import jax
        from repro.compile_cache import enable_compile_cache
        enable_compile_cache()
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        jax.config.update("jax_default_matmul_precision",
                          self.config["matmul_precision"])
        from repro.core.gnn import PMGNSConfig
        from repro.core.predictor import DIPPM

        self.params = weights.make_params(
            seed, self.model, **self.config.get("weights", {}))
        self.pool = loadgen.load_pool(root / self.traffic["pool"])
        self.sizes = [loadgen.graph_size(line) for line in self.pool]
        serve_kw = {**self.config.get("serve", {}),
                    **self.traffic.get("serve", {})}
        cfg = PMGNSConfig(**{k: self.model[k] for k in MODEL_KEYS
                             if k in self.model})
        self.svc = DIPPM.from_params(self.params, cfg).serve(**serve_kw)
        try:
            self.inst = Instruments(self.svc)
            n_rungs = self.svc.warmup(rungs="all")
            escapes = warm_escapes(self.svc, self.pool, self.sizes)
            lone = warm_lone_bins(self.svc, self.pool, self.sizes, escapes)
            if fault is not None:
                fault(self.svc)
            warm_s = float(self.traffic.get("warm_s", 3.0))
            for n_pass in range(1, WARM_PASSES + 1):
                before = self.svc.engine.stats.recompiles
                self.window(warm_s, stream=100 + n_pass)
                if self.svc.engine.stats.recompiles == before:
                    break
        except BaseException:
            self.close()
            raise
        log(f"set-up: {n_rungs} rungs, {len(escapes)} escape shapes, "
            f"{len(lone)} lone shapes, "
            f"{n_pass} warm pass(es) of {warm_s} s")

    def window(self, seconds: float, stream: int = 0, tracer=None,
               traffic: Optional[Dict] = None) -> types.SimpleNamespace:
        """Send the traffic for ``seconds`` seconds, from a seed stream
        of its own, and wait for what was due to settle."""
        traffic = traffic or self.traffic
        rng = np.random.default_rng([self.seed, stream])
        rec = loadgen.Requests()
        c0 = counters(self.svc)
        gc_clock = GcClock()
        cpu0 = time.process_time()
        t0 = time.perf_counter()
        if tracer:
            tracer.arm(t0)
        loadgen.drive(traffic, self.pool, self.svc.submit_json, rng, t0,
                      seconds, rec, self.inst.span)
        t1 = t0 + seconds
        c1 = counters(self.svc)
        cpu_s = time.process_time() - cpu0
        gc_clock.stop()
        if tracer:
            tracer.disarm()
        n_due = sum(1 for d in rec.due if d < t1)
        settle(rec, len(rec), max(time.perf_counter(), t1) + SETTLE_S)
        done = np.sort([d - t0 for d in rec.done if t0 <= d <= t1])
        per_s = np.histogram(done, bins=max(1, int(math.ceil(seconds))),
                             range=(0.0, max(1.0, math.ceil(seconds))))[0]
        edges = np.concatenate([[0.0], done, [seconds]])
        k = int(np.argmax(np.diff(edges)))
        log(f"window {stream} from {time.time() - (time.perf_counter() - t0):.3f}"
            f" (epoch s): process cpu {cpu_s:.3f} s in {seconds} s, "
            f"gc {gc_clock.seconds:.3f} s in {gc_clock.count} collections, "
            f"longest gap between completions {edges[k + 1] - edges[k]:.3f}"
            f" s at {edges[k]:.3f} s, completed per second {per_s.tolist()}")
        return types.SimpleNamespace(
            model=self.model, chips=self.cell["chips"],
            seconds=float(seconds), t0=t0, t1=t1, requests=rec,
            n_due=n_due, sizes=self.sizes,
            deltas={k: c1[k] - c0[k] for k in c0}, peaks=self.peaks,
            counts=counts, trace=None, setup_s=None)

    def memory_peak(self) -> int:
        return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                       for d in self.devs[:self.cell["chips"]]))

    def close(self) -> None:
        self.svc.close(timeout=SETTLE_S)


def run(args, *, require_chip: bool = True, root: Path = ROOT,
        model_overrides: Optional[Dict] = None,
        fault: Optional[Callable] = None) -> Dict:
    """One run of a cell; returns the result object."""
    sess = Session(args.workload, args.seed, require_chip=require_chip,
                   root=root, model_overrides=model_overrides, fault=fault)
    metrics = cell_metrics(sess.spec, sess.cell["name"], bool(args.trace))
    readers = {m["name"]: load_reader(m["name"]) for m in metrics}
    tracer = (trace_reduce.Tracer(
        root / "artifacts" / "bench" / f"{args.workload}-{args.seed}",
        args.seconds) if args.trace else None)
    try:
        setup_s = time.perf_counter() - T_START
        w = sess.window(args.seconds, stream=2, tracer=tracer)
        w.setup_s = setup_s
        mem = sess.memory_peak()
    finally:
        sess.close()

    # -- what the window produced -------------------------------------------
    rec, limit = w.requests, sess.config["limits"]["max_log_gap"]
    due = range(w.n_due)
    failed = [i for i in due if i in rec.errors]
    unsettled = [i for i in due if math.isnan(rec.done[i])]
    finished = [i for i in due if i not in rec.errors
                and not math.isnan(rec.done[i]) and rec.done[i] <= w.t1]
    sample = sample_finished(rec, finished, sess.sizes,
                             np.random.default_rng([args.seed, 3]))
    gaps = compare(sess.reference, rec, sample, sess.pool, sess.params,
                   sess.model, bool(args.control))
    max_gap = float(gaps.max()) if len(gaps) else math.inf
    checks = {
        "max_log_gap": {"value": max_gap, "limit": limit},
        "failed_requests": {"value": len(failed), "limit": 0},
        "unsettled_requests": {"value": len(unsettled), "limit": 0},
    }
    correct = max_gap <= limit and not failed and not unsettled

    devs = sess.devs
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": mem}
    if tracer:
        w.trace = tracer.reduce(n_devices=sess.cell["chips"])
        device["busy_s"] = w.trace["busy_s"]
        device["window_s"] = w.trace["window_s"]
    values = {}
    for m in metrics:
        v = readers[m["name"]](w)
        if v is not None and math.isfinite(v):
            values[m["name"]] = {"value": float(v), "unit": m["unit"]}
    result = {"correct": bool(correct), "attempted": w.n_due,
              "failed": len(failed) + len(unsettled), "metrics": values,
              "device": device}
    if w.trace is not None:
        result["breakdown"] = w.trace["breakdown"]
    result["checks"] = checks
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    return result


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse_args(argv)
    # libtpu would log to a fixed directory under /tmp
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        result = run(args)
    except NoChip as e:
        log(f"no result: {e}")
        return 1
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
