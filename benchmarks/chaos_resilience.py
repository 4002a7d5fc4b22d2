"""Chaos gate: request-lifecycle hardening under replica kills + poison.

Serving an open endpoint means surviving two failure families at once:
*infrastructure* (replicas die mid-stream and later recover) and
*content* (a small fraction of submitted graphs deterministically kill
any bin they ride in — here, NaN-featured graphs the engine flags via
non-finite-output validation). This gate drives a Poisson stream of
mostly-tiny graphs through a 2-replica fleet while a ``FailureInjector``
kills a replica mid-stream (the circuit breaker re-admits it via a
half-open probe after cooldown) and ~1.5% of the stream is poison, and
pins the resilience contract:

* **zero lost futures** — every accepted future resolves with a result
  or a typed error; nothing hangs;
* **innocent completion ≥ 99%** — non-poison requests complete despite
  sharing bins with poison (split-retry bisection isolates offenders);
* **bounded latency damage** — chaos-run p99 ≤ 3x the fault-free p99 on
  the identical workload shape;
* **quarantine goodput ≥ 5x** — innocent completion under
  ``poison_policy="bisect"`` vs the naive whole-bin-rejection baseline
  (``"fail-bin"``). The bins here are wide (tiny graphs, big node
  budget → ~128 graphs/bin), so whole-bin rejection collateral-damages
  most of the stream — exactly the failure mode bisection removes.

Emits ``BENCH_chaos_resilience.json``.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \
        PYTHONPATH=src python -m benchmarks.chaos_resilience
"""
from __future__ import annotations

import os
import sys
import time

from repro.compile_cache import enable_compile_cache

from .common import write_json

FORCE_DEVICES = 4


def _ensure_host_mesh(n: int = FORCE_DEVICES) -> None:
    if "jax" in sys.modules:
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip())


def _tiny_graph(seed: int, poison: bool = False):
    """~12-node chain DAG — small enough that a 2048-node budget packs
    ~128 of them per bin (the wide-bin regime where whole-bin rejection
    is catastrophic). ``poison=True`` plants a NaN flops feature: it
    propagates through featurization → GNN → non-finite output, which
    ``EngineConfig.validate_outputs`` turns into a bin failure."""
    import numpy as np
    from repro.core.ir import OpGraph, OpNode

    rng = np.random.default_rng(seed)
    ops = ["dense", "conv", "relu", "add", "norm", "pool"]
    nn = int(rng.integers(8, 16))
    nodes = [OpNode(i, ops[int(rng.integers(0, len(ops)))],
                    (int(rng.integers(1, 16)), int(rng.integers(1, 64))),
                    flops=(float("nan") if (poison and i == 0)
                           else float(rng.integers(1, 10_000))),
                    macs=float(rng.integers(1, 5_000)))
             for i in range(nn)]
    edges = [(i, i + 1) for i in range(nn - 1)]
    return OpGraph(nodes=nodes, edges=edges,
                   meta={"seed": seed, "poison": poison})


def run(n_requests: int = 512, poison_every: int = 64, replicas: int = 2,
        node_budget: int = 2048, hidden: int = 32, seed: int = 0):
    _ensure_host_mesh()
    import jax
    import numpy as np
    from repro.core import PMGNSConfig, pmgns_init
    from repro.core.engine import EngineConfig
    from repro.runtime.fault import FailureInjector
    from repro.serve import (BreakerConfig, PoisonRequestError,
                             PredictionService, ReplicaPool, ServeConfig)

    n_devices = len(jax.local_devices())
    n_cores = os.cpu_count() or 1
    cfg = PMGNSConfig(hidden=hidden, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg)

    # identical workload *shape* for every run; the chaos runs replace
    # every poison_every-th graph with its NaN-poisoned twin (~1.5%)
    poison_ids = set(range(poison_every - 1, n_requests, poison_every))

    def _stream(poisoned: bool):
        return [_tiny_graph(seed * 100_000 + i,
                            poison=poisoned and i in poison_ids)
                for i in range(n_requests)]

    def _run_once(poisoned: bool, kill: bool, policy: str):
        injectors = None
        if kill:
            # replica 0 dies on its 2nd and 6th bin dispatch; the
            # breaker opens, cools down, and re-admits it via a probe
            injectors = {0: FailureInjector(fail_at_steps=[2, 6])}
        pool = ReplicaPool(params, cfg, EngineConfig(
            node_budget=node_budget), n_replicas=replicas,
            injectors=injectors,
            breaker=BreakerConfig(cooldown_s=0.25))
        svc = PredictionService(engine=pool, serve_cfg=ServeConfig(
            node_budget=node_budget, max_wait_ms=50.0,
            max_batch_graphs=n_requests, poison_policy=policy,
            default_deadline_ms=300_000.0))
        svc.warmup()                    # full rung ladder: bisect
        #                                 sub-bins re-pack compile-free
        stream = _stream(poisoned)
        rng = np.random.default_rng(seed)
        arrivals = np.cumsum(rng.exponential(2e-4, n_requests))
        futs = []
        t0 = time.perf_counter()
        for i, g in enumerate(stream):  # open-loop Poisson arrivals
            dt = t0 + arrivals[i] - time.perf_counter()
            if dt > 0:
                time.sleep(dt)
            futs.append(svc.submit(g))
        svc.flush()
        drained = svc.drain(timeout=600)
        wall = time.perf_counter() - t0
        lost = sum(not f.done() for f in futs)
        errs = [f.exception(timeout=1) if f.done() else None for f in futs]
        innocents = [i for i in range(n_requests) if i not in poison_ids]
        innocents_done = sum(errs[i] is None for i in innocents)
        poison_typed = all(
            isinstance(errs[i], (PoisonRequestError, RuntimeError))
            for i in poison_ids if errs[i] is not None) if poisoned else True
        st = svc.stats
        out = {
            "drained": bool(drained),
            "lost_futures": int(lost),
            "wall_s": round(wall, 3),
            "completed": st.completed,
            "failed": st.failed,
            "deadline_expired": st.deadline_expired,
            "poisoned": st.poisoned,
            "bisect_runs": st.bisect_runs,
            "quarantine_entries": st.quarantine_entries,
            "requeues": st.requeues,
            "revivals": st.revivals,
            "breaker_states": list(st.breaker_states),
            "injected_failures": (injectors[0].failures if injectors
                                  else 0),
            "p99_ms": st.latency_ms_p99,
            "innocent_total": len(innocents),
            "innocent_done": int(innocents_done),
            "innocent_frac": round(innocents_done / len(innocents), 4),
            "poison_errors_typed": bool(poison_typed),
        }
        svc.close()
        pool.close()
        return out

    clean = _run_once(poisoned=False, kill=False, policy="bisect")
    chaos = _run_once(poisoned=True, kill=True, policy="bisect")
    naive = _run_once(poisoned=True, kill=True, policy="fail-bin")

    p99_ratio = (chaos["p99_ms"] / clean["p99_ms"]
                 if clean["p99_ms"] > 0 else float("inf"))
    goodput_ratio = (chaos["innocent_frac"]
                     / max(naive["innocent_frac"], 1.0 / n_requests))

    no_lost = (chaos["lost_futures"] == 0 and naive["lost_futures"] == 0
               and clean["lost_futures"] == 0 and chaos["drained"]
               and naive["drained"])
    innocent_ok = chaos["innocent_frac"] >= 0.99
    latency_ok = p99_ratio <= 3.0
    goodput_ok = goodput_ratio >= 5.0
    typed_ok = chaos["poison_errors_typed"]

    res = {
        "n_cores": n_cores,
        "n_devices": n_devices,
        "n_requests": n_requests,
        "n_poison": len(poison_ids),
        "replicas": replicas,
        "node_budget": node_budget,
        "clean": clean,
        "chaos_bisect": chaos,
        "chaos_failbin": naive,
        "p99_ratio": round(p99_ratio, 2),
        "goodput_ratio": round(goodput_ratio, 2),
        "no_lost_futures": bool(no_lost),
        "innocent_ok": bool(innocent_ok),
        "latency_ok": bool(latency_ok),
        "goodput_ok": bool(goodput_ok),
        "typed_ok": bool(typed_ok),
    }
    res["ok"] = bool(no_lost and innocent_ok and latency_ok
                     and goodput_ok and typed_ok)
    res["artifact"] = write_json("BENCH_chaos_resilience.json", res)
    return res


def main():
    enable_compile_cache()
    res = run()
    ch, na, cl = res["chaos_bisect"], res["chaos_failbin"], res["clean"]
    print(f"host    : {res['n_cores']} cores, {res['n_devices']} jax "
          f"devices; {res['n_requests']} requests, {res['n_poison']} "
          f"poison, {res['replicas']} replicas")
    print(f"clean   : {cl['completed']} completed, p99 "
          f"{cl['p99_ms']:.1f} ms")
    print(f"bisect  : innocents {ch['innocent_done']}/"
          f"{ch['innocent_total']} ({ch['innocent_frac']:.1%}), "
          f"poisoned {ch['poisoned']}, bisect runs {ch['bisect_runs']}, "
          f"p99 {ch['p99_ms']:.1f} ms ({res['p99_ratio']:.2f}x clean)")
    print(f"          kills {ch['injected_failures']}, requeues "
          f"{ch['requeues']}, revivals {ch['revivals']}, breakers "
          f"{ch['breaker_states']}")
    print(f"fail-bin: innocents {na['innocent_done']}/"
          f"{na['innocent_total']} ({na['innocent_frac']:.1%}) -> "
          f"goodput ratio {res['goodput_ratio']:.2f}x")
    print(f"gate    : lost=0 {'PASS' if res['no_lost_futures'] else 'FAIL'}"
          f"; innocents >=99% {'PASS' if res['innocent_ok'] else 'FAIL'}"
          f"; p99 <=3x {'PASS' if res['latency_ok'] else 'FAIL'}"
          f"; goodput >=5x {'PASS' if res['goodput_ok'] else 'FAIL'}"
          f"; typed errors {'PASS' if res['typed_ok'] else 'FAIL'}")
    print("PASS" if res["ok"] else "FAIL")
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
