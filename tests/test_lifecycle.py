"""Request-lifecycle hardening: deadlines, poison-bin quarantine,
circuit breakers, graceful drain, structured validation — and the
hypothesis-driven invariant that every accepted future terminates
exactly once (``repro.serve.lifecycle`` + its wiring)."""
import time

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import DIPPM, PMGNSConfig, PredictionEngine, pmgns_init
from repro.core.engine import EngineConfig, PredictionInvalidError
from repro.core.frontends import from_json
from repro.core.ir import GraphValidationError, OpGraph, OpNode
from repro.runtime.fault import FailureInjector
from repro.serve import (BreakerConfig, CircuitBreaker,
                         DeadlineExceededError, PoisonRequestError,
                         PredictionService, QuarantineList, ReplicaPool,
                         ServeConfig, ServiceDrainingError)
from repro.serve.cache import CacheWaiter, PredictionCache
from repro.serve.queue import PredictionFuture


def _graph(n_nodes, seed=0, nan_flops=False):
    rng = np.random.default_rng(seed)
    ops = ["dense", "conv", "relu", "add"]
    nodes = [OpNode(i, ops[i % len(ops)],
                    (int(rng.integers(1, 16)), int(rng.integers(1, 64))),
                    flops=(float("nan") if (nan_flops and i == 0)
                           else float(rng.integers(1, 10_000))),
                    macs=float(rng.integers(1, 5_000)))
             for i in range(n_nodes)]
    edges = [(i, i + 1) for i in range(n_nodes - 1)]
    return OpGraph(nodes=nodes, edges=edges, meta={"seed": seed})


@pytest.fixture(scope="module")
def packed_dippm():
    cfg = PMGNSConfig(hidden=32, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    return DIPPM.from_params(params, cfg)


# ---- circuit breaker (unit) ------------------------------------------------

def test_breaker_transitions():
    b = CircuitBreaker(BreakerConfig(failure_threshold=2, cooldown_s=10.0))
    assert b.state == "closed" and b.can_dispatch(now=0.0)
    assert not b.record_failure(now=0.0)         # 1 failure: still closed
    assert b.record_failure(now=0.0)             # 2nd trips it open
    assert b.state == "open" and b.trips == 1
    assert not b.can_dispatch(now=5.0)           # cooling down
    assert b.can_dispatch(now=11.0)              # cooldown elapsed → probe
    assert b.state == "half-open"
    b.on_dispatch(now=11.0)                      # probe token consumed
    assert not b.can_dispatch(now=11.0)          # only ONE probe in flight
    assert b.record_success() is True            # probe passed → re-closed
    assert b.state == "closed"


def test_breaker_failed_probe_reopens():
    b = CircuitBreaker(BreakerConfig(failure_threshold=1, cooldown_s=10.0))
    b.record_failure(now=0.0)
    assert b.can_dispatch(now=11.0)              # half-open
    b.on_dispatch(now=11.0)
    assert b.record_failure(now=11.0)            # probe failed → open again
    assert b.state == "open" and b.trips == 2
    assert not b.can_dispatch(now=15.0)          # fresh cooldown from probe
    assert b.can_dispatch(now=22.0)


def test_breaker_failure_rate_window():
    b = CircuitBreaker(BreakerConfig(failure_threshold=100,
                                     failure_rate=0.5, window=8,
                                     min_calls=4, cooldown_s=10.0))
    for _ in range(3):
        b.record_success()
    assert not b.record_failure(now=0.0)         # 1/4 failing < 0.5
    b.record_failure(now=0.0)
    b.record_failure(now=0.0)                    # 3/6 failing → trips
    assert b.state == "open"


# ---- quarantine list (unit) ------------------------------------------------

def test_quarantine_lru_bound_and_remove():
    q = QuarantineList(capacity=2)
    q.record("a", RuntimeError("ka"))
    q.record("b", RuntimeError("kb"))
    assert q.check("a") == "RuntimeError: ka"    # touches "a" (LRU)
    q.record("c", RuntimeError("kc"))            # evicts "b", not "a"
    assert "b" not in q and "a" in q and "c" in q
    assert len(q) == 2 and q.recorded == 3 and q.fastfails == 1
    assert q.remove("a") and not q.remove("a")
    assert q.check("a") is None
    with pytest.raises(ValueError, match="positive"):
        QuarantineList(capacity=0)


# ---- flight-token scoping (regression) -------------------------------------

def test_cache_stale_abort_cannot_tear_down_successor_flight():
    """A racing failure path holding the OLD flight token must not
    settle the successor flight a retry opened for the same key."""
    cache = PredictionCache(capacity=8)

    def _waiter():
        return CacheWaiter(PredictionFuture(), {}, time.perf_counter())

    status, _, flight1 = cache.claim("k", _waiter())
    assert status == "leader"
    assert cache.abort("k", flight1) == []       # leader fails, no followers
    status, _, flight2 = cache.claim("k", _waiter())
    assert status == "leader" and flight2 is not flight1
    w = _waiter()
    assert cache.claim("k", w)[0] == "follower"  # parked on flight2
    assert cache.abort("k", flight1) == []       # stale abort: a no-op
    followers = cache.complete("k", np.ones(3), flight2)
    assert followers == [w]                      # flight2 still intact


# ---- structured frontend validation ----------------------------------------

@pytest.mark.parametrize("doc,msg", [
    ([1, 2], "must be a mapping"),
    ({"edges": []}, "no 'nodes'"),
    ({"nodes": [17]}, "not a mapping"),
    ({"nodes": [{"op": "dense"}]}, "missing required field 'id'"),
    ({"nodes": [{"id": "x", "op": "dense"}]}, "non-integer id"),
    ({"nodes": [{"id": 0, "op": "dense"}, {"id": 0, "op": "relu"}]},
     "duplicate node id 0"),
    ({"nodes": [{"id": 0, "op": "dense", "out_shape": "bad"}]},
     "malformed out_shape"),
    ({"nodes": [{"id": 0, "op": "dense", "out_shape": [4, -1]}]},
     "negative out_shape"),
    ({"nodes": [{"id": 0, "op": "dense", "out_shape": [4]}],
      "edges": [[0, 7]]}, "references node 7"),
    ({"nodes": [{"id": 0, "op": "dense", "out_shape": [4]}],
      "edges": ["nope"]}, "integer pair"),
    ({"nodes": [{"id": 0, "op": "dense", "out_shape": [4]},
                {"id": 1, "op": "relu", "out_shape": [4]}],
      "edges": [[0, 1], [1, 0]]}, "cycle"),
])
def test_from_json_typed_validation_errors(doc, msg):
    with pytest.raises(GraphValidationError, match=msg):
        from_json(doc)


def test_from_json_error_carries_node_context():
    try:
        from_json({"nodes": [{"id": 3, "op": "dense",
                              "out_shape": [4, -2]}]})
    except GraphValidationError as e:
        assert e.node_id == 3
    else:
        pytest.fail("expected GraphValidationError")


def test_submit_json_invalid_rejects_future_without_queue(packed_dippm):
    svc = packed_dippm.serve(max_wait_ms=30_000.0)
    try:
        fut = svc.submit_json({"nodes": [{"op": "dense"}]})
        assert fut.done()                        # rejected immediately
        assert isinstance(fut.exception(timeout=1), GraphValidationError)
        st = svc.stats
        assert st.invalid == 1 and st.failed == 1
        assert st.queue_depth == 0 and st.batches == 0  # queue untouched
    finally:
        svc.close()


# ---- deadlines -------------------------------------------------------------

def test_deadline_expired_in_queue(packed_dippm):
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024)
    try:
        fut = svc.submit(_graph(8, seed=1), deadline_ms=1.0)
        ok = svc.submit(_graph(9, seed=2))       # no deadline: unaffected
        time.sleep(0.03)
        svc.flush()
        assert isinstance(fut.exception(timeout=30), DeadlineExceededError)
        assert ok.result(timeout=30) is not None
        st = svc.stats
        assert st.deadline_expired == 1 and st.completed == 1
        assert st.failed == 0                    # typed, not a failure
    finally:
        svc.close()


def test_default_deadline_ms_applies(packed_dippm):
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024,
                             default_deadline_ms=1.0)
    try:
        fut = svc.submit(_graph(8, seed=3))
        time.sleep(0.03)
        svc.flush()
        assert isinstance(fut.exception(timeout=30), DeadlineExceededError)
    finally:
        svc.close()


def test_follower_deadline_expires_while_parked(packed_dippm):
    """Leader (no deadline) completes; the coalesced follower whose own
    deadline passed while parked rejects instead of resolving late."""
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024)
    try:
        leader = svc.submit(_graph(11, seed=4))
        follower = svc.submit(_graph(11, seed=4), deadline_ms=1.0)
        time.sleep(0.03)
        svc.flush()
        assert leader.result(timeout=30) is not None
        assert isinstance(follower.exception(timeout=30),
                          DeadlineExceededError)
        assert svc.stats.deadline_expired == 1
    finally:
        svc.close()


def test_expired_leader_rejects_followers_and_clears_flight(packed_dippm):
    """An expired single-flight leader aborts its flight: followers
    reject (their leader will never run) and the next duplicate becomes
    a fresh leader that succeeds."""
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024)
    try:
        leader = svc.submit(_graph(12, seed=5), deadline_ms=1.0)
        follower = svc.submit(_graph(12, seed=5))
        time.sleep(0.03)
        svc.flush()
        assert isinstance(leader.exception(timeout=30),
                          DeadlineExceededError)
        assert isinstance(follower.exception(timeout=30),
                          DeadlineExceededError)
        retry = svc.submit(_graph(12, seed=5))   # fresh leader
        svc.flush()
        assert retry.result(timeout=30) is not None
    finally:
        svc.close()


# ---- poison-bin quarantine -------------------------------------------------

def _poisoned_service(dippm, monkeypatch, poison_seed=99, **serve_kw):
    """Service whose engine fails any bin containing the poison graph
    (deterministic, content-dependent — the bisection target)."""
    svc = dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024,
                      **serve_kw)
    orig = svc.engine.run_bin

    def flaky(chunk):
        if any(s.meta.get("seed") == poison_seed for s in chunk):
            raise RuntimeError("kaboom")
        return orig(chunk)

    monkeypatch.setattr(svc.engine, "run_bin", flaky)
    return svc


def test_bisect_isolates_poison_innocents_complete(packed_dippm,
                                                   monkeypatch):
    svc = _poisoned_service(packed_dippm, monkeypatch)
    try:
        futs = [svc.submit(_graph(7, seed=s)) for s in (1, 2, 99, 3, 4)]
        svc.flush()
        errs = [f.exception(timeout=60) for f in futs]
        assert [e is None for e in errs] == [True, True, False, True, True]
        assert isinstance(errs[2], PoisonRequestError)
        assert "kaboom" in str(errs[2])
        assert isinstance(errs[2].__cause__, RuntimeError)
        st = svc.stats
        assert st.completed == 4 and st.failed == 1
        assert st.poisoned == 1 and st.bisect_runs >= 2
        assert st.quarantine_entries == 1
    finally:
        svc.close()


def test_quarantine_fastfails_resubmit_and_readmits(packed_dippm,
                                                    monkeypatch):
    svc = _poisoned_service(packed_dippm, monkeypatch)
    try:
        bad = _graph(7, seed=99)
        first = svc.submit(bad)
        svc.flush()
        assert isinstance(first.exception(timeout=60), PoisonRequestError)
        before = svc.stats.bisect_runs
        again = svc.submit(bad)                  # fast-fail at the door
        assert again.done()
        assert isinstance(again.exception(timeout=1), PoisonRequestError)
        assert "quarantined" in str(again.exception(timeout=1))
        st = svc.stats
        assert st.quarantine_fastfail == 1
        assert st.bisect_runs == before          # no engine work spent
        svc._quarantine.remove(bad.fingerprint())  # manual re-admission
        readmit = svc.submit(bad)
        assert not readmit.done() or readmit.exception(timeout=1) is None
    finally:
        svc.close()


def test_poison_policy_fail_bin_fails_all_riders(packed_dippm,
                                                 monkeypatch):
    svc = _poisoned_service(packed_dippm, monkeypatch,
                            poison_policy="fail-bin")
    try:
        futs = [svc.submit(_graph(7, seed=s)) for s in (1, 2, 99)]
        svc.flush()
        errs = [f.exception(timeout=60) for f in futs]
        assert all(isinstance(e, RuntimeError) for e in errs)
        st = svc.stats
        assert st.failed == 3 and st.completed == 0
        assert st.poisoned == 0 and st.bisect_runs == 0
    finally:
        svc.close()


def test_nan_graph_flagged_invalid_and_isolated(packed_dippm):
    """A graph whose features are NaN yields non-finite predictions;
    the engine flags it (PredictionInvalidError) and the service
    isolates it like any other poison — innocents packed in the same
    bin still complete."""
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024)
    try:
        futs = [svc.submit(_graph(6, seed=s, nan_flops=(s == 2)))
                for s in range(5)]
        svc.flush()
        errs = [f.exception(timeout=60) for f in futs]
        assert sum(e is not None for e in errs) == 1
        assert isinstance(errs[2], PoisonRequestError)
        assert isinstance(errs[2].__cause__, PredictionInvalidError)
        assert svc.stats.completed == 4
    finally:
        svc.close()


def test_engine_output_validation_flag(packed_dippm):
    eng = PredictionEngine(packed_dippm.params, packed_dippm.cfg,
                           EngineConfig(node_budget=256))
    from repro.core.batching import sample_from_graph
    bad = sample_from_graph(_graph(6, seed=1, nan_flops=True),
                            buckets=eng.engine_cfg.buckets,
                            extended_static=eng.engine_cfg.extended_static)
    with pytest.raises(PredictionInvalidError) as ei:
        eng.run_bin([bad])
    assert 0 in ei.value.bad_rows
    lax = PredictionEngine(packed_dippm.params, packed_dippm.cfg,
                           EngineConfig(node_budget=256,
                                        validate_outputs=False))
    out = lax.run_bin([bad])                     # opt-out: raw NaNs back
    assert not np.isfinite(out).all()


def test_infra_failure_does_not_quarantine(packed_dippm):
    """All replicas dead is the SERVICE's fault: riders fail with the
    infra error, nobody is bisected or quarantined."""
    inj = {0: FailureInjector(), 1: FailureInjector()}
    inj[0].fail_next(10)
    inj[1].fail_next(10)
    pool = ReplicaPool(packed_dippm.params, packed_dippm.cfg,
                       EngineConfig(node_budget=256), n_replicas=2,
                       injectors=inj)
    svc = PredictionService(engine=pool, serve_cfg=ServeConfig(
        node_budget=256, max_wait_ms=30_000.0, max_batch_graphs=1024))
    try:
        futs = [svc.submit(_graph(8, seed=s)) for s in range(4)]
        svc.flush()
        errs = [f.exception(timeout=60) for f in futs]
        assert all(e is not None for e in errs)
        assert not any(isinstance(e, PoisonRequestError) for e in errs)
        st = svc.stats
        assert st.poisoned == 0 and st.quarantine_entries == 0
        assert st.failed == 4
    finally:
        svc.close()
        pool.close()


def test_jax_runtime_error_is_infrastructure(packed_dippm, monkeypatch):
    """A compile error or device fault from JAX fails the riders with
    that error as it is: no bisection, nobody condemned as poison."""
    import jax
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024)

    def broken(chunk):
        raise jax.errors.JaxRuntimeError(
            "INTERNAL: Mosaic failed to compile TPU kernel")

    monkeypatch.setattr(svc.engine, "run_bin", broken)
    try:
        futs = [svc.submit(_graph(7, seed=s)) for s in range(4)]
        svc.flush()
        errs = [f.exception(timeout=60) for f in futs]
        assert all(isinstance(e, jax.errors.JaxRuntimeError) for e in errs)
        st = svc.stats
        assert st.failed == 4 and st.completed == 0
        assert st.poisoned == 0 and st.bisect_runs == 0
        assert st.quarantine_entries == 0
    finally:
        svc.close()


# ---- circuit breakers in the fleet -----------------------------------------

def test_breaker_probe_revives_replica_after_outage(packed_dippm):
    inj = {0: FailureInjector()}
    inj[0].fail_window(1, 2)                     # down for dispatch 1 only
    pool = ReplicaPool(packed_dippm.params, packed_dippm.cfg,
                       EngineConfig(node_budget=256), n_replicas=2,
                       injectors=inj,
                       breaker=BreakerConfig(cooldown_s=0.2))
    svc = PredictionService(engine=pool, serve_cfg=ServeConfig(
        node_budget=256, max_wait_ms=2.0))
    try:
        svc.predict_many([_graph(10 + s % 7, seed=s) for s in range(10)],
                         timeout=120)
        assert pool.breaker_states == ("open", "closed")
        assert pool.health == (False, True) and pool.n_healthy == 1
        time.sleep(0.3)                          # cooldown elapses
        preds = svc.predict_many([_graph(9, seed=100 + s)
                                  for s in range(8)], timeout=120)
        assert all(p is not None for p in preds)
        assert pool.breaker_states == ("closed", "closed")
        assert pool.revivals == 1                # half-open probe passed
        assert svc.stats.revivals == 1
        assert svc.stats.breaker_states == ("closed", "closed")
    finally:
        svc.close()
        pool.close()


# ---- graceful drain --------------------------------------------------------

def test_drain_stops_admission_and_settles_in_flight(packed_dippm):
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024)
    futs = [svc.submit(_graph(8, seed=s)) for s in range(5)]
    assert not svc.draining
    assert svc.drain(timeout=60)                 # flushes the queue too
    assert svc.draining
    for f in futs:
        assert f.result(timeout=1) is not None   # all settled pre-return
    with pytest.raises(ServiceDrainingError, match="closed"):
        svc.submit(_graph(5, seed=9))
    # a graph whose fingerprint is already cached must not slip past
    # drain via the hit path — admission stops for EVERY route
    with pytest.raises(ServiceDrainingError, match="closed"):
        svc.submit(_graph(8, seed=0))
    with pytest.raises(ServiceDrainingError, match="closed"):
        svc.submit_many([_graph(8, seed=0)])
    assert svc.drain(timeout=1)                  # idempotent
    assert svc.stats.draining
    svc.close()


def test_context_manager_drains_on_exit(packed_dippm):
    with packed_dippm.serve(max_wait_ms=30_000.0) as svc:
        fut = svc.submit(_graph(8, seed=1))
    assert fut.result(timeout=1) is not None     # settled by __exit__ drain


# ---- the lifecycle invariant (hypothesis) ----------------------------------

@pytest.fixture(scope="module")
def chaos_pool(packed_dippm):
    inj = {0: FailureInjector(), 1: FailureInjector()}
    pool = ReplicaPool(packed_dippm.params, packed_dippm.cfg,
                       EngineConfig(node_budget=256), n_replicas=2,
                       injectors=inj,
                       breaker=BreakerConfig(cooldown_s=0.05))
    yield pool, inj
    pool.close()


_SCHEDULE_OPS = ["submit", "dup", "expired", "poison", "kill", "burst"]


def _run_schedule(chaos_pool, ops, seed):
    """The lifecycle invariant: under arbitrary schedules of submits,
    duplicates, deadline expiries, poison graphs, replica kills, load
    shedding, and a final drain, EVERY accepted future terminates with
    a result or a typed error — exactly once, nothing hangs — and the
    terminal counters conserve: submitted = completed + failed +
    deadline_expired + shed."""
    pool, inj = chaos_pool
    for i in range(pool.n_replicas):             # reset breakers/chaos
        pool.revive(i)
    svc = PredictionService(engine=pool, serve_cfg=ServeConfig(
        node_budget=256, max_wait_ms=1.0, max_queue=6,
        shed_policy="oldest", cache_size=64, quarantine_size=None))
    futs, fires = [], []
    uid = seed * 1000

    def track(fut):
        cell = [0]
        fut.add_done_callback(lambda _f: cell.__setitem__(0, cell[0] + 1))
        futs.append(fut)
        fires.append(cell)

    try:
        for op in ops:
            if op == "submit":
                uid += 1
                track(svc.submit(_graph(6 + uid % 9, seed=uid)))
            elif op == "dup":
                track(svc.submit(_graph(6 + uid % 9, seed=uid)))
            elif op == "expired":
                uid += 1
                track(svc.submit(_graph(6 + uid % 9, seed=uid),
                                 deadline_ms=0.01))
            elif op == "poison":
                uid += 1
                track(svc.submit(_graph(6, seed=uid, nan_flops=True)))
            elif op == "kill":
                inj[uid % 2].fail_next(1)
            elif op == "burst":
                uid += 1
                for f in svc.submit_many(
                        [_graph(5 + k, seed=uid) for k in range(3)]):
                    track(f)
        svc.flush()
        assert svc.drain(timeout=120)
        for fut, cell in zip(futs, fires):
            assert fut.done()                    # nothing hangs
            assert cell[0] == 1                  # settled exactly once
            err = fut.exception(timeout=1)
            if err is not None:                  # typed terminal errors only
                assert isinstance(err, RuntimeError)
        st = svc.stats
        assert st.submitted == (st.completed + st.failed
                                + st.deadline_expired + st.shed_count)
    finally:
        svc.close()
        for i in inj:                            # disarm leftover chaos
            with inj[i]._lock:
                inj[i]._armed = 0


@settings(max_examples=12, deadline=None)
@given(ops=st.lists(st.sampled_from(_SCHEDULE_OPS),
                    min_size=1, max_size=10),
       seed=st.integers(0, 2**16))
def test_every_accepted_future_terminates_exactly_once(chaos_pool, ops,
                                                       seed):
    _run_schedule(chaos_pool, ops, seed)


@pytest.mark.parametrize("seed", [0, 1, 2, 3])
def test_lifecycle_schedule_fixed_seeds(chaos_pool, seed):
    """Deterministic twin of the hypothesis test (runs even where
    hypothesis is not installed): seeded pseudo-random schedules."""
    rng = np.random.default_rng(seed)
    ops = [
        _SCHEDULE_OPS[int(i)]
        for i in rng.integers(0, len(_SCHEDULE_OPS), size=10)
    ]
    _run_schedule(chaos_pool, ops, seed)
