"""Serving semantics: micro-batching, FIFO futures, admission control,
warmup, and the versioned artifact format (``repro.serve``)."""
import dataclasses
import os
import pickle
import threading
import time
import warnings

import jax
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import DIPPM, PMGNSConfig, PredictionEngine, pmgns_init
from repro.core.batching import packed_rung_ladder
from repro.core.ir import OpGraph, OpNode
from repro.serve import (ARTIFACT_VERSION, PredictionService, QueueFullError,
                         ServeConfig, load_artifact, save_artifact)


def _graph(n_nodes, seed=0):
    """Chain graph with varied ops/flops so predictions differ per graph."""
    rng = np.random.default_rng(seed)
    ops = ["dense", "conv", "relu", "add"]
    nodes = [OpNode(i, ops[i % len(ops)],
                    (int(rng.integers(1, 16)), int(rng.integers(1, 64))),
                    flops=float(rng.integers(1, 10_000)),
                    macs=float(rng.integers(1, 5_000)))
             for i in range(n_nodes)]
    edges = [(i, i + 1) for i in range(n_nodes - 1)]
    return OpGraph(nodes=nodes, edges=edges, meta={"seed": seed, "n": n_nodes})


@pytest.fixture(scope="module")
def packed_dippm():
    cfg = PMGNSConfig(hidden=32, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    return DIPPM.from_params(params, cfg)


@pytest.fixture(scope="module")
def dense_dippm():
    cfg = PMGNSConfig(hidden=32)
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    return DIPPM.from_params(params, cfg)


# ---- concurrent-submit determinism ----------------------------------------

def test_concurrent_submits_match_predict_graph(packed_dippm):
    """Requests racing in from many threads must each get the same
    numbers as a lone predict_graph call (≤ 1e-5)."""
    graphs = [_graph(n, seed=i)
              for i, n in enumerate([5, 40, 100, 7, 60, 90, 12, 31])]
    ref = [packed_dippm.predict_graph(g) for g in graphs]
    with packed_dippm.serve(max_wait_ms=20.0, max_batch_graphs=64) as svc:
        results = [None] * len(graphs)

        def worker(tid):
            for k in range(tid, len(graphs), 4):
                results[k] = svc.submit(graphs[k]).result(timeout=60)

        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    for a, b in zip(ref, results):
        np.testing.assert_allclose(
            [b.latency_ms, b.energy_j, b.memory_mb],
            [a.latency_ms, a.energy_j, a.memory_mb], atol=1e-5, rtol=1e-5)
        assert b.meta == a.meta


def test_facade_predict_paths_share_service_numbers(dense_dippm):
    """predict_graph / predict_many / service futures — one engine,
    identical results, order preserved."""
    graphs = [_graph(n, seed=i) for i, n in enumerate([3, 40, 100, 7])]
    loop = [dense_dippm.predict_graph(g) for g in graphs]
    many = dense_dippm.predict_many(graphs)
    for a, b in zip(loop, many):
        # same engine; bins differ (1-graph vs coalesced) → float tol
        np.testing.assert_allclose(b.latency_ms, a.latency_ms,
                                   atol=1e-5, rtol=1e-5)
        assert b.meta == a.meta


# ---- FIFO resolution -------------------------------------------------------

def test_futures_resolve_in_submission_order(packed_dippm):
    with packed_dippm.serve(max_wait_ms=10.0, max_batch_graphs=16) as svc:
        order = []
        futs = []
        for i in range(24):
            fut = svc.submit(_graph(6 + i, seed=i))
            fut.add_done_callback(lambda f, i=i: order.append(i))
            futs.append(fut)
        svc.flush()
        preds = [f.result(timeout=60) for f in futs]
    assert order == sorted(order) == list(range(24))
    assert all(np.isfinite(p.latency_ms) for p in preds)
    assert all(f.latency_ms is not None and f.latency_ms >= 0 for f in futs)


def test_raising_done_callback_does_not_kill_batcher(packed_dippm, capsys):
    """A user callback that raises must be swallowed: later requests on
    the same service must still resolve (the batcher thread survives)."""
    with packed_dippm.serve(max_wait_ms=5.0) as svc:
        bad = svc.submit(_graph(8, seed=0))
        bad.add_done_callback(
            lambda f: (_ for _ in ()).throw(RuntimeError("hook boom")))
        svc.flush()
        assert np.isfinite(bad.result(timeout=30).latency_ms)
        # service still alive after the raising hook
        ok = svc.submit(_graph(9, seed=1))
        svc.flush()
        assert np.isfinite(ok.result(timeout=30).latency_ms)
    capsys.readouterr()                          # swallow the traceback


# ---- max_wait_ms straggler flush ------------------------------------------

def test_max_wait_flushes_single_straggler(packed_dippm):
    """One lone request, nobody else coming, no explicit flush: the
    max_wait_ms deadline alone must resolve it."""
    with packed_dippm.serve(max_wait_ms=50.0,
                            max_batch_graphs=1024) as svc:
        t0 = time.perf_counter()
        fut = svc.submit(_graph(10, seed=3))
        pred = fut.result(timeout=30)            # NOT flushed by anyone
        waited = time.perf_counter() - t0
    assert np.isfinite(pred.latency_ms)
    # resolved via the deadline: after the window opened, well before the
    # result timeout
    assert 0.05 <= waited < 20.0


def test_flush_covers_burst_larger_than_max_batch(packed_dippm):
    """A flushed burst wider than max_batch_graphs must drain fully
    without waiting out the (here: huge) coalescing window — the flush
    watermark covers everything queued at flush time, across drains."""
    with packed_dippm.serve(max_wait_ms=30_000.0,
                            max_batch_graphs=4) as svc:
        preds = svc.predict_many([_graph(6 + i, seed=i) for i in range(11)])
        assert len(preds) == 11
        assert svc.stats.batches == 3            # 4 + 4 + 3, no 30s stall


def test_batch_size_trigger_beats_max_wait(packed_dippm):
    """max_batch_graphs waiting requests flush immediately — a full
    batch must not sit out a long max_wait window."""
    with packed_dippm.serve(max_wait_ms=30_000.0,
                            max_batch_graphs=4) as svc:
        futs = [svc.submit(_graph(8 + i, seed=i)) for i in range(4)]
        preds = [f.result(timeout=30) for f in futs]  # no flush, no 30s wait
    assert len(preds) == 4


# ---- bounded-queue admission control --------------------------------------

def test_bounded_queue_rejects_when_full(packed_dippm):
    # a huge max_wait parks the batcher in its coalescing window, so the
    # queue can only drain via flush — rejection is deterministic
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024,
                             max_queue=2)
    try:
        f1 = svc.submit(_graph(5, seed=0))
        f2 = svc.submit(_graph(6, seed=1))
        with pytest.raises(QueueFullError):
            svc.submit(_graph(7, seed=2))
        assert svc.stats.rejected == 1
        svc.flush()
        assert f1.result(timeout=30) and f2.result(timeout=30)
        assert svc.stats.completed == 2
    finally:
        svc.close()


def test_submit_after_close_raises(packed_dippm):
    svc = packed_dippm.serve()
    svc.close()
    with pytest.raises(RuntimeError, match="closed"):
        svc.submit(_graph(5, seed=0))


def test_engine_failure_rejects_futures(packed_dippm, monkeypatch):
    svc = packed_dippm.serve(max_wait_ms=5.0)
    try:
        monkeypatch.setattr(
            svc.engine, "run_bin",
            lambda chunk: (_ for _ in ()).throw(RuntimeError("boom")))
        fut = svc.submit(_graph(5, seed=0))
        svc.flush()
        assert isinstance(fut.exception(timeout=30), RuntimeError)
        with pytest.raises(RuntimeError, match="boom"):
            fut.result(timeout=1)
        assert svc.stats.failed == 1
    finally:
        svc.close()


# ---- warmup ----------------------------------------------------------------

def test_warmup_precompiles_full_rung_ladder(packed_dippm):
    svc = packed_dippm.serve()
    try:
        expected = len(packed_rung_ladder(
            svc.engine.engine_cfg.node_budget))
        assert svc.expected_rungs() == expected == 5
        assert svc.warmup() == expected
        assert svc.engine.stats.cache_entries == expected
        # typical-density traffic at any request size is compile-free
        # (rung-escalating bins — e.g. > P//16 tiny graphs in one bin —
        # are workload-dependent and still compile on first sight)
        before = svc.engine.stats.cache_misses
        svc.predict_many([_graph(n, seed=i)
                          for i, n in enumerate([4, 60, 300, 900])])
        assert svc.engine.stats.cache_misses == before
    finally:
        svc.close()


def test_engine_warmup_default_still_single_rung(packed_dippm):
    eng = PredictionEngine(packed_dippm.params, packed_dippm.cfg)
    assert eng.warmup() == 1                     # top rung only (legacy)
    eng2 = PredictionEngine(packed_dippm.params, packed_dippm.cfg)
    assert eng2.warmup(rungs="all") == 5


def test_service_warmup_on_dense_trained_model_compiles_the_ladder(
        dense_dippm):
    """A dense-trained model is served on packed bins: the service's
    warmup compiles the whole rung ladder, as for a packed-trained one."""
    svc = PredictionService(dense_dippm.params, dense_dippm.cfg)
    try:
        assert svc.expected_rungs() == len(packed_rung_ladder())
        assert svc.warmup() == svc.expected_rungs()
    finally:
        svc.close()


# ---- serve config plumbing -------------------------------------------------

def test_serve_config_budget_overrides():
    cfg = PMGNSConfig(hidden=32, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    svc = PredictionService(params, cfg, ServeConfig(node_budget=512))
    try:
        assert svc.engine.engine_cfg.node_budget == 512
        assert svc.expected_rungs() == len(packed_rung_ladder(512))
    finally:
        svc.close()


def test_submit_json_and_jax_frontends(dense_dippm):
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S
    with dense_dippm.serve(max_wait_ms=5.0) as svc:
        doc = {"nodes": [{"id": 0, "op": "gemm", "out_shape": [4, 64]},
                         {"id": 1, "op": "relu", "out_shape": [4, 64]}],
               "edges": [[0, 1]], "meta": {"family": "external"}}
        p1 = svc.submit_json(doc)

        def toy(params_, x):
            return jnp.maximum(x @ params_, 0.0)

        p2 = svc.submit_jax(toy, S((64, 64), jnp.float32),
                            S((8, 64), jnp.float32), batch=8)
        svc.flush()
        assert np.isfinite(p1.result(timeout=60).latency_ms)
        r2 = p2.result(timeout=60)
        assert r2.meta.get("batch") == 8


def test_serve_stats_counters(packed_dippm):
    with packed_dippm.serve(max_wait_ms=10.0) as svc:
        svc.predict_many([_graph(10, seed=i) for i in range(6)])
        s = svc.stats
    assert s.submitted == s.completed == 6
    assert s.batches >= 1 and s.bins >= 1
    assert s.batch_occupancy > 1.0               # coalesced, not per-request
    assert s.latency_ms_p99 >= s.latency_ms_p50 > 0.0
    assert 0.0 <= s.padding_waste_frac < 1.0


# ---- versioned artifacts ---------------------------------------------------

def test_artifact_roundtrip_and_predictions(dense_dippm, tmp_path):
    path = str(tmp_path / "model.npz")
    dense_dippm.save(path, metadata={"run": "t1"})
    params, cfg, meta = load_artifact(path)
    assert cfg == dense_dippm.cfg
    assert meta == {"run": "t1"}
    back = DIPPM.from_params(params, cfg)
    g = _graph(12, seed=5)
    assert (back.predict_graph(g).latency_ms
            == pytest.approx(dense_dippm.predict_graph(g).latency_ms,
                             rel=1e-6))


def test_artifact_is_pickle_free(dense_dippm, tmp_path):
    path = str(tmp_path / "model.npz")
    dense_dippm.save(path)
    with open(path, "rb") as f:
        assert f.read(2) == b"PK"                # a zip, not a pickle
    # loads with allow_pickle=False end to end (load_artifact enforces it)
    params, cfg, _ = load_artifact(path)
    assert isinstance(params, dict) and "gnn" in params


def test_legacy_pickle_fallback_warns(dense_dippm, tmp_path):
    path = str(tmp_path / "legacy.pkl")
    with open(path, "wb") as f:
        pickle.dump({"params": jax.tree_util.tree_map(
            np.asarray, dense_dippm.params), "cfg": dense_dippm.cfg}, f)
    with pytest.warns(DeprecationWarning, match="pickle"):
        back = DIPPM.load(path)
    g = _graph(9, seed=2)
    assert (back.predict_graph(g).latency_ms
            == pytest.approx(dense_dippm.predict_graph(g).latency_ms,
                             rel=1e-6))


def test_artifact_with_sparse_mp_alias_keeps_its_layout(dense_dippm,
                                                        tmp_path):
    """A config saved with the retired ``sparse_mp`` alias set and
    ``layout`` ``"auto"`` loads as the sparse layout it trained in."""
    import json
    path = str(tmp_path / "model.npz")
    dense_dippm.save(path)
    with np.load(path, allow_pickle=False) as z:
        doc = json.loads(bytes(z["__dippm_artifact__"]).decode())
        arrays = {k: z[k] for k in z.files if k != "__dippm_artifact__"}
    assert doc["cfg"]["layout"] == "auto"
    for alias, layout in ((True, "sparse"), (False, "auto")):
        doc["cfg"]["sparse_mp"] = alias
        header = np.frombuffer(json.dumps(doc).encode(), np.uint8)
        with open(path, "wb") as f:
            np.savez(f, __dippm_artifact__=header, **arrays)
        _, cfg, _ = load_artifact(path)
        assert cfg == PMGNSConfig(**dict(
            dataclasses.asdict(dense_dippm.cfg), layout=layout))


def test_artifact_rejects_newer_schema(dense_dippm, tmp_path):
    import json
    path = str(tmp_path / "model.npz")
    dense_dippm.save(path)
    with np.load(path, allow_pickle=False) as z:
        doc = json.loads(bytes(z["__dippm_artifact__"]).decode())
        arrays = {k: z[k] for k in z.files if k != "__dippm_artifact__"}
    doc["schema_version"] = ARTIFACT_VERSION + 1
    header = np.frombuffer(json.dumps(doc).encode(), np.uint8)
    newer = str(tmp_path / "newer.npz")
    with open(newer, "wb") as f:
        np.savez(f, __dippm_artifact__=header, **arrays)
    with pytest.raises(ValueError, match="schema_version"):
        load_artifact(newer)


def test_artifact_rejects_foreign_npz(tmp_path):
    path = str(tmp_path / "foreign.npz")
    with open(path, "wb") as f:
        np.savez(f, x=np.zeros(3))
    with pytest.raises(ValueError, match="artifact"):
        load_artifact(path)


@settings(deadline=None, max_examples=15)
@given(st.lists(st.integers(1, 6), min_size=1, max_size=3),
       st.sampled_from(["graphsage", "gcn", "mlp"]),
       st.integers(0, 2 ** 16 - 1))
def test_artifact_roundtrip_property(dims, variant, seed):
    """Property: save→load is exact for arbitrary param trees + configs
    (values, shapes, dtypes, nesting, and cfg fields all survive)."""
    import tempfile
    rng = np.random.default_rng(seed)
    params = {
        "gnn": {f"b{i}": {"w": rng.standard_normal((d, d + 1))
                          .astype(np.float32),
                          "b": rng.standard_normal((d + 1,))
                          .astype(np.float32)}
                for i, d in enumerate(dims)},
        "fc": {"head": {"w": rng.standard_normal((3, 2))}},
    }
    cfg = PMGNSConfig(variant=variant, hidden=8 * dims[0],
                      layout="packed" if seed % 2 else "auto")
    with tempfile.TemporaryDirectory() as tmp:
        path = os.path.join(tmp, f"prop-{seed}.npz")
        save_artifact(path, params, cfg, metadata={"seed": seed})
        back, cfg2, meta = load_artifact(path)
    assert cfg2 == cfg
    assert meta["seed"] == seed

    def assert_equal(a, b):
        assert set(a) == set(b)
        for k in a:
            if isinstance(a[k], dict):
                assert_equal(a[k], b[k])
            else:
                assert a[k].dtype == b[k].dtype
                np.testing.assert_array_equal(a[k], b[k])

    assert_equal(params, back)


# ---- content-addressed prediction cache ------------------------------------

def _pred_vec(p):
    return np.array([p.latency_ms, p.energy_j, p.memory_mb])


def test_cache_hit_is_bit_equal_and_skips_engine(packed_dippm):
    """A duplicate graph (same canonical fingerprint) resolves from the
    cache — EXACTLY equal to the cold-path prediction, and without the
    batcher running another batch."""
    svc = packed_dippm.serve(max_wait_ms=2.0)
    try:
        cold = svc.predict_one(_graph(20, seed=7))
        before = svc.stats
        warm = svc.predict_one(_graph(20, seed=7))
        after = svc.stats
        assert after.cache_hits == before.cache_hits + 1
        assert after.cache_misses == before.cache_misses
        assert after.batches == before.batches   # no engine work at all
        np.testing.assert_array_equal(_pred_vec(warm), _pred_vec(cold))
        assert warm.meta == cold.meta
        assert after.hit_rate == pytest.approx(0.5)
    finally:
        svc.close()


def test_cache_single_flight_coalesces_duplicates_to_one_slot(packed_dippm):
    """N pending requests for the same uncached graph cost ONE engine
    slot: first is the leader, the rest coalesce and resolve from the
    leader's result, all identical."""
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024)
    try:
        futs = [svc.submit(_graph(30, seed=3)) for _ in range(8)]
        st = svc.stats
        assert st.cache_misses == 1 and st.cache_coalesced == 7
        svc.flush()
        preds = [f.result(timeout=60) for f in futs]
        assert len({tuple(_pred_vec(p)) for p in preds}) == 1
        st = svc.stats
        assert st.completed == 8
        assert st.batches == 1 and st.batch_occupancy == 1.0
        for p in preds:                       # per-request latency stamped
            assert p.meta == {"seed": 3, "n": 30}
    finally:
        svc.close()


def test_cache_lru_bound_evicts_oldest(packed_dippm):
    """The cache never exceeds capacity; the least-recently-used entry
    is evicted first and re-misses on its next lookup."""
    svc = packed_dippm.serve(cache_size=4, max_wait_ms=2.0)
    try:
        svc.predict_many([_graph(6, seed=s) for s in range(6)])
        assert svc.stats.cache_entries == 4
        assert svc.stats.cache_misses == 6
        svc.predict_one(_graph(6, seed=5))    # newest: still cached
        assert svc.stats.cache_hits == 1
        svc.predict_one(_graph(6, seed=0))    # oldest: evicted, re-miss
        assert svc.stats.cache_misses == 7
    finally:
        svc.close()


def test_cache_meta_participates_in_key(packed_dippm):
    """Same topology but different graph meta must NOT collide (meta
    feeds the cost model's noise seeding downstream)."""
    g1 = _graph(8, seed=0)
    g2 = OpGraph(nodes=g1.nodes, edges=g1.edges, meta={"other": True})
    svc = packed_dippm.serve(max_wait_ms=2.0)
    try:
        svc.predict_one(g1)
        svc.predict_one(g2)
        assert svc.stats.cache_misses == 2 and svc.stats.cache_hits == 0
    finally:
        svc.close()


def test_cache_failed_leader_aborts_flight_and_next_retry_succeeds(
        packed_dippm, monkeypatch):
    """A leader whose bin fails must clear the in-flight slot: its
    followers reject with the same error, and the NEXT duplicate becomes
    a fresh leader that can succeed once the engine recovers.
    (quarantine_size=None: with quarantine on, the deterministic-failure
    retry would fast-fail at the door instead of re-reaching the engine
    — that path has its own test in test_lifecycle.py.)"""
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024,
                             quarantine_size=None)
    try:
        orig = svc.engine.run_bin
        state = {"fail": True}

        def flaky(chunk):
            if state["fail"]:
                raise RuntimeError("boom")
            return orig(chunk)

        monkeypatch.setattr(svc.engine, "run_bin", flaky)
        leader = svc.submit(_graph(9, seed=11))
        follower = svc.submit(_graph(9, seed=11))
        svc.flush()
        assert isinstance(leader.exception(timeout=30), RuntimeError)
        assert isinstance(follower.exception(timeout=30), RuntimeError)
        assert svc.stats.failed == 2
        state["fail"] = False
        retry = svc.submit(_graph(9, seed=11))  # fresh leader, not follower
        svc.flush()
        assert retry.result(timeout=30) is not None
        assert svc.stats.cache_misses == 2
    finally:
        svc.close()


def test_cache_disabled_with_none(packed_dippm):
    svc = packed_dippm.serve(cache_size=None, max_wait_ms=2.0)
    try:
        svc.predict_one(_graph(5, seed=0))
        svc.predict_one(_graph(5, seed=0))    # duplicate runs twice
        st = svc.stats
        assert st.cache_hits == 0 and st.cache_misses == 0
        assert st.batches == 2
    finally:
        svc.close()


# ---- load shedding ----------------------------------------------------------

def test_shed_oldest_evicts_stalest_request(packed_dippm):
    """shed_policy='oldest': at capacity the stalest waiting request is
    evicted (its future rejects with QueueFullError) and the newcomer is
    admitted — the opposite of the 'reject' door policy."""
    svc = packed_dippm.serve(max_wait_ms=30_000.0, max_batch_graphs=1024,
                             max_queue=2, shed_policy="oldest")
    try:
        f1 = svc.submit(_graph(5, seed=0))
        f2 = svc.submit(_graph(6, seed=1))
        f3 = svc.submit(_graph(7, seed=2))    # sheds f1, admits f3
        assert isinstance(f1.exception(timeout=5), QueueFullError)
        st = svc.stats
        assert st.shed_count == 1 and st.rejected == 0
        svc.flush()
        assert f2.result(timeout=30) and f3.result(timeout=30)
        # the shed request's cache flight was aborted: a duplicate of f1
        # becomes a fresh leader and succeeds
        retry = svc.submit(_graph(5, seed=0))
        svc.flush()
        assert retry.result(timeout=30) is not None
    finally:
        svc.close()


def test_shed_policy_validated(packed_dippm):
    with pytest.raises(ValueError, match="shed_policy"):
        packed_dippm.serve(shed_policy="drop-new")


# ---- replica fleet ----------------------------------------------------------

def _fleet_service(dippm, n_replicas=2, injectors=None, node_budget=256,
                   **serve_kw):
    from repro.core.engine import EngineConfig
    from repro.serve import ReplicaPool
    pool = ReplicaPool(dippm.params, dippm.cfg,
                       EngineConfig(node_budget=node_budget),
                       n_replicas=n_replicas, injectors=injectors)
    svc = PredictionService(engine=pool, serve_cfg=ServeConfig(
        node_budget=node_budget, **serve_kw))
    return pool, svc


def test_fleet_dispatches_bins_across_replicas(packed_dippm):
    """An atomic burst that plans into multiple bins spreads them over
    the replicas (least-loaded dispatch), and results are EXACTLY equal
    to the single-engine path — same plan, same jitted computations."""
    from repro.core.engine import EngineConfig
    graphs = [_graph(10 + (s % 13), seed=s) for s in range(30)]
    pool, svc = _fleet_service(packed_dippm, n_replicas=2)
    try:
        preds = svc.predict_many(graphs, timeout=120)
        st = svc.stats
        assert st.replicas == 2
        assert sum(st.replica_bins) == st.bins >= 2
        assert all(b > 0 for b in st.replica_bins)  # both participated
        eng = PredictionEngine(packed_dippm.params, packed_dippm.cfg,
                               EngineConfig(node_budget=256))
        ref_svc = PredictionService(engine=eng, serve_cfg=ServeConfig(
            node_budget=256))
        try:
            ref = ref_svc.predict_many(graphs, timeout=120)
        finally:
            ref_svc.close()
        for a, b in zip(preds, ref):
            np.testing.assert_array_equal(_pred_vec(a), _pred_vec(b))
    finally:
        svc.close()
        pool.close()


def test_fleet_replica_kill_mid_stream_no_lost_futures(packed_dippm):
    """Chaos drill: a FailureInjector kills replica 0 on its second bin
    dispatch while a Poisson stream is in flight. Every future must
    still resolve (requeued onto the survivor) and the numbers must
    match the single-engine reference."""
    from repro.runtime.fault import FailureInjector
    inj = {0: FailureInjector(fail_at_steps=[2])}
    pool, svc = _fleet_service(packed_dippm, n_replicas=2, injectors=inj,
                               max_wait_ms=2.0)
    graphs = [_graph(10 + (s % 13), seed=s) for s in range(40)]
    try:
        rng = np.random.default_rng(0)
        futs = []
        for g in graphs:                      # open-loop Poisson arrivals
            futs.append(svc.submit(g))
            time.sleep(float(rng.exponential(0.002)))
        svc.flush()
        preds = [f.result(timeout=120) for f in futs]
        assert all(p is not None for p in preds)
        assert inj[0].failures == 1
        assert pool.health == (False, True)
        st = svc.stats
        assert st.completed == len(graphs) and st.failed == 0
        assert st.requeues >= 1
        ref = [packed_dippm.predict_graph(g) for g in graphs]
        for a, b in zip(preds, ref):
            np.testing.assert_allclose(_pred_vec(a), _pred_vec(b),
                                       atol=1e-5, rtol=1e-5)
    finally:
        svc.close()
        pool.close()


def test_fleet_all_replicas_dead_rejects_not_hangs(packed_dippm):
    """When every replica has failed, pending futures reject with the
    underlying error — nothing blocks forever."""
    from repro.runtime.fault import FailureInjector
    inj = {0: FailureInjector(), 1: FailureInjector()}
    inj[0].fail_next(10)
    inj[1].fail_next(10)
    pool, svc = _fleet_service(packed_dippm, n_replicas=2, injectors=inj,
                               cache_size=None, max_wait_ms=30_000.0,
                               max_batch_graphs=1024)
    try:
        futs = svc.submit_many([_graph(8, seed=s) for s in range(5)])
        svc.flush()
        errs = [f.exception(timeout=60) for f in futs]
        assert all(isinstance(e, RuntimeError) for e in errs)
        assert svc.stats.failed == 5
        assert pool.n_healthy == 0
    finally:
        svc.close()
        pool.close()


def test_fleet_warmup_and_heartbeats(packed_dippm, tmp_path):
    """warmup() compiles every replica's ladder; completed bins beat
    per-replica heartbeat files an external supervisor can read."""
    from repro.core.engine import EngineConfig
    from repro.serve import ReplicaPool
    pool = ReplicaPool(packed_dippm.params, packed_dippm.cfg,
                       EngineConfig(node_budget=256), n_replicas=2,
                       heartbeat_dir=str(tmp_path))
    try:
        single = PredictionEngine(packed_dippm.params, packed_dippm.cfg,
                                  EngineConfig(node_budget=256))
        n_single = single.warmup()
        assert pool.warmup() == 2 * n_single
        svc = PredictionService(engine=pool, serve_cfg=ServeConfig(
            node_budget=256))
        try:
            svc.predict_many([_graph(10 + (s % 13), seed=s)
                              for s in range(30)], timeout=120)
        finally:
            svc.close()
        beats = pool._monitors[0].read_all()
        assert {b["replica"] for b in beats} == {0, 1}
        assert all(b["step"] >= 1 for b in beats)
    finally:
        pool.close()


def test_serve_config_replicas_builds_pool(packed_dippm):
    """ServeConfig(replicas=N) is the one-knob fleet entry point — the
    facade's serve() passes it straight through."""
    svc = packed_dippm.serve(replicas=2, node_budget=256)
    try:
        svc.predict_many([_graph(8, seed=s) for s in range(20)],
                         timeout=120)
        st = svc.stats
        assert st.replicas == 2 and sum(st.replica_bins) == st.bins
    finally:
        svc.close()             # service owns the pool: close() shuts it
        assert svc.engine._closed
