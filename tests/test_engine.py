"""Batched prediction engine: ordering, equivalence, cache, edge cases."""
import jax
import numpy as np
import pytest

from repro.core import (DIPPM, EngineConfig, PMGNSConfig, PredictionEngine,
                        pmgns_infer, pmgns_init)
from repro.core.batching import (bucket_for, collate, group_by_bucket,
                                 max_batch_for_bucket, next_pow2,
                                 packed_rung_ladder, sample_from_graph)
from repro.core.ir import OpGraph, OpNode


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


def _dense_reference(params, samples):
    """Dense-layout ``pmgns_apply`` (masked per-graph readout), one graph
    at a time: the reference the engine's packed bins are held to."""
    dense = PMGNSConfig(hidden=32, layout="dense")
    return np.concatenate([np.asarray(pmgns_infer(params, dense,
                                                  collate([s])))
                           for s in samples])


def _phys(pred):
    return [pred.latency_ms, pred.energy_j, pred.memory_mb]


@pytest.fixture(scope="module")
def dippm():
    cfg = PMGNSConfig(hidden=32)
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    return DIPPM.from_params(params, cfg)


# ---- bucketing utilities ---------------------------------------------------

def test_next_pow2():
    assert [next_pow2(n) for n in (1, 2, 3, 5, 8, 9, 64)] == \
        [1, 2, 4, 8, 8, 16, 64]


def test_max_batch_shrinks_with_bucket():
    caps = [max_batch_for_bucket(n, 64) for n in (32, 256, 512, 1024)]
    assert caps[0] == 64 and caps[1] == 64
    assert caps[1] > caps[2] > caps[3] >= 1


def test_group_by_bucket_preserves_order():
    samples = [sample_from_graph(_graph(n, i))
               for i, n in enumerate([5, 40, 7, 100, 9])]
    groups = group_by_bucket(samples)
    assert set(groups) == {32, 64, 128}
    assert groups[32] == [0, 2, 4]          # input order within the bucket
    assert groups[64] == [1] and groups[128] == [3]
    for size, members in groups.items():
        for i in members:
            assert bucket_for(samples[i].n_nodes) == size


# ---- engine behavior -------------------------------------------------------

def test_predict_many_matches_looped_predict_graph(dippm):
    """Core acceptance: batched vs one-at-a-time, same numbers, same
    order, and both equal dense ``pmgns_apply`` on the same params."""
    sizes = [3, 40, 100, 7, 60, 90, 12, 31, 33]   # spans 3 buckets, shuffled
    graphs = [_graph(n, seed=i) for i, n in enumerate(sizes)]
    loop = [dippm.predict_graph(g) for g in graphs]
    many = dippm.predict_many(graphs)
    ref = _dense_reference(dippm.params,
                           [sample_from_graph(g) for g in graphs])
    assert len(many) == len(graphs)
    for a, b, want in zip(loop, many, ref):
        np.testing.assert_allclose(_phys(b), _phys(a), atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(_phys(b), want, atol=1e-5, rtol=1e-5)
        assert b.mig == a.mig and b.tpu_slice == a.tpu_slice
        assert b.meta == a.meta              # order preserved across bins


def test_predictions_are_graph_specific(dippm):
    graphs = [_graph(20, seed=1), _graph(90, seed=2)]
    p1, p2 = dippm.predict_many(graphs)
    assert p1.latency_ms != p2.latency_ms


def test_compiled_fn_cache_reuse(dippm):
    eng = PredictionEngine(dippm.params, dippm.cfg)
    graphs = [_graph(10, seed=i) for i in range(4)]
    eng.predict_graphs(graphs)               # 4 graphs → one bin, P = 256
    assert eng.stats.cache_misses == 1
    assert eng.stats.cache_hits == 0
    eng.predict_graphs(graphs)               # same shape → pure cache hit
    assert eng.stats.cache_misses == 1
    assert eng.stats.cache_hits == 1
    eng.predict_graphs([_graph(50, seed=9)])  # another size, same rung → hit
    assert eng.stats.cache_misses == 1
    assert eng.stats.cache_hits == 2
    eng.predict_graphs([_graph(300, seed=9)])  # past the rung, P = 512: miss
    assert eng.stats.cache_misses == 2
    assert eng.stats.graphs_predicted == 10


def test_empty_and_single_graph(dippm):
    assert dippm.predict_many([]) == []
    single = dippm.predict_many([_graph(6, seed=3)])
    ref = dippm.predict_graph(_graph(6, seed=3))
    assert len(single) == 1
    np.testing.assert_allclose(single[0].latency_ms, ref.latency_ms,
                               atol=1e-5, rtol=1e-5)


def test_batch_padding_rows_do_not_leak(dippm):
    """A bin of 3 graphs leaves 13 of its 16 graph slots (and most node
    rows) as padding; the padding, and a 4th graph filling one of those
    slots, must not perturb the real predictions."""
    eng = PredictionEngine(dippm.params, dippm.cfg)
    graphs = [_graph(12, seed=i) for i in range(3)]
    out3 = eng.predict_graphs(graphs)
    out4 = eng.predict_graphs(graphs + [_graph(12, seed=7)])[:3]
    for a, b in zip(out3, out4):
        np.testing.assert_allclose(a.latency_ms, b.latency_ms,
                                   atol=1e-5, rtol=1e-5)


def test_memory_envelope_splits_large_buckets(dippm):
    """With a tiny node budget (32 nodes, 2 graphs a bin) the engine
    must split the graphs over several bins, still in order."""
    eng = PredictionEngine(dippm.params, dippm.cfg,
                           EngineConfig(node_budget=32))
    graphs = [_graph(10, seed=i) for i in range(5)]
    out = eng.predict_graphs(graphs)
    assert eng.stats.batches_run == 3        # 2 + 2 + 1
    ref = [dippm.predict_graph(g) for g in graphs]
    for a, b in zip(ref, out):
        np.testing.assert_allclose(a.latency_ms, b.latency_ms,
                                   atol=1e-5, rtol=1e-5)


def test_warmup_precompiles(dippm):
    eng = PredictionEngine(dippm.params, dippm.cfg)
    n = eng.warmup(rungs=(256, 512))
    assert n == 2
    eng.predict_graphs([_graph(10, seed=i) for i in range(16)])  # P = 256
    eng.predict_graphs([_graph(300, seed=0)])                    # P = 512
    assert eng.stats.cache_misses == 2       # all served from warmup


def test_predict_zoo_grid(dippm):
    from repro.zoo.families import variant_grid
    grid = variant_grid("mobilenet", {"width": [0.35, 0.5],
                                      "batch": [1], "res": [128]})
    assert len(grid) == 2
    out = dippm.predict_zoo("mobilenet", grid)
    assert [c for c, _ in out] == grid
    for _, p in out:
        assert np.isfinite(p.latency_ms)


def test_extended_static_mismatch_raises(dippm):
    """extended_static=True produces 8-dim F_s; a static_dim=5 model must
    be rejected at construction, not with a shape error mid-jit."""
    with pytest.raises(ValueError, match="static"):
        PredictionEngine(dippm.params, dippm.cfg,
                         EngineConfig(extended_static=True))


def test_variant_grid_unknown_family():
    from repro.zoo.families import variant_grid
    with pytest.raises(KeyError):
        variant_grid("nope", {"batch": [1]})


# ---- one packed path, whatever the training layout -------------------------

@pytest.mark.parametrize("layout", ["dense", "sparse", "packed"])
def test_engine_serves_packed_bins_whatever_the_layout(dippm, layout):
    """A model trained in any layout is served on packed bins: its
    predictions equal dense ``pmgns_apply`` on the same params, and every
    shape it compiles is a rung of the packed ladder, all of which
    ``warmup(rungs="all")`` compiles before traffic."""
    eng = PredictionEngine(dippm.params, PMGNSConfig(hidden=32,
                                                     layout=layout))
    assert eng.cfg.resolved_layout == "packed"
    ladder = packed_rung_ladder(eng.engine_cfg.node_budget)
    assert eng.warmup(rungs="all") == len(ladder)
    sizes = [3, 40, 100, 7, 60, 90, 12]
    samples = [sample_from_graph(_graph(n, seed=i))
               for i, n in enumerate(sizes)]
    np.testing.assert_allclose(eng.predict_samples(samples),
                               _dense_reference(dippm.params, samples),
                               atol=1e-5, rtol=1e-5)
    assert eng.stats.cache_misses == len(ladder)
    assert set(eng.layer_plans) == set(ladder)


def test_packed_engine_matches_dense(dippm):
    """Packed engine: same predictions as dense ``pmgns_apply``, same
    order, one flat node axis."""
    cfg_p = PMGNSConfig(hidden=32, layout="packed")
    eng_p = PredictionEngine(dippm.params, cfg_p)
    sizes = [3, 40, 100, 7, 60, 90, 12]
    graphs = [_graph(n, seed=i) for i, n in enumerate(sizes)]
    ref = _dense_reference(dippm.params,
                           [sample_from_graph(g) for g in graphs])
    packed_out = eng_p.predict_graphs(graphs)
    for g, want, b in zip(graphs, ref, packed_out):
        np.testing.assert_allclose(_phys(b), want, atol=1e-5, rtol=1e-5)
        assert b.meta == g.meta


def test_packed_engine_single_compiled_shape(dippm):
    """Mixed node sizes across five node buckets all land on ONE packed
    budget shape."""
    cfg_p = PMGNSConfig(hidden=32, layout="packed")
    eng = PredictionEngine(dippm.params, cfg_p)
    sizes = [3, 40, 100, 7, 60, 90, 12, 31, 33, 200, 500]   # 5 buckets
    eng.predict_graphs([_graph(n, seed=i) for i, n in enumerate(sizes)])
    assert eng.stats.cache_entries == 1
    assert eng.stats.recompiles == 1
    eng.predict_graphs([_graph(55, seed=77)])   # small request → lower rung
    assert eng.stats.cache_entries == 2


def test_packed_warmup_precompiles(dippm):
    cfg_p = PMGNSConfig(hidden=32, layout="packed")
    eng = PredictionEngine(dippm.params, cfg_p)
    assert eng.warmup() == 1
    assert eng.stats.cache_entries == 1


def test_engine_stats_padding_waste(dippm):
    """Packed waste must undercut padding each graph to its node bucket
    on mixed sizes, and a dense-trained model's engine exposes the same
    counters the benchmark prints (one path)."""
    cfg_p = PMGNSConfig(hidden=32, layout="packed")
    eng_p = PredictionEngine(dippm.params, cfg_p,
                             EngineConfig(node_budget=512))
    eng_d = PredictionEngine(dippm.params, dippm.cfg,
                             EngineConfig(node_budget=512))
    sizes = [33, 33, 70, 70, 140, 9, 9, 9]
    graphs = [_graph(n, seed=i) for i, n in enumerate(sizes)]
    eng_p.predict_graphs(graphs)
    eng_d.predict_graphs(graphs)
    bucketed = 1.0 - sum(sizes) / sum(bucket_for(n) for n in sizes)
    assert 0.0 < eng_p.stats.padding_waste_frac < bucketed
    assert eng_p.stats.node_slots_real == sum(sizes)
    assert (eng_d.stats.node_slots_total, eng_d.stats.node_slots_real) == \
        (eng_p.stats.node_slots_total, eng_p.stats.node_slots_real)


def test_plan_bins_partition_and_run_bin(dippm):
    """plan_bins covers every index exactly once; run_bin on the planned
    bins reproduces predict_samples (the serving micro-batcher's path)."""
    from repro.core.batching import sample_from_graph
    for cfg in (dippm.cfg, PMGNSConfig(hidden=32, layout="packed")):
        eng = PredictionEngine(dippm.params, cfg)
        samples = [sample_from_graph(_graph(n, seed=i))
                   for i, n in enumerate([3, 40, 100, 7, 60, 90, 12])]
        bins = eng.plan_bins(samples)
        flat = sorted(i for b in bins for i in b)
        assert flat == list(range(len(samples)))
        out = np.zeros((len(samples), 3), np.float32)
        for idx in bins:
            out[idx] = eng.run_bin([samples[j] for j in idx])
        ref = PredictionEngine(dippm.params, cfg).predict_samples(samples)
        np.testing.assert_allclose(out, ref, atol=1e-6, rtol=1e-6)
        assert eng.stats.graphs_predicted == len(samples)


def test_run_bin_threadsafe_concurrent_callers(dippm):
    """N threads hammering one engine's run_bin: stats stay consistent
    and every result matches the single-threaded reference."""
    import threading
    from repro.core.batching import sample_from_graph
    cfg = PMGNSConfig(hidden=32, layout="packed")
    eng = PredictionEngine(dippm.params, cfg)
    samples = [sample_from_graph(_graph(10 + i, seed=i)) for i in range(16)]
    ref = PredictionEngine(dippm.params, cfg).predict_samples(samples)
    results = [None] * len(samples)

    def worker(tid):
        for k in range(tid, len(samples), 4):
            results[k] = eng.run_bin([samples[k]])[0]

    threads = [threading.Thread(target=worker, args=(t,)) for t in range(4)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert eng.stats.graphs_predicted == len(samples)
    assert eng.stats.batches_run == len(samples)
    for k in range(len(samples)):
        np.testing.assert_allclose(results[k], ref[k], atol=1e-5, rtol=1e-5)


def test_predict_many_return_stats(dippm):
    graphs = [_graph(10, seed=i) for i in range(3)]
    preds, stats = dippm.predict_many(graphs, return_stats=True)
    assert len(preds) == 3
    assert stats.graphs_predicted >= 3
    assert stats.cache_entries >= 1
    assert 0.0 <= stats.padding_waste_frac < 1.0
    # the snapshot is detached: later traffic doesn't mutate it
    before = stats.graphs_predicted
    dippm.predict_many(graphs)
    assert stats.graphs_predicted == before
