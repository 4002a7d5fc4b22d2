"""Sparse edge-list batch format: buckets, packing, envelope, segments."""
import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core.batching import (MIN_EDGE_BUCKET, collate, collate_packed,
                                 dense_adj, edge_bucket_for, edge_floor,
                                 max_batch_for_bucket, pack_edges,
                                 pack_graphs, packed_shape, pad_sample,
                                 stack_epoch_segments)
from repro.dataset.builder import synthetic_samples


def test_edge_bucket_for():
    assert edge_bucket_for(0) == MIN_EDGE_BUCKET
    assert edge_bucket_for(1) == MIN_EDGE_BUCKET
    assert edge_bucket_for(16) == 16
    assert edge_bucket_for(17) == 32
    assert edge_bucket_for(1000) == 1024


def test_storage_dedups_and_pack_edges_masks():
    """pad_sample canonicalizes edge lists (unique rows) so pack_edges
    is a straight copy; padding rows are (0, 0) with mask 0."""
    from repro.core.batching import pad_sample
    dup = pad_sample(np.zeros((6, 32), np.float32),
                     np.asarray([(0, 1), (0, 1), (2, 3), (0, 1)], np.int32),
                     np.zeros(5, np.float32))
    assert dup.n_edges == 2                       # duplicates collapsed
    samples = synthetic_samples(4, seed=0) + [dup]
    for s in samples:
        assert len(np.unique(s.edges, axis=0)) == s.n_edges
    edges, emask = pack_edges(samples)
    assert edges.shape[1] == emask.shape[1]
    assert edges.dtype == np.int32
    for i, s in enumerate(samples):
        assert emask[i].sum() == s.n_edges
        assert (emask[i][:s.n_edges] == 1.0).all()    # real edges first
        assert (edges[i][emask[i] == 0] == 0).all()   # padding is (0, 0)


def test_sparse_collate_matches_dense_adjacency():
    """Densifying the sparse batch's edge list reproduces collate's adj."""
    samples = synthetic_samples(6, seed=1)
    dense = collate(samples)
    sp = collate(samples, sparse=True)
    assert "adj" not in sp and "edges" in sp and "edge_mask" in sp
    assert sp["edges"].shape[1] == edge_bucket_for(
        max(s.n_edges for s in samples))
    size = samples[0].x.shape[0]
    for i in range(len(samples)):
        live = sp["edges"][i][sp["edge_mask"][i] > 0]
        np.testing.assert_array_equal(dense_adj(live, size),
                                      dense["adj"][i])
    np.testing.assert_array_equal(dense["x"], sp["x"])
    np.testing.assert_array_equal(dense["y"], sp["y"])


def test_sparse_envelope_allows_bigger_batches():
    """The sparse cap must not inherit the dense N² collapse: at N=512+
    the dense envelope quarters the batch, sparse keeps most of it."""
    for n in (512, 1024):
        dense_cap = max_batch_for_bucket(n, 64)
        sparse_cap = max_batch_for_bucket(n, 64, edges=2 * n)
        assert sparse_cap >= 2 * dense_cap
    # small buckets: both saturate at batch_size
    assert max_batch_for_bucket(32, 64, edges=64) == 64
    assert max_batch_for_bucket(256, 64, edges=512) == 64


def test_stack_epoch_segments_sparse_layout():
    samples = synthetic_samples(21, n_min=4, n_max=60, seed=2)
    segs_d = stack_epoch_segments(samples, batch_size=4, max_steps=2)
    segs_s = stack_epoch_segments(samples, batch_size=4, max_steps=2,
                                  sparse=True)
    assert len(segs_d) == len(segs_s)        # same schedule at small N
    assert sum(float(s["wt"].sum()) for s in segs_s) == len(samples)
    for sd, ss in zip(segs_d, segs_s):
        assert "adj" not in ss and ss["edges"].ndim == 4
        S, B, E, _ = ss["edges"].shape
        assert ss["edge_mask"].shape == (S, B, E)
        assert (S, B) == sd["wt"].shape
        np.testing.assert_array_equal(sd["x"], ss["x"])
        np.testing.assert_array_equal(sd["y"], ss["y"])
        # each step/row's edge list densifies to the dense segment's adj
        size = ss["x"].shape[2]
        for si in range(S):
            for bi in range(B):
                live = ss["edges"][si, bi][ss["edge_mask"][si, bi] > 0]
                np.testing.assert_array_equal(
                    dense_adj(live, size), sd["adj"][si, bi])


def test_pack_edges_rejects_overflow():
    samples = synthetic_samples(1, seed=3)
    with pytest.raises(ValueError, match="edge bucket"):
        pack_edges(samples, e_pad=1)


# ---- shared edge-density floor ---------------------------------------------

def test_edge_floor_is_shared_single_source():
    """The trainer derives per-node-bucket edge floors from ONE
    helper."""
    for n in (32, 64, 256, 1024):
        assert edge_floor(n) == edge_bucket_for(2 * n)
    # trainer segments apply the floor: sparse E never below it
    samples = synthetic_samples(9, n_min=4, n_max=20, seed=4)   # bucket 32
    seg = stack_epoch_segments(samples, batch_size=4, sparse=True)[0]
    assert seg["edges"].shape[2] >= edge_floor(32)


# ---- memoized dense adjacency ----------------------------------------------

def test_adj_is_memoized_per_sample():
    """Two accesses return the SAME buffer (no fresh [N, N] per touch)."""
    s = synthetic_samples(1, seed=5)[0]
    a1 = s.adj
    a2 = s.adj
    assert a1 is a2
    np.testing.assert_array_equal(a1, dense_adj(s.edges, s.x.shape[0]))


# ---- packed block-diagonal layout ------------------------------------------

def _empty_graph_sample():
    """A labeled sample with nodes but zero edges (E=0)."""
    return pad_sample(np.random.default_rng(0).standard_normal(
        (5, 32)).astype(np.float32),
        np.zeros((0, 2), np.int32), np.zeros(5, np.float32),
        y=np.ones(3, np.float32))


def _single_node_sample():
    return pad_sample(np.ones((1, 32), np.float32),
                      np.zeros((0, 2), np.int32), np.zeros(5, np.float32),
                      y=np.ones(3, np.float32))


def test_pack_graphs_partitions_all_indices():
    samples = synthetic_samples(23, n_min=4, n_max=200, seed=6)
    bins = pack_graphs(samples, node_budget=256)
    seen = sorted(i for b in bins for i in b)
    assert seen == list(range(len(samples)))
    for b in bins:
        assert sum(samples[i].n_nodes for i in b) <= 256 or len(b) == 1


def test_pack_graphs_respects_all_budgets():
    samples = synthetic_samples(30, n_min=8, n_max=30, seed=7)
    bins = pack_graphs(samples, node_budget=4096, edge_budget=8192,
                       graph_budget=4)
    assert all(len(b) <= 4 for b in bins)
    bins_e = pack_graphs(samples, node_budget=4096, edge_budget=32)
    for b in bins_e:
        assert (sum(samples[i].n_edges for i in b) <= 32 or len(b) == 1)


def test_collate_packed_layout_and_offsets():
    """Globally-offset edges densify back to each sample's adjacency."""
    samples = synthetic_samples(5, n_min=4, n_max=40, seed=8)
    b = collate_packed(samples)
    p = b["x"].shape[0]
    assert b["graph_ids"].shape == (p,) and b["mask"].shape == (p,)
    assert b["wt"].sum() == len(samples)
    off = 0
    for gi, s in enumerate(samples):
        n = s.n_nodes
        np.testing.assert_array_equal(b["x"][off:off + n], s.x[:n])
        assert (b["graph_ids"][off:off + n] == gi).all()
        live = b["edges"][b["edge_mask"] > 0]
        mine = live[(live[:, 0] >= off) & (live[:, 0] < off + n)] - off
        np.testing.assert_array_equal(
            dense_adj(mine, s.x.shape[0]), s.adj)
        off += n
    assert (b["mask"][off:] == 0).all()


def test_packed_edge_cases_empty_and_single_node():
    """E=0 graphs and 1-node graphs pack and predict finitely."""
    import jax
    from repro.core import PMGNSConfig, PredictionEngine, pmgns_init
    samples = [_empty_graph_sample(), _single_node_sample()] \
        + synthetic_samples(3, seed=9)
    bins = pack_graphs(samples, node_budget=512)
    assert sorted(i for b in bins for i in b) == list(range(5))
    cfg = PMGNSConfig(hidden=16, layout="packed")
    eng = PredictionEngine(pmgns_init(jax.random.PRNGKey(0), cfg), cfg)
    out = eng.predict_samples(samples)
    assert np.isfinite(out).all()


def test_packed_budget_boundary_graph():
    """A graph landing exactly on the node budget fills one bin alone;
    one node more forces escalation, never truncation."""
    rng = np.random.default_rng(10)
    exact = pad_sample(rng.standard_normal((32, 32)).astype(np.float32),
                       np.asarray([(i, i + 1) for i in range(31)], np.int32),
                       np.zeros(5, np.float32), y=np.ones(3, np.float32))
    assert exact.n_nodes == 32
    small = synthetic_samples(1, n_min=4, n_max=5, seed=11)
    bins = pack_graphs([exact] + small, node_budget=32)
    assert [0] in bins                      # boundary graph fills its bin
    p, _, _ = packed_shape([exact], node_budget=32)
    assert p == 32
    over = pad_sample(rng.standard_normal((33, 32)).astype(np.float32),
                      np.zeros((0, 2), np.int32), np.zeros(5, np.float32))
    p2, _, _ = packed_shape([over], node_budget=32)
    assert p2 >= 33                         # escalated, not truncated


@settings(deadline=None, max_examples=20)
@given(st.lists(st.integers(min_value=0, max_value=11), min_size=1,
                max_size=12, unique=True),
       st.sampled_from([128, 512, 4096]))
def test_pack_graphs_round_trips_predictions(order, node_budget):
    """Property: for ANY packing order/subset and budget, unpacked
    per-graph engine predictions match per-sample predict_graph."""
    import jax
    from repro.core import (EngineConfig, PMGNSConfig, PredictionEngine,
                            pmgns_init)
    all_samples = synthetic_samples(12, n_min=4, n_max=60, seed=12)
    samples = [all_samples[i] for i in order]
    cfg = PMGNSConfig(hidden=16, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    eng = PredictionEngine(params, cfg,
                           EngineConfig(node_budget=node_budget))
    got = eng.predict_samples(samples)
    # reference: each sample alone through the packed single path
    solo = PredictionEngine(params, cfg,
                            EngineConfig(node_budget=node_budget))
    for i, s in enumerate(samples):
        ref = solo.predict_samples([s])[0]
        np.testing.assert_allclose(got[i], ref, atol=1e-4, rtol=1e-4)
