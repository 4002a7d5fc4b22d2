"""Training stack: sparse-until-collate storage, scan/eager equivalence,
grad clipping, data parallelism, checkpoint-resume, engine-backed eval."""
import dataclasses

import jax
import numpy as np
import pytest

from repro.core import PMGNSConfig, pmgns_init
from repro.core.batching import (GraphSample, collate, dense_adj, pad_sample,
                                 sample_from_graph, stack_epoch_segments)
from repro.core.gnn import decode_targets, pmgns_apply
from repro.core.ir import OpGraph, OpNode
from repro.dataset.builder import (DatasetRecord, records_to_samples,
                                   synthetic_samples as _synth_samples)
from repro.train.gnn_trainer import (TrainConfig, _fold_stats, _target_stats,
                                     predict_batch, train_pmgns)


def _graph(n_nodes, seed=0):
    rng = np.random.default_rng(seed)
    ops = ["dense", "conv", "relu", "add"]
    nodes = [OpNode(i, ops[i % len(ops)],
                    (int(rng.integers(1, 16)), int(rng.integers(1, 64))),
                    flops=float(rng.integers(1, 10_000)),
                    macs=float(rng.integers(1, 5_000)))
             for i in range(n_nodes)]
    edges = [(i, i + 1) for i in range(n_nodes - 1)]
    return OpGraph(nodes=nodes, edges=edges, meta={"n": n_nodes})


# ---- storage contract ------------------------------------------------------

def test_graph_sample_is_sparse_until_collate():
    s = _synth_samples(1)[0]
    field_names = {f.name for f in dataclasses.fields(GraphSample)}
    assert "adj" not in field_names and "edges" in field_names
    assert s.edges.ndim == 2 and s.edges.shape[1] == 2
    size = s.x.shape[0]
    # the adj property densifies on demand and matches the edge list
    a = s.adj
    assert a.shape == (size, size)
    assert a.sum() == len(np.unique(s.edges, axis=0))
    # collate materializes the same adjacency batched
    batch = collate([s, s])
    np.testing.assert_array_equal(batch["adj"][0], a)
    np.testing.assert_array_equal(batch["adj"][1], a)
    # host bytes carry no N² term: a 1024-bucket sample with few edges
    big = pad_sample(np.zeros((600, 32), np.float32),
                     np.asarray([(i, i + 1) for i in range(599)], np.int32),
                     np.zeros(5, np.float32), y=np.ones(3, np.float32))
    assert big.x.shape[0] == 1024
    assert big.nbytes < 0.1 * (1024 * 1024 * 4)


def test_pad_paths_unified():
    """sample_from_graph and records_to_samples share one pad path."""
    g = _graph(40, seed=3)
    from repro.core.node_features import node_feature_matrix
    from repro.core.static_features import static_features
    y = np.asarray([1.0, 2.0, 3.0], np.float32)
    via_graph = sample_from_graph(g, y=y)
    rec = DatasetRecord(
        x=node_feature_matrix(g),
        edges=np.asarray(g.edges, np.int32).reshape(-1, 2),
        static=static_features(g), y=y, family="t", n_nodes=g.num_nodes)
    via_record = records_to_samples([rec])[0]
    np.testing.assert_array_equal(via_graph.x, via_record.x)
    np.testing.assert_array_equal(via_graph.edges, via_record.edges)
    np.testing.assert_array_equal(via_graph.mask, via_record.mask)
    np.testing.assert_array_equal(via_graph.static, via_record.static)


def test_stack_epoch_segments_schedule():
    samples = _synth_samples(21, n_min=4, n_max=60)   # buckets 32 + 64
    segs = stack_epoch_segments(samples, batch_size=4, max_steps=2)
    # every real sample appears exactly once (wt bookkeeping)
    assert sum(float(s["wt"].sum()) for s in segs) == len(samples)
    for s in segs:
        S, B = s["wt"].shape
        assert S <= 2
        assert s["x"].shape[:2] == (S, B)
        assert s["adj"].shape == (S, B, s["x"].shape[2], s["x"].shape[2])
    # batch_multiple rounds B up for data-parallel sharding
    segs8 = stack_epoch_segments(samples, batch_size=3, batch_multiple=8)
    assert all(s["wt"].shape[1] % 8 == 0 for s in segs8)


# ---- scan trainer ----------------------------------------------------------

CFG = PMGNSConfig(hidden=32)


def test_scan_matches_eager_reference():
    """Fused lax.scan epochs reproduce the eager per-step loop."""
    samples = _synth_samples(24, seed=1)
    common = dict(epochs=2, batch_size=8, lr=3e-3, seed=0)
    p_scan, h_scan = train_pmgns(CFG, samples, (),
                                 TrainConfig(mode="scan", **common))
    p_eager, h_eager = train_pmgns(CFG, samples, (),
                                   TrainConfig(mode="eager", **common))
    for hs, he in zip(h_scan, h_eager):
        assert hs["steps"] == he["steps"]
        np.testing.assert_allclose(hs["train_loss"], he["train_loss"],
                                   rtol=1e-4)
    for ls, le in zip(jax.tree_util.tree_leaves(p_scan),
                      jax.tree_util.tree_leaves(p_eager)):
        np.testing.assert_allclose(np.asarray(ls), np.asarray(le),
                                   atol=1e-4, rtol=1e-3)


def test_padded_remainder_rows_are_noops():
    """A bucket whose count doesn't divide B trains identically to the
    same schedule seen by the eager path (weighted loss masks padding)."""
    samples = _synth_samples(13, seed=2)       # 13 % 8 != 0 → padded step
    common = dict(epochs=1, batch_size=8, lr=1e-3, seed=0)
    _, h_scan = train_pmgns(CFG, samples, (),
                            TrainConfig(mode="scan", **common))
    _, h_eager = train_pmgns(CFG, samples, (),
                             TrainConfig(mode="eager", **common))
    np.testing.assert_allclose(h_scan[0]["train_loss"],
                               h_eager[0]["train_loss"], rtol=1e-4)


def test_grad_clip_is_wired_through():
    """grad_clip must reach the optimizer: a near-zero clip norm freezes
    training on huge-gradient data, no clip moves params at lr scale."""
    samples = _synth_samples(8, seed=3, y_scale=1e8)
    common = dict(epochs=1, batch_size=8, lr=0.1, seed=0)
    p_clip, _ = train_pmgns(CFG, samples, (),
                            TrainConfig(grad_clip=1e-12, **common))
    p_free, _ = train_pmgns(CFG, samples, (),
                            TrainConfig(grad_clip=None, **common))
    t_mean, t_std = _target_stats(samples)
    key = jax.random.split(jax.random.PRNGKey(0))[1]
    p0 = _fold_stats(pmgns_init(key, CFG), CFG, t_mean, t_std)
    d_clip = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree_util.tree_leaves(p_clip),
                                 jax.tree_util.tree_leaves(p0)))
    d_free = max(float(np.max(np.abs(np.asarray(a) - np.asarray(b))))
                 for a, b in zip(jax.tree_util.tree_leaves(p_free),
                                 jax.tree_util.tree_leaves(p0)))
    assert d_clip < 1e-3                 # clipped: step magnitude ≈ 0
    assert d_free > 10 * max(d_clip, 1e-6)   # unclipped: full Adam step
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(p_free))


def test_data_parallel_runs_and_trains():
    """shard_map path (1..N devices) — same trainer, psum'd grads."""
    samples = _synth_samples(24, seed=4)
    params, hist = train_pmgns(
        CFG, samples, (),
        TrainConfig(epochs=3, batch_size=8, lr=3e-3, seed=0,
                    data_parallel=True))
    assert hist[-1]["train_loss"] < hist[0]["train_loss"]
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(params))


# ---- durability ------------------------------------------------------------

def test_checkpoint_resume_equivalence(tmp_path):
    """train 2N epochs straight == train N, checkpoint, restore, train N."""
    samples = _synth_samples(20, seed=5)
    val = _synth_samples(8, seed=6)
    ckpt = str(tmp_path / "ckpt")
    common = dict(batch_size=8, lr=3e-3, seed=0)
    p_straight, h_straight = train_pmgns(
        CFG, samples, val, TrainConfig(epochs=4, **common))
    _, h_first = train_pmgns(
        CFG, samples, val,
        TrainConfig(epochs=2, checkpoint_dir=ckpt, checkpoint_every=1,
                    **common))
    p_resumed, h_second = train_pmgns(
        CFG, samples, val, TrainConfig(epochs=4, **common),
        resume_from=ckpt)
    assert [h["epoch"] for h in h_second] == [2, 3]
    for ls, lr_ in zip(jax.tree_util.tree_leaves(p_straight),
                       jax.tree_util.tree_leaves(p_resumed)):
        np.testing.assert_allclose(np.asarray(ls), np.asarray(lr_),
                                   atol=1e-6, rtol=1e-6)
    np.testing.assert_allclose(h_second[-1]["val_mape"],
                               h_straight[-1]["val_mape"], rtol=1e-5)


def test_resume_at_completion_is_idempotent(tmp_path):
    """Relaunching a finished run returns params + a terminal record."""
    samples = _synth_samples(10, seed=9)
    val = _synth_samples(6, seed=10)
    ckpt = str(tmp_path / "ckpt")
    cfg = TrainConfig(epochs=2, batch_size=8, lr=1e-3,
                      checkpoint_dir=ckpt, checkpoint_every=1)
    train_pmgns(CFG, samples, val, cfg)
    params, hist = train_pmgns(CFG, samples, val, cfg, resume_from=ckpt)
    assert hist[-1].get("resumed_complete") is True
    assert np.isfinite(hist[-1]["val_mape"])
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(params))


def test_unknown_mode_and_eager_dp_raise():
    samples = _synth_samples(4, seed=11)
    with pytest.raises(ValueError, match="mode"):
        train_pmgns(CFG, samples, (), TrainConfig(epochs=1, mode="fused"))
    with pytest.raises(ValueError, match="data_parallel"):
        train_pmgns(CFG, samples, (),
                    TrainConfig(epochs=1, mode="eager", data_parallel=True))


def test_resume_from_empty_dir_starts_fresh(tmp_path):
    samples = _synth_samples(10, seed=7)
    params, hist = train_pmgns(
        CFG, samples, (), TrainConfig(epochs=1, batch_size=8, lr=1e-3),
        resume_from=str(tmp_path / "nothing-here"))
    assert [h["epoch"] for h in hist] == [0]


# ---- sparse message passing ------------------------------------------------

def test_sparse_scan_epoch_matches_dense():
    """A full scan-compiled epoch with layout="sparse" reproduces the dense
    path's loss trajectory and parameters (identical schedule, no [B,N,N]
    adjacency anywhere in the segments)."""
    samples = _synth_samples(24, seed=12)
    cfg_sparse = dataclasses.replace(CFG, layout="sparse")
    common = dict(epochs=2, batch_size=8, lr=3e-3, seed=0)
    p_dense, h_dense = train_pmgns(CFG, samples, (),
                                   TrainConfig(mode="scan", **common))
    p_sparse, h_sparse = train_pmgns(cfg_sparse, samples, (),
                                     TrainConfig(mode="scan", **common))
    for hd, hs in zip(h_dense, h_sparse):
        assert hd["steps"] == hs["steps"]
        np.testing.assert_allclose(hs["train_loss"], hd["train_loss"],
                                   rtol=1e-5)
    for ld, ls in zip(jax.tree_util.tree_leaves(p_dense),
                      jax.tree_util.tree_leaves(p_sparse)):
        np.testing.assert_allclose(np.asarray(ls), np.asarray(ld),
                                   atol=1e-4, rtol=1e-3)


def test_sparse_data_parallel_trains():
    """sparse segments shard over the batch axis like dense ones."""
    samples = _synth_samples(24, seed=13)
    cfg_sparse = dataclasses.replace(CFG, layout="sparse")
    params, hist = train_pmgns(
        cfg_sparse, samples, (),
        TrainConfig(epochs=3, batch_size=8, lr=3e-3, seed=0,
                    data_parallel=True))
    assert hist[-1]["train_loss"] < hist[0]["train_loss"]
    assert all(np.isfinite(np.asarray(l)).all()
               for l in jax.tree_util.tree_leaves(params))


def test_packed_scan_epoch_matches_sparse():
    """Packed [S, P, ...] segments keep the padded-sparse schedule, so a
    full scan epoch reproduces its loss trajectory and parameters.
    Dropout off: packed activations have different shapes, so train-mode
    dropout masks are drawn differently by construction."""
    samples = _synth_samples(24, seed=15)
    cfg_sparse = dataclasses.replace(CFG, layout="sparse", dropout=0.0)
    cfg_packed = dataclasses.replace(CFG, layout="packed", dropout=0.0)
    common = dict(epochs=2, batch_size=8, lr=3e-3, seed=0)
    p_s, h_s = train_pmgns(cfg_sparse, samples, (),
                           TrainConfig(mode="scan", **common))
    p_p, h_p = train_pmgns(cfg_packed, samples, (),
                           TrainConfig(mode="scan", **common))
    for hs, hp in zip(h_s, h_p):
        assert hs["steps"] == hp["steps"]
        np.testing.assert_allclose(hp["train_loss"], hs["train_loss"],
                                   rtol=1e-5)
    for ls, lp in zip(jax.tree_util.tree_leaves(p_s),
                      jax.tree_util.tree_leaves(p_p)):
        np.testing.assert_allclose(np.asarray(lp), np.asarray(ls),
                                   atol=1e-4, rtol=1e-3)


def test_packed_segments_layout():
    """Packed segments: flat [S, P, ...] arrays, per-step graph slots,
    every sample exactly once."""
    samples = _synth_samples(21, n_min=4, n_max=60, seed=16)
    segs = stack_epoch_segments(samples, batch_size=4, max_steps=2,
                                layout="packed")
    assert sum(float(s["wt"].sum()) for s in segs) == len(samples)
    for s in segs:
        S, P = s["x"].shape[:2]
        assert s["graph_ids"].shape == (S, P)
        assert s["mask"].shape == (S, P)
        assert s["edges"].ndim == 3 and s["edges"].shape[2] == 2
        assert s["static"].shape[:2] == s["wt"].shape
        # graph ids of real nodes stay inside the step's graph slots
        for si in range(S):
            live = s["graph_ids"][si][s["mask"][si] > 0]
            if live.size:
                assert live.max() < s["wt"].shape[1]


def test_packed_eval_and_predict_batch():
    samples = _synth_samples(10, seed=17)
    cfg_packed = dataclasses.replace(CFG, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), CFG)
    preds_d = predict_batch(params, CFG, samples)
    preds_p = predict_batch(params, cfg_packed, samples)
    np.testing.assert_allclose(preds_p, preds_d, atol=1e-4, rtol=1e-4)
    from repro.train.gnn_trainer import evaluate
    ev_d = evaluate(params, CFG, samples)
    ev_p = evaluate(params, cfg_packed, samples)
    np.testing.assert_allclose(ev_p["loss"], ev_d["loss"], rtol=1e-5)
    np.testing.assert_allclose(ev_p["mape"], ev_d["mape"], rtol=1e-4)
    assert ev_p["n"] == ev_d["n"]


def test_packed_data_parallel_raises():
    """The packed flat node axis cannot shard over the batch axis."""
    samples = _synth_samples(8, seed=18)
    cfg_packed = dataclasses.replace(CFG, layout="packed")
    with pytest.raises(ValueError, match="packed"):
        train_pmgns(cfg_packed, samples, (),
                    TrainConfig(epochs=1, data_parallel=True))


def test_sparse_eval_and_predict_batch():
    samples = _synth_samples(10, seed=14)
    cfg_sparse = dataclasses.replace(CFG, layout="sparse")
    params = pmgns_init(jax.random.PRNGKey(0), CFG)
    preds_d = predict_batch(params, CFG, samples)
    preds_s = predict_batch(params, cfg_sparse, samples)
    np.testing.assert_allclose(preds_s, preds_d, atol=1e-4, rtol=1e-4)
    from repro.train.gnn_trainer import evaluate
    ev_d = evaluate(params, CFG, samples)
    ev_s = evaluate(params, cfg_sparse, samples)
    np.testing.assert_allclose(ev_s["loss"], ev_d["loss"], rtol=1e-5)
    np.testing.assert_allclose(ev_s["mape"], ev_d["mape"], rtol=1e-4)


# ---- engine-backed eval ----------------------------------------------------

def test_predict_batch_routes_through_engine():
    samples = _synth_samples(9, seed=8)
    params = pmgns_init(jax.random.PRNGKey(0), CFG)
    preds = predict_batch(params, CFG, samples)
    assert preds.shape == (len(samples), 3)
    # reference: per-sample collate + apply + decode
    import jax.numpy as jnp
    for i, s in enumerate(samples):
        b = {k: jnp.asarray(v) for k, v in collate([s]).items() if k != "y"}
        ref = np.asarray(decode_targets(
            pmgns_apply(params, CFG, b, train=False)))[0]
        np.testing.assert_allclose(preds[i], ref, atol=1e-5, rtol=1e-5)
