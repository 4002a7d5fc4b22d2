"""Graphs over the top node bucket: whole in every prediction, cut where a
training layout pads to a bucket.

The engine runs such a graph as a lone packed bin at power-of-two
shapes, with each message-passing layer planned onto the fused kernel or
the lax segment composition by its VMEM state, whatever layout the model
was trained in; the dataset builder and the trainer cut it to the top
bucket, each as a step of its own.
"""
import dataclasses
import gzip
import json
import sys
import warnings
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PMGNSConfig, PredictionEngine, pmgns_init
from repro.core.batching import (DEFAULT_BUCKETS, cut_to_bucket, pad_sample,
                                 sample_from_graph)
from repro.core.gnn import (fused_kernel_plan, make_staged_packed_infer_fn,
                            packed_staging_layout)
from repro.core.ir import OpGraph, OpNode
from repro.kernels import ops

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
DSV2_POOL = BENCH / "pool" / "deepseek_v2_prefill.jsonl.gz"


def _chain(n, seed=0, extra=0, cut_every=0):
    """A DAG of ``n`` nodes: a chain (broken after every ``cut_every``-th
    node, if given) plus ``extra`` forward skip edges."""
    rng = np.random.default_rng(seed)
    ops_ = ["dense", "conv", "relu", "add"]
    nodes = [OpNode(i, ops_[i % 4], (int(rng.integers(1, 16)), 8),
                    flops=float(rng.integers(1, 10_000)),
                    macs=float(rng.integers(1, 5_000)))
             for i in range(n)]
    edges = [(i, i + 1) for i in range(n - 1)
             if not cut_every or (i + 1) % cut_every]
    for _ in range(extra):
        a = int(rng.integers(0, n - 2))
        edges.append((a, int(rng.integers(a + 1, n))))
    return OpGraph(nodes=nodes, edges=edges, meta={"batch": 2})


def _smallest_dsv2_doc():
    with gzip.open(DSV2_POOL, "rt") as f:
        docs = [json.loads(line) for line in f if line.strip()]
    return min(docs, key=lambda d: (len(d["nodes"]), d["meta"]["batch"]))


# -- whole graphs on the packed serving path ---------------------------------

@pytest.fixture(scope="module")
def reference():
    sys.path.insert(0, str(BENCH))
    import reference as ref_mod
    import weights as weights_mod
    return ref_mod, weights_mod


@pytest.mark.parametrize("variant", ["graphsage", "gcn"])
def test_service_serves_whole_dsv2_prefill_graph(reference, variant):
    """The seq-1,024 DeepSeek-V2 prefill document (9,381 nodes) through
    ``PredictionService.submit_json`` on the packed path equals the plain
    reference over the whole graph, and every node is served."""
    from repro.core.predictor import DIPPM
    ref_mod, weights_mod = reference
    doc = _smallest_dsv2_doc()
    model = {"variant": variant, "node_feat_dim": 32, "static_dim": 5,
             "hidden": 32, "n_gnn_blocks": 3, "n_fc_blocks": 3,
             "n_targets": 3, "readout": "mean_max"}
    params = weights_mod.make_params(11, model, last_scale=0.05)
    cfg = PMGNSConfig(**model, layout="packed", use_pallas=True)
    with DIPPM.from_params(params, cfg).serve(cache_size=None) as svc:
        pred = svc.submit_json(doc).result(120)
        stats = svc.engine.stats
    served = pred.serving
    assert served["nodes"] == len(doc["nodes"]) == 9381
    assert served["edges"] == len({tuple(e) for e in doc["edges"]})
    assert served["bin"] == (16384, 16384, 256)
    assert served["queue_wait_ms"] >= 0
    assert stats.lone_bins == 1
    with jax.default_matmul_precision("highest"):
        want = ref_mod.forward_log(params, variant,
                                   [ref_mod.featurise(doc)])
    got = np.array([[pred.latency_ms, pred.energy_j, pred.memory_mb]])
    assert ref_mod.served_gap(got, want).max() < 1.5e-5


def test_sample_from_graph_keeps_every_node():
    g = _chain(1500, extra=300)
    s = sample_from_graph(g)
    assert s.x.shape[0] == s.n_nodes == 1500
    assert s.mask.all()
    assert s.n_edges == len(set(g.edges))
    small = sample_from_graph(_chain(40))
    assert small.x.shape[0] == 64 and small.n_nodes == 40


def test_cut_to_bucket_keeps_the_heaviest_nodes():
    rng = np.random.default_rng(3)
    x = rng.standard_normal((1500, 32)).astype(np.float32)
    edges = np.stack([np.arange(1499), np.arange(1, 1500)], -1)
    whole = pad_sample(x, edges, np.zeros(5, np.float32))
    cut = cut_to_bucket(whole)
    keep = np.sort(np.argsort(-x[:, -1], kind="stable")[:1024])
    np.testing.assert_array_equal(cut.x, x[keep])
    assert cut.n_nodes == cut.x.shape[0] == DEFAULT_BUCKETS[-1]
    pos = {int(k): i for i, k in enumerate(keep)}
    want = sorted((pos[a], pos[b]) for a, b in edges
                  if a in pos and b in pos)
    assert [tuple(e) for e in cut.edges] == want
    fits = pad_sample(x[:100], edges[:99], np.zeros(5, np.float32))
    assert cut_to_bucket(fits) is fits


@pytest.mark.parametrize("layout", ["dense", "sparse"])
def test_bucketed_layout_model_serves_the_graph_whole(layout):
    """A model trained in a bucketed layout serves a 1,300-node graph
    whole: its answer equals a packed-layout engine's on the same params,
    differs from the answer on the graph cut to the top bucket, and
    ``DIPPM.predict_graph``'s serving record counts every node."""
    from repro.core.predictor import DIPPM
    cfg = PMGNSConfig(hidden=16, layout=layout)
    params = pmgns_init(jax.random.PRNGKey(4), cfg)
    eng = PredictionEngine(params, cfg)
    g = _chain(1300, seed=5, extra=200)
    whole = sample_from_graph(g)
    small = sample_from_graph(_chain(900, seed=6))
    bins = eng.plan_bins([whole, small])
    assert sorted(i for b in bins for i in b) == [0, 1]
    got = eng.predict_samples([whole, small])
    packed = PredictionEngine(params,
                              dataclasses.replace(cfg, layout="packed"))
    np.testing.assert_allclose(got, packed.predict_samples([whole, small]),
                               atol=1e-5, rtol=1e-5)
    cut = eng.predict_samples([cut_to_bucket(whole)])
    assert not np.allclose(got[:1], cut, atol=1e-3, rtol=1e-3)
    pred = DIPPM.from_params(params, cfg).predict_graph(g)
    assert pred.serving["nodes"] == 1300
    assert pred.serving["bin"] == eng.bin_shape([whole])
    np.testing.assert_allclose(
        [pred.latency_ms, pred.energy_j, pred.memory_mb], got[0],
        atol=1e-5, rtol=1e-5)


# -- the cut where a training layout needs it --------------------------------


def test_dataset_builder_cuts_at_the_top_bucket():
    from repro.dataset.builder import DatasetRecord, records_to_samples
    g = _chain(1100, seed=7, extra=50)
    s = sample_from_graph(g)
    rec = DatasetRecord(x=s.x, edges=s.edges, static=s.static,
                        y=np.ones(3, np.float32), family="chain",
                        n_nodes=1100, meta={})
    (out,) = records_to_samples([rec])
    want = cut_to_bucket(s)
    assert out.n_nodes == out.x.shape[0] == 1024
    np.testing.assert_array_equal(out.x, want.x)
    np.testing.assert_array_equal(out.edges, want.edges)


def test_trainer_cuts_its_inputs_at_the_top_bucket():
    from repro.train.gnn_trainer import TrainConfig, evaluate, train_pmgns
    big = sample_from_graph(_chain(1200, seed=8), y=np.ones(3))
    cfg = PMGNSConfig(hidden=8, layout="sparse")
    params, hist = train_pmgns(cfg, [big, big], [big],
                               TrainConfig(epochs=1, batch_size=4))
    assert len(hist) == 1 and np.isfinite(hist[0]["train_loss"])
    assert evaluate(params, cfg, [big]) == evaluate(params, cfg,
                                                    [cut_to_bucket(big)])


# -- lone bins past the fused kernel's VMEM reach ----------------------------

#: ``fused_fits`` of a GraphSAGE layer (mean mode, 512 outputs) by bin
#: size ``P`` and input width ``F``, and the plan of PMGNS's three layers
#: (``F`` = 32, 512, 512), GraphSAGE then GCN (sum mode, no degree).
FITS = {(4096, 32): True, (4096, 512): True, (8192, 32): True,
        (8192, 512): True, (16384, 32): True, (16384, 512): False,
        (32768, 32): False, (32768, 512): False}
PLANS = {4096: ((3, 0), (3, 0)), 8192: ((3, 0), (3, 0)),
         16384: ((1, 2), (3, 0)), 32768: ((0, 3), (1, 2))}


@pytest.mark.parametrize("p,f", sorted(FITS))
def test_fused_fits_at_lone_bin_sizes(p, f):
    assert ops.fused_fits(p, f, 512, "mean") is FITS[(p, f)]


@pytest.mark.parametrize("p", sorted(PLANS))
def test_fused_kernel_plan_at_lone_bin_sizes(monkeypatch, p):
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    sage, gcn = PLANS[p]
    cfg = PMGNSConfig(layout="packed", use_pallas=True)
    assert fused_kernel_plan(cfg, p) == sage
    assert fused_kernel_plan(dataclasses.replace(cfg, variant="gcn"),
                             p) == gcn


def test_planned_shape_traces_without_a_warning(monkeypatch):
    """A shape whose layers the plan sends to the segment composition is
    not a fallback: tracing it warns about nothing."""
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    cfg = PMGNSConfig(layout="packed", use_pallas=True)
    p, q, g = 32768, 32768, 256
    params = jax.eval_shape(lambda k: pmgns_init(k, cfg),
                            jax.random.PRNGKey(0))
    _, _, _, f_len, i_len = packed_staging_layout(cfg, p, q, g)
    fn = make_staged_packed_infer_fn(cfg, p, q, g)
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        jax.eval_shape(fn, params, jax.ShapeDtypeStruct((f_len,), jnp.float32),
                       jax.ShapeDtypeStruct((i_len,), jnp.int32))


def test_mixed_plan_lone_bin_equals_the_all_lax_composition(reference,
                                                            monkeypatch):
    """P = 16,384 at hidden 512: the first layer runs the fused kernel
    (interpret mode), the other two the segment composition; the bin's
    answer equals the all-lax composition's."""
    cfg = PMGNSConfig(layout="packed", use_pallas=True)
    model = {k: getattr(cfg, k) for k in (
        "variant", "node_feat_dim", "static_dim", "hidden", "n_gnn_blocks",
        "n_fc_blocks", "n_targets", "readout")}
    params = reference[1].make_params(9, model, last_scale=0.05)
    g = _chain(8300, seed=10, cut_every=64)       # 8,170 edges
    sample = sample_from_graph(g)
    lax_eng = PredictionEngine(params, cfg)
    want = lax_eng.run_bin([sample])
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    eng = PredictionEngine(params, cfg)
    got = eng.run_bin([sample])
    shape = eng.bin_shape([sample])
    assert shape == (16384, 8192, 256)
    assert eng.layer_plans == {shape: (1, 2)}
    assert lax_eng.layer_plans == {shape: (0, 0)}
    assert eng.stats.lone_bins == 1
    np.testing.assert_allclose(np.log1p(got), np.log1p(want), atol=2e-5)
