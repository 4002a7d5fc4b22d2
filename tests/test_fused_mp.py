"""Fused message-passing megakernel vs composed oracles.

The fused kernel (``fused_mp_layer_pallas`` + its lax twin
``fused_mp_layer_ref``) collapses gather → edge-mask →
scatter-accumulate (→ mean) → combine → bias → activation → node-mask
into one call; ``fused_gat_aggregate_pallas`` does the GAT post-softmax
stage. All interpret-mode, so the file runs fully on the CPU CI runner.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batching import collate_packed
from repro.core.gnn import PMGNSConfig, pmgns_infer, pmgns_init
from repro.dataset.builder import synthetic_samples
from repro.kernels import ops, ref, segment_spmm

fused_gat_aggregate_pallas = partial(
    segment_spmm.fused_gat_aggregate_pallas, interpret=True)
fused_mp_layer_pallas = partial(segment_spmm.fused_mp_layer_pallas,
                                interpret=True)

RNG = np.random.default_rng(0)


def _packed_graph(p, q, seed=0, masked_tail=0.2):
    """A packed flat-axis graph: x [P,F], globally-offset edges [Q,2],
    masks with a padded tail."""
    rng = np.random.default_rng(seed)
    n_real = max(1, int(p * (1 - masked_tail)))
    x = rng.standard_normal((p, 16)).astype(np.float32)
    edges = rng.integers(0, n_real, (q, 2)).astype(np.int32) if q else \
        np.zeros((0, 2), np.int32)
    emask = np.zeros((q,), np.float32)
    emask[:max(1, q * 3 // 4)] = 1.0 if q else 0
    nmask = np.zeros((p,), np.float32)
    nmask[:n_real] = 1.0
    return (jnp.asarray(x), jnp.asarray(edges), jnp.asarray(emask),
            jnp.asarray(nmask))


def _weights(f, h, seed=0):
    rng = np.random.default_rng(seed + 100)
    return (jnp.asarray(rng.standard_normal((f, h)).astype(np.float32) * .1),
            jnp.asarray(rng.standard_normal((f, h)).astype(np.float32) * .1),
            jnp.asarray(rng.standard_normal((h,)).astype(np.float32) * .1))


# ---------------------------------------------------------------------------
# kernel vs lax reference
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("p,q", [(128, 128), (128, 129), (100, 50),
                                 (257, 300), (64, 0)])
@pytest.mark.parametrize("mode", ["sum", "mean"])
def test_fused_split_matches_ref(p, q, mode):
    x, edges, emask, nmask = _packed_graph(p, q)
    wn, ws, b = _weights(16, 24)
    kw = dict(w_neigh=wn, w_self=ws, bias=b, mode=mode, combine="split",
              act="relu")
    out = fused_mp_layer_pallas(x, edges, emask, nmask, **kw)
    exp = ref.fused_mp_layer_ref(x, edges, emask, nmask, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("act", ["relu", "none"])
@pytest.mark.parametrize("scale", ["vector", "scalar", None])
def test_fused_pre_combine_matches_ref(act, scale):
    p, q = 96, 140
    x, edges, emask, nmask = _packed_graph(p, q, seed=3)
    wn, _, b = _weights(16, 16)
    ss = {"vector": jnp.asarray(RNG.random(p).astype(np.float32)),
          "scalar": jnp.asarray(np.float32(1.37)),
          None: None}[scale]
    kw = dict(w_neigh=wn, bias=b, mode="sum", combine="pre",
              self_scale=ss, act=act)
    out = fused_mp_layer_pallas(x, edges, emask, nmask, **kw)
    exp = ref.fused_mp_layer_ref(x, edges, emask, nmask, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_fused_weighted_edges_no_node_mask():
    # GCN ships normalization weights through edge_mask; node_mask=None
    # (GIN's first stage) must skip the final masking entirely
    x, edges, emask, _ = _packed_graph(80, 200, seed=5)
    w = jnp.asarray(RNG.random(200).astype(np.float32))
    wn, ws, b = _weights(16, 16, seed=5)
    kw = dict(w_neigh=wn, w_self=ws, bias=b, mode="sum", combine="split",
              act="none")
    out = fused_mp_layer_pallas(x, edges, emask * w, None, **kw)
    exp = ref.fused_mp_layer_ref(x, edges, emask * w, None, **kw)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_fused_ref_matches_composed_pipeline():
    # the lax twin itself must equal the hand-composed op pipeline
    x, edges, emask, nmask = _packed_graph(64, 96, seed=7)
    wn, ws, b = _weights(16, 8, seed=7)
    agg = ref.segment_aggregate_ref(edges[None], emask[None], x[None],
                                    mode="mean")[0]
    exp = jax.nn.relu(x @ ws + agg @ wn + b) * nmask[:, None]
    out = ref.fused_mp_layer_ref(x, edges, emask, nmask, w_neigh=wn,
                                 w_self=ws, bias=b, mode="mean",
                                 combine="split", act="relu")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("p,q,h", [(64, 96, 4), (130, 257, 2)])
def test_fused_gat_aggregate_matches_ref(p, q, h):
    rng = np.random.default_rng(9)
    d = 16
    z = jnp.asarray(rng.standard_normal((p, d)).astype(np.float32))
    edges = jnp.asarray(rng.integers(0, p, (q, 2)).astype(np.int32))
    emask = jnp.asarray((rng.random(q) < 0.8).astype(np.float32))
    att = jnp.asarray(rng.random((q, h)).astype(np.float32))
    nmask = jnp.asarray((rng.random(p) < 0.9).astype(np.float32))
    out = fused_gat_aggregate_pallas(z, edges, emask, att, nmask)
    exp = ref.fused_gat_aggregate_ref(z, edges, emask, att, nmask)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_fused_dispatch_vmem_guard_falls_back_to_ref(monkeypatch):
    # a shape whose whole-[P, F] accumulator exceeds the VMEM limit
    # must dispatch to the reference path even under impl="pallas" —
    # and say so; every rung of the default ladder at paper width fits
    assert not ops.fused_fits(200_000, 64, 64, "mean")
    assert ops.fused_fits(4096, 512, 512, "mean")
    x, edges, emask, nmask = _packed_graph(64, 32)
    wn, ws, b = _weights(16, 8)
    out = ops.fused_mp_layer(x, edges, emask, nmask, w_neigh=wn, w_self=ws,
                             bias=b, impl="pallas")
    exp = ref.fused_mp_layer_ref(x, edges, emask, nmask, w_neigh=wn,
                                 w_self=ws, bias=b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)
    monkeypatch.setattr(ops, "fused_fits", lambda *a: False)
    with pytest.warns(UserWarning, match="lax reference"):
        out = ops.fused_mp_layer(x, edges, emask, nmask, w_neigh=wn,
                                 w_self=ws, bias=b, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# model level: fused stack vs composed stack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("variant", ["graphsage", "gcn", "gat", "gin",
                                     "mlp"])
def test_model_fused_matches_composed(variant):
    samples = synthetic_samples(10, seed=11, n_min=4, n_max=30)
    cfg_off = PMGNSConfig(variant=variant, hidden=32, layout="packed",
                          fused_mp="off")
    cfg_on = dataclasses.replace(cfg_off, fused_mp="on")
    params = pmgns_init(jax.random.PRNGKey(0), cfg_off)
    batch = {k: jnp.asarray(v) for k, v in collate_packed(samples).items()
             if k not in ("y", "wt")}
    y_off = np.asarray(pmgns_infer(params, cfg_off, batch))
    y_on = np.asarray(pmgns_infer(params, cfg_on, batch))
    np.testing.assert_allclose(y_on, y_off, atol=1e-5, rtol=1e-5)


def test_fused_cfg_resolution():
    assert PMGNSConfig(layout="packed").resolved_fused          # auto
    assert not PMGNSConfig(layout="packed",
                           fused_mp="off").resolved_fused
    assert not PMGNSConfig(layout="sparse").resolved_fused      # auto
    with pytest.raises(ValueError):
        PMGNSConfig(layout="sparse", fused_mp="on").resolved_fused
    with pytest.raises(ValueError):
        PMGNSConfig(layout="packed", fused_mp="maybe").resolved_fused


def test_fused_training_uses_composed_path():
    # train=True must never take the fused branch (dropout sits between
    # stages); fused on/off must therefore agree under train=True with
    # dropout=0 too
    samples = synthetic_samples(6, seed=13, n_min=4, n_max=20)
    cfg = PMGNSConfig(hidden=16, layout="packed", fused_mp="on",
                      dropout=0.0)
    params = pmgns_init(jax.random.PRNGKey(1), cfg)
    batch = {k: jnp.asarray(v) for k, v in collate_packed(samples).items()
             if k not in ("y", "wt")}
    from repro.core.gnn import pmgns_apply
    y_tr = pmgns_apply(params, cfg, batch, train=True,
                       rng=jax.random.PRNGKey(2))
    y_inf = pmgns_apply(params, cfg, batch, train=False)
    np.testing.assert_allclose(np.asarray(y_tr), np.asarray(y_inf),
                               atol=1e-5, rtol=1e-5)


def test_engine_counts_fused_dispatch(monkeypatch):
    """Every compiled packed shape adds its fused layers to EngineStats,
    split into kernel and segment path by the same VMEM guard the
    dispatcher applies; off a TPU nothing dispatches to Pallas."""
    from repro.core.engine import PredictionEngine
    from repro.core.gnn import fused_kernel_plan
    cfg = PMGNSConfig(hidden=512, layout="packed", use_pallas=True)
    assert fused_kernel_plan(cfg, 4096) == (0, 0)
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    assert fused_kernel_plan(cfg, 4096) == (3, 0)
    assert fused_kernel_plan(dataclasses.replace(cfg, use_pallas=False),
                             4096) == (0, 0)
    small = dataclasses.replace(cfg, hidden=16)
    eng = PredictionEngine(pmgns_init(jax.random.PRNGKey(0), small), small)
    assert eng.stats.kernel_impl == "pallas"
    eng._packed_fn(256, 416, 16)
    monkeypatch.setattr(ops, "fused_fits", lambda *a: False)
    eng._packed_fn(512, 832, 32)
    eng._packed_fn(512, 832, 32)                 # cached: not counted again
    assert (eng.stats.fused_kernel_layers,
            eng.stats.fused_segment_layers) == (3, 3)
    assert eng.layer_plans == {(256, 416, 16): (3, 0),
                               (512, 832, 32): (0, 3)}
