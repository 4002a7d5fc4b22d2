"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps (interpret mode).

Every kernel here runs with ``interpret=True`` — the entry points compile
for the TPU by default — so the whole file executes, not skips, on a
CPU-only runner.
"""
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention_pallas as _fa
from repro.kernels.sage_spmm import dense_aggregate_pallas as _dense
from repro.kernels.sage_spmm import sage_aggregate_pallas as _sage
from repro.kernels.segment_spmm import edge_softmax_pallas as _softmax
from repro.kernels.segment_spmm import segment_aggregate_pallas as _agg
from repro.kernels.segment_spmm import segment_readout_pallas as _readout
from repro.kernels.segment_spmm import segment_scatter_pallas as _scatter
from repro.kernels.ssd_scan import ssd_scan_pallas as _ssd

flash_attention_pallas = partial(_fa, interpret=True)
dense_aggregate_pallas = partial(_dense, interpret=True)
sage_aggregate_pallas = partial(_sage, interpret=True)
edge_softmax_pallas = partial(_softmax, interpret=True)
segment_aggregate_pallas = partial(_agg, interpret=True)
segment_readout_pallas = partial(_readout, interpret=True)
segment_scatter_pallas = partial(_scatter, interpret=True)
ssd_scan_pallas = partial(_ssd, interpret=True)

RNG = np.random.default_rng(0)


def _edge_batch(b, n, e_per_graph, seed=0):
    """Ragged edge lists padded to a common E with mask — the sparse
    batch contract (padding rows are (0,0) with mask 0)."""
    rng = np.random.default_rng(seed)
    e_pad = max(max(e_per_graph, default=1), 1)
    edges = np.zeros((b, e_pad, 2), np.int32)
    emask = np.zeros((b, e_pad), np.float32)
    for i, e in enumerate(e_per_graph):
        if e:
            edges[i, :e] = rng.integers(0, n, (e, 2))
            emask[i, :e] = 1.0
    return jnp.asarray(edges), jnp.asarray(emask)


# ---------------------------------------------------------------------------
# sage_spmm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,f,density", [(33, 17, 0.1), (128, 32, 0.05),
                                         (200, 33, 0.2), (64, 64, 0.0)])
def test_sage_matches_ref(n, f, density):
    adj = (RNG.random((2, n, n)) < density).astype(np.float32)
    h = RNG.standard_normal((2, n, f)).astype(np.float32)
    out = sage_aggregate_pallas(jnp.asarray(adj), jnp.asarray(h))
    exp = ref.sage_aggregate_ref(jnp.asarray(adj), jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_sage_isolated_nodes_zero():
    adj = np.zeros((1, 16, 16), np.float32)
    h = RNG.standard_normal((1, 16, 8)).astype(np.float32)
    out = sage_aggregate_pallas(jnp.asarray(adj), jnp.asarray(h))
    np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


def test_dense_aggregate_sum_mode_matches_ref():
    adj = (RNG.random((2, 48, 48)) < 0.1).astype(np.float32)
    h = RNG.standard_normal((2, 48, 24)).astype(np.float32)
    out = dense_aggregate_pallas(jnp.asarray(adj), jnp.asarray(h),
                                 mode="sum")
    exp = ref.dense_aggregate_ref(jnp.asarray(adj), jnp.asarray(h),
                                  mode="sum")
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# segment_spmm: sparse edge-list aggregation
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["sum", "mean"])
@pytest.mark.parametrize("n,f,e_per_graph", [
    (16, 8, [5, 13, 0]),          # ragged counts incl. an empty graph
    (33, 17, [40, 7, 29]),        # nothing aligned to tile sizes
    (200, 33, [150, 380, 1]),     # multiple node tiles
    (1024, 8, [2048, 100, 0]),    # the largest node bucket, E = 2N
])
def test_segment_aggregate_matches_ref(mode, n, f, e_per_graph):
    b = len(e_per_graph)
    edges, emask = _edge_batch(b, n, e_per_graph, seed=n)
    h = jnp.asarray(RNG.standard_normal((b, n, f)).astype(np.float32))
    out = segment_aggregate_pallas(edges, emask, h, mode=mode)
    exp = ref.segment_aggregate_ref(edges, emask, h, mode=mode)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_segment_aggregate_matches_dense_path():
    """Sparse aggregation over an edge list == dense aggregation over its
    densified adjacency — the cross-layout contract the GNN relies on."""
    n, f = 40, 16
    edges, emask = _edge_batch(2, n, [60, 31], seed=7)
    # dedup: dense adjacency collapses duplicates by assignment
    adj = np.zeros((2, n, n), np.float32)
    for bi in range(2):
        for (s, d), m in zip(np.asarray(edges[bi]), np.asarray(emask[bi])):
            if m:
                adj[bi, d, s] = 1.0
    uniq_edges, uniq_mask = [], []
    for bi in range(2):
        live = np.asarray(edges[bi])[np.asarray(emask[bi]) > 0]
        u = np.unique(live, axis=0)
        uniq_edges.append(np.pad(u, ((0, 64 - len(u)), (0, 0))))
        uniq_mask.append(np.pad(np.ones(len(u), np.float32),
                                (0, 64 - len(u))))
    edges_u = jnp.asarray(np.stack(uniq_edges).astype(np.int32))
    emask_u = jnp.asarray(np.stack(uniq_mask))
    h = jnp.asarray(RNG.standard_normal((2, n, f)).astype(np.float32))
    for mode in ("sum", "mean"):
        sp = segment_aggregate_pallas(edges_u, emask_u, h, mode=mode)
        de = ref.dense_aggregate_ref(jnp.asarray(adj), h, mode=mode)
        np.testing.assert_allclose(np.asarray(sp), np.asarray(de),
                                   atol=1e-5, rtol=1e-5)


def test_segment_scatter_matches_ref():
    n, e, f = 50, 70, 12
    edges, emask = _edge_batch(2, n, [70, 33], seed=3)
    dst = edges[..., 1]
    msgs = jnp.asarray(RNG.standard_normal((2, e, f)).astype(np.float32))
    out = segment_scatter_pallas(dst, emask, msgs, n)
    exp = ref.segment_scatter_ref(dst, emask, msgs, n)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_segment_isolated_nodes_zero():
    """Nodes with no incoming edges aggregate to exactly 0 (sum and mean)."""
    edges, emask = _edge_batch(1, 16, [0])
    h = jnp.asarray(RNG.standard_normal((1, 16, 8)).astype(np.float32))
    for mode in ("sum", "mean"):
        out = segment_aggregate_pallas(edges, emask, h, mode=mode)
        np.testing.assert_allclose(np.asarray(out), 0.0, atol=1e-6)


# ---------------------------------------------------------------------------
# segment_spmm: fused segment-mean/max graph readout (packed layout)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["mean", "mean_max"])
@pytest.mark.parametrize("p,f,g", [
    (33, 17, 3),              # nothing tile-aligned
    (300, 32, 7),             # multiple node tiles
    (4096, 64, 256),          # the default engine budget shape
])
def test_segment_readout_matches_ref(kind, p, f, g):
    rng = np.random.default_rng(p)
    gid = np.sort(rng.integers(0, g, p)).astype(np.int32)
    w = (rng.random(p) < 0.8).astype(np.float32)
    h = rng.standard_normal((p, f)).astype(np.float32)
    out = segment_readout_pallas(jnp.asarray(h), jnp.asarray(gid),
                                 jnp.asarray(w), g, kind=kind)
    exp = ref.segment_readout_ref(jnp.asarray(h), jnp.asarray(gid),
                                  jnp.asarray(w), g, kind=kind)
    assert out.shape == (g, f if kind == "mean" else 2 * f)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_segment_readout_empty_graph_slots_are_zero():
    """Padded graph slots (no real nodes) read out exact zeros — the
    guard that keeps them wt-maskable, never -inf/NaN."""
    rng = np.random.default_rng(1)
    p, f, g = 64, 8, 5
    gid = np.clip(np.sort(rng.integers(0, 3, p)), 0, 2).astype(np.int32)
    w = np.ones(p, np.float32)
    w[gid == 1] = 0.0                     # graph 1: all nodes masked
    h = rng.standard_normal((p, f)).astype(np.float32)
    for fn in (segment_readout_pallas, ref.segment_readout_ref):
        out = np.asarray(fn(jnp.asarray(h), jnp.asarray(gid),
                            jnp.asarray(w), g))
        assert np.isfinite(out).all()
        np.testing.assert_allclose(out[1], 0.0, atol=0)   # masked graph
        np.testing.assert_allclose(out[3:], 0.0, atol=0)  # empty slots


def test_segment_readout_matches_masked_pooling():
    """The packed readout equals the padded layouts' per-graph masked
    mean/max pooling — the cross-layout contract pmgns_apply relies on."""
    rng = np.random.default_rng(2)
    n, f, b = 24, 16, 3
    h_b = rng.standard_normal((b, n, f)).astype(np.float32)
    mask_b = np.zeros((b, n), np.float32)
    counts = [24, 10, 1]
    for i, c in enumerate(counts):
        mask_b[i, :c] = 1.0
    # flatten the real rows
    h_flat = np.concatenate([h_b[i, :c] for i, c in enumerate(counts)])
    gid = np.concatenate([np.full(c, i, np.int32)
                          for i, c in enumerate(counts)])
    w = np.ones(len(gid), np.float32)
    from repro.core.gnn import _readout
    exp = np.asarray(_readout(jnp.asarray(h_b), jnp.asarray(mask_b),
                              "mean_max"))
    for fn in (segment_readout_pallas, ref.segment_readout_ref):
        out = np.asarray(fn(jnp.asarray(h_flat), jnp.asarray(gid),
                            jnp.asarray(w), b))
        np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)


# ---------------------------------------------------------------------------
# segment_spmm: edge softmax (GAT)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n,h_heads,e_per_graph", [
    (16, 2, [5, 13, 0]),
    (33, 4, [40, 7, 29]),
    (200, 4, [150, 380, 1]),
    (1024, 4, [2048, 100, 0]),    # largest bucket
])
def test_edge_softmax_matches_ref(n, h_heads, e_per_graph):
    b = len(e_per_graph)
    edges, emask = _edge_batch(b, n, e_per_graph, seed=n + 1)
    e_pad = edges.shape[1]
    s = jnp.asarray(
        RNG.standard_normal((b, e_pad, h_heads)).astype(np.float32) * 3)
    out = edge_softmax_pallas(s, edges[..., 1], emask, n)
    exp = ref.edge_softmax_ref(s, edges[..., 1], emask, n)
    assert bool(jnp.isfinite(out).all())
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)


def test_edge_softmax_sums_to_one_per_destination():
    edges, emask = _edge_batch(1, 24, [40], seed=9)
    s = jnp.asarray(RNG.standard_normal((1, 40, 2)).astype(np.float32))
    att = edge_softmax_pallas(s, edges[..., 1], emask, 24)
    sums = ref.segment_scatter_ref(edges[..., 1], emask,
                                   jnp.asarray(att), 24)
    live = np.asarray(ref.segment_degree_ref(edges, emask, 24)) > 0
    np.testing.assert_allclose(np.asarray(sums)[live], 1.0,
                               atol=1e-5, rtol=1e-5)


def test_edge_softmax_padded_edge_with_huge_score_no_overflow():
    """A padding edge's raw score is excluded from the max pass; if the
    normalize pass exponentiates it unmasked, exp overflows to inf and
    inf·0 = NaN. Regression for the masked-before-exp contract."""
    edges = jnp.asarray([[[1, 0], [2, 0], [3, 0]]], jnp.int32)
    emask = jnp.asarray([[1.0, 1.0, 0.0]], jnp.float32)
    # real edges score ~-100, the padded edge +100: gap ≫ exp overflow
    s = jnp.asarray([[[-100.0], [-101.0], [100.0]]], jnp.float32)
    for fn in (edge_softmax_pallas, ref.edge_softmax_ref):
        att = fn(s, edges[..., 1], emask, 4)
        assert bool(jnp.isfinite(att).all())
        np.testing.assert_allclose(np.asarray(att[0, :2, 0]).sum(), 1.0,
                                   atol=1e-5)
        assert float(att[0, 2, 0]) == 0.0


def test_edge_softmax_empty_neighborhood_is_zero_not_nan():
    """All-masked destinations (and fully empty graphs) must produce
    exact zeros through the masked-denominator guard — never NaN."""
    edges = jnp.zeros((1, 8, 2), jnp.int32)
    emask = jnp.zeros((1, 8), jnp.float32)
    s = jnp.asarray(RNG.standard_normal((1, 8, 4)).astype(np.float32))
    for fn in (edge_softmax_pallas, ref.edge_softmax_ref):
        att = fn(s, edges[..., 1], emask, 8)
        assert bool(jnp.isfinite(att).all())
        np.testing.assert_allclose(np.asarray(att), 0.0, atol=0)


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sq,skv,causal,window,qoff,dtype", [
    (128, 128, True, 0, 0, np.float32),
    (96, 96, False, 0, 0, np.float32),
    (128, 128, True, 32, 0, np.float32),
    (1, 256, False, 0, 255, np.float32),      # decode
    (128, 128, True, 0, 0, jnp.bfloat16),
])
def test_flash_matches_ref(sq, skv, causal, window, qoff, dtype):
    q = jnp.asarray(RNG.standard_normal((1, 2, sq, 64)), dtype)
    k = jnp.asarray(RNG.standard_normal((1, 2, skv, 64)), dtype)
    v = jnp.asarray(RNG.standard_normal((1, 2, skv, 64)), dtype)
    out = flash_attention_pallas(q, k, v, causal=causal, window=window,
                                 q_offset=qoff, bq=64, bk=64)
    exp = ref.attention_ref(q, k, v, causal=causal, window=window,
                            q_offset=qoff)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(exp, np.float32),
        atol=tol, rtol=tol)


def test_flash_nonaligned_head_dim():
    # head_dim 80 (hubert/zamba) exercises the pad-to-128 path
    q = jnp.asarray(RNG.standard_normal((1, 2, 64, 80)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((1, 2, 64, 80)), jnp.float32)
    v = jnp.asarray(RNG.standard_normal((1, 2, 64, 80)), jnp.float32)
    out = flash_attention_pallas(q, k, v, causal=True, bq=32, bk=32)
    exp = ref.attention_ref(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# SSD scan
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("s,h,p,n,chunk", [
    (128, 2, 16, 8, 32), (96, 1, 8, 4, 32), (256, 2, 32, 16, 64)])
def test_ssd_matches_sequential_ref(s, h, p, n, chunk):
    x = jnp.asarray(RNG.standard_normal((2, s, h, p)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.random((2, s, h)) * 0.1 + 0.01, jnp.float32)
    A = jnp.asarray(-(RNG.random(h) * 0.5 + 0.1), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((2, s, h, n)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((2, s, h, n)) * 0.3, jnp.float32)
    y = ssd_scan_pallas(x, dt, A, B, C, chunk=chunk)
    y_ref = ref.ssd_scan_ref(x, dt, A, B, C)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=2e-4, rtol=2e-3)


def test_ssd_decode_continues_scan():
    """prefill-then-decode == full scan (state handoff correctness)."""
    Bt, S, H, P, N = 1, 64, 2, 8, 4
    x = jnp.asarray(RNG.standard_normal((Bt, S, H, P)) * 0.5, jnp.float32)
    dt = jnp.asarray(RNG.random((Bt, S, H)) * 0.1 + 0.01, jnp.float32)
    A = jnp.asarray(-(RNG.random(H) * 0.5 + 0.1), jnp.float32)
    B = jnp.asarray(RNG.standard_normal((Bt, S, H, N)) * 0.3, jnp.float32)
    C = jnp.asarray(RNG.standard_normal((Bt, S, H, N)) * 0.3, jnp.float32)
    y_full = ref.ssd_scan_ref(x, dt, A, B, C)
    # run first 48 steps, then decode the last 16 one at a time
    y_pre = ref.ssd_scan_ref(x[:, :48], dt[:, :48], A, B[:, :48], C[:, :48])
    state = jnp.zeros((Bt, H, N, P), jnp.float32)
    for t in range(48):
        _, state = ref.ssd_decode_ref(state, x[:, t], dt[:, t], A,
                                      B[:, t], C[:, t])
    ys = []
    for t in range(48, 64):
        y_t, state = ref.ssd_decode_ref(state, x[:, t], dt[:, t], A,
                                        B[:, t], C[:, t])
        ys.append(y_t)
    y_dec = jnp.stack(ys, axis=1)
    np.testing.assert_allclose(np.asarray(y_dec),
                               np.asarray(y_full[:, 48:]),
                               atol=1e-4, rtol=1e-3)
