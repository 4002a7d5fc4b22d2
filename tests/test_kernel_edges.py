"""Tile-boundary and degenerate-bin cases for the packed-layout kernels.

``edge_softmax_pallas`` tiles edges in ``be``-wide blocks and
``segment_readout_pallas`` tiles graphs/nodes — these tests pin the
boundary shapes a sweep over round sizes never hits: E exactly at the
tile multiple, E one past it, every edge masked, a bin whose last graph
slots hold zero real nodes, and a single graph at the exact node
budget. All interpret-mode, so they run fully on the CPU CI runner.
"""
from functools import partial

import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ref, segment_spmm

edge_softmax_pallas = partial(segment_spmm.edge_softmax_pallas,
                              interpret=True)
segment_readout_pallas = partial(segment_spmm.segment_readout_pallas,
                                 interpret=True)

RNG = np.random.default_rng(0)


def _softmax_case(b, e, h, n, mask_frac=0.8, seed=0):
    rng = np.random.default_rng(seed)
    scores = jnp.asarray(rng.standard_normal((b, e, h)).astype(np.float32))
    dst = jnp.asarray(rng.integers(0, n, (b, e)).astype(np.int32))
    emask = jnp.asarray((rng.random((b, e)) < mask_frac).astype(np.float32))
    return scores, dst, emask


# ---------------------------------------------------------------------------
# edge_softmax tile boundaries
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("h", [4, 8])
@pytest.mark.parametrize("e", [256, 129])     # exact 2×be multiple; be+1
def test_edge_softmax_tile_boundaries(e, h):
    scores, dst, emask = _softmax_case(2, e, h, 40, seed=e + h)
    out = edge_softmax_pallas(scores, dst, emask, 40, be=128)
    exp = ref.edge_softmax_ref(scores, dst, emask, 40)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-5, rtol=1e-5)
    # per-destination weights over real edges must sum to 1 (or 0 for
    # destinations with no real incoming edge)
    w = np.asarray(out) * np.asarray(emask)[..., None]
    sums = np.zeros((2, 40, h), np.float32)
    d = np.asarray(dst)
    for bi in range(2):
        for ei in range(e):
            sums[bi, d[bi, ei]] += w[bi, ei]
    assert np.all((np.abs(sums - 1.0) < 1e-5) | (np.abs(sums) < 1e-6))


def test_edge_softmax_all_edges_masked():
    # the all-padding bin: every edge masked → exact zeros, never NaN
    scores, dst, _ = _softmax_case(1, 192, 4, 24, seed=3)
    emask = jnp.zeros((1, 192), jnp.float32)
    out = np.asarray(edge_softmax_pallas(scores, dst, emask, 24))
    assert not np.any(np.isnan(out))
    np.testing.assert_allclose(out, 0.0, atol=0.0)


def test_edge_softmax_single_fully_masked_destination():
    # one destination keeps real edges, another has all its incoming
    # edges masked — the masked one must read back exact zeros
    scores = jnp.asarray(RNG.standard_normal((1, 8, 2)).astype(np.float32))
    dst = jnp.asarray(np.array([[0, 0, 0, 0, 1, 1, 1, 1]], np.int32))
    emask = jnp.asarray(np.array([[1, 1, 1, 1, 0, 0, 0, 0]], np.float32))
    out = np.asarray(edge_softmax_pallas(scores, dst, emask, 2))
    np.testing.assert_allclose(out[0, 4:], 0.0, atol=0.0)
    np.testing.assert_allclose(out[0, :4].sum(axis=0), 1.0, atol=1e-5)


# ---------------------------------------------------------------------------
# segment_readout degenerate bins
# ---------------------------------------------------------------------------

def test_readout_trailing_graphs_zero_nodes():
    # packed bins pad the graph axis: the last G - g_real slots own no
    # node rows at all and must pool to exact zeros in mean AND max
    p, f, g, g_real = 96, 12, 8, 3
    h = RNG.standard_normal((p, f)).astype(np.float32) + 5.0   # all > 0
    gid = np.sort(RNG.integers(0, g_real, p)).astype(np.int32)
    nmask = np.ones((p,), np.float32)
    for kind in ("mean", "mean_max"):
        out = np.asarray(segment_readout_pallas(
            jnp.asarray(h), jnp.asarray(gid), jnp.asarray(nmask), g,
            kind=kind))
        exp = np.asarray(ref.segment_readout_ref(
            jnp.asarray(h), jnp.asarray(gid), jnp.asarray(nmask), g,
            kind=kind))
        np.testing.assert_allclose(out, exp, atol=1e-5, rtol=1e-5)
        np.testing.assert_allclose(out[g_real:], 0.0, atol=0.0)


def test_readout_single_graph_exact_node_budget():
    # one graph filling the bin to the exact node budget (no tail
    # padding, P a multiple of the node tile)
    p, f = 256, 8
    h = RNG.standard_normal((p, f)).astype(np.float32)
    gid = np.zeros((p,), np.int32)
    nmask = np.ones((p,), np.float32)
    out = np.asarray(segment_readout_pallas(
        jnp.asarray(h), jnp.asarray(gid), jnp.asarray(nmask), 1))
    np.testing.assert_allclose(out[0, :f], h.mean(axis=0),
                               atol=1e-5, rtol=1e-5)
    np.testing.assert_allclose(out[0, f:], h.max(axis=0),
                               atol=1e-5, rtol=1e-5)


def test_readout_max_ignores_masked_garbage():
    # masked node rows carry huge garbage values: the max readout must
    # not leak them (and the fill value must not leak either when every
    # real value is very negative)
    p, f, g = 64, 4, 2
    h = np.full((p, f), -1e3, np.float32)
    h[32:] = 1e9                                 # garbage in masked rows
    gid = np.zeros((p,), np.int32)
    gid[16:32] = 1
    nmask = np.zeros((p,), np.float32)
    nmask[:32] = 1.0
    out = np.asarray(segment_readout_pallas(
        jnp.asarray(h), jnp.asarray(gid), jnp.asarray(nmask), g))
    exp = np.asarray(ref.segment_readout_ref(
        jnp.asarray(h), jnp.asarray(gid), jnp.asarray(nmask), g))
    np.testing.assert_allclose(out, exp, atol=1e-4, rtol=1e-5)
    # max over real rows is exactly -1e3, not 1e9 and not a fill value
    np.testing.assert_allclose(out[:, f:], -1e3, rtol=1e-6)
