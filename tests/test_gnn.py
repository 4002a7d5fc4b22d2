"""PMGNS + GNN baselines: shapes, training signal, metrics."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.gnn import (PMGNSConfig, decode_targets, encode_targets,
                            huber, mape, pmgns_apply, pmgns_init)

RNG = np.random.default_rng(0)


def _batch(B=4, N=16, F=32, sdim=5):
    adj = (RNG.random((B, N, N)) < 0.2).astype(np.float32)
    return {
        "x": jnp.asarray(RNG.standard_normal((B, N, F)), jnp.float32),
        "adj": jnp.asarray(adj),
        "mask": jnp.ones((B, N), jnp.float32),
        "static": jnp.asarray(RNG.standard_normal((B, sdim)), jnp.float32),
        "y": jnp.asarray(RNG.random((B, 3)) * 100 + 1, jnp.float32),
    }


@pytest.mark.parametrize("variant", ["graphsage", "gcn", "gat", "gin", "mlp"])
def test_all_variants_forward(variant):
    cfg = PMGNSConfig(variant=variant, hidden=32)
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    out = pmgns_apply(params, cfg, _batch())
    assert out.shape == (4, 3)
    assert bool(jnp.isfinite(out).all())


def test_masking_ignores_padding():
    """Padded nodes must not change predictions."""
    cfg = PMGNSConfig(hidden=32)
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    b = _batch(B=2, N=8)
    out1 = pmgns_apply(params, cfg, b)
    # pad to N=16 with garbage in the masked region
    pad = {
        "x": jnp.concatenate([b["x"], jnp.full((2, 8, 32), 7.0)], axis=1),
        "adj": jnp.zeros((2, 16, 16)).at[:, :8, :8].set(b["adj"]),
        "mask": jnp.concatenate([b["mask"], jnp.zeros((2, 8))], axis=1),
        "static": b["static"],
    }
    out2 = pmgns_apply(params, cfg, pad)
    np.testing.assert_allclose(np.asarray(out1), np.asarray(out2),
                               atol=1e-5)


def test_training_reduces_loss():
    cfg = PMGNSConfig(hidden=32)
    params = pmgns_init(jax.random.PRNGKey(1), cfg)
    b = _batch(B=8)
    target = encode_targets(b["y"])

    def loss_fn(p):
        pred = pmgns_apply(p, cfg, b)
        return jnp.mean(huber(pred, target))

    loss0 = float(loss_fn(params))
    for _ in range(30):
        g = jax.grad(loss_fn)(params)
        params = jax.tree_util.tree_map(lambda p, gg: p - 0.01 * gg,
                                        params, g)
    assert float(loss_fn(params)) < loss0 * 0.9


def test_target_transform_roundtrip():
    y = jnp.asarray([[1.0, 50.0, 3000.0]])
    np.testing.assert_allclose(np.asarray(decode_targets(encode_targets(y))),
                               np.asarray(y), rtol=1e-5)


def test_mape_zero_for_exact():
    y = jnp.asarray([[10.0, 20.0, 30.0]])
    assert float(mape(y, y)) == 0.0


def test_huber_quadratic_then_linear():
    small = float(huber(jnp.asarray(0.5), jnp.asarray(0.0)))
    assert small == pytest.approx(0.125)
    big = float(huber(jnp.asarray(10.0), jnp.asarray(0.0)))
    assert big == pytest.approx(0.5 + 9.0)  # delta=1


def test_pallas_sage_path_matches_ref_path():
    cfg_ref = PMGNSConfig(hidden=32, use_pallas=False)
    cfg_pal = PMGNSConfig(hidden=32, use_pallas=True)
    params = pmgns_init(jax.random.PRNGKey(2), cfg_ref)
    b = _batch()
    o1 = pmgns_apply(params, cfg_ref, b)
    o2 = pmgns_apply(params, cfg_pal, b)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-4, rtol=1e-4)


# ---------------------------------------------------------------------------
# sparse edge-list message passing
# ---------------------------------------------------------------------------

def _paired_batches(B=6, N=24, F=32, sdim=5, density=0.08, seed=3):
    """Matching dense + sparse batches for the same random graphs."""
    rng = np.random.default_rng(seed)
    adj = (rng.random((B, N, N)) < density).astype(np.float32)
    e_max = int(adj.sum(axis=(1, 2)).max())
    e_pad = max(16, 1 << (e_max - 1).bit_length())
    edges = np.zeros((B, e_pad, 2), np.int32)
    emask = np.zeros((B, e_pad), np.float32)
    for b in range(B):
        dst, src = np.nonzero(adj[b])            # adj[dst, src]
        edges[b, :len(src)] = np.stack([src, dst], -1)
        emask[b, :len(src)] = 1.0
    common = {
        "x": jnp.asarray(rng.standard_normal((B, N, F)), jnp.float32),
        "mask": jnp.ones((B, N), jnp.float32),
        "static": jnp.asarray(rng.standard_normal((B, sdim)), jnp.float32),
    }
    dense = dict(common, adj=jnp.asarray(adj))
    sparse = dict(common, edges=jnp.asarray(edges),
                  edge_mask=jnp.asarray(emask))
    return dense, sparse


@pytest.mark.parametrize("variant", ["graphsage", "gcn", "gat", "gin", "mlp"])
def test_sparse_mp_matches_dense(variant):
    """Every variant: sparse edge-list path == dense adjacency path."""
    cfg_d = PMGNSConfig(variant=variant, hidden=32)
    cfg_s = PMGNSConfig(variant=variant, hidden=32, sparse_mp=True)
    params = pmgns_init(jax.random.PRNGKey(0), cfg_d)
    dense, sparse = _paired_batches()
    od = pmgns_apply(params, cfg_d, dense)
    os_ = pmgns_apply(params, cfg_s, sparse)
    assert bool(jnp.isfinite(os_).all())
    np.testing.assert_allclose(np.asarray(od), np.asarray(os_),
                               atol=1e-5, rtol=1e-5)


@pytest.mark.parametrize("variant", ["graphsage", "gcn", "gat", "gin"])
def test_sparse_pallas_matches_sparse_ref(variant, monkeypatch):
    cfg_ref = PMGNSConfig(variant=variant, hidden=32, sparse_mp=True)
    cfg_pal = PMGNSConfig(variant=variant, hidden=32, sparse_mp=True,
                          use_pallas=True)
    params = pmgns_init(jax.random.PRNGKey(1), cfg_ref)
    _, sparse = _paired_batches(seed=5)
    o1 = pmgns_apply(params, cfg_ref, sparse)
    from repro.kernels import ops
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    o2 = pmgns_apply(params, cfg_pal, sparse)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-4, rtol=1e-4)


def test_sparse_mp_is_differentiable():
    """Training runs on the sparse path: grads exist and are finite."""
    cfg = PMGNSConfig(hidden=32, sparse_mp=True)
    params = pmgns_init(jax.random.PRNGKey(1), cfg)
    _, sparse = _paired_batches(seed=7)
    y = jnp.asarray(RNG.random((6, 3)) * 100 + 1, jnp.float32)

    def loss_fn(p):
        return jnp.mean(huber(pmgns_apply(p, cfg, sparse),
                              encode_targets(y)))

    g = jax.grad(loss_fn)(params)
    leaves = jax.tree_util.tree_leaves(g)
    assert all(bool(jnp.isfinite(l).all()) for l in leaves)
    assert any(float(jnp.abs(l).max()) > 0 for l in leaves)


@pytest.mark.parametrize("sparse_mp", [False, True])
def test_gat_empty_neighborhood_no_nan(sparse_mp):
    """Regression: a graph whose nodes have no incoming edges at all
    (every destination row fully masked) must predict finite values on
    both layouts — the all-padding softmax row is the NaN risk."""
    cfg = PMGNSConfig(variant="gat", hidden=32, sparse_mp=sparse_mp)
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    B, N = 2, 8
    batch = {
        "x": jnp.asarray(RNG.standard_normal((B, N, 32)), jnp.float32),
        "mask": jnp.ones((B, N), jnp.float32),
        "static": jnp.asarray(RNG.standard_normal((B, 5)), jnp.float32),
    }
    if sparse_mp:
        batch["edges"] = jnp.zeros((B, 4, 2), jnp.int32)
        batch["edge_mask"] = jnp.zeros((B, 4), jnp.float32)
    else:
        batch["adj"] = jnp.zeros((B, N, N), jnp.float32)
    out = pmgns_apply(params, cfg, batch)
    assert bool(jnp.isfinite(out).all())
    # and its gradients stay finite too (the softmax-backward NaN trap)
    def loss_fn(p):
        return jnp.sum(pmgns_apply(p, cfg, batch) ** 2)
    g = jax.tree_util.tree_leaves(jax.grad(loss_fn)(params))
    assert all(bool(jnp.isfinite(l).all()) for l in g)


def test_gat_edgeless_graph_inside_mixed_batch():
    """An empty-neighborhood graph batched next to a normal one must not
    perturb the normal graph's prediction (dense vs sparse both)."""
    dense, sparse = _paired_batches(B=2, seed=11)
    # kill every edge of graph 0 only
    adj = np.asarray(dense["adj"]).copy()
    adj[0] = 0.0
    emask = np.asarray(sparse["edge_mask"]).copy()
    emask[0] = 0.0
    dense = dict(dense, adj=jnp.asarray(adj))
    sparse = dict(sparse, edge_mask=jnp.asarray(emask))
    cfg_d = PMGNSConfig(variant="gat", hidden=32)
    cfg_s = PMGNSConfig(variant="gat", hidden=32, sparse_mp=True)
    params = pmgns_init(jax.random.PRNGKey(2), cfg_d)
    od = pmgns_apply(params, cfg_d, dense)
    os_ = pmgns_apply(params, cfg_s, sparse)
    assert bool(jnp.isfinite(od).all()) and bool(jnp.isfinite(os_).all())
    np.testing.assert_allclose(np.asarray(od), np.asarray(os_),
                               atol=1e-5, rtol=1e-5)


def test_layout_mismatch_raises():
    cfg_s = PMGNSConfig(hidden=32, sparse_mp=True)
    cfg_d = PMGNSConfig(hidden=32)
    params = pmgns_init(jax.random.PRNGKey(0), cfg_d)
    dense, sparse = _paired_batches(B=2)
    with pytest.raises(ValueError, match="sparse_mp=True"):
        pmgns_apply(params, cfg_s, dense)
    with pytest.raises(ValueError, match="sparse_mp=False"):
        pmgns_apply(params, cfg_d, sparse)


# ---------------------------------------------------------------------------
# packed block-diagonal layout
# ---------------------------------------------------------------------------

def _mixed_samples(n=6, seed=21):
    from repro.dataset.builder import synthetic_samples
    return synthetic_samples(n, n_min=4, n_max=60, seed=seed)


@pytest.mark.parametrize("variant", ["graphsage", "gcn", "gat", "gin", "mlp"])
def test_packed_matches_dense_per_sample(variant):
    """Every variant: packed flat-axis forward == per-sample dense."""
    from repro.core.batching import collate, collate_packed
    cfg_d = PMGNSConfig(variant=variant, hidden=32)
    cfg_p = PMGNSConfig(variant=variant, hidden=32, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg_d)
    samples = _mixed_samples()
    bp = {k: jnp.asarray(v) for k, v in collate_packed(samples).items()
          if k not in ("y", "wt")}
    op = pmgns_apply(params, cfg_p, bp)[:len(samples)]
    assert bool(jnp.isfinite(op).all())
    for i, s in enumerate(samples):
        bd = {k: jnp.asarray(v) for k, v in collate([s]).items()
              if k != "y"}
        od = pmgns_apply(params, cfg_d, bd)[0]
        np.testing.assert_allclose(np.asarray(od), np.asarray(op[i]),
                                   atol=1e-5, rtol=1e-5)


def test_packed_pallas_matches_packed_ref(monkeypatch):
    """use_pallas routes the packed readout + segment layers through the
    kernels; numbers match the lax reference."""
    from repro.core.batching import collate_packed
    from repro.kernels import ops
    cfg_ref = PMGNSConfig(hidden=32, layout="packed")
    cfg_pal = PMGNSConfig(hidden=32, layout="packed", use_pallas=True)
    params = pmgns_init(jax.random.PRNGKey(1), cfg_ref)
    b = {k: jnp.asarray(v)
         for k, v in collate_packed(_mixed_samples(seed=22)).items()
         if k not in ("y", "wt")}
    o1 = pmgns_apply(params, cfg_ref, b)
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    o2 = pmgns_apply(params, cfg_pal, b)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               atol=1e-4, rtol=1e-4)


def test_packed_is_differentiable():
    from repro.core.batching import collate_packed
    cfg = PMGNSConfig(hidden=32, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(2), cfg)
    samples = _mixed_samples(seed=23)
    b = {k: jnp.asarray(v) for k, v in collate_packed(samples).items()}

    def loss_fn(p):
        pred = pmgns_apply(p, cfg, b)
        h = huber(pred, encode_targets(b["y"]))
        return jnp.sum(h * b["wt"][:, None])

    g = jax.grad(loss_fn)(params)
    leaves = jax.tree_util.tree_leaves(g)
    assert all(bool(jnp.isfinite(l).all()) for l in leaves)
    assert any(float(jnp.abs(l).max()) > 0 for l in leaves)


def test_packed_layout_requires_packed_batch():
    cfg_p = PMGNSConfig(hidden=32, layout="packed")
    params = pmgns_init(jax.random.PRNGKey(0), cfg_p)
    dense, _ = _paired_batches(B=2)
    with pytest.raises(ValueError, match="packed"):
        pmgns_apply(params, cfg_p, dense)
    with pytest.raises(ValueError, match="layout"):
        PMGNSConfig(layout="banana").resolved_layout
