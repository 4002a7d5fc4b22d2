"""Compile the serving path's kernels for a described TPU v5e chip.

No chip is attached: the TPU compiler builds for a topology that is only
described, which refuses what a chip would refuse — a Mosaic layout it
cannot lower, a kernel over its VMEM limit, a program over device
memory. Nothing runs, so these tests say nothing about results or
times. Shapes are the serving path's real ones: the top packed rung of
the default ladder (P=4096, Q=6656, G=256) at the paper's width (512).
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.batching import packed_rung_ladder
from repro.core.gnn import (PMGNSConfig, make_staged_packed_infer_fn,
                            packed_staging_layout, pmgns_init)
from repro.kernels import ops, segment_spmm

P, Q, G, F = 4096, 6656, 256, 512


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    prior = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prior)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _custom_calls(fn, *args) -> int:
    return jax.jit(fn).lower(*args).compile().as_text().count(
        "tpu_custom_call")


@pytest.mark.parametrize("mode,combine", [("mean", "split"), ("sum", "pre")])
def test_fused_mp_layer_top_rung(one_chip, mode, combine):
    def layer(x, e, em, nm, wn, ws, b):
        return segment_spmm.fused_mp_layer_pallas(
            x, e, em, nm, w_neigh=wn, w_self=ws, bias=b, mode=mode,
            combine=combine)
    s = lambda *a: _spec(one_chip, *a)
    assert _custom_calls(layer, s((P, F)), s((Q, 2), jnp.int32), s((Q,)),
                         s((P,)), s((F, F)), s((F, F)), s((F,))) == 1


def test_segment_readout_top_rung(one_chip):
    def readout(h, gid, nm):
        return segment_spmm.segment_readout_pallas(h, gid, nm, G)
    s = lambda *a: _spec(one_chip, *a)
    assert _custom_calls(readout, s((P, F)), s((P,), jnp.int32),
                         s((P,))) == 1


def test_edge_softmax_top_rung(one_chip):
    def softmax(scores, dst, em):
        return segment_spmm.edge_softmax_pallas(scores, dst, em, P)
    s = lambda *a: _spec(one_chip, *a)
    assert _custom_calls(softmax, s((1, Q, 4)), s((1, Q), jnp.int32),
                         s((1, Q))) == 2


def test_fused_gat_aggregate_top_rung(one_chip):
    def aggregate(z, e, em, att, nm):
        return segment_spmm.fused_gat_aggregate_pallas(z, e, em, att, nm)
    s = lambda *a: _spec(one_chip, *a)
    assert _custom_calls(aggregate, s((P, F)), s((Q, 2), jnp.int32),
                         s((Q,)), s((Q, 4)), s((P,))) == 1


#: custom calls of the staged apply: one fused kernel per GNN layer (GAT
#: adds its two-pass edge softmax) and the segment readout
STAGED_CUSTOM_CALLS = {"graphsage": 4, "gcn": 4, "gin": 4, "gat": 10}


@pytest.mark.parametrize("variant", sorted(STAGED_CUSTOM_CALLS))
def test_staged_packed_apply_top_rung(one_chip, monkeypatch, variant):
    # the dispatcher picks kernels from the backend, which is the CPU
    # here: steer it to compiled (not interpreted) Pallas
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = PMGNSConfig(variant=variant, layout="packed", use_pallas=True)
    assert cfg.hidden == F
    p, q, g = packed_rung_ladder()[-1]
    assert (p, q, g) == (P, Q, G)
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: pmgns_init(k, cfg), jax.random.PRNGKey(0)))
    _, _, _, f_len, i_len = packed_staging_layout(cfg, p, q, g)
    fn = make_staged_packed_infer_fn(cfg, p, q, g)
    text = fn.lower(params, _spec(one_chip, (f_len,)),
                    _spec(one_chip, (i_len,), jnp.int32)
                    ).compile().as_text()
    assert text.count("tpu_custom_call") == STAGED_CUSTOM_CALLS[variant]


def test_staged_packed_apply_lone_bin(one_chip, monkeypatch):
    """A whole LLM prefill graph's lone bin (P = Q = 16,384): GraphSAGE's
    first layer runs the fused kernel, the two others the segment
    composition, and Mosaic takes the kernel at that size."""
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    monkeypatch.setattr(ops, "_interpret", lambda: False)
    cfg = PMGNSConfig(layout="packed", use_pallas=True)
    p, q, g = 16384, 16384, 256
    params = jax.tree_util.tree_map(
        lambda a: _spec(one_chip, a.shape, a.dtype),
        jax.eval_shape(lambda k: pmgns_init(k, cfg), jax.random.PRNGKey(0)))
    _, _, _, f_len, i_len = packed_staging_layout(cfg, p, q, g)
    text = make_staged_packed_infer_fn(cfg, p, q, g).lower(
        params, _spec(one_chip, (f_len,)),
        _spec(one_chip, (i_len,), jnp.int32)).compile().as_text()
    assert text.count("tpu_custom_call") == 2     # one layer + the readout
