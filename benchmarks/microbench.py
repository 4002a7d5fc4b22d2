"""Microbenchmarks of the framework's own hot paths (CPU timings — these
are pipeline-cost numbers, not TPU projections): tracing, feature
generation, kernel calls (interpret + ref), end-to-end prediction.

Kernel rows carry an achieved-bandwidth column: modeled HBM traffic
(``repro.roofline.analysis`` byte-counting helpers, one read per
operand / one write per result per stage) divided by measured wall
time, plus the %-of-roofline that wall explains against the nominal
host envelope. Emits ``BENCH_microbench.json``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax import ShapeDtypeStruct as S

from repro.core.batching import collate, sample_from_graph
from repro.core.gnn import PMGNSConfig, pmgns_apply, pmgns_init
from repro.core.node_features import node_feature_matrix
from repro.core.tracer import trace_graph
from repro.kernels import ops, ref
from repro.roofline.analysis import (achieved_rates, dense_aggregate_traffic,
                                     edge_softmax_traffic,
                                     mp_layer_traffic,
                                     segment_aggregate_traffic,
                                     segment_readout_traffic)
from repro.zoo.families import build_family

from .common import bench_hardware, timed, write_json


def _rate_row(name: str, derived: str, wall_s: float, traffic):
    """One kernel row with achieved GB/s + %-of-roofline columns."""
    r = achieved_rates(traffic["flops"], traffic["bytes"], wall_s,
                       bench_hardware())
    return {"name": name, "us_per_call": round(wall_s * 1e6),
            "derived": derived,
            "gb_s": round(r["achieved_gb_s"], 2),
            "pct_roofline": round(r["pct_of_roofline"], 1),
            "bound": r["bound"]}


def run():
    rng = np.random.default_rng(0)
    rows = []

    # trace + featurize a mid-size zoo model
    specs, fwd, meta = build_family("resnet", {"batch": 8, "res": 224})
    x = S((8, 224, 224, 3), jnp.float32)
    g, t_trace = timed(lambda: trace_graph(fwd, specs, x, meta=meta),
                       repeats=3)
    rows.append({"name": "trace_resnet", "us_per_call": round(t_trace * 1e6),
                 "derived": f"nodes={g.num_nodes}"})
    _, t_feat = timed(lambda: node_feature_matrix(g), repeats=3)
    rows.append({"name": "node_features", "us_per_call": round(t_feat * 1e6),
                 "derived": f"dim=32"})

    # GNN forward (batched padded graphs)
    cfg = PMGNSConfig(hidden=512)
    params = pmgns_init(jax.random.PRNGKey(0), cfg)
    batch = collate([sample_from_graph(g)])
    jb = {k: jnp.asarray(v) for k, v in batch.items()}
    fn = jax.jit(lambda p, b: pmgns_apply(p, cfg, b))
    fn(params, jb).block_until_ready()
    _, t_fwd = timed(lambda: fn(params, jb).block_until_ready(), repeats=5)
    rows.append({"name": "pmgns_forward_b1", "us_per_call":
                 round(t_fwd * 1e6), "derived": "hidden=512"})

    # kernels: ref vs pallas (interpret mode off a TPU), with
    # achieved-GB/s columns from the roofline traffic models
    adj = jnp.asarray((rng.random((4, 256, 256)) < 0.05), jnp.float32)
    h = jnp.asarray(rng.standard_normal((4, 256, 64)), jnp.float32)
    r = jax.jit(ref.sage_aggregate_ref)
    r(adj, h).block_until_ready()
    _, t_ref = timed(lambda: r(adj, h).block_until_ready(), repeats=5)
    rows.append(_rate_row("sage_ref_jit", "B4xN256xF64", t_ref,
                          dense_aggregate_traffic(4, 256, 64)))
    ops.sage_aggregate(adj, h, impl="pallas").block_until_ready()
    _, t_pl = timed(lambda: ops.sage_aggregate(
        adj, h, impl="pallas").block_until_ready(), repeats=2)
    on_tpu = jax.default_backend() == "tpu"
    rows.append(_rate_row(
        "sage_pallas" if on_tpu else "sage_pallas_interpret",
        "B4xN256xF64" if on_tpu else "correctness-mode (CPU interpret)",
        t_pl, dense_aggregate_traffic(4, 256, 64)))

    # sparse / packed kernels at a full-bin-ish shape
    b, e, n, f, hd, p, g = 4, 1024, 512, 64, 4, 4096, 256
    edges = jnp.asarray(
        rng.integers(0, n, (b, e, 2)), jnp.int32)
    emask = jnp.asarray(rng.random((b, e)) < 0.9, jnp.float32)
    hb = jnp.asarray(rng.standard_normal((b, n, f)), jnp.float32)
    fn = jax.jit(lambda ed, m, x: ref.segment_aggregate_ref(ed, m, x))
    fn(edges, emask, hb).block_until_ready()
    _, t = timed(lambda: fn(edges, emask, hb).block_until_ready(), repeats=5)
    rows.append(_rate_row("segment_aggregate_ref", f"B{b}xE{e}xN{n}xF{f}",
                          t, segment_aggregate_traffic(b, e, n, f)))

    scores = jnp.asarray(rng.standard_normal((b, e, hd)), jnp.float32)
    fn = jax.jit(lambda s, d, m: ref.edge_softmax_ref(s, d, m, n))
    fn(scores, edges[..., 1], emask).block_until_ready()
    _, t = timed(lambda: fn(scores, edges[..., 1],
                            emask).block_until_ready(), repeats=5)
    rows.append(_rate_row("edge_softmax_ref", f"B{b}xE{e}xH{hd}", t,
                          edge_softmax_traffic(b, e, hd, n)))

    hp = jnp.asarray(rng.standard_normal((p, f)), jnp.float32)
    gids = jnp.asarray(np.sort(rng.integers(0, g, p)), jnp.int32)
    nmask = jnp.asarray(rng.random(p) < 0.95, jnp.float32)
    fn = jax.jit(lambda x, i, m: ref.segment_readout_ref(x, i, m, g))
    fn(hp, gids, nmask).block_until_ready()
    _, t = timed(lambda: fn(hp, gids, nmask).block_until_ready(), repeats=5)
    rows.append(_rate_row("segment_readout_ref", f"P{p}xF{f}xG{g}", t,
                          segment_readout_traffic(p, f, g)))

    # fused packed MP layer (ref composition; the Pallas megakernel is
    # gated in benchmarks/fused_mp.py)
    pe = 6656
    pedges = jnp.asarray(rng.integers(0, p, (pe, 2)), jnp.int32)
    pemask = jnp.asarray(rng.random(pe) < 0.9, jnp.float32)
    wn = jnp.asarray(rng.standard_normal((f, f)) * 0.1, jnp.float32)
    ws = jnp.asarray(rng.standard_normal((f, f)) * 0.1, jnp.float32)
    fn = jax.jit(lambda x, ed, m, nm: ops.fused_mp_layer(
        x, ed, m, nm, w_neigh=wn, w_self=ws, mode="mean",
        combine="split", impl="ref"))
    fn(hp, pedges, pemask, nmask).block_until_ready()
    _, t = timed(lambda: fn(hp, pedges, pemask, nmask).block_until_ready(),
                 repeats=5)
    rows.append(_rate_row("fused_mp_layer_ref", f"P{p}xQ{pe}xF{f}", t,
                          mp_layer_traffic(p, pe, f, f, fused=True)))

    res = {"rows": rows}
    res["artifact"] = write_json("BENCH_microbench.json", res)
    return res
