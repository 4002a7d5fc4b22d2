"""Fused message-passing gate: megakernel equivalence, traffic, precision.

PR 7 collapses each packed message-passing layer (edge gather → mask →
scatter-accumulate [→ degree/mean] → self/neighbor combine → bias →
activation → node-mask) into **one kernel call** — a single
``pallas_call`` on TPU (``repro.kernels.segment_spmm
.fused_mp_layer_pallas``; GAT rides fused up to its softmax via
``fused_gat_aggregate_pallas``), one fused jnp composition on CPU —
selected by ``PMGNSConfig(fused_mp=...)``. It also threads the
inference ``precision`` policy (f32 / bf16 staging / int8-weight
artifacts) end to end. This gate pins:

* **Equivalence** — fused vs composed predictions agree to ≤ 1e-5 at
  f32 for all five variants, on both the lax reference route and the
  forced interpret-mode Pallas route.
* **Modeled HBM traffic** — the fused layer moves ≥ 1.3× fewer HBM
  bytes than the composed pipeline at the full-bin shape
  (``roofline.analysis.mp_layer_traffic``; the deterministic,
  machine-independent form of the speedup claim — on a CPU host both
  paths sit at the same XLA fusion floor, so wall clock is gated only
  as **no regression**, stream preds/s ratio ≥ 0.90×). Every kernel
  row converts measured wall time into achieved GFLOP/s / GB/s and
  %-of-roofline via ``achieved_rates``.
* **Memory-term baseline** — the fused kernel's modeled bytes at the
  full-bin shape must stay ≤ 1.2× the checked-in baseline
  (``benchmarks/baselines/fused_mp_roofline.json``): a refactor that
  quietly reintroduces an HBM round-trip fails CI.
* **Precision** — bf16 inference end-to-end (engine + artifact
  round-trip + serving stats) drifts ≤ 0.5 % MAPE vs f32; int8-weight
  artifacts load with ``allow_pickle=False``.

Emits ``BENCH_fused_mp.json`` for CI.

    PYTHONPATH=src python -m benchmarks.fused_mp
"""
from __future__ import annotations

import json
import os
import sys

from repro.compile_cache import enable_compile_cache

from .common import bench_hardware, timed, write_json

VARIANTS = ("graphsage", "gcn", "gat", "gin", "mlp")
#: Full-bin packed shape under the default budgets (4096-node ladder
#: top: Q = 1.625·P edges, G = P/16 graphs).
FULL_BIN = {"p": 4096, "q": 6656, "g": 256}


def _mixed_zoo(n_graphs: int, seed: int = 0):
    """DIPPM-like mixed-size sample zoo: 60 % small (8–40 nodes), 30 %
    medium (50–200), 10 % large (300–700)."""
    from repro.dataset.builder import synthetic_samples
    n_small = int(0.6 * n_graphs)
    n_med = int(0.3 * n_graphs)
    n_large = n_graphs - n_small - n_med
    return (synthetic_samples(n_small, seed=seed, n_min=8, n_max=40)
            + synthetic_samples(n_med, seed=seed + 1, n_min=50, n_max=200)
            + synthetic_samples(n_large, seed=seed + 2, n_min=300,
                                n_max=700))
BASELINE_PATH = os.path.join(os.path.dirname(__file__), "baselines",
                             "fused_mp_roofline.json")
#: Variants with a true fused MP layer (gat fuses its aggregate only,
#: mlp has no message passing) — the traffic model covers these.
_MP_VARIANTS = {"graphsage": dict(mode="mean", combine="split"),
                "gcn": dict(mode="sum", combine="pre")}


def _layer_shapes(cfg):
    """(f_in, f_out) of each message-passing layer in the stack."""
    return ([(cfg.node_feat_dim, cfg.hidden)]
            + [(cfg.hidden, cfg.hidden)] * (cfg.n_gnn_blocks - 1))


def _equivalence(samples, hidden: int):
    """max |Δ| fused-vs-composed per variant, lax ref route and forced
    interpret-mode Pallas route."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.batching import collate_packed
    from unittest import mock

    from repro.core.gnn import PMGNSConfig, pmgns_infer, pmgns_init
    from repro.kernels import ops

    out = {"ref": {}, "pallas": {}}
    for variant in VARIANTS:
        cfg_off = PMGNSConfig(variant=variant, hidden=hidden,
                              layout="packed", fused_mp="off")
        cfg_on = dataclasses.replace(cfg_off, fused_mp="on")
        params = pmgns_init(jax.random.PRNGKey(0), cfg_off)
        bp = {k: jnp.asarray(v) for k, v in collate_packed(samples).items()
              if k not in ("y", "wt")}
        y_off = np.asarray(pmgns_infer(params, cfg_off, bp))
        y_on = np.asarray(pmgns_infer(params, cfg_on, bp))
        out["ref"][variant] = float(np.abs(y_off - y_on).max())
        # forced Pallas megakernel (interpret mode on CPU) vs the same
        # composed lax baseline
        cfg_pl = dataclasses.replace(cfg_on, use_pallas=True)
        with mock.patch.object(ops, "kernel_impl", lambda: "pallas"):
            y_pl = np.asarray(pmgns_infer(params, cfg_pl, bp))
        out["pallas"][variant] = float(np.abs(y_off - y_pl).max())
    return out


def _throughput(samples, hidden: int, repeats: int, request_size: int):
    """Fused vs composed packed engine, bulk + request stream.

    On a CPU host both paths bottom out at the same XLA fusion floor
    (measured across PRs: every composed-path reformulation lands at
    0.9–1.05×), so the wall-clock gate is **no regression** (≥ 0.90×);
    the ≥ 1.3× claim lives in the modeled-traffic section where it is
    machine-independent. Min-of-N interleaved rounds keep the ratio
    stable under shared-runner load.
    """
    import dataclasses
    import jax
    import numpy as np
    from repro.core.engine import PredictionEngine
    from repro.core.gnn import PMGNSConfig, pmgns_init

    cfg_off = PMGNSConfig(hidden=hidden, layout="packed", fused_mp="off")
    cfg_on = dataclasses.replace(cfg_off, fused_mp="on")
    params = pmgns_init(jax.random.PRNGKey(0), cfg_off)
    eng_off = PredictionEngine(params, cfg_off)
    eng_on = PredictionEngine(params, cfg_on)

    rng = np.random.default_rng(0)
    order = rng.permutation(len(samples))
    sizes, requests, i = (max(1, request_size // 2), request_size,
                          2 * request_size), [], 0
    while i < len(order):
        k = sizes[len(requests) % len(sizes)]
        requests.append([samples[j] for j in order[i:i + k]])
        i += k

    def stream(eng):
        for req in requests:
            eng.predict_samples(req)

    y_off = eng_off.predict_samples(samples)     # warm compiled fns
    y_on = eng_on.predict_samples(samples)
    stream(eng_off)
    stream(eng_on)
    t_off = t_on = r_off = r_on = float("inf")
    for _ in range(repeats):
        _, t = timed(lambda: eng_off.predict_samples(samples), repeats=1)
        t_off = min(t_off, t)
        _, t = timed(lambda: eng_on.predict_samples(samples), repeats=1)
        t_on = min(t_on, t)
        _, t = timed(lambda: stream(eng_off), repeats=1)
        r_off = min(r_off, t)
        _, t = timed(lambda: stream(eng_on), repeats=1)
        r_on = min(r_on, t)
    return {
        "bulk": {
            "unfused_pred_per_s": round(len(samples) / t_off, 2),
            "fused_pred_per_s": round(len(samples) / t_on, 2),
            "speedup": round(t_off / t_on, 2),
        },
        "stream": {
            "request_size": request_size,
            "unfused_pred_per_s": round(len(samples) / r_off, 2),
            "fused_pred_per_s": round(len(samples) / r_on, 2),
            "speedup": round(r_off / r_on, 2),
        },
        "max_abs_diff": float(np.abs(y_off - y_on).max()),
    }


def _full_bin_batch(samples, budgets):
    """Pack a ~full bin (node total just under the budget) → jnp batch."""
    import jax.numpy as jnp
    from repro.core.batching import collate_packed
    chosen, tn, te = [], 0, 0
    for s in samples:
        if (tn + s.n_nodes <= budgets["p"] and te + s.n_edges
                <= budgets["q"] and len(chosen) < budgets["g"]):
            chosen.append(s)
            tn += s.n_nodes
            te += s.n_edges
    b = collate_packed(chosen, node_budget=budgets["p"],
                       edge_budget=budgets["q"],
                       graph_budget=budgets["g"])
    return ({k: jnp.asarray(v) for k, v in b.items()
             if k not in ("y", "wt")}, len(chosen), tn)


def _modeled_traffic(samples, hidden: int):
    """Analytic HBM traffic at the full-bin shape + achieved-rate rows
    from measured full-bin walls (wall split evenly across the MP
    layers — a reporting approximation, stated in the row)."""
    import dataclasses
    import jax
    from repro.core.gnn import PMGNSConfig, pmgns_apply, pmgns_init
    from repro.roofline.analysis import achieved_rates, mp_layer_traffic

    p, q = FULL_BIN["p"], FULL_BIN["q"]
    rows, ratios, fused_bytes = [], {}, {}
    for variant, kw in _MP_VARIANTS.items():
        cfg_off = PMGNSConfig(variant=variant, hidden=hidden,
                              layout="packed", fused_mp="off")
        cfg_on = dataclasses.replace(cfg_off, fused_mp="on")
        fl_f = by_f = fl_u = by_u = 0.0
        for f_in, f_out in _layer_shapes(cfg_off):
            tf = mp_layer_traffic(p, q, f_in, f_out, fused=True, **kw)
            tu = mp_layer_traffic(p, q, f_in, f_out, fused=False, **kw)
            fl_f += tf["flops"]
            by_f += tf["bytes"]
            fl_u += tu["flops"]
            by_u += tu["bytes"]
        ratios[variant] = round(by_u / by_f, 2)
        fused_bytes[variant] = by_f

        params = pmgns_init(jax.random.PRNGKey(0), cfg_off)
        batch, ng, tn = _full_bin_batch(samples, FULL_BIN)
        n_layers = cfg_off.n_gnn_blocks
        for cfg, fl, by, tag in ((cfg_on, fl_f, by_f, "fused"),
                                 (cfg_off, fl_u, by_u, "unfused")):
            fn = jax.jit(lambda pr, b, c=cfg: pmgns_apply(pr, c, b,
                                                          train=False))
            fn(params, batch).block_until_ready()
            _, wall = timed(lambda: fn(params, batch).block_until_ready(),
                            repeats=5)
            row = {"kernel": f"mp_stack_{tag}", "variant": variant,
                   "shape": f"P{p}xQ{q}xH{hidden}",
                   "graphs": ng, "real_nodes": tn,
                   "wall_us": round(wall * 1e6),
                   "note": ("full-bin forward wall; traffic summed over "
                            f"{n_layers} MP layers")}
            row.update(achieved_rates(fl, by, wall, bench_hardware()))
            rows.append(row)
    return {"full_bin": dict(FULL_BIN), "traffic_ratio": ratios,
            "fused_modeled_bytes": fused_bytes, "rows": rows}


def _memory_gate(fused_bytes):
    """Fused modeled bytes ≤ checked-in baseline × 1.2 per variant."""
    with open(BASELINE_PATH) as f:
        base = json.load(f)
    checks = {}
    for variant, by in fused_bytes.items():
        ref = base["fused_modeled_bytes"][variant]
        checks[variant] = {"bytes": by, "baseline": ref,
                           "ratio": round(by / ref, 3),
                           "ok": bool(by <= 1.2 * ref)}
    return checks


def _precision(hidden: int, epochs: int = 20):
    """bf16 end-to-end (engine, artifact round-trip, serving stats)
    MAPE drift vs f32 on the eval set, plus the int8-weight artifact
    path.

    The drift is measured with a *trained* predictor on the zoo eval
    dataset: MAPE is relative to the f32 predictions, so the metric is
    only meaningful when those predictions sit at calibrated physical
    magnitudes — an underfit model that decodes some graph to ~0 ms
    divides by the ``1e-6`` floor and reports metric noise, not
    precision drift (measured: random-init params swing 0.4–4.6 %
    across seeds; the trained predictor sits at ~0.15 %)."""
    import dataclasses
    import os as _os
    import tempfile
    from repro.core.engine import PredictionEngine
    from repro.core.gnn import PMGNSConfig, mape
    from repro.dataset.builder import records_to_samples
    from repro.serve.artifact import load_artifact, save_artifact
    from repro.serve.service import PredictionService
    from repro.train.gnn_trainer import TrainConfig, train_pmgns

    from .common import bench_dataset

    samples = records_to_samples(bench_dataset(96))
    cfg32 = PMGNSConfig(hidden=hidden, layout="packed", dropout=0.0)
    cfg16 = dataclasses.replace(cfg32, precision="bf16")
    params, hist = train_pmgns(
        cfg32, samples, (), TrainConfig(epochs=epochs, batch_size=16,
                                        lr=1e-3, seed=0, mode="scan",
                                        scan_steps=16))
    e32 = PredictionEngine(params, cfg32)
    e16 = PredictionEngine(params, cfg16)
    e16.warmup()
    y32 = e32.predict_samples(samples)
    y16 = e16.predict_samples(samples)
    res = {
        "eval_graphs": len(samples),
        "train_epochs": epochs,
        "train_loss": round(hist[-1]["train_loss"], 4),
        "bf16_engine_mape": float(mape(y16, y32)),
        "bf16_warmup_max_abs_delta": e16.stats.bf16_max_abs_delta,
    }

    d = tempfile.mkdtemp(prefix="dippm_bench_")
    # bf16 *runtime* policy round-trips through a v3 artifact: the cfg
    # carries precision="bf16" (staging compression at load time) while
    # the weights stay f32 in the file — rounding the stored weights too
    # was measured at ~1.9 % MAPE, over the 0.5 % end-to-end gate.
    path16 = _os.path.join(d, "bf16_runtime.npz")
    save_artifact(path16, params, cfg16, precision="f32")
    p16, c16, _ = load_artifact(path16)
    er = PredictionEngine(p16, c16)
    yr = er.predict_samples(samples)
    res["bf16_artifact_mape"] = float(mape(yr, y32))
    res["bf16_artifact_precision"] = er.stats.precision
    with PredictionService(engine=er) as svc:
        st = svc.stats
        res["serve_precision"] = st.precision
        res["serve_bf16_delta_reported"] = st.bf16_max_abs_delta is not None

    f32_size = _os.path.getsize(path16)
    # bf16 *weight* encoding (explicit opt-in): half-size file, exact
    # uint16-bit-view round-trip — reported, not MAPE-gated
    pathw = _os.path.join(d, "bf16_weights.npz")
    save_artifact(pathw, params, cfg32, precision="bf16")
    pw, cw, _ = load_artifact(pathw)
    res["bf16_weights_size_ratio"] = round(
        _os.path.getsize(pathw) / f32_size, 3)
    res["bf16_weights_mape"] = float(
        mape(PredictionEngine(pw, cw).predict_samples(samples), y32))

    path8 = _os.path.join(d, "int8.npz")
    save_artifact(path8, params, cfg32, precision="int8-weights")
    with open(path8, "rb") as f:
        assert f.read(2) == b"PK"               # npz, not pickle
    p8, c8, _ = load_artifact(path8)            # allow_pickle=False inside
    y8 = PredictionEngine(p8, c8).predict_samples(samples)
    res["int8_size_ratio"] = round(_os.path.getsize(path8) / f32_size, 3)
    res["int8_artifact_mape"] = float(mape(y8, y32))
    res["int8_loads_unpickled"] = True
    return res


def run(n_graphs: int = 192, hidden: int = 64, repeats: int = 4,
        request_size: int = 8):
    samples = _mixed_zoo(n_graphs)
    thr = _throughput(samples, hidden, repeats, request_size)
    equiv = _equivalence(samples[:8] + samples[-4:], hidden)
    traffic = _modeled_traffic(samples, hidden)
    mem = _memory_gate(traffic["fused_modeled_bytes"])
    prec = _precision(hidden)

    res = {
        "n_graphs": len(samples),
        **thr,
        "equivalence_max_abs_diff": equiv,
        "roofline": traffic,
        "memory_gate": mem,
        "precision": prec,
    }
    res["ok"] = bool(
        all(d <= 1e-5 for route in equiv.values() for d in route.values())
        and thr["max_abs_diff"] <= 1e-5
        and all(r >= 1.3 for r in traffic["traffic_ratio"].values())
        and thr["stream"]["speedup"] >= 0.90
        and all(c["ok"] for c in mem.values())
        and prec["bf16_engine_mape"] <= 0.005
        and prec["bf16_artifact_mape"] <= 0.005
        and prec["int8_loads_unpickled"])
    res["artifact"] = write_json("BENCH_fused_mp.json", res)
    return res


def main():
    enable_compile_cache()
    res = run()
    st, bk = res["stream"], res["bulk"]
    print(f"stream : unfused {st['unfused_pred_per_s']:8.2f}/s  fused "
          f"{st['fused_pred_per_s']:8.2f}/s  ratio {st['speedup']:.2f}x "
          f"(no-regression gate ≥0.90x)")
    print(f"bulk   : unfused {bk['unfused_pred_per_s']:8.2f}/s  fused "
          f"{bk['fused_pred_per_s']:8.2f}/s  ratio {bk['speedup']:.2f}x")
    for v, r in res["roofline"]["traffic_ratio"].items():
        gate = res["memory_gate"][v]
        print(f"traffic: {v:9s} modeled HBM bytes unfused/fused = "
              f"{r:.2f}x (gate ≥1.3x); fused vs baseline "
              f"{gate['ratio']:.3f}x (gate ≤1.2x)")
    for row in res["roofline"]["rows"]:
        print(f"roofln : {row['kernel']:18s} {row['variant']:9s} "
              f"{row['achieved_gb_s']:7.2f} GB/s  "
              f"{row['pct_of_roofline']:5.1f}% of roofline  "
              f"[{row['bound']}-bound]")
    worst_ref = max(res["equivalence_max_abs_diff"]["ref"].values())
    worst_pl = max(res["equivalence_max_abs_diff"]["pallas"].values())
    print(f"equiv  : fused-vs-composed |diff| ref ≤ {worst_ref:.2e}, "
          f"pallas ≤ {worst_pl:.2e}  (gate ≤1e-5)")
    pr = res["precision"]
    print(f"bf16   : engine MAPE {pr['bf16_engine_mape']:.4%}, artifact "
          f"round-trip MAPE {pr['bf16_artifact_mape']:.4%} (gate ≤0.5%), "
          f"warmup |Δ| {pr['bf16_warmup_max_abs_delta']:.2e}")
    print(f"int8   : artifact {pr['int8_size_ratio']:.2f}x size, MAPE "
          f"{pr['int8_artifact_mape']:.4%}, allow_pickle=False load ok")
    print("PASS" if res["ok"] else "FAIL",
          "(gates: equiv ≤1e-5, traffic ≥1.3x, stream ≥0.90x, "
          "memory ≤1.2x baseline, bf16 ≤0.5% MAPE)")
    return 0 if res["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
