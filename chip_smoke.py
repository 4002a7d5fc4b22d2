#!/usr/bin/env python3
"""Bring-up check: the packed PMGNS serving path on a TPU, end to end.

    python3 chip_smoke.py [--seed N]      # one chip
    python3 chip_smoke.py --chips 4       # the four-chip paths only

One chip: builds PMGNS at the paper's width (GraphSAGE, hidden 512, three
GNN and three FC blocks; ``layout="packed"``, ``use_pallas=True``) with
random weights from ``--seed``; traces Table-2 zoo models at published
sizes and one published-width LLM through the normal frontends; opens
``DIPPM.serve``, warms the whole packed rung ladder and serves every
request; checks each prediction against the same parameters through the
lax path on the host CPU; then runs a few ``train_pmgns`` scan steps on
a small factory dataset.

``--chips 4``: the same requests through ``ServeConfig(replicas=4)``
against ``replicas=1``, and ``data_parallel=True`` training on four
chips against one.

Exits non-zero, printing no result, when JAX finds no TPU or any check
fails. The last line of standard output is one JSON object:
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import shutil
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
sys.path.insert(0, str(ROOT / "src"))

# Predictions are compared in log1p space, the model's output space
# (``decode_targets`` is ``expm1``): there an error is scale-free, and
# random weights put some predictions near zero, where a relative error
# in physical units means nothing.
#: Served path against the CPU reference: at default precision every f32
#: matmul on the TPU, in XLA and inside the Pallas kernels, is one bf16
#: pass, and that rounding compounds through six layers at width 512.
LOG_TOL = 0.1
#: The same bins with matmul precision "highest" (full f32 passes), which
#: leaves only summation order between the chip and the CPU.
EXACT_LOG_TOL = 1e-4
#: Data-parallel against one-device training loss at matmul precision
#: "highest", relative: the psum of per-device gradients sums in another
#: order than one device does.
LOSS_RTOL = 5e-2

#: Zoo requests: Table-2 families at their published defaults (res 224).
ZOO_FAMILIES = ("resnet", "vgg", "vit", "mobilenet", "densenet",
                "efficientnet", "swin", "convnext")
ZOO_BATCHES = (1, 2, 4, 8, 16, 32, 64, 128)
LLM_ARCH = "qwen2.5-3b"
LLM_SHAPES = ((1, 256), (8, 1024))           # (batch, seq)


def log(msg: str) -> None:
    print(msg, flush=True)


def model_config(hidden: int = 512):
    from repro.core.gnn import PMGNSConfig
    return PMGNSConfig(hidden=hidden, layout="packed", use_pallas=True)


def trace_requests(zoo_families=ZOO_FAMILIES, zoo_batches=ZOO_BATCHES,
                   llm_arch=LLM_ARCH, llm_shapes=LLM_SHAPES):
    """Zoo graphs at published sizes + one published-width LLM."""
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as S

    from repro.configs import get_config
    from repro.core.frontends import from_jax
    from repro.models import lm
    from repro.zoo.families import trace_family

    graphs = [trace_family(f, {"batch": b})
              for f in zoo_families for b in zoo_batches]
    acfg = get_config(llm_arch)
    for batch, seq in llm_shapes:
        def fwd(params, tokens):
            return lm.forward(params, acfg, {"tokens": tokens})[0]
        graphs.append(from_jax(fwd, lm.param_specs(acfg),
                               S((batch, seq), jnp.int32),
                               meta={"family": llm_arch, "batch": batch,
                                     "seq": seq}))
    return graphs


def as_array(preds):
    import numpy as np
    return np.array([[p.latency_ms, p.energy_j, p.memory_mb] for p in preds],
                    dtype=np.float64)


def max_rel_err(got, ref):
    """Per-target max relative error, ``[3]``."""
    import numpy as np
    return (np.abs(got - ref) / np.maximum(np.abs(ref), 1e-30)).max(0)


def max_log_err(got, ref):
    """Per-target max ``|log1p(got) - log1p(ref)|``, ``[3]``.

    Values are floored at -0.99: below it float32 keeps too few bits of
    ``1 + y`` for its log to mean anything (such negative predictions
    come only from random weights).
    """
    import numpy as np

    def lg(v):
        return np.log1p(np.maximum(v, -0.99))
    return np.abs(lg(got) - lg(ref)).max(0)


def log_errors(what, got, ref, tol):
    rel, lg = max_rel_err(got, ref), max_log_err(got, ref)
    names = ("latency_ms", "energy_j", "memory_mb")
    log(f"{what}: max rel err " + " ".join(
        f"{n}={v:.3e}" for n, v in zip(names, rel)) + "; max log1p err "
        + " ".join(f"{n}={v:.3e}" for n, v in zip(names, lg))
        + f" (tolerance {tol})")
    return bool((lg <= tol).all())


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SystemExit(f"FAILED: {what}")


def custom_calls_per_rung(engine, cfg):
    """``tpu_custom_call`` count of each compiled rung of the ladder."""
    import jax.numpy as jnp

    from repro.core.batching import packed_rung_ladder, resolve_packed_budgets
    from repro.core.gnn import (make_staged_packed_infer_fn,
                                packed_staging_layout)
    ec = engine.engine_cfg
    out = {}
    for p, q, g in packed_rung_ladder(*resolve_packed_budgets(
            ec.node_budget, ec.edge_budget, ec.graph_budget)):
        _, _, _, f_len, i_len = packed_staging_layout(cfg, p, q, g)
        fn = make_staged_packed_infer_fn(cfg, p, q, g)
        # the arguments warmup ran with, so the persistent cache can hit
        text = fn.lower(engine.params, jnp.zeros((f_len,), jnp.float32),
                        jnp.zeros((i_len,), jnp.int32)).compile().as_text()
        out[(p, q, g)] = text.count("tpu_custom_call")
    return out


def serve_requests(dippm, graphs, **serve_kw):
    """Serve ``graphs`` through ``dippm.serve``: warm every rung, submit
    each graph, collect every future. Returns (predictions, stats)."""
    import numpy as np
    svc = dippm.serve(max_wait_ms=60_000.0, max_batch_graphs=4096,
                      **serve_kw)
    try:
        t0 = time.perf_counter()
        n_compiled = svc.warmup(rungs="all")
        compile_s = time.perf_counter() - t0
        before = svc.engine.stats.recompiles
        t0 = time.perf_counter()
        futures = [svc.submit(g) for g in graphs]
        svc.flush()
        preds = [f.result(timeout=600) for f in futures]
        window_s = time.perf_counter() - t0
        st = svc.stats
        est = svc.engine.stats
        info = {"compiled": n_compiled, "compile_s": compile_s,
                "window_s": window_s,
                "recompiles_in_window": est.recompiles - before,
                "serve": st, "engine": est, "engine_obj": svc.engine}
        return np.asarray(as_array(preds)), info
    finally:
        svc.close()


def one_chip(seed: int, hidden: int = 512, train_graphs: int = 32,
             train_epochs: int = 3, requests=None) -> None:
    import jax
    import numpy as np

    from repro.core.batching import (packed_shape, resolve_packed_budgets,
                                     sample_from_graph)
    from repro.core.engine import EngineConfig, PredictionEngine
    from repro.core.gnn import pmgns_init
    from repro.core.predictor import DIPPM

    t_setup = time.perf_counter()
    cfg = model_config(hidden)
    params = pmgns_init(jax.random.PRNGKey(seed), cfg)
    dippm = DIPPM.from_params(params, cfg)
    graphs = requests if requests is not None else trace_requests()
    trace_s = time.perf_counter() - t_setup
    log(f"trace: {len(graphs)} requests "
        f"({sum(g.num_nodes for g in graphs)} nodes) in {trace_s:.2f} s")

    # the bins the service will plan, to show the top rung is reached
    ecfg = EngineConfig()
    budgets = resolve_packed_budgets(ecfg.node_budget, ecfg.edge_budget,
                                     ecfg.graph_budget)
    samples = [sample_from_graph(g, buckets=ecfg.buckets) for g in graphs]
    shapes = [packed_shape([samples[i] for i in b], *budgets)
              for b in PredictionEngine(params, cfg, ecfg).plan_bins(samples)]
    log(f"bins planned: {len(shapes)}, shapes (P, Q, G): {shapes}")
    check(max(p for p, _, _ in shapes) == budgets[0],
          "no bin reaches the top rung")

    y, info = serve_requests(dippm, graphs)
    st, est = info["serve"], info["engine"]
    log(f"compile: warmup compiled {info['compiled']} rungs in "
        f"{info['compile_s']:.2f} s; set-up (trace + warmup) "
        f"{trace_s + info['compile_s']:.2f} s")
    log(f"serve: {len(graphs)} requests in {info['window_s']:.3f} s; "
        f"completed={st.completed} failed={st.failed} "
        f"poisoned={st.poisoned} bins={st.bins} "
        f"recompiles_in_window={info['recompiles_in_window']}")
    log(f"kernels: impl={est.kernel_impl} "
        f"fused_kernel_layers={est.fused_kernel_layers} "
        f"fused_segment_layers={est.fused_segment_layers}")
    calls = custom_calls_per_rung(info["engine_obj"], cfg)
    for shape, n in calls.items():
        log(f"tpu_custom_calls rung P={shape[0]} Q={shape[1]} "
            f"G={shape[2]}: {n}")

    # independent reference: same params, lax path, host CPU
    cpu = jax.devices("cpu")[0]
    ref_cfg = dataclasses.replace(cfg, use_pallas=False)
    y_ref = PredictionEngine(params, ref_cfg, ecfg,
                             device=cpu).predict_samples(samples)
    served_ok = log_errors("served vs CPU reference", y, y_ref, LOG_TOL)
    with jax.default_matmul_precision("highest"):
        y_hi = PredictionEngine(params, cfg, ecfg).predict_samples(samples)
    exact_ok = log_errors("precision=highest vs CPU reference", y_hi, y_ref,
                          EXACT_LOG_TOL)

    check(st.completed == len(graphs), "not every request completed")
    check(st.failed == 0 and st.poisoned == 0, "failed or poisoned requests")
    check(np.isfinite(y).all(), "non-finite predictions")
    check(est.kernel_impl == "pallas", "kernels did not dispatch to Pallas")
    check(est.fused_segment_layers == 0, "a fused layer left the kernel")
    check(est.fused_kernel_layers == 3 * est.cache_entries,
          "not every compiled shape runs its three fused layers")
    check(all(n == 4 for n in calls.values()),
          "a rung lacks its 3 fused layers + readout custom calls")
    check(served_ok, "served predictions off the CPU reference")
    check(exact_ok, "precision=highest predictions off the CPU reference")

    hist = train_steps(seed, hidden, train_graphs, train_epochs)
    losses = [h["train_loss"] for h in hist]
    log(f"train: {sum(h['steps'] for h in hist)} scan steps, "
        f"loss per epoch {losses}")
    check(bool(np.isfinite(losses).all()), "non-finite training loss")
    check(losses[-1] < losses[0], "training loss did not fall")


def factory_samples(seed: int, n_graphs: int):
    """A small factory dataset built in-process from ``seed``."""
    from repro.dataset.builder import records_to_samples
    from repro.dataset.factory import FactoryConfig, build, iter_records
    out = ROOT / "artifacts" / "chip_smoke" / f"dataset-{seed}-{n_graphs}"
    shutil.rmtree(out, ignore_errors=True)
    build(str(out), FactoryConfig(n_graphs=n_graphs, seed=seed,
                                  shard_size=n_graphs), workers=1)
    return records_to_samples(list(iter_records(str(out))))


def train_steps(seed: int, hidden: int, n_graphs: int, epochs: int,
                layout: str = "packed", data_parallel: bool = False,
                samples=None, batch_size: int = 8, **model_kw):
    """A few scan steps of ``train_pmgns``; returns the epoch history.

    Training runs the composed lax path (gradients do not flow through
    the inference kernels), on the default device, or data-parallel
    over every local device.
    """
    from repro.core.gnn import PMGNSConfig
    from repro.train.gnn_trainer import TrainConfig, train_pmgns
    samples = samples or factory_samples(seed, n_graphs)
    _, hist = train_pmgns(
        PMGNSConfig(hidden=hidden, layout=layout, **model_kw), samples,
        cfg=TrainConfig(epochs=epochs, batch_size=batch_size, lr=1e-3,
                        seed=seed,
                        data_parallel=data_parallel))
    return hist


def four_chips(seed: int, hidden: int = 512, train_graphs: int = 32,
               train_epochs: int = 3, requests=None) -> None:
    import jax
    import numpy as np

    from repro.core.gnn import pmgns_init
    from repro.core.predictor import DIPPM

    devices = jax.devices()
    check(len(devices) == 4, f"--chips 4 needs 4 devices, "
          f"found {len(devices)}")
    cfg = model_config(hidden)
    dippm = DIPPM.from_params(pmgns_init(jax.random.PRNGKey(seed), cfg), cfg)
    graphs = requests if requests is not None else trace_requests()
    log(f"setup: {len(graphs)} requests traced")

    y1, one = serve_requests(dippm, graphs)
    y4, four = serve_requests(dippm, graphs, replicas=4)
    st = four["serve"]
    homes = [sorted({d.id for leaf in jax.tree_util.tree_leaves(r.params)
                     for d in leaf.devices()})
             for r in four["engine_obj"].replicas]
    log(f"replicas=1: compile {one['compile_s']:.2f} s, serve "
        f"{one['window_s']:.3f} s; replicas=4: compile "
        f"{four['compile_s']:.2f} s, serve {four['window_s']:.3f} s")
    log(f"replica params on device ids {homes}; bins per replica "
        f"{list(st.replica_bins)}")
    # same bins, same program, same kind of chip: only the device differs
    same = log_errors("replicas=4 vs replicas=1", y4, y1, EXACT_LOG_TOL)
    check(st.completed == len(graphs) and st.failed == 0
          and st.poisoned == 0, "replicas=4 lost requests")
    check(all(len(h) == 1 for h in homes)
          and len({h[0] for h in homes}) == 4,
          "replica params are not on four distinct devices")
    check(len(st.replica_bins) == 4 and min(st.replica_bins) > 0,
          "bins did not reach all four replicas")
    check(same, "replicas=4 differs from replicas=1")

    # the packed layout has no batch axis to shard; dropout masks are
    # drawn per device, so only dropout-free runs compare step for step.
    # Data-parallel rounds each bucket's batch cap up to a multiple of 4:
    # at batch 16 every cap of this dataset already is one, so one chip
    # and four run the same batch schedule (checked by the step counts).
    samples = factory_samples(seed, train_graphs)

    def run(precision, data_parallel):
        with jax.default_matmul_precision(precision):
            hist = train_steps(seed, hidden, 0, train_epochs,
                               layout="sparse", data_parallel=data_parallel,
                               samples=samples, batch_size=16, dropout=0.0)
        return (np.array([h["train_loss"] for h in hist]),
                [h["steps"] for h in hist])

    rels = {}
    for precision in ("default", "highest"):
        (l1, s1), (l4, s4) = run(precision, False), run(precision, True)
        rels[precision] = float(np.max(np.abs(l4 - l1) / np.abs(l1)))
        log(f"train (precision={precision}): data_parallel over 4 chips "
            f"loss {l4.tolist()} vs one chip {l1.tolist()}; max rel diff "
            f"{rels[precision]:.3e}; steps per epoch {s4} vs {s1}")
        check(s1 == s4, "one chip and four ran different batch schedules")
        check(bool(np.isfinite(l4).all()) and l4[-1] < l4[0],
              "data-parallel loss not finite or not falling")
    check(rels["highest"] <= LOSS_RTOL,
          "data-parallel loss differs from one chip")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    # the reference runs on the host CPU in this process: keep that
    # backend available where the platforms are pinned
    plats = os.environ.get("JAX_PLATFORMS")
    if plats and "cpu" not in plats.split(","):
        os.environ["JAX_PLATFORMS"] = plats + ",cpu"
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX's first device is {dev.platform!r}",
              file=sys.stderr)
        return 1
    from repro.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")
    log(f"device: {dev.device_kind} x{len(jax.devices())}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(args.seed)
    log(f"total: {time.perf_counter() - t0:.2f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
