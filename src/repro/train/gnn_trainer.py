"""PMGNS training stack (paper §4.3, Table 3 settings) — scan-compiled.

Settings faithful to the paper: Adam, lr 2.754e-5 (their LR-finder value),
Huber loss, dropout 0.05, hidden 512, 70/15/15 split, MAPE metric. The
paper trains 10 epochs for the GNN comparison (Table 4) and 500 epochs for
the headline 1.9 % MAPE; both are reachable via ``TrainConfig.epochs``.

The trainer is built in four layers:

1. **Storage** — samples hold sparse edge lists
   (``repro.core.batching.GraphSample``); the dense ``[B, N, N]``
   adjacency exists only inside batch assembly, so host memory is
   O(nodes + edges) per sample. With ``PMGNSConfig(sparse_mp=True)``
   the adjacency never exists at all: segments carry padded edge lists
   (``edges [S, B, E, 2]`` + ``edge_mask``) and the model aggregates by
   segment gather/scatter — same schedule, same numerics (within float
   tolerance), O(N·F + E) device memory per batch row instead of O(N²).
   ``PMGNSConfig(layout="packed")`` goes further: each step's rows are
   flattened onto one packed node axis (``x [S, P, F]`` +
   ``graph_ids``, globally-offset edges, per-graph ``static``/``y``/
   ``wt``) under the *identical* batch schedule, cutting the padded row
   volume roughly in half while matching the sparse loss trajectory to
   float tolerance (dropout off — packed activation shapes draw a
   different dropout stream). Packed training is single-device:
   ``data_parallel`` needs the sparse layout's batch axis.
2. **Step fusion** — each epoch is stacked into per-bucket
   ``[num_steps, B, ...]`` device segments
   (:func:`~repro.core.batching.stack_epoch_segments`) and driven by
   ``jax.lax.scan`` over a fused loss+grad+update step with donated
   ``(params, opt_state)``: one dispatch per segment instead of per step.
   ``TrainConfig(mode="eager")`` keeps the un-fused per-step loop as the
   numerical reference; both modes share one batch schedule and one
   per-step RNG stream, so they match within float tolerance.
3. **Data parallelism** — ``TrainConfig(data_parallel=True)`` shards the
   scan's batch axis across all local devices via ``repro.compat.shard_map``
   with psum-averaged gradients; the same trainer runs 1-device and
   N-device unchanged (batch rows pad to a device multiple with
   zero-weight rows).
4. **Durability** — ``TrainConfig(checkpoint_dir=..., checkpoint_every=k)``
   checkpoints ``(params, opt_state, step, epoch, target-stats)`` through
   ``repro.checkpoint``; ``train_pmgns(resume_from=...)`` continues a run
   exactly (per-epoch RNG is derived from ``(seed, epoch)``, not carried
   state).

Targets are regressed in log1p space (4+ orders of magnitude spread);
MAPE is always computed in physical units after decoding, like the paper.
"""
from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..core.batching import (GraphSample, batches_by_bucket, collate,
                             collate_packed, cut_to_bucket, next_pow2,
                             pack_graphs, stack_epoch_segments)
from ..core.gnn import (PMGNSConfig, decode_targets, encode_targets, huber,
                        mape, pmgns_apply, pmgns_init)
from ..checkpoint import CheckpointManager, latest_step, restore_checkpoint
from ..optim import adam, constant

Params = Dict[str, Any]


@dataclasses.dataclass
class TrainConfig:
    epochs: int = 10
    batch_size: int = 32
    lr: float = 2.754e-5          # paper Table 3
    huber_delta: float = 1.0
    seed: int = 0
    log_every: int = 0            # 0 = silent
    grad_clip: Optional[float] = None   # global-norm clip (adam transform)
    mode: str = "scan"            # "scan" (fused) | "eager" (reference)
    scan_steps: int = 32          # max fused steps per compiled segment
    data_parallel: bool = False   # shard batch axis over local devices
    checkpoint_dir: Optional[str] = None
    checkpoint_every: int = 0     # epochs between checkpoints (0 = off)
    checkpoint_keep: int = 3


def _loss_terms(params, cfg: PMGNSConfig, batch, rng, delta, mean, std):
    """(Σ wt·huber, Σ wt·n_targets) — the weighted-loss building blocks.

    ``batch["wt"]`` (1 real row / 0 padding) makes batch-padding rows
    exact no-ops: they contribute nothing to either term, so a padded
    remainder step computes the same loss and gradients as the short
    batch it stands for.
    """
    pred = pmgns_apply(params, cfg, batch, train=True, rng=rng)
    target = (encode_targets(batch["y"]) - mean) / std
    h = huber(pred, target, delta)                       # [B, T]
    wt = batch.get("wt")
    if wt is None:
        wt = jnp.ones((h.shape[0],), h.dtype)
    return jnp.sum(h * wt[:, None]), jnp.sum(wt) * h.shape[-1]


def _target_stats(samples):
    """Per-target mean/std of the log-space labels over the train set.
    Training on standardized targets converges in O(100) steps instead of
    O(10k); the stats are FOLDED into the last FC layer afterwards
    (w'=w·σ, b'=b·σ+μ) so the saved model still predicts raw log-space —
    downstream code (DIPPM API, eval) is unchanged."""
    ys = np.stack([np.asarray(encode_targets(jnp.asarray(s.y)))
                   for s in samples])
    mean = ys.mean(axis=0)
    std = np.maximum(ys.std(axis=0), 1e-3)
    return jnp.asarray(mean, jnp.float32), jnp.asarray(std, jnp.float32)


def _fold_stats(params, cfg: PMGNSConfig, mean, std):
    import jax as _jax
    params = _jax.tree_util.tree_map(lambda x: x, params)  # shallow copy
    last = f"b{cfg.n_fc_blocks - 1}"
    head = dict(params["fc"][last])
    head["w"] = head["w"] * std[None, :]
    head["b"] = head["b"] * std + mean
    fc = dict(params["fc"])
    fc[last] = head
    out = dict(params)
    out["fc"] = fc
    return out


@partial(jax.jit, static_argnames=("cfg", "delta"))
def _eval_batch(params, cfg: PMGNSConfig, batch, delta: float = 1.0):
    """Per-graph (huber-loss, APE) rows — aggregated host-side so packed
    batches can drop their padded graph slots before averaging."""
    pred = pmgns_apply(params, cfg, batch, train=False)
    target = encode_targets(batch["y"])
    loss_rows = jnp.mean(huber(pred, target, delta), axis=-1)   # [B]
    pred_phys = decode_targets(pred)
    # per-target absolute percentage errors, summed (averaged outside)
    denom = jnp.maximum(jnp.abs(batch["y"]), 1e-6)
    ape = jnp.abs(pred_phys - batch["y"]) / denom       # [B, 3]
    return loss_rows, ape


def _eval_packed_batches(samples: Sequence[GraphSample],
                         batch_size: int) -> List[Dict[str, np.ndarray]]:
    """Packed eval bins at one shared budget triple (order-free metrics).

    Budgets are resolved once and passed through to both the packer and
    the collate, so every full bin lands on the same compiled
    ``_eval_batch`` shape instead of a tight per-bin signature.
    """
    from ..core.batching import resolve_packed_budgets
    total = sum(s.n_nodes for s in samples)
    nb, eb, gb = resolve_packed_budgets(
        min(next_pow2(batch_size * 256), next_pow2(max(total, 1))))
    bins = pack_graphs(samples, nb, eb, gb)
    return [collate_packed([samples[j] for j in idx], nb, eb, gb)
            for idx in bins]


def evaluate(params, cfg: PMGNSConfig, samples: Sequence[GraphSample],
             batch_size: int = 32) -> Dict[str, float]:
    """Loss + overall and per-target MAPE over a sample set.

    Batch layout follows ``cfg.resolved_layout`` — sparse eval batches
    carry padded edge lists and never densify the adjacency; packed eval
    bin-packs mixed-size graphs onto one flat node axis and masks the
    padded graph slots out of every metric. A graph over the top bucket
    is cut to it, as in training.
    """
    samples = [cut_to_bucket(s) for s in samples]
    layout = cfg.resolved_layout
    if layout == "packed":
        batches = _eval_packed_batches(samples, batch_size)
    else:
        batches = batches_by_bucket(samples, batch_size,
                                    sparse=layout == "sparse")
    losses, apes = [], []
    for b in batches:
        wt = b.pop("wt", None)
        jb = {k: jnp.asarray(v) for k, v in b.items()}
        loss_rows, ape = _eval_batch(params, cfg, jb)
        real = (np.asarray(wt) > 0 if wt is not None
                else np.ones(ape.shape[0], bool))
        losses.append(float(np.asarray(loss_rows)[real].sum()))
        apes.append(np.asarray(ape)[real])
    if not apes:
        return {"loss": float("nan"), "mape": float("nan")}
    ape_all = np.concatenate(apes, axis=0)
    n = ape_all.shape[0]
    out = {
        "loss": float(np.sum(losses) / n),
        "mape": float(ape_all.mean()),
        "mape_latency": float(ape_all[:, 0].mean()),
        "mape_energy": float(ape_all[:, 1].mean()),
        "mape_memory": float(ape_all[:, 2].mean()),
        "n": n,
    }
    return out


_PREDICT_ENGINE_CACHE: List[Any] = []   # [(params, cfg, engine)] — one slot


def predict_batch(params, cfg: PMGNSConfig,
                  samples: Sequence[GraphSample],
                  engine=None) -> np.ndarray:
    """Physical-unit predictions [n, 3] for a list of samples.

    Routed through the batched prediction engine (``repro.core.engine``)
    — bucketed, batched, one compiled apply per padded shape — so eval
    and serving share a single inference implementation. A one-slot
    module cache reuses the engine (and its compiled functions) across
    calls with the *same params object*; callers holding several models,
    or params trees rebuilt per call, should pass their own ``engine``
    (``DIPPM.engine()`` or a ``PredictionEngine``) to keep the
    compile-once-per-shape property.
    """
    if engine is not None:
        return engine.predict_samples(list(samples))
    from ..core.engine import EngineConfig, PredictionEngine
    from ..core.static_features import STATIC_FEATURE_DIM_EXT
    if not (_PREDICT_ENGINE_CACHE
            and _PREDICT_ENGINE_CACHE[0][0] is params
            and _PREDICT_ENGINE_CACHE[0][1] == cfg):
        eng = PredictionEngine(params, cfg, EngineConfig(
            extended_static=(cfg.static_dim == STATIC_FEATURE_DIM_EXT)))
        _PREDICT_ENGINE_CACHE[:] = [(params, cfg, eng)]
    return _PREDICT_ENGINE_CACHE[0][2].predict_samples(list(samples))


# ---------------------------------------------------------------------------
# scan-compiled epoch runner
# ---------------------------------------------------------------------------

def _make_step_body(model_cfg: PMGNSConfig, opt, delta, mean, std,
                    axis: Optional[str]):
    """Fused loss+grad+update step, the ``lax.scan`` body.

    With ``axis`` set (shard_map data parallelism) the batch rows on each
    device are a shard: the weight denominator and the gradients are
    psum-reduced so every device applies the identical global update.
    """
    def body(carry, xs):
        params, opt_state, step = carry
        batch, key = xs
        if axis is not None:
            key = jax.random.fold_in(key, jax.lax.axis_index(axis))

        def loss_fn(p):
            wl, wn = _loss_terms(p, model_cfg, batch, key, delta, mean, std)
            if axis is not None:
                wn = jax.lax.psum(wn, axis)
            return wl / jnp.maximum(wn, 1.0), (wl, wn)

        (_, (wl, wn)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        if axis is not None:
            grads = jax.lax.psum(grads, axis)
            wl = jax.lax.psum(wl, axis)
        params, opt_state = opt.update(step, opt_state, params, grads)
        return (params, opt_state, step + 1), (wl, wn)

    return body


def _make_segment_runner(model_cfg: PMGNSConfig, opt, delta, mean, std,
                         axis: Optional[str] = None, mesh=None):
    """Jitted ``(params, opt_state, step, batches, keys)`` epoch-segment
    runner: one ``lax.scan`` over ``[S, B, ...]`` stacked batches with
    ``(params, opt_state)`` donated, returning the summed weighted-loss
    terms for epoch-loss bookkeeping."""
    body = _make_step_body(model_cfg, opt, delta, mean, std, axis)

    def run(params, opt_state, step, batches, keys):
        (params, opt_state, step), (wl, wn) = jax.lax.scan(
            body, (params, opt_state, step), (batches, keys))
        return params, opt_state, step, jnp.sum(wl), jnp.sum(wn)

    if axis is not None:
        from jax.sharding import PartitionSpec as P
        from ..compat import shard_map
        run = shard_map(
            run, mesh=mesh,
            in_specs=(P(), P(), P(), P(None, axis), P()),
            out_specs=(P(), P(), P(), P(), P()))
    return jax.jit(run, donate_argnums=(0, 1))


def _epoch_rng(seed: int, epoch: int) -> np.random.Generator:
    """Shuffle RNG derived from (seed, epoch) — resume-safe by design."""
    return np.random.default_rng([seed, 1, epoch])


def _epoch_keys(seed: int, epoch: int, n_steps: int) -> jax.Array:
    """[n_steps, 2] dropout keys derived from (seed, epoch)."""
    base = jax.random.fold_in(jax.random.PRNGKey(seed), epoch)
    return jax.random.split(base, max(n_steps, 1))


def train_pmgns(
    model_cfg: PMGNSConfig,
    train_samples: Sequence[GraphSample],
    val_samples: Sequence[GraphSample] = (),
    cfg: TrainConfig = TrainConfig(),
    resume_from: Optional[str] = None,
) -> Tuple[Params, List[Dict[str, float]]]:
    """Train the PMGNS; returns (params, per-epoch history).

    ``resume_from`` points at a checkpoint directory (typically the same
    as ``cfg.checkpoint_dir``): the latest committed checkpoint restores
    ``(params, opt_state, step, epoch, target-stats)`` and training
    continues from the next epoch, bit-matching an uninterrupted run. If
    the directory has no committed checkpoint, training starts fresh —
    so a relaunch loop can always pass ``resume_from=checkpoint_dir``.

    Training batches by node bucket in every layout, so a sample over
    the top bucket is cut to it first
    (:func:`~repro.core.batching.cut_to_bucket`).
    """
    if cfg.mode not in ("scan", "eager"):
        raise ValueError(f"TrainConfig.mode must be 'scan' or 'eager', "
                         f"got {cfg.mode!r}")
    layout = model_cfg.resolved_layout
    if cfg.data_parallel and layout == "packed":
        raise ValueError(
            "data_parallel=True shards the scan's batch axis, but packed "
            "segments have no batch axis to shard (one flat node axis per "
            "step) — train data-parallel with layout='sparse' instead")
    train_samples = [cut_to_bucket(s) for s in train_samples]
    val_samples = [cut_to_bucket(s) for s in val_samples]
    key = jax.random.PRNGKey(cfg.seed)
    key, init_key = jax.random.split(key)
    params = pmgns_init(init_key, model_cfg)
    opt = adam(constant(cfg.lr), grad_clip_norm=cfg.grad_clip)
    opt_state = opt.init(params)
    step = jnp.zeros((), jnp.int32)
    t_mean, t_std = _target_stats(train_samples)
    start_epoch = 0

    if resume_from is not None and latest_step(resume_from) is not None:
        like = {"params": params, "opt_state": opt_state,
                "step": np.zeros((), np.int32),
                "epoch": np.zeros((), np.int64),
                "t_mean": t_mean, "t_std": t_std}
        state = restore_checkpoint(resume_from, None, like)
        params = jax.tree_util.tree_map(jnp.asarray, state["params"])
        opt_state = jax.tree_util.tree_map(jnp.asarray, state["opt_state"])
        step = jnp.asarray(state["step"], jnp.int32)
        t_mean = jnp.asarray(state["t_mean"], jnp.float32)
        t_std = jnp.asarray(state["t_std"], jnp.float32)
        start_epoch = int(state["epoch"]) + 1

    axis, mesh, ndev = None, None, 1
    if cfg.data_parallel and cfg.mode != "scan":
        raise ValueError(
            "data_parallel=True requires mode='scan' — the eager reference "
            "loop is single-device by design")
    if cfg.data_parallel:
        from ..launch.mesh import make_mesh
        ndev = len(jax.devices())
        mesh = make_mesh((ndev,), ("data",))
        axis = "data"

    run_segment = _make_segment_runner(
        model_cfg, opt, cfg.huber_delta, t_mean, t_std, axis=axis, mesh=mesh)

    # eager reference path: same schedule, same keys, un-fused dispatch
    @partial(jax.jit, static_argnames=())
    def eager_grad(params, batch, key):
        def loss_fn(p):
            wl, wn = _loss_terms(p, model_cfg, batch, key,
                                 cfg.huber_delta, t_mean, t_std)
            return wl / jnp.maximum(wn, 1.0), (wl, wn)
        (_, (wl, wn)), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params)
        return wl, wn, grads

    eager_update = jax.jit(opt.update)

    mgr = None
    if cfg.checkpoint_dir:
        mgr = CheckpointManager(cfg.checkpoint_dir, keep=cfg.checkpoint_keep)

    history: List[Dict[str, float]] = []
    for epoch in range(start_epoch, cfg.epochs):
        t0 = time.time()
        segments = stack_epoch_segments(
            train_samples, cfg.batch_size, rng=_epoch_rng(cfg.seed, epoch),
            batch_multiple=ndev, max_steps=cfg.scan_steps, layout=layout)
        total_steps = sum(int(s["wt"].shape[0]) for s in segments)
        keys = _epoch_keys(cfg.seed, epoch, total_steps)
        wl_sum, wn_sum, k0 = 0.0, 0.0, 0
        for seg in segments:
            n_steps = int(seg["wt"].shape[0])
            seg_keys = keys[k0:k0 + n_steps]
            k0 += n_steps
            if cfg.mode == "scan":
                batches = {k: jnp.asarray(v) for k, v in seg.items()}
                params, opt_state, step, wl, wn = run_segment(
                    params, opt_state, step, batches, seg_keys)
                wl_sum += float(wl)
                wn_sum += float(wn)
            else:
                # reference loop: per-step host→device transfer + two
                # dispatches + blocking loss sync, like the pre-scan trainer
                for si in range(n_steps):
                    b = {k: jnp.asarray(v[si]) for k, v in seg.items()}
                    wl, wn, grads = eager_grad(params, b, seg_keys[si])
                    params, opt_state = eager_update(step, opt_state,
                                                     params, grads)
                    step = step + 1
                    wl_sum += float(wl)
                    wn_sum += float(wn)
        rec = {"epoch": epoch, "train_loss": wl_sum / max(wn_sum, 1.0),
               "steps": total_steps, "seconds": time.time() - t0}
        if val_samples:
            folded = _fold_stats(params, model_cfg, t_mean, t_std)
            rec.update({f"val_{k}": v for k, v in
                        evaluate(folded, model_cfg, val_samples,
                                 cfg.batch_size).items()})
        history.append(rec)
        if mgr is not None and cfg.checkpoint_every and \
                (epoch + 1) % cfg.checkpoint_every == 0:
            mgr.save(int(step), {
                "params": params, "opt_state": opt_state,
                "step": np.asarray(int(step), np.int32),
                "epoch": np.asarray(epoch, np.int64),
                "t_mean": t_mean, "t_std": t_std})
        if cfg.log_every and (epoch % cfg.log_every == 0):
            print(f"[pmgns] epoch {epoch}: "
                  + " ".join(f"{k}={v:.4g}" for k, v in rec.items()
                             if k != "epoch"))
    if mgr is not None:
        mgr.wait()
    if not history and start_epoch > 0:
        # resumed at/past cfg.epochs: the run is already complete. Emit
        # one terminal record so relaunch loops indexing hist[-1] work.
        rec = {"epoch": start_epoch - 1, "train_loss": float("nan"),
               "steps": 0, "seconds": 0.0, "resumed_complete": True}
        if val_samples:
            folded = _fold_stats(params, model_cfg, t_mean, t_std)
            rec.update({f"val_{k}": v for k, v in
                        evaluate(folded, model_cfg, val_samples,
                                 cfg.batch_size).items()})
        history.append(rec)
    return _fold_stats(params, model_cfg, t_mean, t_std), history
