"""Batched multi-graph prediction engine — DIPPM's one inference path.

Every prediction runs here: ``DIPPM.predict_graph`` / ``predict_many``,
the serving micro-batcher (``repro.serve``) and the trainer's eval
(``repro.train.gnn_trainer.predict_batch``). The engine serves packed
bins whatever ``PMGNSConfig.layout`` the model was trained in: that
field only selects the trainer's batch layout, and ``pmgns_init``
builds the same parameter tree for all three, so the engine's ``cfg``
is the model's with ``layout="packed"``.

:class:`PredictionEngine`:

1. **Pack** — a greedy token-budget bin-packer
   (:func:`~repro.core.batching.pack_graphs`) mixes graphs of different
   sizes onto one flat node axis under the ``(node, edge, graph)``
   budgets of :class:`EngineConfig`.
2. **Stage** — each bin ships as two flat staging buffers
   (:meth:`PredictionEngine._stage_packed`).
3. **Compile once per rung** — the compile cache is keyed by the
   bin's ``(P, Q, G)`` rung (:func:`~repro.core.batching.packed_shape`),
   a handful of shapes for any traffic mix; a lone graph over a budget
   runs whole at a power-of-two shape past the ladder.
4. **Restore order** — results are scattered back to input positions,
   so ``engine.predict_graphs(gs)[i]`` always corresponds to ``gs[i]``.

Typical use goes through :meth:`repro.core.predictor.DIPPM.predict_many`;
instantiate the engine directly only to tune the budgets or to
pre-compile with :meth:`PredictionEngine.warmup`.
"""
from __future__ import annotations

import dataclasses
import threading
from typing import List, Optional, Sequence, Tuple

import numpy as np

from .batching import (DEFAULT_BUCKETS, DEFAULT_NODE_BUDGET, GraphSample,
                       collate_packed, pack_graphs, packed_rung,
                       packed_rung_ladder, packed_shape,
                       resolve_packed_budgets, sample_from_graph)
from ..kernels import ops as kernel_ops
from .gnn import (PMGNSConfig, fused_kernel_plan,
                  make_staged_packed_infer_fn, packed_staging_layout)
from .ir import OpGraph
from . import spans
from .static_features import STATIC_FEATURE_DIM, STATIC_FEATURE_DIM_EXT


class PredictionInvalidError(RuntimeError):
    """The engine produced non-finite (NaN/Inf) outputs for a bin.

    Degenerate inputs (NaN node statistics, overflowing feature
    magnitudes) silently corrupt every downstream consumer if the raw
    vector is returned — or worse, cached. :meth:`PredictionEngine.run_bin`
    validates outputs and raises this instead; ``bad_rows`` lists the
    in-chunk indices whose output rows were non-finite (advisory: with
    gather/scatter kernels a NaN can bleed across rows of a packed bin,
    so the serving layer isolates the true poison request by split-retry
    bisection rather than trusting the row list).
    """

    def __init__(self, message: str, bad_rows: Tuple[int, ...] = ()):
        super().__init__(message)
        self.bad_rows = tuple(bad_rows)


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Knobs for the batched prediction engine.

    ``buckets`` are the node buckets (the training buckets) that
    :func:`~repro.core.batching.sample_from_graph` pads a sample's ``x``
    to; the packed bins read only its real rows.
    """

    buckets: Tuple[int, ...] = DEFAULT_BUCKETS
    extended_static: bool = False
    #: Packed bin budgets: every bin pads onto the ``(node_budget,
    #: edge_budget, graph_budget)`` rung ladder
    #: (``repro.core.batching.packed_shape``), so the whole engine
    #: compiles a handful of shapes (oversize lone graphs escalate).
    #: ``None`` edge/graph budgets resolve via
    #: ``repro.core.batching.resolve_packed_budgets`` (``2·node_budget``
    #: edges, ``node_budget // 16`` graphs).
    node_budget: int = DEFAULT_NODE_BUDGET
    edge_budget: Optional[int] = None
    graph_budget: Optional[int] = None
    #: Validate bin outputs for NaN/Inf and raise
    #: :class:`PredictionInvalidError` instead of returning (or letting
    #: serving cache) silently corrupt numbers. The check is a
    #: ``np.isfinite`` pass over the tiny ``[G, n_targets]`` output —
    #: negligible next to the apply itself.
    validate_outputs: bool = True


@dataclasses.dataclass
class EngineStats:
    """Counters exposed as :attr:`PredictionEngine.stats`.

    ``cache_entries`` is the live number of distinct compiled shapes and
    ``recompiles`` the number of compilation events (they coincide until
    an eviction story exists — both are kept so dashboards distinguish
    steady-state size from churn). ``node_slots_total`` /
    ``node_slots_real`` count padded vs real node rows shipped to the
    device; :attr:`padding_waste_frac` is the derived waste ratio the
    packed layout exists to crush.
    """

    graphs_predicted: int = 0
    batches_run: int = 0
    cache_hits: int = 0
    cache_misses: int = 0
    cache_entries: int = 0
    recompiles: int = 0
    node_slots_total: int = 0
    node_slots_real: int = 0
    #: Active inference precision policy (``cfg.resolved_precision``).
    precision: str = "f32"
    #: Max |bf16 − f32| prediction delta measured on a synthetic packed
    #: batch at warmup (``None`` until a bf16 engine warms up).
    bf16_max_abs_delta: Optional[float] = None
    #: What the model's kernel dispatchers run: ``"pallas"`` when the
    #: model asks for kernels (``use_pallas``) on a TPU backend, else
    #: ``"ref"`` (the lax twins; ``repro.kernels.ops``).
    kernel_impl: str = "ref"
    #: Fused message-passing layers, summed over the compiled packed
    #: shapes, planned onto the Pallas kernel / onto the lax segment
    #: composition because their VMEM state does not fit
    #: (``repro.core.gnn.fused_kernel_plan``; per shape in
    #: :attr:`PredictionEngine.layer_plans`).
    fused_kernel_layers: int = 0
    fused_segment_layers: int = 0
    #: Packed bins of one graph over a node or edge budget, run whole at
    #: a power-of-two shape past the rung ladder.
    lone_bins: int = 0

    @property
    def padding_waste_frac(self) -> float:
        """Fraction of device node rows that were padding (0.0 if no
        batch has run yet)."""
        if self.node_slots_total <= 0:
            return 0.0
        return 1.0 - self.node_slots_real / self.node_slots_total

    def snapshot(self) -> "EngineStats":
        """A detached copy (for ``predict_many(..., return_stats=True)``)."""
        return dataclasses.replace(self)


class PredictionEngine:
    """Order-preserving batched inference over many ``OpGraph``s.

    Holds trained PMGNS ``params`` + ``cfg`` and a compiled-function cache
    keyed on the packed ``(P, Q, G)`` bin shape. :meth:`run_bin` — the
    single device-dispatch entry shared by :meth:`predict_samples` and
    the serving micro-batcher (``repro.serve``) — is **thread-safe**: an
    internal lock guards the stats counters and compiled-shape
    bookkeeping only, while staging (thread-local buffers) and the
    jitted device call run unlocked, so concurrent callers — and the
    replica workers of a serving fleet — execute bins in parallel.
    ``device=`` binds the engine (params + every jitted apply) to one
    jax device.
    """

    def __init__(self, params, cfg: PMGNSConfig,
                 engine_cfg: EngineConfig = EngineConfig(), *,
                 device=None):
        feat_dim = (STATIC_FEATURE_DIM_EXT if engine_cfg.extended_static
                    else STATIC_FEATURE_DIM)
        if cfg.static_dim != feat_dim:
            raise ValueError(
                f"extended_static={engine_cfg.extended_static} produces "
                f"{feat_dim}-dim static features but the model was built "
                f"with PMGNSConfig(static_dim={cfg.static_dim})")
        #: Optional jax device this engine is bound to. Committing the
        #: params pins every jitted apply to that device (staging buffers
        #: are uncommitted numpy and follow the params), which is how a
        #: serving :class:`~repro.serve.fleet.ReplicaPool` runs N
        #: replicas side by side on a multi-device host mesh.
        self.device = device
        if device is not None:
            import jax
            params = jax.device_put(params, device)
        self.params = params
        #: The model's config with ``layout="packed"``: whatever layout
        #: the model was trained in, the engine serves packed bins (the
        #: parameter tree does not depend on the layout).
        self.cfg = cfg = dataclasses.replace(cfg, layout="packed")
        self.engine_cfg = engine_cfg
        self.stats = EngineStats()
        #: bf16 precision = *staging* compression on the packed hot
        #: path: the per-request host→device float buffer ships as
        #: bfloat16 (half the recurring transfer bytes) and the staged
        #: infer fn upcasts to f32 before compute
        #: (``make_staged_packed_infer_fn``). Parameters stay f32 —
        #: they transfer once at load, and holding them in bf16 was
        #: measured at ~1.9 % prediction MAPE vs ~0.4 % for
        #: staging-only (``benchmarks/fused_mp.py`` gates ≤ 0.5 %).
        #: ``int8-weights`` is artifact-level (``serve.artifact``), so
        #: runtime behaves as f32 here.
        self._precision = cfg.resolved_precision
        self.stats.precision = self._precision
        self.stats.kernel_impl = ("pallas" if cfg.use_pallas
                                  and kernel_ops.kernel_impl() == "pallas"
                                  else "ref")
        if self._precision == "bf16":
            import ml_dtypes
            self._stage_dtype = ml_dtypes.bfloat16
        else:
            self._stage_dtype = np.float32
        self._budgets = resolve_packed_budgets(
            engine_cfg.node_budget, engine_cfg.edge_budget,
            engine_cfg.graph_budget)
        # One staged-buffer closure per packed (P, Q, G) shape (two flat
        # host→device transfers per bin); the key set tracks which shapes
        # have compiled, for stats.
        self._staged: dict = {}
        self._compiled_shapes: set = set()
        #: ``(P, Q, G)`` → ``(kernel, segment)``: how many message-passing
        #: layers of each compiled packed shape run the fused Pallas
        #: kernel and how many the lax segment composition.
        self.layer_plans: dict = {}
        #: Guards stats counters + compiled-shape bookkeeping ONLY (not
        #: the jitted call): concurrent submitters — the serving
        #: micro-batcher, replica-pool workers, parallel sweeps — share
        #: one engine and still execute on the device concurrently.
        self._lock = threading.RLock()

    # -- compiled-fn cache ---------------------------------------------------
    def _track_shape(self, key: Tuple) -> None:
        if key in self._compiled_shapes:
            self.stats.cache_hits += 1
        else:
            self.stats.cache_misses += 1
            self.stats.recompiles += 1
            self._compiled_shapes.add(key)
            self.stats.cache_entries = len(self._compiled_shapes)

    def _packed_fn(self, p: int, q: int, g: int):
        with self._lock:
            self._track_shape(("packed", p, q, g))
            key = (p, q, g)
            if key not in self._staged:
                self._staged[key] = make_staged_packed_infer_fn(
                    self.cfg, p, q, g)
                kern, seg = self.layer_plans[key] = fused_kernel_plan(
                    self.cfg, p)
                self.stats.fused_kernel_layers += kern
                self.stats.fused_segment_layers += seg
            return self._staged[key]

    def warmup(self, rungs=None) -> int:
        """Pre-compile budget rungs before traffic (serving cold-start).

        ``rungs=None`` compiles the top rung, the shape full bins hit
        (``P`` = the node budget with its typical-density edge/graph
        rungs; part-full bins on lower rungs still compile on first
        sight). ``"all"`` precompiles the whole typical-density ladder
        (:func:`repro.core.batching.packed_rung_ladder` — steady
        traffic at any request *size* then runs compile-free; bins that
        escalate past a rung on edge density or graph count still
        compile on first sight), and a sequence of ``P`` values selects
        specific rungs. Returns the number of functions compiled.
        """
        import jax.numpy as jnp
        nb, eb, gb = self._budgets
        if rungs is None:
            shapes = [(nb, *packed_rung(nb, eb, gb))]
        elif rungs == "all":
            shapes = packed_rung_ladder(nb, eb, gb)
        else:
            shapes = [(int(p), *packed_rung(int(p), eb, gb))
                      for p in rungs]
        # before/compile/after all under the lock: a concurrent run_bin
        # compiling its own shape mid-warmup must not leak into the
        # returned count
        with self._lock:
            before = self.stats.cache_misses
            for p, q, g in shapes:
                fn = self._packed_fn(p, q, g)
                _, _, _, f_len, i_len = packed_staging_layout(
                    self.cfg, p, q, g)
                fn(self.params,
                   jnp.zeros((f_len,), self._stage_dtype),
                   jnp.zeros((i_len,), jnp.int32)).block_until_ready()
            if self._precision == "bf16":
                self.stats.bf16_max_abs_delta = self._measure_bf16_delta()
            return self.stats.cache_misses - before

    def _measure_bf16_delta(self) -> float:
        """Max |bf16 − f32| prediction delta on one synthetic packed bin.

        Runs the engine's bf16 staged path and an f32 twin of the same
        ``(P, Q, G)`` shape over identical random inputs and compares
        real graph rows — the per-warmup numerics probe surfaced as
        ``EngineStats.bf16_max_abs_delta``.
        """
        import jax.numpy as jnp

        nb, eb, gb = self._budgets
        p = min(nb, 256)
        q, g = packed_rung(p, eb, gb)
        feat, sdim = self.cfg.node_feat_dim, self.cfg.static_dim
        o1, o2, o3, f_len, i_len = packed_staging_layout(self.cfg, p, q, g)
        rng = np.random.default_rng(0)
        n_real, q_real, g_real = p * 7 // 8, q // 2, max(g // 2, 1)
        fbuf = np.zeros(f_len, np.float32)
        ibuf = np.zeros(i_len, np.int32)
        x = fbuf[:o1].reshape(p, feat)
        x[:n_real] = rng.standard_normal((n_real, feat)).astype(np.float32)
        fbuf[o1:o1 + n_real] = 1.0                      # node mask
        fbuf[o2:o2 + q_real] = 1.0                      # edge mask
        fbuf[o3:] = rng.standard_normal(g * sdim).astype(np.float32)
        ibuf[:2 * q_real] = rng.integers(0, n_real, 2 * q_real)
        ibuf[2 * q:] = np.minimum(np.arange(p) * g_real // max(n_real, 1),
                                  g_real - 1)           # ascending ids
        y16 = np.asarray(self._packed_fn(p, q, g)(
            self.params, jnp.asarray(fbuf.astype(self._stage_dtype)),
            jnp.asarray(ibuf)))
        cfg32 = dataclasses.replace(self.cfg, precision="f32")
        y32 = np.asarray(make_staged_packed_infer_fn(cfg32, p, q, g)(
            self.params, jnp.asarray(fbuf), jnp.asarray(ibuf)))
        return float(np.max(np.abs(y16[:g_real] - y32[:g_real])))

    # -- packed bins ---------------------------------------------------------
    @staticmethod
    def _apply(fn, args, graphs: int, new_shape: Optional[dict],
               plan: Tuple[int, int]):
        """Call a jitted apply and fetch its result to the host: under
        ``dippm.compile`` (stats: the shape) when ``new_shape`` is given,
        the shape's first call, else under ``dippm.run``. Both carry the
        shape's layer ``plan`` (kernel and segment layers)."""
        kern, seg = plan
        span = (spans.TraceAnnotation(spans.RUN, graphs=graphs,
                                      kernel_layers=kern, segment_layers=seg)
                if new_shape is None
                else spans.TraceAnnotation(spans.COMPILE, **new_shape,
                                           kernel_layers=kern,
                                           segment_layers=seg))
        with span:
            dev = fn(*args)
            with spans.TraceAnnotation(spans.FETCH):
                return np.asarray(dev)

    def _stage_packed(self, chunk: Sequence[GraphSample], p: int, q: int,
                      g: int) -> Tuple[np.ndarray, np.ndarray]:
        """Packed chunk builder: flatten a bin into the two staging
        buffers consumed by the staged infer fn (float32:
        ``x ⊕ mask ⊕ edge_mask ⊕ static``; int32:
        ``edges ⊕ graph_ids``). The fill itself is
        :func:`~repro.core.batching.collate_packed` writing through
        views into the flat buffers — one layout source of truth, one
        pass, zero extra copies.
        """
        feat = self.cfg.node_feat_dim
        sdim = self.cfg.static_dim
        o1, o2, o3, f_len, i_len = packed_staging_layout(self.cfg, p, q, g)
        with spans.TraceAnnotation(spans.STAGE, graphs=len(chunk), p=p, q=q,
                                   g=g):
            fbuf = np.zeros(f_len, self._stage_dtype)
            ibuf = np.zeros(i_len, np.int32)
            collate_packed(chunk, out={
                "x": fbuf[:o1].reshape(p, feat),
                "mask": fbuf[o1:o2],
                "edge_mask": fbuf[o2:o3],
                "static": fbuf[o3:].reshape(g, sdim),
                "edges": ibuf[:2 * q].reshape(q, 2),
                "graph_ids": ibuf[2 * q:],
            })
        return fbuf, ibuf

    def _run_packed(self, chunk: Sequence[GraphSample]) -> np.ndarray:
        """Run one packed bin; returns ``[len(chunk), n_targets]``.

        The bin flattens onto a rung of the engine's ``(P, Q, G)``
        budget ladder (:func:`~repro.core.batching.packed_shape`); a
        lone graph over a budget runs whole, its shape escalated to
        powers of two. The chunk ships as two flat staging buffers which
        the jitted apply slices.
        """
        nb, eb, gb = self._budgets
        p, q, g = packed_shape(chunk, nb, eb, gb)
        fbuf, ibuf = self._stage_packed(chunk, p, q, g)
        with self._lock:
            fresh = ("packed", p, q, g) not in self._compiled_shapes
            fn = self._packed_fn(p, q, g)
            plan = self.layer_plans[(p, q, g)]
        out = self._apply(fn, (self.params, fbuf, ibuf), len(chunk),
                          dict(p=p, q=q, g=g) if fresh else None, plan)
        n_real = sum(s.n_nodes for s in chunk)
        with self._lock:
            self.stats.batches_run += 1
            self.stats.node_slots_total += p
            self.stats.node_slots_real += n_real
            self.stats.lone_bins += len(chunk) == 1 and (
                n_real > nb or chunk[0].n_edges > eb)
        return out[:len(chunk)]

    def bin_shape(self,
                  chunk: Sequence[GraphSample]) -> Tuple[int, int, int]:
        """The packed ``(P, Q, G)`` shape :meth:`run_bin` gives ``chunk``."""
        return packed_shape(chunk, *self._budgets)

    def plan_bins(self, samples: Sequence[GraphSample]) -> List[List[int]]:
        """Split samples into the device bins :meth:`run_bin` accepts.

        Bin-packs mixed-size graphs under the budget rungs
        (:func:`~repro.core.batching.pack_graphs`). Returns lists of
        sample *indices*; every index appears exactly once, so callers
        can scatter per-bin results back to input order. Shared by
        :meth:`predict_samples` and the serving micro-batcher
        (``repro.serve.PredictionService``).
        """
        return pack_graphs(samples, *self._budgets)

    def run_bin(self, chunk: Sequence[GraphSample]) -> np.ndarray:
        """Run one pre-planned bin on the device — **thread-safe**.

        The single dispatch point both prediction paths share:
        :meth:`predict_samples` (bulk sweeps) and the serving
        micro-batcher feed their :meth:`plan_bins` bins here. The
        engine lock covers only the compiled-fn bookkeeping and stats
        counters — staging builds thread-local buffers and the jitted
        call itself is thread-safe in jax — so concurrent callers (a
        serving batcher fanning bins across a
        :class:`~repro.serve.fleet.ReplicaPool`, parallel sweeps)
        genuinely overlap on the device instead of serializing at bin
        granularity. Every node of every graph is served. Returns
        ``[len(chunk), n_targets]`` physical-unit predictions in chunk
        order.
        """
        chunk = list(chunk)
        if not chunk:
            return np.zeros((0, self.cfg.n_targets), dtype=np.float32)
        out = self._run_packed(chunk)
        if self.engine_cfg.validate_outputs:
            finite = np.isfinite(out).all(axis=-1)
            if not finite.all():
                bad = tuple(int(i) for i in np.flatnonzero(~finite))
                raise PredictionInvalidError(
                    f"non-finite predictions for {len(bad)}/{len(chunk)} "
                    f"graphs in bin (rows {bad[:8]}"
                    f"{'...' if len(bad) > 8 else ''}) — degenerate "
                    f"input features or numeric overflow", bad_rows=bad)
        with self._lock:
            self.stats.graphs_predicted += len(chunk)
        return out

    def predict_samples(self, samples: Sequence[GraphSample]) -> np.ndarray:
        """Predict targets for padded samples, in input order.

        Returns ``[len(samples), n_targets]`` physical-unit predictions
        (latency ms, energy J, memory MB). Mixed-size graphs bin-pack
        onto the flat node axis (:meth:`plan_bins`); results are
        scattered back to input order. Each bin dispatches through the
        thread-safe :meth:`run_bin`, so bulk sweeps and serving traffic
        can share one engine.
        """
        samples = list(samples)
        out = np.zeros((len(samples), self.cfg.n_targets), dtype=np.float32)
        if not samples:
            return out
        for idx in self.plan_bins(samples):
            out[idx] = self.run_bin([samples[j] for j in idx])
        return out

    def predict_graphs(self, graphs: Sequence[OpGraph]) -> List["Prediction"]:
        """Featurise and predict many graphs; one ``Prediction`` each, in
        input order."""
        from .predictor import Prediction, make_prediction
        samples = [
            sample_from_graph(g, buckets=self.engine_cfg.buckets,
                              extended_static=self.engine_cfg.extended_static)
            for g in graphs
        ]
        ys = self.predict_samples(samples)
        return [make_prediction(y, meta=dict(g.meta))
                for g, y in zip(graphs, ys)]
