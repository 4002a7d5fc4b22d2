"""User-facing DIPPM API — the paper's Fig. 5 usability surface.

    from repro.core.predictor import DIPPM
    dippm = DIPPM.from_params(params, cfg)
    out = dippm.predict_jax(forward, param_specs, input_spec, batch=16)
    out.latency_ms, out.energy_j, out.memory_mb, out.mig, out.tpu_slice

Frontends: any JAX callable (``predict_jax``), a serialized portable graph
(``predict_json``), or a pre-built OpGraph (``predict_graph``). The MIG
profile (eq. 2) and the TPU-slice recommendation are derived from the
predicted memory exactly as §3.5 prescribes.

Every prediction path is a thin client of a shared default
:class:`~repro.serve.PredictionService`: ``predict_graph`` is a
submit + flush + wait round trip, ``predict_many`` a synchronous burst
through the same micro-batcher — identical numbers either way because
both flow through the one engine the service wraps. ``predict_zoo``
runs a model-family grid end to end (build → trace → predict) without
executing any of the candidate models, and ``DIPPM.serve(**overrides)``
hands out a dedicated service for real request traffic
(``docs/serving.md``).

Persistence is the versioned pickle-free artifact format
(``repro.serve.artifact``): ``save`` emits a v2 npz+JSON artifact;
``load`` reads v2 and falls back — with a ``DeprecationWarning`` — to
legacy pickle files.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

import numpy as np

from .frontends import from_jax, from_json
from .gnn import PMGNSConfig
from .ir import OpGraph
from .mig import predict_mig, predict_pods, predict_tpu_slice


@dataclasses.dataclass
class Prediction:
    """One model's predicted inference profile + resource advice.

    ``latency_ms`` / ``energy_j`` / ``memory_mb`` are the PMGNS regression
    targets in physical units; ``mig`` / ``tpu_slice`` / ``pods`` are the
    §3.5 resource recommendations derived from the predicted memory.
    ``meta`` is the graph's own metadata. ``serving`` is how the
    service's engine ran it: ``nodes`` and ``edges`` served, the device
    shape ``bin`` of its bin (packed ``(P, Q, G)``) and
    ``queue_wait_ms`` from enqueue to the batcher's drain; ``None`` for
    cache hits and coalesced duplicates, which no bin ran, and outside
    the service. It is a record of one serving, so two predictions of
    the same graph compare equal whatever it says.
    """

    latency_ms: float
    energy_j: float
    memory_mb: float
    mig: Optional[str]
    tpu_slice: Optional[str]
    pods: int
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)
    serving: Optional[Dict[str, Any]] = dataclasses.field(
        default=None, compare=False)

    def __repr__(self) -> str:  # pragma: no cover — cosmetic
        return (f"Prediction(latency={self.latency_ms:.3f} ms, "
                f"energy={self.energy_j:.4f} J, "
                f"memory={self.memory_mb:.1f} MB, mig={self.mig}, "
                f"tpu_slice={self.tpu_slice}, pods={self.pods})")


def make_prediction(y: np.ndarray,
                    meta: Optional[Dict[str, Any]] = None) -> Prediction:
    """Wrap decoded targets ``[latency_ms, energy_j, memory_mb]`` into a
    :class:`Prediction` with the §3.5 MIG / TPU-slice advice attached."""
    lat, enr, mem = [float(v) for v in np.asarray(y).reshape(-1)[:3]]
    return Prediction(
        latency_ms=lat, energy_j=enr, memory_mb=mem,
        mig=predict_mig(mem),
        tpu_slice=predict_tpu_slice(mem),
        pods=predict_pods(mem),
        meta=dict(meta or {}),
    )


class DIPPM:
    """Trained predictor + frontends + resource advisors."""

    def __init__(self, params, cfg: PMGNSConfig):
        import threading
        self.params = params
        self.cfg = cfg
        self._engine = None
        self._service = None
        #: guards lazy init of the default engine/service — concurrent
        #: first calls must share ONE engine (and its compiled-fn
        #: cache) and ONE batcher thread, not race into duplicates
        self._init_lock = threading.Lock()

    # -- constructors / persistence -----------------------------------------
    @classmethod
    def from_params(cls, params, cfg: PMGNSConfig) -> "DIPPM":
        """Wrap already-trained PMGNS parameters."""
        return cls(params, cfg)

    @classmethod
    def load(cls, path: str) -> "DIPPM":
        """Load a predictor saved with :meth:`save`.

        Reads the v2 artifact format
        (``repro.serve.artifact.load_artifact`` — npz params + JSON
        config, no pickle execution); legacy pickle files from older
        versions still load through the deprecated fallback, which
        warns. Re-save to migrate them.
        """
        from ..serve.artifact import load_artifact
        params, cfg, _meta = load_artifact(path)
        return cls(params, cfg)

    def save(self, path: str,
             metadata: Optional[Dict[str, Any]] = None) -> None:
        """Write a **v2 versioned artifact** (npz params + JSON config)
        to ``path`` — see ``repro.serve.artifact``. Replaces the old
        pickle format so serving processes can load models without
        arbitrary-code-execution pickle; :meth:`load` still reads old
        pickle files (with a ``DeprecationWarning``).
        """
        import jax

        from ..serve.artifact import save_artifact
        params = jax.tree_util.tree_map(np.asarray, self.params)
        save_artifact(path, params, self.cfg, metadata=metadata)

    # -- serving -------------------------------------------------------------
    def serve(self, **overrides) -> "PredictionService":
        """A dedicated micro-batching service over this predictor.

        Keyword overrides are :class:`repro.serve.ServeConfig` fields
        (``max_wait_ms``, ``max_batch_graphs``, ``node_budget``,
        ``max_queue``, ...). Each call returns a **fresh**
        :class:`~repro.serve.PredictionService` with its own engine and
        batcher thread — close it (or use it as a context manager) when
        done. The facade's own ``predict_*`` methods use a separate
        shared default service and are unaffected.
        """
        from ..serve import PredictionService, ServeConfig
        return PredictionService(self.params, self.cfg,
                                 ServeConfig(**overrides))

    def _default_service(self) -> "PredictionService":
        """The lazily-built shared service behind ``predict_graph`` /
        ``predict_many`` — wraps the default engine, so facade calls
        and direct engine sweeps share one compiled-fn cache + stats.
        A finalizer closes it when this ``DIPPM`` is collected, so a
        loop over many loaded predictors doesn't accumulate batcher
        threads (each would otherwise pin its engine + params forever).
        """
        if self._service is None:
            import weakref

            from ..serve import PredictionService
            engine = self.engine()          # before the lock (own lock)
            with self._init_lock:
                if self._service is None:   # double-checked: one batcher
                    svc = PredictionService(engine=engine)
                    weakref.finalize(self, PredictionService.close, svc,
                                     timeout=1.0)
                    self._service = svc
        return self._service

    # -- prediction ----------------------------------------------------------
    def predict_graph(self, g: OpGraph) -> Prediction:
        """Predict one pre-built :class:`OpGraph`.

        A synchronous round trip through the shared default service
        (submit + flush + wait): single-shot calls ride the same
        jit-compiled engine bins as sweeps — no eager batch-of-1 apply —
        and concurrent callers coalesce into shared bins automatically.
        """
        return self._default_service().predict_one(g)

    def predict_jax(self, forward, param_specs, *input_specs,
                    batch: Optional[int] = None,
                    meta: Optional[Dict[str, Any]] = None) -> Prediction:
        """Trace a JAX callable abstractly and predict it — Fig. 5 flow."""
        m = dict(meta or {})
        if batch is not None:
            m.setdefault("batch", batch)
        g = from_jax(forward, param_specs, *input_specs, meta=m)
        return self.predict_graph(g)

    def predict_json(self, doc: Dict[str, Any]) -> Prediction:
        """Predict a portable serialized graph (``repro.opgraph.v1``)."""
        return self.predict_graph(from_json(doc))

    # -- batched sweeps ------------------------------------------------------
    def engine(self, **overrides) -> "PredictionEngine":
        """The batched prediction engine for this predictor.

        With no arguments, returns the cached default-config engine that
        ``predict_many`` / ``predict_zoo`` use. Keyword overrides are
        :class:`repro.core.engine.EngineConfig` fields (``buckets``,
        ``max_batch``, ``extended_static``) and return a **fresh**,
        un-cached engine — the default engine (and its compiled-function
        cache and stats) is left untouched, so sweeps through
        ``predict_many`` keep their bit-for-bit equivalence with
        ``predict_graph`` regardless of custom engines in flight.
        """
        from .engine import EngineConfig, PredictionEngine
        if overrides:
            return PredictionEngine(self.params, self.cfg,
                                    EngineConfig(**overrides))
        with self._init_lock:
            if self._engine is None:
                self._engine = PredictionEngine(self.params, self.cfg,
                                                EngineConfig())
            return self._engine

    def predict_many(self, graphs: Sequence[OpGraph],
                     return_stats: bool = False):
        """Predict many graphs at once, preserving input order.

        Equivalent to ``[self.predict_graph(g) for g in graphs]`` but
        bucketed + batched (or bin-packed, with a
        ``PMGNSConfig(layout="packed")`` model): one compiled apply per
        padded shape instead of one eager apply per graph. This is the
        entry point for zoo sweeps.

        With ``return_stats=True`` returns ``(predictions, stats)``
        where ``stats`` is a detached
        :class:`~repro.core.engine.EngineStats` snapshot — cumulative
        engine counters including ``padding_waste_frac``,
        ``cache_entries``, and ``recompiles``, so sweeps can report how
        much device work was padding and how many shapes compiled.

        Delegates to the shared default service (a synchronous burst
        through its micro-batcher — same engine, same bins, same
        numbers as before the serving redesign).
        """
        graphs = list(graphs)
        svc = self._default_service()       # one engine, snapshotted once
        preds = svc.predict_many(graphs)
        if return_stats:
            return preds, svc.engine.stats.snapshot()
        return preds

    def predict_zoo(self, family: str,
                    grid: Iterable[Dict[str, Any]],
                    ) -> List[Tuple[Dict[str, Any], Prediction]]:
        """Sweep a zoo family over a config grid without running any model.

        ``grid`` is an iterable of variant configs for
        ``repro.zoo.families.build_family`` (see
        ``repro.zoo.families.variant_grid`` for the cartesian-product
        helper). Returns ``(cfg, Prediction)`` pairs in grid order.
        """
        from ..zoo.families import trace_family
        cfgs = list(grid)
        graphs = [trace_family(family, cfg) for cfg in cfgs]
        return list(zip(cfgs, self.predict_many(graphs)))
