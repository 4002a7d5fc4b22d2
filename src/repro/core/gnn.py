"""Performance Model Graph Network Structure (paper §3.4) + GNN baselines.

The PMGNS is: 3 × GraphSAGE blocks → graph readout → ``z ⊕ F_s`` →
3 × FC blocks → 3-way multi-regression head (memory MB, latency ms,
energy J). Table 4 baselines — GCN, GAT, GIN, and a no-GNN MLP — share the
same skeleton with the message-passing layer swapped, exactly the paper's
ablation design.

All layers operate on batches (``repro.core.batching``) in one of three
layouts. ``PMGNSConfig.layout`` selects the trainer's; inference always
runs packed (``repro.core.engine``), whatever layout trained the model:

    x     [B, N, F]     node features
    mask  [B, N]        node validity
    adj   [B, N, N]     A[dst, src]            (dense, the reference)
    edges [B, E, 2]     (src, dst) int32       (sparse)
    edge_mask [B, E]    1.0 real edge / 0.0 padding

    x     [P, F]        packed: ONE flat node axis for many graphs
    graph_ids [P]       segment id of each node's graph
    edges [Q, 2]        globally-offset block-diagonal edge list
    static/y [G, ·]     per-graph rows         (packed, the hot path)

**Packed** batches (``collate_packed``) run the sparse segment layers
over the flat axis as a batch of one — block-diagonal edges keep graphs
independent — and pool with a fused segment-mean/max readout over
``graph_ids`` (``repro.kernels.segment_spmm.segment_readout_pallas``)
instead of per-graph masked pooling, so mixed-size graphs share one
compiled shape with no bucket padding.

**Dense** aggregation is a batched matmul (O(B·N²·F)); **sparse**
aggregation is gather→segment-scatter over the edge list (O(B·E·F)) —
DIPPM DAGs carry ~1–3 edges per node, so at the big buckets the sparse
path does ~N/3 × less aggregation work and never materializes the
adjacency. Both paths are masked so padding is numerically inert, and
they agree to float tolerance; the dense path remains the numerical
reference.

``use_pallas=True`` routes every aggregation through the shared kernel
dispatchers (``repro.kernels.ops``): dense SAGE/GCN/GIN hit the blocked
MXU SpMM (``repro.kernels.sage_spmm``), sparse layers hit the segment
kernels (``repro.kernels.segment_spmm``), and sparse GAT additionally
uses the edge-softmax kernel. Dense GAT has no Pallas attention path
(the ``[B, N, N, heads]`` tensor is exactly what the sparse layout
removes)
and warns once before falling back to jnp; the no-message-passing MLP
baseline has nothing to accelerate and ignores the flag by design.

Targets are trained in ``log1p`` space (they span 4+ orders of magnitude);
:func:`decode_targets` maps predictions back to physical units.
"""
from __future__ import annotations

import dataclasses
import warnings
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .. import nn

Params = Dict[str, Any]

TARGET_NAMES = ("latency_ms", "energy_j", "memory_mb")
N_TARGETS = 3


# ---------------------------------------------------------------------------
# aggregation helpers (dense + sparse, masked)
# ---------------------------------------------------------------------------

def _neighbor_mean(adj: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    """mean_{j in N(i)} h_j  via row-normalized dense adjacency."""
    deg = jnp.maximum(adj.sum(axis=-1, keepdims=True), 1.0)
    return jnp.einsum("bnm,bmf->bnf", adj / deg, h)


def _neighbor_sum(adj: jnp.ndarray, h: jnp.ndarray) -> jnp.ndarray:
    return jnp.einsum("bnm,bmf->bnf", adj, h)


def _gcn_norm_adj(adj: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """D^-1/2 (A + I) D^-1/2 with masked self-loops."""
    eye = jnp.eye(adj.shape[-1], dtype=adj.dtype)[None]
    a = adj + eye * mask[:, :, None]
    deg = jnp.maximum(a.sum(axis=-1), 1.0)
    dinv = jax.lax.rsqrt(deg)
    return a * dinv[:, :, None] * dinv[:, None, :]


def _aggregate(h, mode, adj=None, edges=None, edge_mask=None,
               use_pallas=False):
    """Shared neighborhood aggregation behind SAGE/GCN/GIN.

    Dispatches on layout (``edges`` present → sparse segment path, else
    dense matmul) and on ``use_pallas`` (kernel dispatcher vs direct
    jnp/lax reference). ``edge_mask`` may carry per-edge *weights* (GCN
    normalization), not just 0/1 validity — every sparse path multiplies
    the scattered message by it.
    """
    if edges is not None:
        if use_pallas:
            from ..kernels.ops import segment_aggregate
            return segment_aggregate(edges, edge_mask, h, mode=mode)
        from ..kernels.ref import segment_aggregate_ref
        return segment_aggregate_ref(edges, edge_mask, h, mode=mode)
    if use_pallas:
        from ..kernels.ops import dense_aggregate
        return dense_aggregate(adj, h, mode=mode)
    return _neighbor_mean(adj, h) if mode == "mean" else _neighbor_sum(adj, h)


def _scatter_edges(msgs, dst, edge_mask, n_nodes, use_pallas=False):
    """Scatter per-edge messages ``[B, E, F]`` into ``[B, N, F]`` sums."""
    if use_pallas:
        from ..kernels.ops import segment_scatter
        return segment_scatter(dst, edge_mask, msgs, n_nodes)
    from ..kernels.ref import segment_scatter_ref
    return segment_scatter_ref(dst, edge_mask, msgs, n_nodes)


_WARNED_NO_PALLAS = set()


def _warn_no_pallas_path(layer: str, hint: str) -> None:
    if layer not in _WARNED_NO_PALLAS:            # once per process
        _WARNED_NO_PALLAS.add(layer)
        warnings.warn(
            f"use_pallas=True: {layer} has no Pallas path for this "
            f"layout — falling back to jnp. {hint}", stacklevel=3)


# ---------------------------------------------------------------------------
# message-passing layers
# ---------------------------------------------------------------------------

def sage_layer_init(key, d_in: int, d_out: int) -> Params:
    k1, k2 = jax.random.split(key)
    return {"self": nn.linear_init(k1, d_in, d_out),
            "neigh": nn.linear_init(k2, d_in, d_out, bias=False)}


def sage_layer(p: Params, x, adj, mask, *, edges=None, edge_mask=None,
               use_pallas: bool = False):
    agg = _aggregate(x, "mean", adj=adj, edges=edges, edge_mask=edge_mask,
                     use_pallas=use_pallas)
    y = nn.linear(p["self"], x) + nn.linear(p["neigh"], agg)
    return y * mask[..., None]


def gcn_layer_init(key, d_in: int, d_out: int) -> Params:
    return {"lin": nn.linear_init(key, d_in, d_out)}


def gcn_layer(p: Params, x, adj, mask, *, edges=None, edge_mask=None,
              use_pallas: bool = False):
    if edges is None:
        a = _gcn_norm_adj(adj, mask)
        agg = _aggregate(x, "sum", adj=a, use_pallas=use_pallas)
    else:
        # sparse D^-1/2 (A + I) D^-1/2 @ x without forming A: the edge
        # weight dinv[dst]·dinv[src] rides in through edge_mask, and the
        # masked self-loop contributes dinv²·x directly.
        from ..kernels.ref import segment_degree_ref
        n = x.shape[1]
        src, dst = edges[..., 0], edges[..., 1]
        deg = segment_degree_ref(edges, edge_mask, n) + mask  # A+I row-sums
        dinv = jax.lax.rsqrt(jnp.maximum(deg, 1.0))     # [B, N]
        w = (edge_mask
             * jnp.take_along_axis(dinv, dst, axis=1)
             * jnp.take_along_axis(dinv, src, axis=1))
        agg = _aggregate(x, "sum", edges=edges, edge_mask=w,
                         use_pallas=use_pallas)
        agg = agg + (dinv * dinv * mask)[..., None] * x
    y = nn.linear(p["lin"], agg)
    return y * mask[..., None]


def gat_layer_init(key, d_in: int, d_out: int, heads: int = 4) -> Params:
    k1, k2, k3 = jax.random.split(key, 3)
    dh = d_out // heads
    return {
        "proj": nn.linear_init(k1, d_in, d_out, bias=False),
        "att_src": nn.normal_init(k2, (heads, dh)),
        "att_dst": nn.normal_init(k3, (heads, dh)),
    }


def gat_layer(p: Params, x, adj, mask, *, edges=None, edge_mask=None,
              use_pallas: bool = False):
    h = p["att_src"].shape[0]
    z = nn.linear(p["proj"], x)                       # [B,N,D]
    B, N, D = z.shape
    zh = z.reshape(B, N, h, D // h)
    es = jnp.einsum("bnhd,hd->bnh", zh, p["att_src"])  # source score
    ed = jnp.einsum("bnhd,hd->bnh", zh, p["att_dst"])  # dest score
    if edges is not None:
        # per-edge attention: [B, E, heads] instead of [B, N, N, heads]
        src, dst = edges[..., 0], edges[..., 1]
        s = jax.nn.leaky_relu(
            jnp.take_along_axis(ed, dst[..., None], axis=1)
            + jnp.take_along_axis(es, src[..., None], axis=1),
            0.2)                                       # [B, E, heads]
        if use_pallas:
            from ..kernels.ops import edge_softmax
            att = edge_softmax(s, dst, edge_mask, N)
        else:
            from ..kernels.ref import edge_softmax_ref
            att = edge_softmax_ref(s, dst, edge_mask, N)
        zs = jnp.take_along_axis(z, src[..., None], axis=1)  # [B, E, D]
        msgs = (zs.reshape(B, -1, h, D // h)
                * att[..., None]).reshape(B, -1, D)
        out = _scatter_edges(msgs, dst, edge_mask, N, use_pallas=use_pallas)
        return out * mask[..., None]
    if use_pallas:
        _warn_no_pallas_path(
            "gat_layer (dense)", "The Pallas GAT path is the sparse "
            "edge-softmax kernel — use PMGNSConfig(layout='sparse').")
    # e[b, i, j, h] — attention of dst i over j; explicit masked softmax
    # with a guarded denominator so an all-padding (empty-neighborhood)
    # destination row yields exact zeros instead of relying on post-hoc
    # NaN masking.
    e = jax.nn.leaky_relu(ed[:, :, None, :] + es[:, None, :, :], 0.2)
    neg = jnp.finfo(z.dtype).min
    live = (adj > 0)[..., None]
    e = jnp.where(live, e, neg)
    p_e = jnp.where(live, jnp.exp(e - jnp.max(e, axis=2, keepdims=True)),
                    0.0)
    denom = jnp.sum(p_e, axis=2, keepdims=True)
    att = p_e / jnp.maximum(denom, jnp.finfo(z.dtype).tiny)
    out = jnp.einsum("bijh,bjhd->bihd", att, zh).reshape(B, N, D)
    return out * mask[..., None]


def gin_layer_init(key, d_in: int, d_out: int) -> Params:
    return {"mlp": nn.mlp_init(key, (d_in, d_out, d_out)),
            "eps": jnp.zeros(())}


def gin_layer(p: Params, x, adj, mask, *, edges=None, edge_mask=None,
              use_pallas: bool = False):
    agg = _aggregate(x, "sum", adj=adj, edges=edges, edge_mask=edge_mask,
                     use_pallas=use_pallas)
    y = nn.mlp(p["mlp"], (1.0 + p["eps"]) * x + agg)
    return y * mask[..., None]


def mlp_layer_init(key, d_in: int, d_out: int) -> Params:
    return {"lin": nn.linear_init(key, d_in, d_out)}


def mlp_layer(p: Params, x, adj, mask, *, edges=None, edge_mask=None,
              use_pallas: bool = False):
    """No message passing — the paper's plain-MLP baseline.

    ``use_pallas`` is accepted but meaningless here by design: there is
    no aggregation to accelerate, so the flag is intentionally a no-op
    (not a silent bug — nothing is being skipped).
    """
    return nn.linear(p["lin"], x) * mask[..., None]


_LAYERS = {
    "graphsage": (sage_layer_init, sage_layer),
    "gcn": (gcn_layer_init, gcn_layer),
    "gat": (gat_layer_init, gat_layer),
    "gin": (gin_layer_init, gin_layer),
    "mlp": (mlp_layer_init, mlp_layer),
}


# ---------------------------------------------------------------------------
# PMGNS model
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class PMGNSConfig:
    """Paper Table 3 settings."""

    variant: str = "graphsage"       # graphsage | gcn | gat | gin | mlp
    node_feat_dim: int = 32
    static_dim: int = 5
    hidden: int = 512                # "Nr hidden layers 512"
    n_gnn_blocks: int = 3            # Fig. 2: three graphSAGE blocks
    n_fc_blocks: int = 3             # Fig. 2: three FC blocks
    dropout: float = 0.05
    n_targets: int = N_TARGETS
    readout: str = "mean_max"        # graph-level pooling
    use_pallas: bool = False
    #: The trainer's batch layout: ``"auto"`` (dense), ``"dense"`` (the
    #: ``[B, N, N]`` adjacency, the numerical reference), ``"sparse"``
    #: (padded edge lists, segment gather/scatter — O(N·F + E) memory
    #: instead of O(N²)), or ``"packed"`` — the block-diagonal
    #: flat-node-axis layout (``repro.core.batching.collate_packed``):
    #: one ``x [P, F]`` axis for the whole batch, segment message
    #: passing over globally-offset edges, and a segment-mean/max graph
    #: readout over ``graph_ids`` instead of per-graph masked pooling.
    #: Inference is always packed: the parameters do not depend on the
    #: layout, and ``repro.core.engine.PredictionEngine`` serves any
    #: trained model on packed bins. All three layouts agree to ≤1e-5
    #: (``tests/test_engine.py``).
    layout: str = "auto"
    #: Inference precision policy. ``"f32"`` is the reference.
    #: ``"bf16"`` stages request buffers (features/masks/statics) in
    #: bfloat16 — half the host→device staging bytes — and upcasts to
    #: float32 inside the jitted function; parameters stay f32 (rounding
    #: the weights too was measured at ~1.9 % MAPE drift vs ~0.4 % for
    #: staging-only, blowing the ≤ 0.5 % gate in
    #: ``benchmarks/fused_mp.py``). ``"int8-weights"`` is an *artifact-level*
    #: policy: ``serve.artifact.save_artifact`` block-quantizes ≥2-D
    #: floating weights to int8 with per-row scales and the loader
    #: dequantizes back to f32, so runtime numerics are plain f32.
    precision: str = "f32"
    #: Fused message-passing megakernel policy (packed layout only):
    #: ``"auto"`` fuses on the packed layout at inference, ``"on"``
    #: requires the packed layout (raises otherwise), ``"off"`` keeps
    #: the composed per-op path. The fused path collapses each MP layer
    #: (gather → mask → scatter → combine → bias → act → node-mask)
    #: into one kernel call — a single ``pallas_call`` on TPU
    #: (``repro.kernels.segment_spmm.fused_mp_layer_pallas``), one fused
    #: jnp composition on CPU. Training always uses the composed path
    #: (dropout between stages).
    fused_mp: str = "auto"

    @property
    def resolved_layout(self) -> str:
        """The effective batch layout: ``auto`` is dense."""
        if self.layout == "auto":
            return "dense"
        if self.layout not in ("dense", "sparse", "packed"):
            raise ValueError(
                f"layout must be auto|dense|sparse|packed, "
                f"got {self.layout!r}")
        return self.layout

    @property
    def resolved_precision(self) -> str:
        """Validated inference precision policy."""
        if self.precision not in ("f32", "bf16", "int8-weights"):
            raise ValueError(
                f"precision must be f32|bf16|int8-weights, "
                f"got {self.precision!r}")
        return self.precision

    @property
    def resolved_fused(self) -> bool:
        """Whether inference runs the fused message-passing stack."""
        if self.fused_mp == "off":
            return False
        if self.fused_mp == "auto":
            return self.resolved_layout == "packed"
        if self.fused_mp == "on":
            if self.resolved_layout != "packed":
                raise ValueError(
                    "fused_mp='on' requires layout='packed' — the fused "
                    "megakernel operates on the flat packed node axis")
            return True
        raise ValueError(
            f"fused_mp must be auto|on|off, got {self.fused_mp!r}")


def pmgns_init(key, cfg: PMGNSConfig) -> Params:
    layer_init, _ = _LAYERS[cfg.variant]
    keys = jax.random.split(key, cfg.n_gnn_blocks + cfg.n_fc_blocks + 1)
    p: Params = {"gnn": {}, "fc": {}}
    d = cfg.node_feat_dim
    for i in range(cfg.n_gnn_blocks):
        p["gnn"][f"b{i}"] = layer_init(keys[i], d, cfg.hidden)
        d = cfg.hidden
    pool_mult = 2 if cfg.readout == "mean_max" else 1
    d_in = cfg.hidden * pool_mult + cfg.static_dim
    for i in range(cfg.n_fc_blocks):
        last = i == cfg.n_fc_blocks - 1
        d_out = cfg.n_targets if last else cfg.hidden
        p["fc"][f"b{i}"] = nn.linear_init(
            keys[cfg.n_gnn_blocks + i], d_in, d_out)
        d_in = d_out
    return p


def _readout(h: jnp.ndarray, mask: jnp.ndarray, kind: str) -> jnp.ndarray:
    m = mask[..., None]
    denom = jnp.maximum(mask.sum(axis=1, keepdims=True), 1.0)[..., None]
    mean = (h * m).sum(axis=1, keepdims=True) / denom
    mean = mean[:, 0]
    if kind == "mean":
        return mean
    mx = jnp.where(m > 0, h, jnp.finfo(h.dtype).min).max(axis=1)
    mx = jnp.where(mask.sum(axis=1, keepdims=True) > 0, mx, 0.0)
    return jnp.concatenate([mean, mx], axis=-1)


def _readout_packed(h, graph_ids, node_mask, n_graphs, kind,
                    use_pallas=False):
    """Segment-pooled graph readout over the packed flat node axis.

    The packed counterpart of :func:`_readout`: ``h [P, F]`` →
    ``[G, F or 2F]`` via the fused segment-mean/max kernel (or its lax
    reference) instead of per-graph masked pooling.
    """
    if use_pallas:
        from ..kernels.ops import segment_readout
        return segment_readout(h, graph_ids, node_mask, n_graphs, kind=kind)
    from ..kernels.ref import segment_readout_ref
    return segment_readout_ref(h, graph_ids, node_mask, n_graphs, kind=kind)


def _fused_mp_stack(p: Params, cfg: PMGNSConfig, x, mask, edges, edge_mask):
    """All GNN blocks as fused per-layer megakernel calls (packed layout).

    Operates directly on the flat packed axis (``x [P, F]``, globally
    offset ``edges [Q, 2]``) with no per-layer batch-of-one wrapping.
    Each variant maps onto :func:`repro.kernels.ops.fused_mp_layer`'s
    combine modes — GraphSAGE as ``mean``/``split``, GCN as ``sum``/
    ``pre`` with the ``d̂⁻¹·d̂⁻¹`` self-loop scale and normalization
    weights riding in through ``edge_mask``, GIN's first MLP linear as
    ``sum``/``pre`` with scale ``1 + ε`` (the second linear stays
    outside: its bias must be applied before the node mask, exactly as
    the composed path does). GAT runs the composed projection +
    edge-softmax, then the fused gather⊙attention→scatter stage.
    Numerics match the composed path to float tolerance
    (``benchmarks/fused_mp.py`` gates ≤ 1e-5).

    Each layer takes the path :func:`fused_layer_impls` plans for the
    bin's ``P``: with ``use_pallas``, the fused kernel where its VMEM
    state fits; else the lax segment composition.
    """
    from ..kernels.ops import fused_mp_layer
    impls = fused_layer_impls(cfg, x.shape[0])
    h = x
    if cfg.variant == "graphsage":
        for i in range(cfg.n_gnn_blocks):
            lp = p["gnn"][f"b{i}"]
            h = fused_mp_layer(h, edges, edge_mask, mask,
                               w_neigh=lp["neigh"]["w"],
                               w_self=lp["self"]["w"],
                               bias=lp["self"].get("b"), mode="mean",
                               combine="split", act="relu", impl=impls[i])
    elif cfg.variant == "gcn":
        from ..kernels.ref import segment_degree_ref
        n = x.shape[0]
        src, dst = edges[:, 0], edges[:, 1]
        # the normalization depends only on the graph, not the layer —
        # hoisted out of the loop (the composed path recomputes it)
        deg = segment_degree_ref(edges[None], edge_mask[None], n)[0] + mask
        dinv = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
        w = edge_mask * jnp.take(dinv, dst) * jnp.take(dinv, src)
        ss = dinv * dinv * mask
        for i in range(cfg.n_gnn_blocks):
            lp = p["gnn"][f"b{i}"]["lin"]
            h = fused_mp_layer(h, edges, w, mask, w_neigh=lp["w"],
                               bias=lp.get("b"), mode="sum", combine="pre",
                               self_scale=ss, act="relu", impl=impls[i])
    elif cfg.variant == "gin":
        for i in range(cfg.n_gnn_blocks):
            lp = p["gnn"][f"b{i}"]
            m0, m1 = lp["mlp"]["l0"], lp["mlp"]["l1"]
            r = fused_mp_layer(h, edges, edge_mask, None, w_neigh=m0["w"],
                               bias=m0.get("b"), mode="sum", combine="pre",
                               self_scale=1.0 + lp["eps"], act="relu",
                               impl=impls[i])
            h = jax.nn.relu((r @ m1["w"] + m1["b"]) * mask[:, None])
    elif cfg.variant == "gat":
        from ..kernels.ops import edge_softmax, fused_gat_aggregate
        softmax_impl = None if cfg.use_pallas else "ref"
        n = x.shape[0]
        src, dst = edges[:, 0], edges[:, 1]
        for i in range(cfg.n_gnn_blocks):
            lp = p["gnn"][f"b{i}"]
            heads = lp["att_src"].shape[0]
            z = nn.linear(lp["proj"], h)                # [P, D]
            zh = z.reshape(n, heads, -1)
            es = jnp.einsum("phd,hd->ph", zh, lp["att_src"])
            ed = jnp.einsum("phd,hd->ph", zh, lp["att_dst"])
            s = jax.nn.leaky_relu(
                jnp.take(ed, dst, axis=0) + jnp.take(es, src, axis=0),
                0.2)                                    # [Q, heads]
            att = edge_softmax(s[None], dst[None], edge_mask[None], n,
                               impl=softmax_impl)[0]
            h = jax.nn.relu(fused_gat_aggregate(z, edges, edge_mask, att,
                                                mask, impl=impls[i]))
    else:                                               # "mlp" baseline
        for i in range(cfg.n_gnn_blocks):
            lp = p["gnn"][f"b{i}"]
            h = jax.nn.relu(nn.linear(lp["lin"], h) * mask[:, None])
    return h


def fused_layer_impls(cfg: PMGNSConfig, p: int) -> Tuple[str, ...]:
    """The path of each message-passing layer of a packed bin of ``p``
    nodes, as :func:`_fused_mp_stack` runs it: ``"pallas"`` (the fused
    kernel) where the layer's resident state fits VMEM
    (:func:`repro.kernels.ops.fused_fits`), ``"ref"`` (the lax segment
    composition) where it does not, and ``"ref"`` throughout when
    inference does not dispatch to Pallas (``use_pallas`` off, the MLP
    baseline, or a non-TPU backend). A whole graph past the fused
    kernel's reach is planned here, so the dispatcher never warns."""
    from ..kernels.ops import fused_fits, kernel_impl
    if (not cfg.use_pallas or cfg.variant == "mlp"
            or kernel_impl() != "pallas"):
        return ("ref",) * cfg.n_gnn_blocks
    mode = "mean" if cfg.variant == "graphsage" else "sum"
    # GAT's fused stage aggregates the [P, hidden] projection
    f_in = cfg.hidden if cfg.variant == "gat" else cfg.node_feat_dim
    dims = [f_in] + [cfg.hidden] * (cfg.n_gnn_blocks - 1)
    return tuple("pallas" if fused_fits(p, f, cfg.hidden, mode) else "ref"
                 for f in dims)


def fused_kernel_plan(cfg: PMGNSConfig, p: int) -> Tuple[int, int]:
    """``(kernel, segment)`` fused layers for a packed bin of ``p`` nodes:
    how many of :func:`fused_layer_impls` run the fused Pallas kernel
    and how many the lax segment composition. ``(0, 0)`` when inference
    does not dispatch to Pallas at all (``use_pallas`` off, no fused
    stack, the MLP baseline, or a non-TPU backend)."""
    from ..kernels.ops import kernel_impl
    if (not cfg.use_pallas or not cfg.resolved_fused or cfg.variant == "mlp"
            or kernel_impl() != "pallas"):
        return 0, 0
    kern = fused_layer_impls(cfg, p).count("pallas")
    return kern, cfg.n_gnn_blocks - kern


def pmgns_apply(p: Params, cfg: PMGNSConfig, batch: Dict[str, jnp.ndarray],
                *, train: bool = False,
                rng: Optional[jax.Array] = None) -> jnp.ndarray:
    """Forward pass → [B, n_targets] predictions in log1p space.

    The batch layout must match ``cfg.resolved_layout``: dense batches
    carry ``adj``, sparse batches carry ``edges`` + ``edge_mask`` (see
    ``repro.core.batching.collate``), packed batches carry the flat
    ``x [P, F]`` / ``graph_ids [P]`` / globally-offset ``edges [Q, 2]``
    format (``repro.core.batching.collate_packed``) and return one row
    per *graph slot* ``[G, n_targets]``. Mixing layouts raises — a
    silent fallback would hide a miswired pipeline.

    Packed message passing reuses the sparse segment layers unchanged:
    the flat axis rides as a batch of one, the block-diagonal edge list
    keeps graphs independent, and only the readout changes — a
    segment-mean/max pool over ``graph_ids`` instead of per-graph
    masked pooling.

    When ``cfg.resolved_fused`` holds (packed layout, inference), the
    GNN blocks run through :func:`_fused_mp_stack` instead — one fused
    megakernel call per layer with no batch-of-one wrapping; training
    keeps the composed path (dropout sits between the fused stages).
    """
    _, layer = _LAYERS[cfg.variant]
    layout = cfg.resolved_layout
    x, mask = batch["x"], batch["mask"]
    packed = layout == "packed"
    if packed:
        if any(k not in batch for k in ("graph_ids", "edges", "edge_mask")):
            raise ValueError(
                "PMGNSConfig(layout='packed') needs a packed batch with "
                "'graph_ids', 'edges', and 'edge_mask' — build it via "
                "collate_packed(samples)")
        # flat node axis rides as a batch of one through the sparse layers
        x, mask_mp = x[None], mask[None]
        adj = None
        edges, edge_mask = batch["edges"][None], batch["edge_mask"][None]
    elif layout == "sparse":
        if "edges" not in batch or "edge_mask" not in batch:
            raise ValueError(
                "PMGNSConfig(layout='sparse') needs a sparse batch with "
                "'edges' and 'edge_mask' — build it via "
                "collate(samples, sparse=True)")
        mask_mp = mask
        adj, edges, edge_mask = None, batch["edges"], batch["edge_mask"]
    else:
        if "adj" not in batch:
            raise ValueError(
                "PMGNSConfig(layout='dense') needs a dense batch with "
                "'adj' — build it via collate(samples) or set "
                "layout='sparse' for edge-list batches")
        mask_mp = mask
        adj, edges, edge_mask = batch["adj"], None, None
    if packed and cfg.resolved_fused and not train:
        h_flat = _fused_mp_stack(p, cfg, batch["x"], mask,
                                 batch["edges"], batch["edge_mask"])
        z = _readout_packed(h_flat, batch["graph_ids"], mask,
                            batch["static"].shape[0], cfg.readout,
                            use_pallas=cfg.use_pallas)
    else:
        h = x
        for i in range(cfg.n_gnn_blocks):
            h = layer(p["gnn"][f"b{i}"], h, adj, mask_mp, edges=edges,
                      edge_mask=edge_mask, use_pallas=cfg.use_pallas)
            h = jax.nn.relu(h)
            if train and rng is not None:
                rng, sub = jax.random.split(rng)
                h = nn.dropout(sub, h, cfg.dropout, train)
        if packed:
            z = _readout_packed(h[0], batch["graph_ids"], mask,
                                batch["static"].shape[0], cfg.readout,
                                use_pallas=cfg.use_pallas)
        else:
            z = _readout(h, mask, cfg.readout)         # node embedding z
    feats = jnp.concatenate([z, batch["static"]], axis=-1)  # z ⊕ F_s
    y = feats
    for i in range(cfg.n_fc_blocks):
        y = nn.linear(p["fc"][f"b{i}"], y)
        if i < cfg.n_fc_blocks - 1:
            y = jax.nn.relu(y)
            if train and rng is not None:
                rng, sub = jax.random.split(rng)
                y = nn.dropout(sub, y, cfg.dropout, train)
    return y


def pmgns_infer(p: Params, cfg: PMGNSConfig,
                batch: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """Batched inference: padded batch → ``[B, n_targets]`` physical units.

    Fuses the forward pass with the ``log1p``-space decode so the whole
    prediction (apply + decode) is one jittable function — the unit
    :func:`make_staged_packed_infer_fn` compiles per ``(P, Q, G)``
    shape for the prediction engine (``repro.core.engine``).
    """
    return decode_targets(pmgns_apply(p, cfg, batch, train=False))


def packed_staging_layout(cfg: PMGNSConfig, p: int, q: int,
                          g: int) -> Tuple[int, int, int, int, int]:
    """Offsets of the flat staged packed buffers — the single source of
    truth shared by the producer (``PredictionEngine._stage_packed``)
    and the consumer (:func:`make_staged_packed_infer_fn`), so the two
    sides can never desynchronize silently.

    Float32 buffer: ``x [P·F] ⊕ mask [P] ⊕ edge_mask [Q] ⊕
    static [G·D]``; int32 buffer: ``edges [Q·2] ⊕ graph_ids [P]``.
    Returns ``(o1, o2, o3, f_len, i_len)`` — the three float-buffer
    split points and both total lengths.
    """
    o1 = p * cfg.node_feat_dim
    o2 = o1 + p
    o3 = o2 + q
    return o1, o2, o3, o3 + g * cfg.static_dim, 2 * q + p


def make_staged_packed_infer_fn(cfg: PMGNSConfig, p: int, q: int, g: int):
    """Jitted packed infer over two flat staging buffers (one shape).

    The packed serving hot path (direct dict-based packed inference goes
    through :func:`pmgns_infer` with a ``collate_packed`` batch): the
    caller stages the whole packed chunk into **one float32 buffer**
    (``x ⊕ mask ⊕ edge_mask ⊕ static``, flattened) and **one int32
    buffer** (``edges ⊕ graph_ids``), so a chunk costs two host→device
    transfers instead of six — on small serving requests the per-array
    dispatch overhead dominates the transfer time. The jitted function
    slices the buffers back into the packed batch dict (free at trace
    time — all offsets are static for the fixed ``(P, Q, G)`` shape).
    The buffers are not donated: no output can alias them, so XLA would
    refuse the donation. Returns ``(params, fbuf, ibuf) →
    [G, n_targets]`` physical-unit predictions.
    """
    feat, sdim = cfg.node_feat_dim, cfg.static_dim
    o1, o2, o3, _, _ = packed_staging_layout(cfg, p, q, g)
    # bf16 policy: the engine stages fbuf and holds params in bfloat16
    # (half the transfer/parameter bytes); compute stays f32 — upcast
    # here, inside the jitted function, so drift is storage rounding only
    cast = cfg.resolved_precision != "f32"

    @jax.jit
    def infer(params: Params, fbuf: jnp.ndarray,
              ibuf: jnp.ndarray) -> jnp.ndarray:
        if cast:
            params = nn.tree_cast(params, jnp.float32)
            fbuf = fbuf.astype(jnp.float32)
        batch = {
            "x": fbuf[:o1].reshape(p, feat),
            "mask": fbuf[o1:o2],
            "edge_mask": fbuf[o2:o3],
            "static": fbuf[o3:].reshape(g, sdim),
            "edges": ibuf[:2 * q].reshape(q, 2),
            "graph_ids": ibuf[2 * q:],
        }
        return pmgns_infer(params, cfg, batch)
    return infer


# ---------------------------------------------------------------------------
# target transforms & metrics
# ---------------------------------------------------------------------------

def encode_targets(y: jnp.ndarray) -> jnp.ndarray:
    """physical units → log1p training space."""
    return jnp.log1p(jnp.maximum(y, 0.0))


def decode_targets(yhat: jnp.ndarray) -> jnp.ndarray:
    """log1p space → physical units (latency ms, energy J, memory MB)."""
    return jnp.expm1(yhat)


def huber(pred: jnp.ndarray, target: jnp.ndarray,
          delta: float = 1.0) -> jnp.ndarray:
    """Huber loss (paper Table 3) — elementwise."""
    err = pred - target
    abs_err = jnp.abs(err)
    quad = jnp.minimum(abs_err, delta)
    return 0.5 * quad * quad + delta * (abs_err - quad)


def mape(pred_phys: jnp.ndarray, target_phys: jnp.ndarray) -> jnp.ndarray:
    """Mean Absolute Percentage Error (paper's metric), in [0, ...]."""
    denom = jnp.maximum(jnp.abs(target_phys), 1e-6)
    return jnp.mean(jnp.abs(pred_phys - target_phys) / denom)
