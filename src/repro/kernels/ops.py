"""Dispatchers from the model code to the Pallas kernels or their lax twins.

Policy: :func:`kernel_impl` is ``"pallas"`` on a TPU backend and
``"ref"`` (the ``jnp``/``lax`` twins in :mod:`repro.kernels.ref`)
everywhere else — the Pallas TPU kernels do not lower for a CPU. A caller
may pass ``impl="pallas"`` or ``impl="ref"`` explicitly; ``"pallas"`` off
a TPU runs the kernel in Pallas interpret mode, which is how the tests
check the kernels on a CPU (they steer :func:`kernel_impl` with
``monkeypatch`` to force it model-wide).

The fused message-passing kernels keep a whole ``[P, F]`` block resident
in VMEM. A shape whose estimated working set does not fit the kernel's
scoped VMEM limit (:func:`fused_fits`) runs the reference composition
instead. PMGNS plans that per layer (``repro.core.gnn.fused_layer_impls``)
and passes ``impl`` explicitly, and the prediction engine counts each
compiled shape's layers on either path (``EngineStats.fused_kernel_layers``,
``fused_segment_layers``); a caller that leaves ``impl`` to the dispatcher
on such a shape gets a trace-time warning.
"""
from __future__ import annotations

import warnings
from typing import Optional

import jax

from . import ref as _ref
from .flash_attention import flash_attention_pallas
from .sage_spmm import dense_aggregate_pallas
from .segment_spmm import (VMEM_LIMIT_BYTES, edge_softmax_pallas,
                           fused_gat_aggregate_pallas, fused_mp_layer_pallas,
                           fused_vmem_bytes, segment_aggregate_pallas,
                           segment_readout_pallas, segment_scatter_pallas)
from .ssd_scan import ssd_scan_pallas


def kernel_impl() -> str:
    """The default implementation: ``"pallas"`` on a TPU, else ``"ref"``."""
    return "pallas" if jax.default_backend() == "tpu" else "ref"


def _interpret() -> bool:
    return jax.default_backend() != "tpu"


def sage_aggregate(adj: jax.Array, h: jax.Array,
                   impl: Optional[str] = None) -> jax.Array:
    """Batched GraphSAGE mean aggregation — see ``sage_spmm``."""
    return dense_aggregate(adj, h, mode="mean", impl=impl)


def dense_aggregate(adj: jax.Array, h: jax.Array, *, mode: str = "mean",
                    impl: Optional[str] = None) -> jax.Array:
    """Dense masked neighborhood aggregation — see ``sage_spmm``.

    The shared kernel behind every dense-path GNN variant: GraphSAGE
    (``mean``), GIN (``sum``), GCN (``sum`` over the pre-normalized
    adjacency).
    """
    impl = impl or kernel_impl()
    if impl == "pallas":
        return dense_aggregate_pallas(adj, h, mode=mode,
                                      interpret=_interpret())
    return _ref.dense_aggregate_ref(adj, h, mode=mode)


def segment_aggregate(edges: jax.Array, edge_mask: jax.Array, h: jax.Array,
                      *, mode: str = "mean",
                      impl: Optional[str] = None) -> jax.Array:
    """Sparse edge-list aggregation — see ``segment_spmm``.

    The sparse-path counterpart of :func:`dense_aggregate`: O(E·F)
    gather→segment-scatter instead of an O(N²·F) dense matmul, and no
    ``[B, N, N]`` adjacency anywhere. The ``ref`` impl (CPU default) is
    a differentiable ``jnp.take``/``segment_sum`` pipeline; ``pallas``
    is the tiled one-hot-matmul kernel.
    """
    impl = impl or kernel_impl()
    if impl == "pallas":
        return segment_aggregate_pallas(edges, edge_mask, h, mode=mode,
                                        interpret=_interpret())
    return _ref.segment_aggregate_ref(edges, edge_mask, h, mode=mode)


def segment_scatter(dst: jax.Array, edge_mask: jax.Array, msgs: jax.Array,
                    n_nodes: int, impl: Optional[str] = None) -> jax.Array:
    """Scatter per-edge messages into per-node sums — see ``segment_spmm``."""
    impl = impl or kernel_impl()
    if impl == "pallas":
        return segment_scatter_pallas(dst, edge_mask, msgs, n_nodes,
                                      interpret=_interpret())
    return _ref.segment_scatter_ref(dst, edge_mask, msgs, n_nodes)


def segment_readout(h: jax.Array, graph_ids: jax.Array,
                    node_mask: jax.Array, n_graphs: int, *,
                    kind: str = "mean_max",
                    impl: Optional[str] = None) -> jax.Array:
    """Fused segment-mean/max graph readout — see ``segment_spmm``.

    The packed-layout graph pooling: ``h [P, F]`` over one flat node
    axis + ``graph_ids [P]`` → per-graph ``[G, F]`` (mean) or
    ``[G, 2F]`` (mean ⊕ max), replacing per-graph masked pooling.
    """
    impl = impl or kernel_impl()
    if impl == "pallas":
        return segment_readout_pallas(h, graph_ids, node_mask, n_graphs,
                                      kind=kind, interpret=_interpret())
    return _ref.segment_readout_ref(h, graph_ids, node_mask, n_graphs,
                                    kind=kind)


def edge_softmax(scores: jax.Array, dst: jax.Array, edge_mask: jax.Array,
                 n_nodes: int, impl: Optional[str] = None) -> jax.Array:
    """Per-destination softmax over incoming edges — see ``segment_spmm``.

    GAT attention without the dense ``[B, N, N, heads]`` tensor; NaN-safe
    for destinations whose whole neighborhood is masked out.
    """
    impl = impl or kernel_impl()
    if impl == "pallas":
        return edge_softmax_pallas(scores, dst, edge_mask, n_nodes,
                                   interpret=_interpret())
    return _ref.edge_softmax_ref(scores, dst, edge_mask, n_nodes)


def fused_fits(p: int, f: int, h: int, mode: str) -> bool:
    """True if a fused layer of this shape fits the kernel's VMEM limit."""
    return fused_vmem_bytes(p, f, h, mode=mode) <= VMEM_LIMIT_BYTES


def _fused_impl(impl: Optional[str], p: int, f: int, h: int,
                mode: str, name: str) -> str:
    impl = impl or kernel_impl()
    if impl == "pallas" and not fused_fits(p, f, h, mode):
        warnings.warn(
            f"{name}: P={p}, F={f}, H={h} needs "
            f"{fused_vmem_bytes(p, f, h, mode=mode) / 2**20:.0f} MiB of "
            f"VMEM, over the {VMEM_LIMIT_BYTES / 2**20:.0f} MiB limit — "
            f"running the lax reference", stacklevel=3)
        return "ref"
    return impl


def fused_mp_layer(x: jax.Array, edges: jax.Array, edge_mask: jax.Array,
                   node_mask: Optional[jax.Array] = None, *,
                   w_neigh: jax.Array, w_self: Optional[jax.Array] = None,
                   bias: Optional[jax.Array] = None, mode: str = "mean",
                   combine: str = "split",
                   self_scale: Optional[jax.Array] = None,
                   act: str = "relu",
                   impl: Optional[str] = None) -> jax.Array:
    """One fused message-passing layer over the packed flat node axis.

    gather → edge-mask → scatter(+mean) → self/neighbor combine → bias →
    activation → node-mask in a single kernel — see ``segment_spmm``.
    Runs the reference composition, with a warning, when the kernel's
    resident state would not fit VMEM (:func:`fused_fits`).
    """
    impl = _fused_impl(impl, x.shape[0], x.shape[1], w_neigh.shape[1], mode,
                       "fused_mp_layer")
    if impl == "pallas":
        return fused_mp_layer_pallas(
            x, edges, edge_mask, node_mask, w_neigh=w_neigh, w_self=w_self,
            bias=bias, mode=mode, combine=combine, self_scale=self_scale,
            act=act, interpret=_interpret())
    return _ref.fused_mp_layer_ref(
        x, edges, edge_mask, node_mask, w_neigh=w_neigh, w_self=w_self,
        bias=bias, mode=mode, combine=combine, self_scale=self_scale,
        act=act)


def fused_gat_aggregate(z: jax.Array, edges: jax.Array,
                        edge_mask: jax.Array, att: jax.Array,
                        node_mask: jax.Array,
                        impl: Optional[str] = None) -> jax.Array:
    """Fused GAT post-softmax gather⊙attention→scatter — see ``segment_spmm``."""
    impl = _fused_impl(impl, z.shape[0], z.shape[1], z.shape[1], "sum",
                       "fused_gat_aggregate")
    if impl == "pallas":
        return fused_gat_aggregate_pallas(z, edges, edge_mask, att,
                                          node_mask, interpret=_interpret())
    return _ref.fused_gat_aggregate_ref(z, edges, edge_mask, att, node_mask)


def flash_attention(q, k, v, *, causal: bool = False,
                    scale: Optional[float] = None, window: int = 0,
                    q_offset: int = 0, impl: Optional[str] = None):
    """Streaming-softmax attention — see ``flash_attention``."""
    impl = impl or kernel_impl()
    if impl == "pallas":
        return flash_attention_pallas(
            q, k, v, causal=causal, scale=scale, window=window,
            q_offset=q_offset, interpret=_interpret())
    return _ref.attention_ref(q, k, v, causal=causal, scale=scale,
                              window=window, q_offset=q_offset)


def ssd_scan(x, dt, A, B, C, *, chunk: int = 128,
             impl: Optional[str] = None):
    """Chunked Mamba2 SSD scan — see ``ssd_scan``."""
    impl = impl or kernel_impl()
    if impl == "pallas":
        return ssd_scan_pallas(x, dt, A, B, C, chunk=chunk,
                               interpret=_interpret())
    return _ref.ssd_scan_ref(x, dt, A, B, C)


ssd_decode = _ref.ssd_decode_ref
