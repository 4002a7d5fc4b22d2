"""Pallas TPU kernel: fused GraphSAGE mean-aggregation (dense blocked SpMM).

The GNN hot-spot. GPU stacks do gather/scatter over a sparse edge list; on
TPU the idiomatic form is a **dense blocked matmul on the MXU**: DIPPM
graphs are ≤1024 nodes, so the (masked) adjacency fits comfortably and the
aggregation ``mean_{j∈N(i)} h_j`` becomes ``(A / deg) @ H`` — one
systolic-array pass instead of thousands of scattered loads (see DESIGN.md
§2, hardware adaptation).

The kernel fuses the degree normalization into the matmul epilogue so the
normalized adjacency is never materialized in HBM:

    grid = (B, N/bn, F/bf)
    adj block  (1, bn, N)   — full in-neighborhood rows for bn nodes
    h   block  (1, N, bf)   — all source nodes, bf feature columns
    out block  (1, bn, bf)

VMEM at the default tile (bn=bf=128, N≤1024): 512 KB (adj) + 512 KB (h)
+ 64 KB (out) ≈ 1.1 MB — well under the ~16 MB VMEM budget, and both
matmul dims are multiples of 128 (MXU-aligned).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _sage_kernel(adj_ref, h_ref, o_ref, *, mean: bool):
    adj = adj_ref[0]                                  # [bn, N]
    h = h_ref[0]                                      # [N, bf]
    acc = jnp.dot(adj, h, preferred_element_type=jnp.float32)
    if mean:
        deg = jnp.maximum(jnp.sum(adj, axis=-1, keepdims=True), 1.0)
        acc = acc / deg
    o_ref[0] = acc.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "bn", "bf", "interpret"))
def dense_aggregate_pallas(adj: jax.Array, h: jax.Array, *,
                           mode: str = "mean", bn: int = 128,
                           bf: int = 128, interpret: bool = False) -> jax.Array:
    """agg_{j∈N(i)} h_j (``mean`` | ``sum``) for batched dense graphs.

    adj: [B, N, N] with adj[b, dst, src] ∈ {0,1};  h: [B, N, F].
    Returns [B, N, F]. N and F are padded to tile multiples internally.
    The shared dense-aggregation kernel behind the GraphSAGE (mean), GCN
    (sum over a pre-normalized adjacency), and GIN (sum) Pallas paths.
    """
    if mode not in ("sum", "mean"):
        raise ValueError(f"mode must be 'sum' or 'mean', got {mode!r}")
    B, N, _ = adj.shape
    F = h.shape[-1]
    bn = min(bn, N)
    bf = min(bf, F)
    pn = (-N) % bn
    pf = (-F) % bf
    if pn:
        adj = jnp.pad(adj, ((0, 0), (0, pn), (0, pn)))
        h = jnp.pad(h, ((0, 0), (0, pn), (0, 0)))
    if pf:
        h = jnp.pad(h, ((0, 0), (0, 0), (0, pf)))
    Np, Fp = N + pn, F + pf

    out = pl.pallas_call(
        functools.partial(_sage_kernel, mean=(mode == "mean")),
        grid=(B, Np // bn, Fp // bf),
        in_specs=[
            pl.BlockSpec((1, bn, Np), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, Np, bf), lambda b, i, j: (b, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, bn, bf), lambda b, i, j: (b, i, j)),
        out_shape=jax.ShapeDtypeStruct((B, Np, Fp), h.dtype),
        interpret=interpret,
    )(adj, h)
    return out[:, :N, :F]


def sage_aggregate_pallas(adj: jax.Array, h: jax.Array, *, bn: int = 128,
                          bf: int = 128, interpret: bool = False) -> jax.Array:
    """mean_{j∈N(i)} h_j — the original GraphSAGE entry point."""
    return dense_aggregate_pallas(adj, h, mode="mean", bn=bn, bf=bf,
                                  interpret=interpret)
