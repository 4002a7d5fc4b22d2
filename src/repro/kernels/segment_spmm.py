"""Pallas TPU kernels: sparse edge-list segment aggregation + edge softmax.

DIPPM graphs are computation DAGs with ~1–3 edges per node, so the dense
``[B, N, N]`` adjacency the original layers consume is ≥99 % zeros at the
big buckets. These kernels run message passing directly on the padded
edge-list batch format (``repro.core.batching.collate(sparse=True)``):

    src, dst   [B, E]   int32 edge endpoints (E padded to an edge bucket)
    edge_mask  [B, E]   1.0 real edge / 0.0 padding
    h          [B, N, F]

``segment_aggregate_pallas`` is a tiled two-pass gather→accumulate-scatter:
a gather pass over ``(batch, edge-tile)`` reads each edge's source row
exactly once, then a scatter pass over ``(batch, node-tile, edge-tile)``
(edge axis innermost) accumulates masked messages into destination-node
tiles by revisiting the output block — so the dominant gather matmul is
never recomputed per node tile. Gather/scatter are expressed as
**one-hot matmuls** — the MXU-native form (TPUs have no vector gather; a
``[be, N]`` selection matrix against ``h`` is a systolic-array pass, see
the dense-blocked rationale in ``sage_spmm``) — so the kernels lower on
real TPUs and run under ``interpret=True`` on CPU unchanged. The dense
adjacency never exists: HBM traffic per batch is O(N·F + E) instead of
O(N²).

``edge_softmax_pallas`` (GAT) is two passes sharing the same layout with
heads on the sublane axis: an **online-softmax** pass (flash-attention
style running max + rescaled denominator, accumulated across edge tiles)
produces per-destination ``(max, denom)``, and a per-edge pass gathers
them back through one-hot matmuls to normalize. This replaces the dense
path's ``[B, N, N, heads]`` attention tensor with ``[B, E, heads]``.

Padding contract: padded edges carry in-range endpoints (0) and
``edge_mask == 0`` — every kernel multiplies the scatter one-hot by the
mask, so padding contributes exactly 0. Fully-masked destinations come
out as exact zeros (masked-denominator guard), never NaN.

VMEM at the default tiles (bn=be=128, N≤1024, F≤512): h block
``N·F·4 ≤ 2 MB``, one-hots ≤ 128 KB, accumulators ≤ 256 KB — comfortably
under the compiler's default 16 MiB scoped limit, with every matmul
dimension a multiple of 128. The fused packed-layout kernels keep a whole
``[P, F]`` feature block and accumulator resident instead; they raise the
scoped limit to :data:`VMEM_LIMIT_BYTES` and single-buffer their resident
blocks (:func:`fused_vmem_bytes` is their working-set estimate).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_DEG_LANES = 128   # degree accumulator lane width (TPU min lane tile)

#: Scoped VMEM the resident-state kernels may claim. A TPU v5e core has
#: 128 MiB of VMEM; at the compiler's default 16 MiB the fused layer is
#: refused at the top packed rung (P=4096, F=H=512). 100 MiB compiles
#: P=4096 and P=8192 at that width for a described v5e chip and leaves
#: headroom for Mosaic's internal scratch.
VMEM_LIMIT_BYTES = 100 * 2**20

_RESIDENT_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=VMEM_LIMIT_BYTES)


def _resident(shape):
    """BlockSpec for an operand that every grid step reads whole: one
    buffer suffices, since its block index never changes."""
    return pl.BlockSpec(shape, lambda t: (0,) * len(shape),
                        pipeline_mode=pl.Buffered(1))


def fused_vmem_bytes(p: int, f: int, h: int, *, mode: str = "sum",
                     bn: int = 128, be: int = 128) -> int:
    """Estimated VMEM working set of :func:`fused_mp_layer_pallas` (and,
    with ``h = f``, of :func:`fused_gat_aggregate_pallas`) in bytes.

    Resident feature block + f32 accumulator (+ degree accumulator for
    ``mode="mean"``), both weight matrices, and the edge phase's
    ``[be, P]``/``[P, be]`` one-hot and iota temporaries.
    """
    pp = p + ((-p) % bn)
    resident = 2 * pp * f * 4 + 2 * f * h * 4 + 16 * pp * 4
    deg = 2 * pp * _DEG_LANES * 4 if mode == "mean" else 0
    temps = 4 * be * pp * 4 + be * f * 4 + 2 * bn * h * 4
    return resident + deg + temps


def _seg_gather_kernel(src_ref, h_ref, o_ref, *, n_pad: int):
    """Per-edge message gather: ``msgs[e] = h[src_e]`` for one edge tile.

    Runs once per (batch, edge tile) — independent of node tiles, so the
    dominant gather matmul is never recomputed. Padding edges (src 0)
    gather a legal row; the scatter pass masks them out.
    """
    src = src_ref[0]                                    # [be] int32
    h = h_ref[0]                                        # [N, F]
    cols = jax.lax.broadcasted_iota(jnp.int32, (src.shape[0], n_pad), 1)
    oh_src = (src[:, None] == cols).astype(h.dtype)     # [be, N]
    o_ref[0] = jnp.dot(oh_src, h,
                       preferred_element_type=jnp.float32).astype(o_ref.dtype)


def _seg_scatter_kernel(dst_ref, em_ref, m_ref, o_ref, deg_ref, *, bn: int):
    """Scatter-accumulate per-edge messages into a node tile.

    ``edge_mask`` (which may carry per-edge weights, e.g. GCN
    normalization) is applied exactly once, here.
    """
    k = pl.program_id(2)
    dst = dst_ref[0]                                    # [be]
    em = em_ref[0]                                      # [be]
    msgs = m_ref[0]                                     # [be, F]
    be = dst.shape[0]
    rows = pl.program_id(1) * bn + jax.lax.broadcasted_iota(
        jnp.int32, (bn, be), 0)
    oh_dst = (dst[None, :] == rows).astype(msgs.dtype) * em[None, :]
    contrib = jnp.dot(oh_dst, msgs, preferred_element_type=jnp.float32)
    deg = jnp.sum(oh_dst, axis=1)                       # [bn]

    @pl.when(k == 0)
    def _init():
        o_ref[0] = jnp.zeros_like(o_ref[0])
        deg_ref[0] = jnp.zeros_like(deg_ref[0])

    o_ref[0] += contrib.astype(o_ref.dtype)
    deg_ref[0] += jnp.broadcast_to(deg[:, None],
                                   (bn, _DEG_LANES)).astype(deg_ref.dtype)


def _scatter_with_degree(dst, em, msgs, n_nodes, bn, be, interpret):
    """Shared scatter pallas_call: ``(sums [B, N, F], deg [B, N, 1])``.

    Inputs must already be padded to tile multiples (``be`` divides E).
    """
    B, Ep, F = msgs.shape
    pn = (-n_nodes) % bn
    Np = n_nodes + pn
    out, deg = pl.pallas_call(
        functools.partial(_seg_scatter_kernel, bn=bn),
        grid=(B, Np // bn, Ep // be),
        in_specs=[
            pl.BlockSpec((1, be), lambda b, i, k: (b, k)),
            pl.BlockSpec((1, be), lambda b, i, k: (b, k)),
            pl.BlockSpec((1, be, F), lambda b, i, k: (b, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bn, F), lambda b, i, k: (b, i, 0)),
            pl.BlockSpec((1, bn, _DEG_LANES), lambda b, i, k: (b, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Np, F), msgs.dtype),
            jax.ShapeDtypeStruct((B, Np, _DEG_LANES), msgs.dtype),
        ],
        interpret=interpret,
    )(dst, em, msgs)
    return out[:, :n_nodes], deg[:, :n_nodes, :1]


@functools.partial(jax.jit, static_argnames=("mode", "bn", "be", "interpret"))
def segment_aggregate_pallas(edges: jax.Array, edge_mask: jax.Array,
                             h: jax.Array, *, mode: str = "mean",
                             bn: int = 128, be: int = 128,
                             interpret: bool = False) -> jax.Array:
    """Sparse neighborhood aggregation ``agg_{e: dst_e=i} h[src_e]``.

    edges: [B, E, 2] int32 (src, dst); edge_mask: [B, E]; h: [B, N, F].
    ``mode`` is ``"sum"`` or ``"mean"`` (mean divides by real in-degree,
    isolated nodes yield 0). Returns [B, N, F].
    """
    if mode not in ("sum", "mean"):
        raise ValueError(f"mode must be 'sum' or 'mean', got {mode!r}")
    B, E, _ = edges.shape
    N, F = h.shape[1], h.shape[2]
    if E == 0:                       # edgeless batch: aggregation is zero
        return jnp.zeros_like(h)
    bn = min(bn, max(N, 1))
    be = min(be, max(E, 1))
    pn = (-N) % bn
    pe = (-E) % be
    src = edges[..., 0].astype(jnp.int32)
    dst = edges[..., 1].astype(jnp.int32)
    em = edge_mask.astype(h.dtype)
    if pe:
        src = jnp.pad(src, ((0, 0), (0, pe)))
        dst = jnp.pad(dst, ((0, 0), (0, pe)))
        em = jnp.pad(em, ((0, 0), (0, pe)))
    if pn:
        h = jnp.pad(h, ((0, 0), (0, pn), (0, 0)))
    Np, Ep = N + pn, E + pe

    # pass 1 — gather per-edge messages, once per edge tile
    msgs = pl.pallas_call(
        functools.partial(_seg_gather_kernel, n_pad=Np),
        grid=(B, Ep // be),
        in_specs=[
            pl.BlockSpec((1, be), lambda b, k: (b, k)),
            pl.BlockSpec((1, Np, F), lambda b, k: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, be, F), lambda b, k: (b, k, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Ep, F), h.dtype),
        interpret=interpret,
    )(src, h)
    # pass 2 — masked scatter-accumulate into node tiles (+ in-degree)
    out, deg = _scatter_with_degree(dst, em, msgs, N, bn, be, interpret)
    if mode == "mean":
        out = out / jnp.maximum(deg, 1.0)
    return out.astype(h.dtype)


@functools.partial(jax.jit, static_argnames=("n_nodes", "bn", "be",
                                             "interpret"))
def segment_scatter_pallas(dst: jax.Array, edge_mask: jax.Array,
                           msgs: jax.Array, n_nodes: int, *,
                           bn: int = 128, be: int = 128,
                           interpret: bool = False) -> jax.Array:
    """Scatter per-edge messages ``[B, E, F]`` into ``[B, N, F]`` sums.

    The scatter half of :func:`segment_aggregate_pallas`, for callers
    whose messages are already per-edge (GAT: attention-weighted source
    features).
    """
    B, E, F = msgs.shape
    if E == 0:
        return jnp.zeros((B, n_nodes, F), msgs.dtype)
    bn = min(bn, max(n_nodes, 1))
    be = min(be, max(E, 1))
    pe = (-E) % be
    d = dst.astype(jnp.int32)
    em = edge_mask.astype(msgs.dtype)
    if pe:
        d = jnp.pad(d, ((0, 0), (0, pe)))
        em = jnp.pad(em, ((0, 0), (0, pe)))
        msgs = jnp.pad(msgs, ((0, 0), (0, pe), (0, 0)))
    out, _ = _scatter_with_degree(d, em, msgs, n_nodes, bn, be, interpret)
    return out


def _seg_readout_kernel(gid_ref, w_ref, gidc_ref, wc_ref, h_ref, sum_ref,
                        cnt_ref, max_ref, *, bg: int):
    """Fused per-graph (sum, count, max) over one node tile.

    Runs per (graph-tile, node-tile) with the node axis innermost: the
    output blocks are revisited across node tiles and accumulated. The
    one-hot selection matmul is the MXU-native gather (see module
    docstring) and the count is the same one-hot against a ones block.
    Max is a masked max on the VPU, one graph of the tile at a time:
    graph ids and the mask also arrive as ``[bp, 1]`` columns so the
    selection broadcasts along lanes — Mosaic cannot lay out a
    ``[bg, bp, F]`` select or move a lane vector onto sublanes.
    """
    k = pl.program_id(1)
    g0 = pl.program_id(0) * bg
    gid = gid_ref[0]                                    # [bp] int32
    w = w_ref[0]                                        # [bp]
    h = h_ref[0]                                        # [bp, F]
    bp = gid.shape[0]
    neg = jnp.finfo(h.dtype).min
    rows = g0 + jax.lax.broadcasted_iota(jnp.int32, (bg, bp), 0)
    oh = ((gid[None, :] == rows) & (w[None, :] > 0)).astype(h.dtype)

    @pl.when(k == 0)
    def _init():
        sum_ref[0] = jnp.zeros_like(sum_ref[0])
        cnt_ref[0] = jnp.zeros_like(cnt_ref[0])
        max_ref[0] = jnp.full_like(max_ref[0], neg)

    sum_ref[0] += jnp.dot(oh, h,
                          preferred_element_type=jnp.float32
                          ).astype(sum_ref.dtype)
    cnt_ref[0] += jnp.dot(oh, jnp.ones((bp, _DEG_LANES), h.dtype),
                          preferred_element_type=jnp.float32
                          ).astype(cnt_ref.dtype)
    live = wc_ref[...] > 0                              # [bp, 1]
    gid_c = gidc_ref[...]                               # [bp, 1]
    for j in range(bg):
        sel = live & (gid_c == g0 + j)                  # [bp, 1]
        m = jnp.max(jnp.where(sel, h, neg), axis=0, keepdims=True)
        max_ref[0, j:j + 1, :] = jnp.maximum(max_ref[0, j:j + 1, :], m)


@functools.partial(jax.jit, static_argnames=("n_graphs", "kind", "bg", "bp",
                                             "interpret"))
def segment_readout_pallas(h: jax.Array, graph_ids: jax.Array,
                           node_mask: jax.Array, n_graphs: int, *,
                           kind: str = "mean_max", bg: int = 8,
                           bp: int = 128,
                           interpret: bool = False) -> jax.Array:
    """Fused segment-mean/max graph readout over a packed flat node axis.

    h: [P, F]; graph_ids: [P] int32; node_mask: [P]. One pass computes
    per-graph sum, node count, and masked max; returns ``[G, F]``
    (``kind="mean"``) or ``[G, 2F]`` (mean ⊕ max). Graphs with no real
    nodes read out exact zeros. This replaces the padded layouts'
    per-graph masked-mean/max pooling without ever un-flattening the
    node axis.
    """
    if kind not in ("mean", "mean_max"):
        raise ValueError(f"kind must be 'mean' or 'mean_max', got {kind!r}")
    P, F = h.shape
    bg = min(bg, max(n_graphs, 1))
    bp = min(bp, max(P, 1))
    pg = (-n_graphs) % bg
    pp = (-P) % bp
    gid = graph_ids.astype(jnp.int32)
    w = node_mask.astype(h.dtype)
    if pp:
        h = jnp.pad(h, ((0, pp), (0, 0)))
        gid = jnp.pad(gid, (0, pp))                     # id 0, masked out
        w = jnp.pad(w, (0, pp))
    Gp, Pp = n_graphs + pg, P + pp
    # leading dummy batch axis keeps the (1, ...) block style of the
    # other segment kernels
    sums, cnt, mx = pl.pallas_call(
        functools.partial(_seg_readout_kernel, bg=bg),
        grid=(Gp // bg, Pp // bp),
        in_specs=[
            pl.BlockSpec((1, bp), lambda i, k: (0, k)),
            pl.BlockSpec((1, bp), lambda i, k: (0, k)),
            pl.BlockSpec((bp, 1), lambda i, k: (k, 0)),
            pl.BlockSpec((bp, 1), lambda i, k: (k, 0)),
            pl.BlockSpec((1, bp, F), lambda i, k: (0, k, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, bg, F), lambda i, k: (0, i, 0)),
            pl.BlockSpec((1, bg, _DEG_LANES), lambda i, k: (0, i, 0)),
            pl.BlockSpec((1, bg, F), lambda i, k: (0, i, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((1, Gp, F), h.dtype),
            jax.ShapeDtypeStruct((1, Gp, _DEG_LANES), h.dtype),
            jax.ShapeDtypeStruct((1, Gp, F), h.dtype),
        ],
        interpret=interpret,
    )(gid[None], w[None], gid[:, None], w[:, None], h[None])
    sums, cnt, mx = sums[0, :n_graphs], cnt[0, :n_graphs, :1], mx[0, :n_graphs]
    mean = sums / jnp.maximum(cnt, 1.0)
    if kind == "mean":
        return mean.astype(h.dtype)
    mx = jnp.where(cnt > 0, mx, 0.0)
    return jnp.concatenate([mean, mx], axis=-1).astype(h.dtype)


def _fused_mp_kernel(src_ref, dst_ref, em_ref, nm_ref, ss_ref, x_ref,
                     wn_ref, ws_ref, b_ref, o_ref, acc_ref, *deg_scratch,
                     ke: int, bn: int, mode: str, combine: str, act: str):
    """One message-passing layer as a single phased grid.

    The grid is ``(ke + kn,)``: iterations ``t < ke`` are the **edge
    phase** (one-hot gather → mask → one-hot scatter into a whole-
    ``[Pp, F]`` VMEM scratch accumulator, plus a degree accumulator for
    ``mode="mean"``); iterations ``t >= ke`` are the **node phase**
    (slice the accumulator, divide by degree, combine with the self
    term, bias, activation, node mask, write one output tile). The
    features never round-trip HBM between stages — that is the entire
    point of the fusion.
    """
    t = pl.program_id(0)
    p_pad = acc_ref.shape[0]
    deg_ref = deg_scratch[0] if deg_scratch else None

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        if deg_ref is not None:
            deg_ref[...] = jnp.zeros_like(deg_ref)

    @pl.when(t < ke)
    def _edge_phase():
        src = src_ref[0]                                # [be] int32
        dst = dst_ref[0]                                # [be]
        em = em_ref[0]                                  # [be]
        x = x_ref[...]                                  # [Pp, F]
        be = src.shape[0]
        cols = jax.lax.broadcasted_iota(jnp.int32, (be, p_pad), 1)
        oh_src = (src[:, None] == cols).astype(x.dtype)  # [be, Pp]
        msgs = jnp.dot(oh_src, x, preferred_element_type=jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (p_pad, be), 0)
        oh_dst = (dst[None, :] == rows).astype(x.dtype) * em[None, :]
        acc_ref[...] += jnp.dot(
            oh_dst, msgs,
            preferred_element_type=jnp.float32).astype(acc_ref.dtype)
        if deg_ref is not None:
            d = jnp.sum(oh_dst, axis=1)                 # [Pp]
            deg_ref[...] += jnp.broadcast_to(
                d[:, None], (p_pad, _DEG_LANES)).astype(deg_ref.dtype)

    @pl.when(t >= ke)
    def _node_phase():
        i = t - ke
        sl = pl.ds(i * bn, bn)
        x_t = x_ref[sl, :]                              # [bn, F]
        agg = acc_ref[sl, :]                            # [bn, H-in == F]
        if deg_ref is not None:
            dg = deg_ref[sl, :][:, :1]                  # [bn, 1]
            agg = agg / jnp.maximum(dg, 1.0)
        if combine == "split":
            y = (jnp.dot(x_t, ws_ref[...],
                         preferred_element_type=jnp.float32)
                 + jnp.dot(agg, wn_ref[...],
                           preferred_element_type=jnp.float32))
        else:                                           # "pre"
            s = ss_ref[0, sl][:, None]                  # [bn, 1]
            y = jnp.dot(s * x_t + agg, wn_ref[...],
                        preferred_element_type=jnp.float32)
        y = y + b_ref[0]
        if act == "relu":
            y = jnp.maximum(y, 0.0)
        y = y * nm_ref[0, sl][:, None]
        o_ref[...] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "combine", "act", "bn",
                                             "be", "interpret"))
def fused_mp_layer_pallas(x: jax.Array, edges: jax.Array,
                          edge_mask: jax.Array,
                          node_mask: jax.Array | None = None, *,
                          w_neigh: jax.Array,
                          w_self: jax.Array | None = None,
                          bias: jax.Array | None = None,
                          mode: str = "mean", combine: str = "split",
                          self_scale: jax.Array | None = None,
                          act: str = "relu", bn: int = 128, be: int = 128,
                          interpret: bool = False) -> jax.Array:
    """Fused message-passing megakernel over the packed flat node axis.

    One ``pallas_call`` covers gather → edge-mask → scatter-accumulate
    (→ mean) → self/neighbor combine → bias → activation → node mask;
    semantics are exactly :func:`repro.kernels.ref.fused_mp_layer_ref`.
    x: [P, F]; edges: [Q, 2] int32 globally offset; edge_mask: [Q]
    (may carry GCN edge weights); node_mask: [P] or None. Returns
    [P, H] where H = ``w_neigh.shape[1]``.
    """
    if mode not in ("sum", "mean"):
        raise ValueError(f"mode must be 'sum' or 'mean', got {mode!r}")
    if combine not in ("split", "pre"):
        raise ValueError(f"combine must be 'split' or 'pre', got {combine!r}")
    if act not in ("relu", "none"):
        raise ValueError(f"act must be 'relu' or 'none', got {act!r}")
    if combine == "split" and w_self is None:
        raise ValueError("combine='split' requires w_self")
    P, F = x.shape
    H = w_neigh.shape[1]
    Q = edges.shape[0]
    bn = min(bn, max(P, 1))
    be = min(be, max(Q, 1))
    pp = (-P) % bn
    # always pad the edge axis to ≥ one full tile so ke ≥ 1 (an edgeless
    # packed bin still flows through the same phased grid)
    Qp = max(be, Q + ((-Q) % be))

    src = jnp.pad(edges[:, 0].astype(jnp.int32), (0, Qp - Q))
    dst = jnp.pad(edges[:, 1].astype(jnp.int32), (0, Qp - Q))
    em = jnp.pad(edge_mask.astype(x.dtype), (0, Qp - Q))
    nm = (jnp.ones((P,), x.dtype) if node_mask is None
          else node_mask.astype(x.dtype))
    ss = jnp.broadcast_to(
        jnp.asarray(1.0 if self_scale is None else self_scale,
                    x.dtype), (P,))
    ws = (jnp.zeros_like(w_neigh) if w_self is None
          else w_self.astype(x.dtype))
    b = (jnp.zeros((H,), x.dtype) if bias is None
         else bias.astype(x.dtype))
    if pp:
        x = jnp.pad(x, ((0, pp), (0, 0)))
        nm = jnp.pad(nm, (0, pp))                       # masked → zero rows
        ss = jnp.pad(ss, (0, pp), constant_values=1.0)
    Pp = P + pp
    ke = Qp // be
    kn = Pp // bn

    scratch = [pltpu.VMEM((Pp, F), jnp.float32)]
    if mode == "mean":
        scratch.append(pltpu.VMEM((Pp, _DEG_LANES), jnp.float32))
    out = pl.pallas_call(
        functools.partial(_fused_mp_kernel, ke=ke, bn=bn, mode=mode,
                          combine=combine, act=act),
        grid=(ke + kn,),
        in_specs=[
            pl.BlockSpec((1, be), lambda t: (0, jnp.minimum(t, ke - 1))),
            pl.BlockSpec((1, be), lambda t: (0, jnp.minimum(t, ke - 1))),
            pl.BlockSpec((1, be), lambda t: (0, jnp.minimum(t, ke - 1))),
            _resident((1, Pp)),
            _resident((1, Pp)),
            _resident((Pp, F)),
            _resident((F, H)),
            _resident((F, H)),
            _resident((1, H)),
        ],
        out_specs=pl.BlockSpec((bn, H),
                               lambda t: (jnp.maximum(t - ke, 0), 0)),
        out_shape=jax.ShapeDtypeStruct((Pp, H), x.dtype),
        scratch_shapes=scratch,
        compiler_params=_RESIDENT_PARAMS,
        interpret=interpret,
    )(src[None], dst[None], em[None], nm[None], ss[None], x, w_neigh, ws,
      b[None])
    return out[:P].astype(x.dtype)


def _fused_gat_kernel(src_ref, dst_ref, em_ref, nm_ref, z_ref, att_ref,
                      o_ref, acc_ref, *, ke: int, bn: int, dh: int):
    """Fused GAT aggregate: gather ⊙ head-broadcast attention → scatter.

    Same phased-grid shape as :func:`_fused_mp_kernel`. The per-head
    attention ``[be, H]`` is broadcast over each head's ``dh``-wide
    feature slice via an in-kernel one-hot expansion matmul
    ``expand[h, d] = (d // dh == h)`` — MXU-native, no vector gather.
    """
    t = pl.program_id(0)
    p_pad, d_full = acc_ref.shape

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(t < ke)
    def _edge_phase():
        src = src_ref[0]                                # [be]
        dst = dst_ref[0]                                # [be]
        em = em_ref[0]                                  # [be]
        z = z_ref[...]                                  # [Pp, D]
        att = att_ref[...]                              # [be, Hp]
        be = src.shape[0]
        hp = att.shape[1]
        cols = jax.lax.broadcasted_iota(jnp.int32, (be, p_pad), 1)
        oh_src = (src[:, None] == cols).astype(z.dtype)
        zs = jnp.dot(oh_src, z, preferred_element_type=jnp.float32)
        h_rows = jax.lax.broadcasted_iota(jnp.int32, (hp, d_full), 0)
        d_cols = jax.lax.broadcasted_iota(jnp.int32, (hp, d_full), 1)
        expand = (d_cols // dh == h_rows).astype(z.dtype)   # [Hp, D]
        msgs = zs * jnp.dot(att, expand,
                            preferred_element_type=jnp.float32)
        rows = jax.lax.broadcasted_iota(jnp.int32, (p_pad, be), 0)
        oh_dst = (dst[None, :] == rows).astype(z.dtype) * em[None, :]
        acc_ref[...] += jnp.dot(
            oh_dst, msgs,
            preferred_element_type=jnp.float32).astype(acc_ref.dtype)

    @pl.when(t >= ke)
    def _node_phase():
        i = t - ke
        sl = pl.ds(i * bn, bn)
        o_ref[...] = (acc_ref[sl, :]
                      * nm_ref[0, sl][:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("bn", "be", "interpret"))
def fused_gat_aggregate_pallas(z: jax.Array, edges: jax.Array,
                               edge_mask: jax.Array, att: jax.Array,
                               node_mask: jax.Array, *, bn: int = 128,
                               be: int = 128,
                               interpret: bool = False) -> jax.Array:
    """Fused GAT post-softmax stage over the packed flat node axis.

    z: [P, D] projected features (heads concatenated, D = H·dh);
    edges: [Q, 2]; edge_mask: [Q]; att: [Q, H] softmax-normalized
    attention; node_mask: [P]. Oracle:
    :func:`repro.kernels.ref.fused_gat_aggregate_ref`.
    """
    P, D = z.shape
    Q, H = att.shape
    if D % H:
        raise ValueError(f"head count {H} must divide feature dim {D}")
    bn = min(bn, max(P, 1))
    be = min(be, max(Q, 1))
    pp = (-P) % bn
    ph = (-H) % 8                     # f32 sublane multiple
    Qp = max(be, Q + ((-Q) % be))

    src = jnp.pad(edges[:, 0].astype(jnp.int32), (0, Qp - Q))
    dst = jnp.pad(edges[:, 1].astype(jnp.int32), (0, Qp - Q))
    em = jnp.pad(edge_mask.astype(z.dtype), (0, Qp - Q))
    a = jnp.pad(att.astype(z.dtype), ((0, Qp - Q), (0, ph)))
    nm = node_mask.astype(z.dtype)
    if pp:
        z = jnp.pad(z, ((0, pp), (0, 0)))
        nm = jnp.pad(nm, (0, pp))
    Pp = P + pp
    Hp = H + ph
    ke = Qp // be
    kn = Pp // bn

    out = pl.pallas_call(
        functools.partial(_fused_gat_kernel, ke=ke, bn=bn, dh=D // H),
        grid=(ke + kn,),
        in_specs=[
            pl.BlockSpec((1, be), lambda t: (0, jnp.minimum(t, ke - 1))),
            pl.BlockSpec((1, be), lambda t: (0, jnp.minimum(t, ke - 1))),
            pl.BlockSpec((1, be), lambda t: (0, jnp.minimum(t, ke - 1))),
            _resident((1, Pp)),
            _resident((Pp, D)),
            pl.BlockSpec((be, Hp), lambda t: (jnp.minimum(t, ke - 1), 0)),
        ],
        out_specs=pl.BlockSpec((bn, D),
                               lambda t: (jnp.maximum(t - ke, 0), 0)),
        out_shape=jax.ShapeDtypeStruct((Pp, D), z.dtype),
        scratch_shapes=[pltpu.VMEM((Pp, D), jnp.float32)],
        compiler_params=_RESIDENT_PARAMS,
        interpret=interpret,
    )(src[None], dst[None], em[None], nm[None], z, a)
    return out[:P].astype(z.dtype)


def _softmax_stats_kernel(s_ref, dst_ref, em_ref, m_ref, d_ref, *,
                          bn: int):
    """Online (max, denom) per destination node, heads on sublanes.

    s: [H, be] logits; running m/d: [H, bn] revisited across edge tiles.
    """
    k = pl.program_id(2)
    s = s_ref[0]                                        # [H, be]
    dst = dst_ref[0]                                    # [be]
    em = em_ref[0]                                      # [be]
    be = dst.shape[0]
    neg = jnp.finfo(s.dtype).min

    rows = pl.program_id(1) * bn + jax.lax.broadcasted_iota(
        jnp.int32, (bn, be), 0)
    oh = (dst[None, :] == rows) & (em[None, :] > 0)     # [bn, be] bool

    @pl.when(k == 0)
    def _init():
        m_ref[0] = jnp.full_like(m_ref[0], neg)
        d_ref[0] = jnp.zeros_like(d_ref[0])

    m_old = m_ref[0]                                    # [H, bn]
    s_b = jnp.where(oh[None, :, :], s[:, None, :], neg)  # [H, bn, be]
    m_tile = jnp.max(s_b, axis=-1)                      # [H, bn]
    m_new = jnp.maximum(m_old, m_tile)
    # guard: fully-masked rows keep m == neg; exp(neg - neg) would be
    # exp(0)=1 garbage, so compute against a zeroed safe max instead and
    # rely on the one-hot to zero the terms.
    m_safe = jnp.where(m_new > neg, m_new, 0.0)
    p = jnp.where(oh[None, :, :],
                  jnp.exp(s_b - m_safe[:, :, None]), 0.0)
    rescale = jnp.where(m_old > neg, jnp.exp(m_old - m_safe), 0.0)
    d_ref[0] = d_ref[0] * rescale + jnp.sum(p, axis=-1)
    m_ref[0] = m_new


def _softmax_norm_kernel(s_ref, dst_ref, em_ref, m_ref, d_ref, a_ref, *,
                         n_pad: int):
    """Per-edge normalize: gather (m, d) by dst via one-hot matmuls."""
    s = s_ref[0]                                        # [H, be]
    dst = dst_ref[0]                                    # [be]
    em = em_ref[0]                                      # [be]
    m = m_ref[0]                                        # [H, N]
    d = d_ref[0]                                        # [H, N]
    be = dst.shape[0]
    neg = jnp.finfo(s.dtype).min

    oh = (jax.lax.broadcasted_iota(jnp.int32, (n_pad, be), 0)
          == dst[None, :]).astype(s.dtype)              # [N, be]
    m_g = jnp.dot(jnp.where(m > neg, m, 0.0), oh,
                  preferred_element_type=jnp.float32)   # [H, be]
    d_g = jnp.dot(d, oh, preferred_element_type=jnp.float32)
    # mask scores before the exp: a padded edge's raw score is excluded
    # from the max pass, so it could exceed m_g and overflow exp() into
    # inf·0 = NaN — the ref kernel masks first, match it exactly.
    s = jnp.where(em[None, :] > 0, s, neg)
    p = jnp.exp(s - m_g) * em[None, :]
    a_ref[0] = (p / jnp.maximum(d_g, jnp.finfo(s.dtype).tiny)
                ).astype(a_ref.dtype)


@functools.partial(jax.jit, static_argnames=("n_nodes", "bn", "be",
                                             "interpret"))
def edge_softmax_pallas(scores: jax.Array, dst: jax.Array,
                        edge_mask: jax.Array, n_nodes: int, *,
                        bn: int = 128, be: int = 128,
                        interpret: bool = False) -> jax.Array:
    """Per-destination softmax over incoming edges (GAT attention).

    scores: [B, E, H]; dst: [B, E] int32; edge_mask: [B, E].
    Returns [B, E, H] weights summing to 1 over each destination's real
    incoming edges; fully-masked destinations give exact zeros.
    """
    B, E, H = scores.shape
    if E == 0:
        return jnp.zeros_like(scores)
    bn = min(bn, max(n_nodes, 1))
    be = min(be, max(E, 1))
    pn = (-n_nodes) % bn
    pe = (-E) % be
    ph = (-H) % 8                     # f32 sublane multiple
    s = jnp.moveaxis(scores, -1, 1)                     # [B, H, E]
    d = dst.astype(jnp.int32)
    em = edge_mask.astype(scores.dtype)
    if ph:
        s = jnp.pad(s, ((0, 0), (0, ph), (0, 0)))
    if pe:
        s = jnp.pad(s, ((0, 0), (0, 0), (0, pe)))
        d = jnp.pad(d, ((0, 0), (0, pe)))
        em = jnp.pad(em, ((0, 0), (0, pe)))
    Np, Ep, Hp = n_nodes + pn, E + pe, H + ph

    m, den = pl.pallas_call(
        functools.partial(_softmax_stats_kernel, bn=bn),
        grid=(B, Np // bn, Ep // be),
        in_specs=[
            pl.BlockSpec((1, Hp, be), lambda b, i, k: (b, 0, k)),
            pl.BlockSpec((1, be), lambda b, i, k: (b, k)),
            pl.BlockSpec((1, be), lambda b, i, k: (b, k)),
        ],
        out_specs=[
            pl.BlockSpec((1, Hp, bn), lambda b, i, k: (b, 0, i)),
            pl.BlockSpec((1, Hp, bn), lambda b, i, k: (b, 0, i)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, Hp, Np), s.dtype),
            jax.ShapeDtypeStruct((B, Hp, Np), s.dtype),
        ],
        interpret=interpret,
    )(s, d, em)

    att = pl.pallas_call(
        functools.partial(_softmax_norm_kernel, n_pad=Np),
        grid=(B, Ep // be),
        in_specs=[
            pl.BlockSpec((1, Hp, be), lambda b, k: (b, 0, k)),
            pl.BlockSpec((1, be), lambda b, k: (b, k)),
            pl.BlockSpec((1, be), lambda b, k: (b, k)),
            pl.BlockSpec((1, Hp, Np), lambda b, k: (b, 0, 0)),
            pl.BlockSpec((1, Hp, Np), lambda b, k: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, Hp, be), lambda b, k: (b, 0, k)),
        out_shape=jax.ShapeDtypeStruct((B, Hp, Ep), s.dtype),
        interpret=interpret,
    )(s, d, em, m, den)
    return jnp.moveaxis(att[:, :H, :E], 1, -1).astype(scores.dtype)
