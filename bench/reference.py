"""Plain reference of what one request computes: featurise, then PMGNS.

Independent of the program under test: it reads the request's JSON
document itself, builds the paper's node features (§3.2, Algorithm 1)
and static features (§3.3, eq. 1) with numpy, and runs the PMGNS forward
pass (§3.4) for one graph at a time in plain ``jax.numpy``: no kernels,
no packing into bins, no staging buffers. Each graph is padded with
masks to at least ``N_PAD`` nodes and ``E_PAD`` edges, or to the next
power of two above its size, and the graphs of one padded shape run in
blocks, each one ``vmap`` of the per-graph function: every graph of the
zoo pool shares one compiled shape, and a graph of any size computes.

Float32 under matmul precision ``"highest"`` is the reference. The
control is the same code with every matrix product computed as three
bfloat16 passes (``"high"`` precision on a TPU: the high and low
bfloat16 halves of each operand, all products but low × low), the
nearest precision below the float32 at ``"highest"`` that the
configurations state. It is written out here, so that it computes the
same on every backend.
"""
from __future__ import annotations

import math
from typing import Dict, List, Sequence, Tuple

import numpy as np

#: Operator vocabulary of the one-hot node feature, in the paper's order
#: of the repository's IR (16 kinds).
OP_VOCAB = ("dense", "conv", "add", "mul", "div", "relu", "gelu", "tanh",
            "exp", "softmax", "reduce", "norm", "pool", "gather", "scatter",
            "elementwise")
OP_INDEX = {op: i for i, op in enumerate(OP_VOCAB)}
DTYPE_BYTES = {"float64": 8, "float32": 4, "float16": 2, "bfloat16": 2,
               "int64": 8, "int32": 4, "int16": 2, "int8": 1, "uint8": 1,
               "bool": 1, "float8_e4m3fn": 1, "float8_e5m2": 1}
NODE_FEATURE_DIM = 32
STATIC_FEATURE_DIM = 5

#: The smallest padded shape: every zoo pool graph fits it (at most
#: 1,024 nodes; the densest has under 2,048 edges). A larger graph pads
#: each axis to the next power of two.
N_PAD = 1024
E_PAD = 2048
#: Graphs per compiled reference call at the smallest shape; a call
#: holds at most ``BLOCK * N_PAD`` node rows, so larger shapes run
#: fewer graphs a call, down to one.
BLOCK = 32


def featurise(doc: Dict) -> Dict[str, np.ndarray]:
    """``x [n, 32]``, ``edges [e, 2]`` (src, dst; unique, no self-loops)
    and ``static [5]`` of one ``repro.opgraph.v1`` document."""
    nodes = sorted(doc["nodes"], key=lambda d: int(d["id"]))
    index = {int(d["id"]): i for i, d in enumerate(nodes)}
    n = len(nodes)
    x = np.zeros((n, NODE_FEATURE_DIM), np.float64)
    for i, d in enumerate(nodes):
        x[i, OP_INDEX.get(d["op"], OP_INDEX["elementwise"])] = 1.0
        a = d.get("attrs", {})
        kernel = a.get("kernel", (0, 0))
        stride = a.get("stride", (1,))
        window = a.get("window", (0,))
        k0 = float(kernel[0]) if len(kernel) > 0 else 0.0
        shape = [int(s) for s in d.get("out_shape", ())]
        numel = math.prod(shape)
        raw = [k0, float(kernel[1]) if len(kernel) > 1 else k0,
               float(stride[0]) if len(stride) > 0 else 1.0,
               a.get("groups", 1),
               float(window[0]) if len(window) > 0 else 0.0,
               a.get("contract_k", 0), a.get("moved_elems", 0),
               DTYPE_BYTES.get(str(d.get("dtype", "float32")), 4),
               len(shape)] + [shape[k] if len(shape) > k else 0
                              for k in range(4)] + [
               numel, float(d.get("param_bytes", 0.0)),
               float(d.get("flops", 0.0))]
        x[i, 16:] = raw
    # log1p of the magnitudes: groups, contract_k, moved elements,
    # dims 0-3, numel, parameter bytes, flops
    for c in (3, 5, 6, 9, 10, 11, 12, 13, 14, 15):
        x[:, 16 + c] = np.log1p(np.maximum(x[:, 16 + c], 0.0))
    pairs = {(index[int(s)], index[int(t)]) for s, t in doc.get("edges", ())}
    edges = np.array(sorted(p for p in pairs if p[0] != p[1]),
                     np.int32).reshape(-1, 2)
    meta = doc.get("meta", {})
    batch = float(meta.get("batch", meta.get("batch_size", 1)))
    ops = [d["op"] for d in nodes]
    static = [np.log1p(sum(float(d.get("macs", 0.0)) for d in nodes)),
              np.log1p(batch), ops.count("conv"), ops.count("dense"),
              ops.count("relu")]
    return {"x": x.astype(np.float32), "edges": edges,
            "static": np.asarray(static, np.float32)}


def _pow2(n: int) -> int:
    return 1 << max(0, int(n) - 1).bit_length()


def pad_shape(f: Dict[str, np.ndarray]) -> Tuple[int, int]:
    """``(nodes, edges)`` that the graph of features ``f`` is padded to."""
    return (max(N_PAD, _pow2(len(f["x"]))),
            max(E_PAD, _pow2(len(f["edges"]))))


def padded(feats: Sequence[Dict[str, np.ndarray]],
           shape: Tuple[int, int]) -> Dict[str, np.ndarray]:
    """Stack per-graph features, each padded to ``shape`` (nodes,
    edges); padding is masked out of every sum, mean and max."""
    b, (n_pad, e_pad) = len(feats), shape
    out = {"x": np.zeros((b, n_pad, NODE_FEATURE_DIM), np.float32),
           "node_mask": np.zeros((b, n_pad), np.float32),
           "edges": np.zeros((b, e_pad, 2), np.int32),
           "edge_mask": np.zeros((b, e_pad), np.float32),
           "static": np.zeros((b, STATIC_FEATURE_DIM), np.float32)}
    for i, f in enumerate(feats):
        n, e = len(f["x"]), len(f["edges"])
        if n > n_pad or e > e_pad:
            raise ValueError(f"graph of {n} nodes, {e} edges exceeds the "
                             f"padded shape {n_pad}/{e_pad}")
        out["x"][i, :n] = f["x"]
        out["node_mask"][i, :n] = 1.0
        out["edges"][i, :e] = f["edges"]
        out["edge_mask"][i, :e] = 1.0
        out["static"][i] = f["static"]
    return out


def _three_pass(a, b):
    """``a @ b`` from bfloat16 halves: ``hi·hi + hi·lo + lo·hi``.

    The halves are rounded with ``lax.reduce_precision``: a TPU compiler
    folds a round trip through ``bfloat16`` away and feeds the halves to
    one bfloat16 pass, which is the default precision, not three
    passes."""
    import jax

    def bf16(v):
        return jax.lax.reduce_precision(v, exponent_bits=8, mantissa_bits=7)

    def halves(v):
        hi = bf16(v)
        return hi, bf16(v - hi)
    ah, al = halves(a)
    bh, bl = halves(b)
    return ah @ bh + (ah @ bl + al @ bh)


def _graph_forward(params, variant: str, x, edges, edge_mask, node_mask,
                   static, mm=None):
    """PMGNS on one padded graph → ``[3]`` targets in log1p space.
    ``mm`` computes the matrix products (default ``@``)."""
    import jax
    import jax.numpy as jnp
    mm = mm or jnp.matmul
    n = x.shape[0]
    src, dst = edges[:, 0], edges[:, 1]
    dt = x.dtype
    em, nm = edge_mask.astype(dt), node_mask.astype(dt)
    if variant == "graphsage":
        deg = jax.ops.segment_sum(em, dst, num_segments=n)
        inv_deg = (1.0 / jnp.maximum(deg, 1.0))[:, None]
    elif variant == "gcn":
        deg = jax.ops.segment_sum(em, dst, num_segments=n) + nm
        dinv = jax.lax.rsqrt(jnp.maximum(deg, 1.0))
        em = em * dinv[dst] * dinv[src]              # D^-1/2 A D^-1/2
        self_w = (dinv * dinv * nm)[:, None]         # the added self-loop
    else:
        raise ValueError(f"no reference for variant {variant!r}")
    h = x
    for i in range(len(params["gnn"])):
        lp = params["gnn"][f"b{i}"]
        agg = jax.ops.segment_sum(h[src] * em[:, None], dst, num_segments=n)
        if variant == "graphsage":
            y = (mm(h, lp["self"]["w"]) + mm(agg * inv_deg, lp["neigh"]["w"])
                 + lp["self"]["b"])
        else:
            y = mm(self_w * h + agg, lp["lin"]["w"]) + lp["lin"]["b"]
        h = jnp.maximum(y, 0.0) * nm[:, None]
    count = jnp.maximum(nm.sum(), 1.0)
    mean = h.sum(axis=0) / count
    mx = jnp.where(nm[:, None] > 0, h, jnp.finfo(dt).min).max(axis=0)
    y = jnp.concatenate([mean, mx, static.astype(dt)])
    n_fc = len(params["fc"])
    for i in range(n_fc):
        lp = params["fc"][f"b{i}"]
        y = mm(y[None], lp["w"])[0] + lp["b"]
        if i < n_fc - 1:
            y = jnp.maximum(y, 0.0)
    return y


_COMPILED: Dict = {}


def forward_log(params, variant: str, feats: Sequence[Dict[str, np.ndarray]],
                control: bool = False) -> np.ndarray:
    """``[len(feats), 3]`` log1p-space targets: the reference, or with
    ``control`` the control. Graphs are grouped by :func:`pad_shape`;
    each group runs in calls of ``max(1, BLOCK * N_PAD // nodes)``
    graphs, the last filled up with repeats, so a shape compiles once."""
    import jax
    key = (variant, control)
    fn = _COMPILED.get(key)
    if fn is None:
        mm = _three_pass if control else None

        def block(p, x, edges, edge_mask, node_mask, static):
            return jax.vmap(
                lambda *a: _graph_forward(p, variant, *a, mm=mm))(
                x, edges, edge_mask, node_mask, static)
        fn = _COMPILED[key] = jax.jit(block)
    out = np.zeros((len(feats), 3), np.float32)
    groups: Dict[Tuple[int, int], List[int]] = {}
    for i, f in enumerate(feats):
        groups.setdefault(pad_shape(f), []).append(i)
    for shape, idx in groups.items():
        block = max(1, BLOCK * N_PAD // shape[0])
        for s in range(0, len(idx), block):
            rows = idx[s:s + block]
            chunk = [feats[i] for i in rows]
            chunk += [chunk[-1]] * (block - len(rows))  # one compiled shape
            b = padded(chunk, shape)
            with jax.default_matmul_precision("highest"):
                y = fn(params, b["x"], b["edges"], b["edge_mask"],
                       b["node_mask"], b["static"])
            out[rows] = np.asarray(y)[:len(rows)]
    return out


def served_gap(served_phys, ref_log) -> np.ndarray:
    """Per-graph widest gap ``|log1p(served) - reference|`` over the
    three targets: served physical units against the reference's log1p
    outputs. A served value of -1 or below reads an infinite gap."""
    with np.errstate(divide="ignore", invalid="ignore"):
        served = np.log1p(np.asarray(served_phys, np.float64))
    gap = np.abs(served - np.asarray(ref_log, np.float64)).max(-1)
    return np.where(np.isnan(gap), np.inf, gap)
