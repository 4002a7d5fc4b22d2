"""Node Feature Generator (paper §3.2, Algorithm 1).

Each operator node gets a fixed-length **32-dim** feature vector:

    F_node = F_oh ⊕ F_attr ⊕ F_shape          (Algorithm 1, lines 6-8)

* ``F_oh``    — 16-dim one-hot over :data:`repro.core.ir.OP_VOCAB`.
* ``F_attr``  — 8-dim operator attributes (kernel/stride/groups/window/
                contraction size/moved elements/dtype width).
* ``F_shape`` — 8-dim output-shape descriptor (rank, leading log-dims,
                log-numel, log-param-bytes).

All magnitude-like entries are ``log1p``-scaled: node features must live on
comparable scales for the GNN, and operator sizes span 9 orders of
magnitude across the dataset.
"""
from __future__ import annotations

import itertools
from operator import attrgetter
from typing import Any, Dict, NamedTuple, Sequence, Tuple, Union

import numpy as np

from .ir import OP_INDEX, OP_VOCAB, GraphArrays, OpGraph, OpNode, dtype_bytes

N_OP = len(OP_VOCAB)            # 16
N_ATTR = 8
N_SHAPE = 8
NODE_FEATURE_DIM = N_OP + N_ATTR + N_SHAPE   # 32 — matches the paper


def node_feature(nd: OpNode) -> np.ndarray:
    """One node's 32-dim feature row.

    Delegates to :func:`node_feature_matrix` on a single-node graph so
    there is exactly one implementation of the feature layout.
    """
    return node_feature_matrix(OpGraph(nodes=[nd], edges=[]))[0]


#: The F_attr scalars of a node whose ``attrs`` is empty:
#: ``attr_scalars({})``.
_NO_ATTRS = (0.0, 0.0, 1.0, 1, 0.0, 0, 0)
_OP = attrgetter("op")
_OUT_SHAPE = attrgetter("out_shape")
_DTYPE = attrgetter("dtype")
_ATTRS = attrgetter("attrs")
_FLOPS = attrgetter("flops")
_MACS = attrgetter("macs")
_PARAM_BYTES = attrgetter("param_bytes")


def attr_scalars(a: Dict[str, Any]) -> Tuple[Any, ...]:
    """The seven F_attr scalars of one node's ``attrs`` (paper §3.2):
    kernel height and width, stride, groups, window, contraction size
    and moved elements."""
    kernel = a.get("kernel", (0, 0))
    stride = a.get("stride", (1,))
    window = a.get("window", (0,))
    k0 = float(kernel[0]) if len(kernel) > 0 else 0.0
    return (
        k0,
        float(kernel[1]) if len(kernel) > 1 else k0,
        float(stride[0]) if len(stride) > 0 else 1.0,
        a.get("groups", 1),
        float(window[0]) if len(window) > 0 else 0.0,
        a.get("contract_k", 0),
        a.get("moved_elems", 0),
    )


def attr_matrix(attrs: Sequence[Dict[str, Any]]) -> np.ndarray:
    """``[n, 7]`` float64 :func:`attr_scalars` of each node's ``attrs``;
    read only where ``attrs`` is non-empty."""
    n = len(attrs)
    out = np.empty((n, len(_NO_ATTRS)), np.float64)
    out[:] = _NO_ATTRS
    has = np.flatnonzero(np.fromiter(map(len, attrs), np.intp, n))
    if has.size:
        out[has] = np.asarray([attr_scalars(attrs[i]) for i in has.tolist()],
                              np.float64)
    return out


class NodeColumns(NamedTuple):
    """An operator graph's nodes as columns, in node order: all that
    node features, static features and the graph totals read of them.
    :func:`node_columns` reads them off ``OpNode``\\ s,
    ``frontends.from_json_arrays`` off a document's node dicts."""

    #: [n] int64: the op's index in :data:`~repro.core.ir.OP_VOCAB`.
    op: np.ndarray
    #: [n, 7] float64: :func:`attr_scalars` of each node's ``attrs``.
    attrs: np.ndarray
    #: [n] int64: ``dtype_bytes`` of each node's dtype.
    dtype_bytes: np.ndarray
    #: [n] int64: each ``out_shape``'s length.
    rank: np.ndarray
    #: [sum(rank)] int64: every ``out_shape``, one after another.
    dims: np.ndarray
    flops: np.ndarray        #: [n] float64
    macs: np.ndarray         #: [n] float64
    param_bytes: np.ndarray  #: [n] float64

    def leading_dims(self, k: int) -> np.ndarray:
        """``[n, k]`` int64: each node's first ``k`` ``out_shape`` dims,
        0 past its rank. Work and memory are linear in ``len(dims)``."""
        n = len(self.rank)
        owner = np.repeat(np.arange(n), self.rank)
        pos = np.arange(len(self.dims)) - (np.cumsum(self.rank)
                                           - self.rank)[owner]
        lead = pos < k
        grid = np.zeros((n, k), np.int64)
        grid[owner[lead], pos[lead]] = self.dims[lead]
        return grid

    def numel(self) -> np.ndarray:
        """[n] each output's element count, exact: int64, or Python ints
        (an object array) where a count could pass 2**62."""
        shaped = np.flatnonzero(self.rank)      # rank 0 holds one element
        start = (np.cumsum(self.rank) - self.rank)[shaped]
        out = np.ones(len(self.rank), np.int64)
        if not shaped.size:
            return out
        approx = np.multiply.reduceat(self.dims.astype(np.float64), start)
        if not (np.abs(approx) < 2.0 ** 62).all():
            out = out.astype(object)
            out[shaped] = np.multiply.reduceat(self.dims.astype(object),
                                               start)
            return out
        out[shaped] = np.multiply.reduceat(self.dims, start)
        return out


def node_columns(nodes: Sequence[OpNode]) -> NodeColumns:
    """The :class:`NodeColumns` of ``nodes`` (ops in ``OP_VOCAB``)."""
    n = len(nodes)
    shapes = list(map(_OUT_SHAPE, nodes))
    rank = np.fromiter(map(len, shapes), np.int64, n)
    return NodeColumns(
        op=np.fromiter(map(OP_INDEX.__getitem__, map(_OP, nodes)),
                       np.int64, n),
        attrs=attr_matrix(list(map(_ATTRS, nodes))),
        dtype_bytes=np.fromiter(map(dtype_bytes, map(_DTYPE, nodes)),
                                np.int64, n),
        rank=rank,
        dims=np.fromiter(itertools.chain.from_iterable(shapes), np.int64,
                         int(rank.sum())),
        flops=np.fromiter(map(_FLOPS, nodes), np.float64, n),
        macs=np.fromiter(map(_MACS, nodes), np.float64, n),
        param_bytes=np.fromiter(map(_PARAM_BYTES, nodes), np.float64, n),
    )


def graph_columns(g: Union[OpGraph, GraphArrays]) -> NodeColumns:
    """The :class:`NodeColumns` of either form of a graph: read off an
    ``OpGraph``'s nodes, or those a ``GraphArrays`` was parsed into."""
    return g.cols if isinstance(g, GraphArrays) else node_columns(g.nodes)


def node_feature_matrix(g: OpGraph) -> np.ndarray:
    """X with shape [N_op, N_features] (paper notation): the features of
    ``g``'s :class:`NodeColumns` (:func:`columns_feature_matrix`)."""
    return columns_feature_matrix(node_columns(g.nodes))


#: Columns of F_attr ⊕ F_shape that are log1p-scaled: groups,
#: contract_k, moved_elems, dim0..dim3, numel, param_bytes, flops.
_LOG_COLS = [3, 5, 6, 9, 10, 11, 12, 13, 14, 15]


def columns_feature_matrix(c: NodeColumns) -> np.ndarray:
    """The ``[n, 32]`` float32 node features of a graph's
    :class:`NodeColumns`: the one feature layout.

    F_attr ⊕ F_shape are staged as float64 columns: kernel_h, kernel_w,
    stride, groups*, window, contract_k*, moved_elems*, dtype_bytes,
    rank, dim0*..dim3*, numel*, param_bytes*, flops* (* = one
    array-wide ``log1p`` of the value clipped at 0)."""
    n = len(c.op)
    f = np.zeros((n, NODE_FEATURE_DIM), dtype=np.float64)
    f[np.arange(n), c.op] = 1.0
    raw = f[:, N_OP:]
    raw[:, :7] = c.attrs
    raw[:, 7] = c.dtype_bytes
    raw[:, 8] = c.rank
    raw[:, 9:13] = c.leading_dims(4)
    raw[:, 13] = c.numel()
    raw[:, 14] = c.param_bytes
    raw[:, 15] = c.flops
    raw[:, _LOG_COLS] = np.log1p(np.maximum(raw[:, _LOG_COLS], 0.0))
    return f.astype(np.float32)


def adjacency_matrix(g: OpGraph) -> np.ndarray:
    """A[dst, src] — row i holds the in-neighbourhood of node i."""
    return g.adjacency()


def graph_tensors(g: OpGraph) -> Tuple[np.ndarray, np.ndarray]:
    """The (A, X) pair of Algorithm 1."""
    return adjacency_matrix(g), node_feature_matrix(g)
