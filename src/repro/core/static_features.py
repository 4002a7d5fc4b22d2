"""Static Feature Generator (paper §3.3, eq. 1).

    F_s = F_mac ⊕ F_batch ⊕ F_Tconv ⊕ F_Tdense ⊕ F_Trelu

The paper computes F_mac with TVM's relay analysis, which only counts
Conv2D / Conv2D-transpose / dense / batch-matmul — our tracer attributes
MACs to exactly the ``dense`` and ``conv`` node kinds, i.e. the same
operator set, so the semantics match.

A 3-feature extension (total params, total activation bytes, total flops)
is available behind ``extended=True`` — used by the beyond-paper ablation
in benchmarks; the default is the faithful 5-vector.
"""
from __future__ import annotations

import operator
from typing import Any, Dict

import numpy as np

from .ir import OP_INDEX, OpGraph
from .node_features import NodeColumns, node_columns

STATIC_FEATURE_DIM = 5
STATIC_FEATURE_DIM_EXT = 8


def static_features(g: OpGraph, extended: bool = False) -> np.ndarray:
    """F_s of ``g``: :func:`columns_static_features` of its nodes'
    columns."""
    return columns_static_features(node_columns(g.nodes), g.meta, extended)


def columns_static_features(c: NodeColumns, meta: Dict[str, Any],
                            extended: bool = False) -> np.ndarray:
    """F_s of a graph's :class:`~repro.core.node_features.NodeColumns`
    and ``meta``. The totals are summed in node order, as
    ``OpGraph.total_macs`` sums them."""
    batch = float(meta.get("batch", meta.get("batch_size", 1)))
    f = [
        np.log1p(float(sum(c.macs.tolist()))),          # F_mac
        np.log1p(batch),                                 # F_batch
        float(np.count_nonzero(c.op == OP_INDEX["conv"])),   # F_Tconv
        float(np.count_nonzero(c.op == OP_INDEX["dense"])),  # F_Tdense
        float(np.count_nonzero(c.op == OP_INDEX["relu"])),   # F_Trelu
    ]
    if extended:
        total_act = sum(map(operator.mul, c.numel().tolist(),
                            c.dtype_bytes.tolist()))
        params = float(sum(c.param_bytes.tolist()))
        f += [
            np.log1p(float(meta.get("param_bytes", params))),
            np.log1p(float(total_act)),
            np.log1p(float(sum(c.flops.tolist()))),
        ]
    return np.asarray(f, dtype=np.float32)
