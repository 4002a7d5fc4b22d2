"""Operations and HBM bytes that the algorithm needs, from shapes alone.

These are the work a computation requires, not what a kernel happens to
do: the fused message-passing kernel gathers and scatters through
one-hot matrix products that cost far more than the ``2·q·f`` of a
scatter-accumulate, and that excess is what a roofline share exposes.
Kept with the benchmark so that a change to the program cannot change
how its work is counted.
"""
from __future__ import annotations

from typing import Dict


def mp_layer(p: int, q: int, f: int, h: int, *, mode: str = "mean",
             combine: str = "split") -> Dict[str, float]:
    """One fused message-passing layer over ``p`` nodes and ``q`` edges,
    ``f`` features in and ``h`` out: inputs read once, output written
    once, everything between kept on chip."""
    nw = 2 if combine == "split" else 1      # weight matmuls in combine
    flops = 2.0 * q * f                      # scatter-accumulate MACs
    flops += 2.0 * p * f * h * nw            # combine matmul(s)
    flops += p * h                           # bias + activation
    if mode == "mean":
        flops += p * f                       # degree divide
    weights = f * h * nw + h
    elems = (p * f                           # x, read once
             + q                             # edge mask / weights
             + 2 * p                         # node mask + self-scale
             + weights
             + p * h)                        # output, written once
    return {"flops": flops, "bytes": 4.0 * elems + 8.0 * q}  # + edges


def segment_readout(p: int, f: int, g: int, *,
                    kind: str = "mean_max") -> Dict[str, float]:
    """Segment mean (and max) of ``p`` node rows of width ``f`` into
    ``g`` graphs."""
    out_f = 2 * f if kind == "mean_max" else f
    flops = 2.0 * p * f + g * f              # sum+max sweep, mean divide
    elems = p * f + 2.0 * p + g * out_f + g  # h, ids+mask, out, counts
    return {"flops": flops, "bytes": 4.0 * elems}


def layer_modes(variant: str) -> Dict[str, str]:
    """How a PMGNS variant drives the fused layer."""
    if variant == "graphsage":
        return {"mode": "mean", "combine": "split"}
    if variant == "gcn":
        return {"mode": "sum", "combine": "pre"}
    raise ValueError(f"no counts for variant {variant!r}")


def model_flops(model: Dict, n: int, e: int) -> float:
    """Operations one prediction needs: a graph of ``n`` nodes and ``e``
    edges through every message-passing layer, the readout and the FC
    head of ``model`` (a configuration's ``model`` block)."""
    h = model["hidden"]
    modes = layer_modes(model["variant"])
    total, f = 0.0, model["node_feat_dim"]
    for _ in range(model["n_gnn_blocks"]):
        total += mp_layer(n, e, f, h, **modes)["flops"]
        f = h
    total += segment_readout(n, h, 1)["flops"]
    d = 2 * h + model["static_dim"]
    for i in range(model["n_fc_blocks"]):
        out = model["n_targets"] if i == model["n_fc_blocks"] - 1 else h
        total += 2.0 * d * out + out
        d = out
    return total
