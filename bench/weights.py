"""PMGNS weights made from the run's seed, on the device, in one call.

The benchmark makes the weights itself, so that the program under test
and the plain reference (``bench/reference.py``) start from the same
arrays and neither side makes them. Shapes follow the paper's Table 3
through the configuration file; the layout is the program's parameter
tree: ``gnn/b{i}`` (GraphSAGE: ``self{w,b}``, ``neigh{w}``; GCN:
``lin{w,b}``) and ``fc/b{i}{w,b}``.

A configuration file may carry a ``"weights"`` block, ``{"last_scale":
..., "target_offset": ...}``, passed to :func:`make_params` as keyword
arguments. It calibrates the random weights for the graphs of that
configuration's traffic, so that their targets lie where a trained
model's do, as :data:`LAST_SCALE` and :data:`TARGET_OFFSET` do for the
zoo pool: the static features of a whole LLM graph (thousands of
``dense`` nodes) are far larger than a zoo graph's, and at the zoo's
scale they drive targets far out of that range. Without the block the
weights are those of the two constants.
"""
from __future__ import annotations

from typing import Dict

#: Added to the last FC block's bias, so that the three log1p targets
#: lie where a trained model's do (a few to a few tens) and not around
#: 0: a served value just above -1 keeps too few float32 bits of
#: ``1 + y`` for its log1p to be compared.
TARGET_OFFSET = 10.0
#: Scales the last FC block's matrix. At full Glorot scale the targets
#: of a seed stray up to about 17 from the offset over the pool: below
#: -3 on some seeds, where float32 keeps too few bits of ``1 + y``, and
#: above 25 on others, a memory of petabytes for which the program's
#: pod count alone takes milliseconds a request. Half keeps every seed's
#: targets between about 0 and 20, and the control's gap still several
#: times the program's.
LAST_SCALE = 0.5


def shapes(model: Dict) -> Dict:
    """The parameter tree of ``model`` (a configuration's ``model``
    block) as nested dicts of shape tuples."""
    hidden, variant = model["hidden"], model["variant"]
    gnn, d = {}, model["node_feat_dim"]
    for i in range(model["n_gnn_blocks"]):
        if variant == "graphsage":
            gnn[f"b{i}"] = {"self": {"w": (d, hidden), "b": (hidden,)},
                            "neigh": {"w": (d, hidden)}}
        elif variant == "gcn":
            gnn[f"b{i}"] = {"lin": {"w": (d, hidden), "b": (hidden,)}}
        else:
            raise ValueError(f"no weights for variant {variant!r}")
        d = hidden
    fc, d = {}, 2 * hidden + model["static_dim"]        # mean ⊕ max ⊕ F_s
    for i in range(model["n_fc_blocks"]):
        out = model["n_targets"] if i == model["n_fc_blocks"] - 1 else hidden
        fc[f"b{i}"] = {"w": (d, out), "b": (out,)}
        d = out
    return {"gnn": gnn, "fc": fc}


def make_params(seed: int, model: Dict, *, last_scale: float = LAST_SCALE,
                target_offset: float = TARGET_OFFSET):
    """Glorot-uniform matrices and small uniform biases, float32, made
    on the default device by one jitted call from ``seed``; the last
    matrix is scaled by ``last_scale`` and the last bias shifted by
    ``target_offset``."""
    import jax
    import jax.numpy as jnp
    tree = shapes(model)
    leaves, treedef = jax.tree_util.tree_flatten(
        tree, is_leaf=lambda v: isinstance(v, tuple))

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, shp in zip(keys, leaves):
            if len(shp) == 2:
                lim = (6.0 / (shp[0] + shp[1])) ** 0.5
            else:
                lim = 0.1
            out.append(jax.random.uniform(k, shp, jnp.float32, -lim, lim))
        p = jax.tree_util.tree_unflatten(treedef, out)
        last = p["fc"][f"b{model['n_fc_blocks'] - 1}"]
        last["w"] = last["w"] * last_scale
        last["b"] = last["b"] + target_offset
        return p

    key = jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                             (seed >> 32) & 0xFFFFFFFF)
    return jax.jit(init)(key)
