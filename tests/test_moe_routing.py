"""The MoE router against a plain numpy router, and DeepSeek-V2's
published routing (``group_limited_greedy``, unnormalised gates scaled
by ``routed_scaling_factor``) against the defaults every other MoE
configuration keeps."""
import jax.numpy as jnp
import numpy as np
import pytest
from jax import ShapeDtypeStruct

from repro.configs import get_config, get_smoke_config
from repro.core.frontends import from_jax
from repro.models import lm
from repro.models.config import MoEConfig
from repro.models.layers import _route


def np_route(logits, mo: MoEConfig):
    """Softmax scores; keep each token's ``topk_group`` best groups
    (ranked by their best expert); greedy top-k among what is left;
    renormalise or scale the gates."""
    z = np.exp(logits - logits.max(-1, keepdims=True))
    scores = z / z.sum(-1, keepdims=True)
    t, e = scores.shape
    if mo.n_group > 1:
        best = scores.reshape(t, mo.n_group, -1).max(-1)
        groups = np.argsort(-best, axis=1, kind="stable")[:, :mo.topk_group]
        keep = np.zeros((t, mo.n_group), bool)
        keep[np.arange(t)[:, None], groups] = True
        scores = np.where(np.repeat(keep, e // mo.n_group, axis=1),
                          scores, 0.0)
    ids = np.argsort(-scores, axis=1, kind="stable")[:, :mo.top_k]
    gates = np.take_along_axis(scores, ids, 1)
    if mo.norm_topk_prob:
        gates = gates / gates.sum(-1, keepdims=True)
    else:
        gates = gates * mo.routed_scaling_factor
    return gates, ids


ROUTERS = {
    "greedy": MoEConfig(n_experts=8, top_k=2, d_expert=4),
    "group_limited": MoEConfig(n_experts=12, top_k=3, d_expert=4,
                               n_group=4, topk_group=2,
                               norm_topk_prob=False,
                               routed_scaling_factor=16.0),
}


@pytest.mark.parametrize("name", sorted(ROUTERS))
def test_router_matches_numpy(name):
    mo = ROUTERS[name]
    rng = np.random.default_rng(0)
    x = rng.standard_normal((7, 6)).astype(np.float32)
    w = rng.standard_normal((6, mo.n_experts)).astype(np.float32)
    gates, ids, _ = _route(jnp.asarray(w), jnp.asarray(x), mo)
    want_gates, want_ids = np_route(x.astype(np.float64) @ w, mo)
    np.testing.assert_array_equal(np.asarray(ids), want_ids)
    np.testing.assert_allclose(np.asarray(gates), want_gates, rtol=1e-5)
    if mo.n_group > 1:      # every pick lies in one of the kept groups
        per = mo.n_experts // mo.n_group
        assert all(len({int(i) // per for i in row}) <= mo.topk_group
                   for row in np.asarray(ids))


def test_deepseek_v2_routes_as_published():
    mo = get_config("deepseek_v2_236b").moe
    assert (mo.n_experts, mo.top_k, mo.n_shared) == (160, 6, 2)
    assert (mo.n_group, mo.topk_group) == (8, 3)
    assert mo.norm_topk_prob is False
    assert mo.routed_scaling_factor == 16.0


def _trace(arch):
    acfg = get_smoke_config(arch)

    def forward(params, tokens):
        return lm.forward(params, acfg, {"tokens": tokens})[0]
    return from_jax(forward, lm.param_specs(acfg),
                    ShapeDtypeStruct((1, 64), jnp.int32))


def test_other_moe_smoke_presets_trace_as_before():
    """Routing fields default to the plain greedy router, so grok-1's
    smoke preset traces to the graph it always did; DeepSeek-V2's gains
    the group-limited routing's three operators in each of its two MoE
    layers (426 nodes before it)."""
    grok = _trace("grok_1_314b")
    assert (grok.num_nodes, len(grok.edges)) == (278, 376)
    mo = get_smoke_config("grok_1_314b").moe
    assert (mo.n_group, mo.topk_group, mo.norm_topk_prob,
            mo.routed_scaling_factor) == (1, 1, True, 1.0)
    assert _trace("deepseek_v2_236b").num_nodes == 426 + 3 * 2
