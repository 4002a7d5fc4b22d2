"""Sharding rules + HLO roofline parser."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import all_arch_names, get_config
from repro.models import lm
from repro.roofline.analysis import (analyze_hlo, parse_collectives,
                                     parse_flops_and_bytes, V5E)
from repro.sharding import ShardingPolicy, param_partition_specs, cache_specs


@pytest.mark.parametrize("arch", all_arch_names())
def test_param_specs_cover_every_leaf(arch):
    cfg = get_config(arch)
    pspec = lm.param_specs(cfg)
    policy = ShardingPolicy(data_axes=("data",), model_axis="model",
                            axis_sizes={"data": 16, "model": 16})
    specs = param_partition_specs(pspec, cfg, policy)
    leaves_p = jax.tree_util.tree_leaves(pspec)
    leaves_s = jax.tree_util.tree_leaves(
        specs, is_leaf=lambda x: isinstance(x, P))
    assert len(leaves_p) == len(leaves_s)
    # rank alignment + divisibility (the sanitizer contract)
    for arr, spec in zip(leaves_p, leaves_s):
        assert len(spec) <= arr.ndim
        for dim, entry in zip(arr.shape, spec):
            if entry is None:
                continue
            axes = entry if isinstance(entry, tuple) else (entry,)
            size = 1
            for a in axes:
                size *= {"data": 16, "model": 16}[a]
            assert dim % size == 0, (arch, arr.shape, spec)


@pytest.mark.parametrize("arch", ["qwen2.5-3b", "deepseek-v2-236b",
                                  "mamba2-370m", "zamba2-2.7b"])
def test_cache_specs_structure_matches_cache(arch):
    cfg = get_config(arch)
    cache = lm.init_cache(cfg, batch=16, max_len=128, abstract=True)
    policy = ShardingPolicy(data_axes=("data",), model_axis="model")
    specs = cache_specs(cfg, policy, tp=16)
    assert set(specs.keys()) == set(cache.keys())
    for k in cache:
        assert len(specs[k]) <= cache[k].ndim


# ---- roofline parser on a synthetic HLO -------------------------------------

_SYNTH_HLO = """
%cond.1 (arg.1: (s32[], f32[8,8])) -> pred[] {
  %p = (s32[], f32[8,8]) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %c = s32[] constant(10)
  ROOT %lt = pred[] compare(%i, %c), direction=LT
}

%body.1 (arg.2: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p2 = (s32[], f32[8,8]) parameter(0)
  %x = f32[8,8]{1,0} get-tuple-element(%p2), index=1
  %ar = f32[8,8]{1,0} all-reduce(%x), replica_groups={{0,1,2,3}}
  %d = f32[8,8]{1,0} dot(%ar, %ar), lhs_contracting_dims={1}, rhs_contracting_dims={0}
  ROOT %t = (s32[], f32[8,8]) tuple(%p2, %d)
}

ENTRY %main (a: f32[8,8]) -> f32[8,8] {
  %a = f32[8,8]{1,0} parameter(0)
  %ag = f32[16,8]{1,0} all-gather(%a), replica_groups={{0,1}}, dimensions={0}
  %w = (s32[], f32[8,8]) while(%a), condition=%cond.1, body=%body.1
  ROOT %r = f32[8,8]{1,0} get-tuple-element(%w), index=1
}
"""


def test_collective_parser_counts_trip_counts():
    total, breakdown, nops = parse_collectives(_SYNTH_HLO, n_devices=4)
    # all-reduce inside the while body: 8*8*4 bytes × 10 trips
    assert breakdown["all-reduce"] == pytest.approx(256 * 10)
    # all-gather at top level: result 16*8*4 / group 2
    assert breakdown["all-gather"] == pytest.approx(512 / 2)
    assert nops == 2


def test_flop_parser_scales_while_body():
    flops, _ = parse_flops_and_bytes(_SYNTH_HLO)
    # dot: 2*8*8*8 = 1024 flops × 10 trips
    assert flops == pytest.approx(1024 * 10)


def test_analyze_dominant_term():
    rep = analyze_hlo(_SYNTH_HLO, V5E, n_devices=4)
    assert rep.dominant in ("compute", "memory", "collective")
    assert rep.collective_bytes > 0


def test_shard_map_moe_on_single_device_mesh():
    """EP dispatch path compiles & runs on a 1×1 mesh (CI twin of the
    production path)."""
    import dataclasses
    from repro.configs import get_smoke_config
    from repro.launch.mesh import make_mesh
    from repro.models.parallel import ParallelCtx
    mesh = make_mesh((1, 1), ("data", "model"))
    cfg = get_smoke_config("deepseek-v2-236b")
    ctx = ParallelCtx(mesh=mesh, data_axes=("data",), model_axis="model",
                      moe_impl="ep")
    params = lm.init_params(jax.random.PRNGKey(0), cfg)
    inputs = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (2, 16),
                                           0, cfg.vocab),
              "labels": jax.random.randint(jax.random.PRNGKey(2), (2, 16),
                                           0, cfg.vocab)}
    with mesh:
        loss, _ = jax.jit(lambda p, b: lm.loss_fn(p, cfg, b, ctx))(
            params, inputs)
    assert bool(jnp.isfinite(loss))


def test_default_hardware_looks_up_device_kind():
    """Peaks come from the device's kind; an unknown kind (the CPU
    among them) raises instead of borrowing another device's envelope."""
    from types import SimpleNamespace
    from repro.roofline.analysis import V5E, default_hardware
    assert default_hardware(SimpleNamespace(device_kind="TPU v5 lite")) is V5E
    with pytest.raises(KeyError, match="CPU_HOST"):
        default_hardware(jax.devices("cpu")[0])
