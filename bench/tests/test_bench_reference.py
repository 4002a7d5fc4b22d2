"""The plain reference against the program, the control against the
limit, and the work counts and peaks the metrics divide by."""
import gzip
import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import counts  # noqa: E402
import peaks  # noqa: E402
import reference  # noqa: E402
import weights  # noqa: E402


def config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


@pytest.fixture(scope="module")
def docs():
    with gzip.open(BENCH / "pool" / "zoo_table2.jsonl.gz", "rt") as f:
        lines = f.read().splitlines()
    sizes = [len(json.loads(line)["nodes"]) for line in lines]
    pick = [int(np.argmax(sizes))] + list(range(0, 310, 10))
    return [json.loads(lines[i]) for i in pick]


def served(params, model, docs, hidden=None):
    """The program's packed serving path, as the engine runs it."""
    from repro.core.batching import sample_from_graph
    from repro.core.engine import PredictionEngine
    from repro.core.frontends import from_json
    from repro.core.gnn import PMGNSConfig
    cfg = PMGNSConfig(variant=model["variant"],
                      hidden=hidden or model["hidden"], layout="packed",
                      use_pallas=True)
    eng = PredictionEngine(params, cfg)
    return eng.predict_samples([sample_from_graph(from_json(d))
                                for d in docs])


@pytest.mark.parametrize("name", ["pmgns-sage-512", "pmgns-gcn-512"])
def test_reference_agrees_with_program_and_control_does_not(name, docs):
    conf = config(name)
    model, limit = conf["model"], conf["limits"]["max_log_gap"]
    params = weights.make_params(2**31 + 7, model)
    feats = [reference.featurise(d) for d in docs]
    ref = reference.forward_log(params, model["variant"], feats)
    assert np.isfinite(ref).all()
    gap = reference.served_gap(served(params, model, docs), ref)
    assert gap.max() <= limit / 2
    ctrl = reference.forward_log(params, model["variant"], feats,
                                 control=True)
    assert reference.served_gap(np.expm1(ctrl.astype(np.float64)),
                                ref).max() > limit


@pytest.mark.parametrize("variant", ["graphsage", "gcn"])
def test_reference_agrees_with_pallas_kernels(variant, docs, monkeypatch):
    """The fused and readout kernels, in interpret mode, at width 64."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "kernel_impl", lambda: "pallas")
    model = dict(config("pmgns-sage-512")["model"], variant=variant,
                 hidden=64)
    params = weights.make_params(3, model)
    sub = docs[1:4]
    ref = reference.forward_log(params, variant,
                                [reference.featurise(d) for d in sub])
    gap = reference.served_gap(served(params, model, sub, hidden=64), ref)
    assert gap.max() <= config("pmgns-sage-512")["limits"]["max_log_gap"]


def test_bf16_weights_fall_outside_the_limit(docs):
    import jax
    import jax.numpy as jnp
    conf = config("pmgns-sage-512")
    model = conf["model"]
    params = weights.make_params(11, model)
    feats = [reference.featurise(d) for d in docs]
    ref = reference.forward_log(params, model["variant"], feats)
    cast = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16).astype(jnp.float32), params)
    low = reference.forward_log(cast, model["variant"], feats)
    gap = reference.served_gap(np.expm1(low.astype(np.float64)), ref)
    assert gap.max() > 3 * conf["limits"]["max_log_gap"]


def test_featurise_matches_the_program(docs):
    from repro.core.batching import sample_from_graph
    from repro.core.frontends import from_json
    for d in docs:
        f = reference.featurise(d)
        s = sample_from_graph(from_json(d))
        n = s.n_nodes
        np.testing.assert_allclose(f["x"], s.x[:n], rtol=1e-6)
        np.testing.assert_allclose(f["static"], s.static, rtol=1e-6)
        assert {tuple(e) for e in f["edges"]} == \
            {tuple(e) for e in s.edges}


def test_weights_repeat_by_seed():
    model = config("pmgns-gcn-512")["model"]
    a = weights.make_params(2**33 + 1, model)
    b = weights.make_params(2**33 + 1, model)
    c = weights.make_params(1, model)
    assert (np.asarray(a["fc"]["b0"]["w"]) ==
            np.asarray(b["fc"]["b0"]["w"])).all()
    assert not (np.asarray(a["fc"]["b0"]["w"]) ==
                np.asarray(c["fc"]["b0"]["w"])).all()
    assert a["gnn"]["b0"]["lin"]["w"].shape == (32, 512)
    assert a["fc"]["b0"]["w"].shape == (2 * 512 + 5, 512)


def test_mp_layer_counts_by_hand():
    w = counts.mp_layer(4, 3, 2, 5, mode="mean", combine="split")
    # scatter 2·3·2 + combine 2·4·2·5·2 + bias/act 4·5 + divide 4·2
    assert w["flops"] == 12 + 160 + 20 + 8
    # x 8, mask 3, node mask + scale 8, weights 25, out 20 elements;
    # 8 bytes per edge of endpoints
    assert w["bytes"] == 4 * (8 + 3 + 8 + 25 + 20) + 8 * 3
    w = counts.mp_layer(4, 3, 2, 5, mode="sum", combine="pre")
    assert w["flops"] == 12 + 80 + 20
    assert w["bytes"] == 4 * (8 + 3 + 8 + 15 + 20) + 8 * 3


def test_readout_and_model_counts_by_hand():
    w = counts.segment_readout(4, 2, 1)
    assert w == {"flops": 16 + 2, "bytes": 4.0 * (8 + 8 + 4 + 1)}
    model = {"variant": "gcn", "hidden": 2, "node_feat_dim": 3,
             "static_dim": 1, "n_gnn_blocks": 1, "n_fc_blocks": 2,
             "n_targets": 1}
    # layer 2·e·f + 2·n·f·h + n·h, readout 2·n·h + h, FC 2·5·2+2, 2·2·1+1
    n, e = 4, 3
    assert counts.model_flops(model, n, e) == (
        2 * 3 * 3 + 2 * 4 * 3 * 2 + 4 * 2) + (2 * 4 * 2 + 2) + 22 + 5


def test_peaks_lookup():
    p = peaks.peaks_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12
    assert p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        peaks.peaks_for("TPU v99")
