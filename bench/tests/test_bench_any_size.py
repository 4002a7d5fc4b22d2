"""Graphs of any size through the harness: the plain reference past
1,024 nodes, the reference and the weights that a configuration names,
and lone oversize bins compiled in set-up."""
import gzip
import hashlib
import json
import sys
import types
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import reference  # noqa: E402
import run  # noqa: E402
import weights  # noqa: E402

OPS = ("dense", "add", "relu", "norm", "softmax", "mul", "gelu", "reduce")


def config(name):
    with open(BENCH / "configs" / f"{name}.json") as f:
        return json.load(f)


def synthetic_doc(n, seed):
    """A seeded ``repro.opgraph.v1`` DAG of ``n`` nodes: a chain with a
    skip edge into about a third of the nodes."""
    rng = np.random.default_rng(seed)
    nodes = []
    for i in range(n):
        op = OPS[int(rng.integers(len(OPS)))]
        width = int(rng.choice([64, 128, 512, 1024]))
        flops = float(rng.integers(1, 10**9))
        nodes.append({"id": i, "op": op, "out_shape": [4, 256, width],
                      "dtype": "float32", "attrs": {}, "flops": flops,
                      "macs": flops / 2 if op == "dense" else 0.0,
                      "param_bytes": float(4 * width * width)
                      if op == "dense" else 0.0})
    edges = [[i - 1, i] for i in range(1, n)]
    edges += [[i - int(rng.integers(2, 9)), i] for i in range(9, n)
              if rng.random() < 0.33]
    return {"schema": "repro.opgraph.v1", "nodes": nodes, "edges": edges,
            "meta": {"family": "synthetic", "batch": 4}}


@pytest.fixture(scope="module")
def zoo_docs():
    with gzip.open(BENCH / "pool" / "zoo_table2.jsonl.gz", "rt") as f:
        lines = f.read().splitlines()
    return [json.loads(lines[i]) for i in (0, 69, 97)]


@pytest.fixture(scope="module")
def big():
    return reference.featurise(synthetic_doc(3000, 11))


def small_params(name, hidden=64, seed=2**31 + 7):
    model = dict(config(name)["model"], hidden=hidden)
    return model, weights.make_params(seed, model)


@pytest.mark.parametrize("name", ["pmgns-sage-512", "pmgns-gcn-512"])
def test_large_graph_agrees_with_its_unpadded_forward(name, big):
    """Padded to its shape, the graph computes what it computes at its
    exact size: in float64 to 1e-9, where rounding cannot hide a padding
    row that leaks into a sum, a mean or a max; and through
    ``forward_log`` in float32 to the few ulps by which two orders of
    summation differ."""
    import jax
    n, e = len(big["x"]), len(big["edges"])
    assert n == 3000 and e > 2048
    shape = reference.pad_shape(big)
    assert shape == (4096, 4096)
    model, params = small_params(name)
    fwd = jax.jit(lambda p, *a: reference._graph_forward(
        p, model["variant"], *a))
    exact = (big["x"], big["edges"], np.ones(e), np.ones(n), big["static"])
    pad = reference.padded([big], shape)
    pad = tuple(pad[k][0] for k in ("x", "edges", "edge_mask", "node_mask",
                                    "static"))

    def f64(args):
        return [a if a.dtype.kind == "i" else a.astype(np.float64)
                for a in args]
    with jax.enable_x64(True):
        p64 = jax.tree_util.tree_map(lambda a: np.asarray(a, np.float64),
                                     params)
        gap64 = np.abs(np.asarray(fwd(p64, *f64(pad)))
                       - np.asarray(fwd(p64, *f64(exact)))).max()
    assert gap64 <= 1e-9
    got = reference.forward_log(params, model["variant"], [big])[0]
    with jax.default_matmul_precision("highest"):
        want = np.asarray(fwd(params, *[a.astype(np.float32)
                                        if a.dtype.kind == "f" else a
                                        for a in exact]))
    ulp = np.spacing(np.abs(want).max().astype(np.float32))
    assert np.abs(got - want).max() <= 4 * ulp


def test_mixed_block_gives_each_graph_its_own_result(big, zoo_docs):
    model, params = small_params("pmgns-sage-512")
    zoo = [reference.featurise(d) for d in zoo_docs]
    feats = [zoo[0], big, zoo[1], zoo[2], big]
    mixed = reference.forward_log(params, model["variant"], feats)
    for f, row in zip(feats, mixed):
        alone = reference.forward_log(params, model["variant"], [f])[0]
        np.testing.assert_array_equal(row, alone)


#: ``forward_log`` of pool graphs 0, 69 and 97 at hidden 64, seed
#: 2**31 + 7, as the reference computed them with one padded shape for
#: every graph (the zoo pool's shape is unchanged).
ZOO_OUTPUTS = {
    "pmgns-sage-512": [
        [14.722532272338867, 11.4650297164917, 13.955183982849121],
        [22.25649642944336, 10.000219345092773, 19.680042266845703],
        [19.006877899169922, 11.13406753540039, 16.910675048828125]],
    "pmgns-gcn-512": [
        [14.057487487792969, 9.772690773010254, 15.435867309570312],
        [20.93128204345703, 9.227975845336914, 21.727115631103516],
        [18.113000869750977, 9.998529434204102, 18.989356994628906]],
}


@pytest.mark.parametrize("name", sorted(ZOO_OUTPUTS))
def test_zoo_documents_keep_their_outputs(name, zoo_docs):
    model, params = small_params(name)
    feats = [reference.featurise(d) for d in zoo_docs]
    assert {reference.pad_shape(f) for f in feats} == {
        (reference.N_PAD, reference.E_PAD)}
    got = reference.forward_log(params, model["variant"], feats)
    np.testing.assert_allclose(got, ZOO_OUTPUTS[name], rtol=0, atol=1e-6)


STUB = '''
import numpy as np
calls = []

def featurise(doc):
    calls.append(("featurise", len(doc["nodes"])))
    return {"n": len(doc["nodes"])}

def forward_log(params, variant, feats, control=False):
    calls.append(("forward_log", variant, len(feats), control))
    return np.array([[np.log1p(f["n"])] * 3 for f in feats], np.float32)

def served_gap(served_phys, ref_log):
    return np.abs(np.log1p(np.asarray(served_phys)) - ref_log).max(-1)
'''


def test_compare_calls_the_reference_the_configuration_names(tmp_path):
    (tmp_path / "stub_ref.py").write_text(STUB)
    conf = dict(config("pmgns-sage-512"), reference="stub_ref.py")
    ref = run.load_reference(tmp_path, conf, "stub")
    pool = [json.dumps({"nodes": [{}] * n}) for n in (3, 5, 9)]
    # five requests over two distinct documents, each served as its size
    idx = [1, 2, 1, 1, 2]
    rec = types.SimpleNamespace(pool_idx=idx, futures=[
        types.SimpleNamespace(result=lambda _t, n=(3, 5, 9)[i]:
                              types.SimpleNamespace(latency_ms=n,
                                                    energy_j=n,
                                                    memory_mb=n))
        for i in idx])
    gaps = run.compare(ref, rec, list(range(5)), pool, None,
                       {"variant": "graphsage"}, control=False)
    assert ref.calls == [("featurise", 5), ("featurise", 9),
                         ("forward_log", "graphsage", 2, False)]
    np.testing.assert_allclose(gaps, 0.0, atol=1e-6)


def test_configuration_without_a_reference_is_an_error(tmp_path):
    conf = dict(config("pmgns-gcn-512"))
    del conf["reference"]
    with pytest.raises(SystemExit, match="pmgns-gcn-512"):
        run.load_reference(tmp_path, conf, "pmgns-gcn-512")


def test_weights_block_changes_only_the_last_fc_block():
    import jax
    model = dict(config("pmgns-sage-512")["model"], hidden=32)
    base = weights.make_params(5, model)
    cal = weights.make_params(5, model, last_scale=0.05, target_offset=4.0)
    flat_b = jax.tree_util.tree_flatten_with_path(base)[0]
    flat_c = dict(jax.tree_util.tree_flatten_with_path(cal)[0])
    last = f"b{model['n_fc_blocks'] - 1}"
    for path, leaf in flat_b:
        keys = [getattr(k, "key", k) for k in path]
        a, b = np.asarray(leaf), np.asarray(flat_c[path])
        if keys[:2] != ["fc", last]:
            np.testing.assert_array_equal(a, b)
        elif keys[2] == "w":
            np.testing.assert_allclose(b, a / weights.LAST_SCALE * 0.05,
                                       rtol=1e-6)
        else:
            np.testing.assert_allclose(
                b, a - weights.TARGET_OFFSET + 4.0, atol=1e-5)


#: sha256 over every leaf's bytes of ``weights.make_params(seed,
#: model)`` at hidden 512, as the weights were before configurations
#: could calibrate them.
WEIGHT_DIGESTS = {
    ("pmgns-sage-512", 1):
        "3d99125721a7f263a4b90f18302f29cfd36d5b742d7a73d672bea8cf98884ebc",
    ("pmgns-sage-512", 2**31 + 7):
        "49324758e5ff9df177ede3fa06f9de0b4c4d94d98e0309709bc012d2b767bb31",
    ("pmgns-gcn-512", 1):
        "4be4d0d5c6fc88c8627895ede1b54b5e45c92d43056568c671f1fe932d2ba398",
    ("pmgns-gcn-512", 2**31 + 7):
        "2a68b4954fc227f273590198fd58920ba49c016b952d7a434ad5f163f4da7208",
}


@pytest.mark.parametrize("name,seed", sorted(WEIGHT_DIGESTS))
def test_weights_without_a_block_are_unchanged(name, seed):
    import jax
    h = hashlib.sha256()
    for leaf in jax.tree_util.tree_leaves(
            weights.make_params(seed, config(name)["model"])):
        h.update(np.asarray(leaf).tobytes())
    assert h.hexdigest() == WEIGHT_DIGESTS[(name, seed)]


def test_lone_oversize_bin_is_compiled_in_setup(tmp_path):
    """One 5,000-node document under a node budget of 512: whether or
    not the program cuts it, its bin is lone and outside the ladder."""
    from repro.core.batching import (packed_shape, resolve_packed_budgets,
                                     sample_from_graph)
    from repro.core.frontends import from_json
    doc = synthetic_doc(5000, 3)
    with gzip.open(tmp_path / "pool.jsonl.gz", "wt") as f:
        f.write(json.dumps(doc) + "\n")
    conf = dict(config("pmgns-sage-512"),
                reference=str(BENCH / "reference.py"),
                weights={"last_scale": 0.05})
    (tmp_path / "lone.json").write_text(json.dumps(conf))
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(
        {"configs": [{"name": "lone", "file": "lone.json"}]}))
    (tmp_path / "bench" / "traffic").mkdir(parents=True)
    (tmp_path / "bench" / "traffic" / "lone.json").write_text(json.dumps(
        {"loop": "closed", "outstanding": 4, "pool": "pool.jsonl.gz",
         "serve": {"cache_size": None, "node_budget": 512},
         "warm_s": 0.5}))
    seen = {}

    def before_warm_passes(svc):
        seen["shapes"] = set(svc.engine._compiled_shapes)

    sess = run.Session({"name": "lone", "config": "lone",
                        "traffic": "lone", "chips": 1}, 7,
                       require_chip=False, root=tmp_path,
                       model_overrides={"hidden": 32},
                       fault=before_warm_passes)
    try:
        ec = sess.svc.engine.engine_cfg
        sample = sample_from_graph(from_json(doc), buckets=ec.buckets)
        budgets = resolve_packed_budgets(ec.node_budget, ec.edge_budget,
                                         ec.graph_budget)
        assert budgets[0] == 512
        shape = packed_shape([sample], *budgets)
        assert shape[0] > 512
        assert ("packed", *shape) in seen["shapes"]
        w = sess.window(1.0, stream=2)
    finally:
        sess.close()
    assert w.deltas["completed"] > 0
    assert w.deltas["recompiles"] == 0
    model = dict(conf["model"], hidden=32)
    last = f"b{model['n_fc_blocks'] - 1}"
    want = weights.make_params(7, model, last_scale=0.05)
    np.testing.assert_array_equal(np.asarray(sess.params["fc"][last]["w"]),
                                  np.asarray(want["fc"][last]["w"]))
