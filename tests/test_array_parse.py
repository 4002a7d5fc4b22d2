"""``frontends.from_json_arrays``: a canonical ``repro.opgraph.v1``
document parsed straight into arrays gives, bit for bit, the sample and
the fingerprint of the ``OpGraph`` that ``from_json`` builds of it; any
other document, invalid ones included, is left to ``from_json``."""
import copy
import gzip
import json
import tracemalloc
from pathlib import Path

import jax
import numpy as np
import pytest

from repro.core import DIPPM, PMGNSConfig, pmgns_init
from repro.core.batching import pad_sample, sample_from_graph
from repro.core.frontends import from_json, from_json_arrays
from repro.core.ir import OP_INDEX, GraphValidationError, dtype_bytes
from repro.core.node_features import (N_OP, NODE_FEATURE_DIM,
                                      node_feature_matrix)
from repro.core.static_features import static_features

POOL = Path(__file__).resolve().parents[1] / "bench" / "pool"
V1 = "repro.opgraph.v1"


def _assert_same(doc):
    """The array path's sample (both static settings), meta and
    fingerprint equal the object path's; returns its GraphArrays."""
    g, ga = from_json(doc), from_json_arrays(doc)
    assert ga is not None
    for extended in (False, True):
        want = sample_from_graph(g, extended_static=extended)
        got = sample_from_graph(ga, extended_static=extended)
        for field in ("x", "edges", "mask", "static"):
            a, b = getattr(got, field), getattr(want, field)
            assert a.dtype == b.dtype and a.shape == b.shape, field
            np.testing.assert_array_equal(a, b, err_msg=field)
        assert got.meta == want.meta
    assert ga.fingerprint() == g.fingerprint()
    assert ga.num_nodes == g.num_nodes
    return ga


def _features_reference(g):
    """The per-node loop that ``node_feature_matrix`` replaced."""
    n = g.num_nodes
    f = np.zeros((n, NODE_FEATURE_DIM), dtype=np.float64)
    if n == 0:
        return f.astype(np.float32)
    f[np.arange(n), [OP_INDEX[nd.op] for nd in g.nodes]] = 1.0
    rows = []
    for nd in g.nodes:
        a = nd.attrs
        kernel = a.get("kernel", (0, 0))
        stride = a.get("stride", (1,))
        window = a.get("window", (0,))
        k0 = float(kernel[0]) if len(kernel) > 0 else 0.0
        shape = nd.out_shape
        rows.append((
            k0, float(kernel[1]) if len(kernel) > 1 else k0,
            float(stride[0]) if len(stride) > 0 else 1.0,
            a.get("groups", 1),
            float(window[0]) if len(window) > 0 else 0.0,
            a.get("contract_k", 0), a.get("moved_elems", 0),
            dtype_bytes(nd.dtype), len(shape),
            shape[0] if len(shape) > 0 else 0,
            shape[1] if len(shape) > 1 else 0,
            shape[2] if len(shape) > 2 else 0,
            shape[3] if len(shape) > 3 else 0,
            nd.out_elems, nd.param_bytes, nd.flops))
    raw = np.asarray(rows, dtype=np.float64)
    cols = [3, 5, 6, 9, 10, 11, 12, 13, 14, 15]
    raw[:, cols] = np.log1p(np.maximum(raw[:, cols], 0.0))
    f[:, N_OP:] = raw
    return f.astype(np.float32)


def _static_reference(g, extended):
    """The per-node sums that ``static_features`` replaced."""
    batch = float(g.meta.get("batch", g.meta.get("batch_size", 1)))
    f = [np.log1p(g.total_macs()), np.log1p(batch),
         float(g.op_count("conv")), float(g.op_count("dense")),
         float(g.op_count("relu"))]
    if extended:
        f += [np.log1p(float(g.meta.get("param_bytes",
                                        g.total_param_bytes()))),
              np.log1p(float(sum(nd.out_bytes for nd in g.nodes))),
              np.log1p(float(sum(nd.flops for nd in g.nodes)))]
    return np.asarray(f, dtype=np.float32)


def _assert_matches_reference(g):
    np.testing.assert_array_equal(node_feature_matrix(g),
                                  _features_reference(g))
    for extended in (False, True):
        np.testing.assert_array_equal(static_features(g, extended),
                                      _static_reference(g, extended))


def _pool(name):
    with gzip.open(POOL / name, "rt") as f:
        return [json.loads(line) for line in f]


def test_every_zoo_document_matches_the_object_path():
    docs = _pool("zoo_table2.jsonl.gz")
    assert len(docs) == 512
    for d in docs:
        _assert_same(d)
    for d in docs[::16]:
        _assert_matches_reference(from_json(d))


def test_smallest_deepseek_v2_document_matches_the_object_path():
    doc = min(_pool("deepseek_v2_prefill.jsonl.gz"),
              key=lambda d: len(d["nodes"]))
    assert len(doc["nodes"]) == 9381
    _assert_same(doc)


def _base():
    """A small v1 graph with every kind of field: attrs of each F_attr
    key, ranks 0 to 6, several dtypes, sizes past 2**53."""
    nodes = [
        {"id": 0, "op": "conv", "out_shape": [8, 112, 112, 64],
         "dtype": "bfloat16", "attrs": {"kernel": [7, 7], "stride": [2, 2],
                                        "groups": 1},
         "flops": 2.3e9, "macs": 1.18e9, "bytes_accessed": 3.1e7,
         "param_bytes": 18816.0},
        {"id": 1, "op": "relu", "out_shape": [8, 112, 112, 64],
         "dtype": "bfloat16", "attrs": {}, "flops": 6.4e6, "macs": 0.0,
         "bytes_accessed": 1.2e7, "param_bytes": 0.0},
        {"id": 2, "op": "pool", "out_shape": [8, 56, 56, 64],
         "dtype": "float32", "attrs": {"window": [3, 3], "stride": [2]},
         "flops": 1.6e6, "macs": 0.0, "bytes_accessed": 8e6,
         "param_bytes": 0.0},
        {"id": 3, "op": "dense", "out_shape": [8, 1000], "dtype": "float32",
         "attrs": {"contract_k": 200704, "batch_dims": 0},
         "flops": 3.2e9, "macs": 1.6e9, "bytes_accessed": 8e8,
         "param_bytes": 8.02e8},
        {"id": 4, "op": "scatter", "out_shape": [2, 3, 5, 7, 11, 13],
         "dtype": "int32", "attrs": {"moved_elems": 30030, "kernel": []},
         "flops": 3.0e4, "macs": 0.0, "bytes_accessed": 1.2e5,
         "param_bytes": 0.0},
        {"id": 5, "op": "reduce", "out_shape": [], "dtype": "float8_e4m3fn",
         "attrs": {"kernel": [5]}, "flops": 1000.0, "macs": 0.0,
         "bytes_accessed": 4.0, "param_bytes": 0.0},
        {"id": 6, "op": "add", "out_shape": [2 ** 31, 2 ** 31, 3],
         "dtype": "bool", "attrs": {}, "flops": 9007199254740993.0,
         "macs": 0.0, "bytes_accessed": 0.0, "param_bytes": 1e20},
    ]
    edges = [[0, 1], [1, 2], [1, 3], [2, 3], [2, 4], [3, 5], [4, 6], [5, 6]]
    return {"schema": V1, "nodes": nodes, "edges": edges,
            "meta": {"family": "hand", "batch": 8}}


def _relabel(doc, new_id, order):
    """``doc`` with node ``i`` renamed ``new_id[i]`` and the node list in
    ``order``."""
    doc = copy.deepcopy(doc)
    for nd in doc["nodes"]:
        nd["id"] = new_id[nd["id"]]
    doc["nodes"] = [doc["nodes"][i] for i in order]
    doc["edges"] = [[new_id[a], new_id[b]] for a, b in doc["edges"]]
    return doc


def _shuffled_ids(doc):
    return _relabel(doc, [40, 3, 17, 99, 5, 61, 8], [4, 0, 6, 2, 5, 1, 3])


def _backward_edge(doc):
    return _relabel(doc, [6, 5, 4, 3, 2, 1, 0], [6, 5, 4, 3, 2, 1, 0])


def _duplicate_edge(doc):
    doc["edges"].append([2, 3])
    return doc


def _self_loop(doc):
    doc["edges"].insert(3, [3, 3])
    return doc


def _unsorted_edges(doc):
    doc["edges"].reverse()
    return doc


def _aliased_and_unknown_ops(doc):
    for nd, op in zip(doc["nodes"], ["Conv2D", "relu6", "max_pool2d",
                                     "MatMul", "reshape", "MyCustomOp",
                                     "Residual"]):
        nd["op"] = op
    return doc


def _tuple_shapes(doc):
    for nd in doc["nodes"]:
        nd["out_shape"] = tuple(nd["out_shape"])
    doc["edges"] = [tuple(e) for e in doc["edges"]]
    return doc


def _optional_fields_left_out(doc):
    for nd in doc["nodes"][1::2]:
        for key in ("dtype", "attrs", "flops", "macs", "bytes_accessed",
                    "param_bytes"):
            nd.pop(key)
    del doc["meta"]
    return doc


def _cancelling_sums(doc):
    """Totals that a sum in another order than node order gets wrong."""
    for key in ("macs", "flops", "param_bytes"):
        for nd, v in zip(doc["nodes"], (1e20, 1.0, -1e20, 0, 0, 0, 0)):
            nd[key] = v
    doc["meta"].pop("batch")
    return doc


def _no_nodes_no_edges(doc):
    return {"schema": V1, "nodes": [], "edges": [], "meta": {"batch": 2}}


@pytest.mark.parametrize("make,canonical", [
    (_shuffled_ids, False),
    (_backward_edge, False),
    (_duplicate_edge, False),
    (_self_loop, False),
    (_unsorted_edges, False),
    (_aliased_and_unknown_ops, True),
    (_tuple_shapes, True),
    (_optional_fields_left_out, True),
    (_cancelling_sums, True),
    (_no_nodes_no_edges, True),
])
def test_hand_made_document_matches_the_object_path(make, canonical):
    """A document that fails a check is left to ``from_json``; the form
    that ``from_json`` gives it (``to_json``) takes the array path to the
    same sample and fingerprint where its edges all point forward."""
    doc = make(_base())
    if canonical:
        _assert_same(doc)
    else:
        assert from_json_arrays(doc) is None
    g = from_json(doc)
    if all(a < b for a, b in g.edges):
        _assert_same(g.to_json())
    else:
        assert from_json_arrays(g.to_json()) is None
    _assert_matches_reference(g)


def test_a_long_out_shape_costs_memory_linear_in_its_dims():
    """One node of rank 50,000 among 500: the sample takes memory in
    proportion to the dims, not to the nodes times the longest rank
    (which would be 200 MB here)."""
    doc = {"schema": V1, "edges": [[i, i + 1] for i in range(499)],
           "nodes": [{"id": i, "op": "add", "out_shape": [2, 3]}
                     for i in range(500)]}
    doc["nodes"][7]["out_shape"] = [5, 6] + [1] * 49_998
    ga = _assert_same(doc)
    tracemalloc.start()
    try:
        x = sample_from_graph(ga).x
        peak = tracemalloc.get_traced_memory()[1]
    finally:
        tracemalloc.stop()
    assert peak < 16 << 20, peak
    np.testing.assert_array_equal(
        x[7, N_OP + 9:N_OP + 14], np.log1p([5, 6, 1, 1, 30]).astype(np.float32))


@pytest.mark.parametrize("doc", [
    {"nodes": [{"id": 0, "op": "dense", "out_shape": [4]}], "edges": []},
    {"schema": "other", "nodes": [], "edges": []},
    [1, 2],
    {**_base(), "nodes": [{**nd, "dtype": 4} for nd in _base()["nodes"]]},
    {**_base(), "nodes": [{**nd, "flops": None} for nd in _base()["nodes"]]},
    {**_base(), "nodes": [{**nd, "macs": float("inf")}
                          for nd in _base()["nodes"]]},
    {**_base(), "nodes": [{**nd, "id": 2 ** 70} for nd in _base()["nodes"]]},
    {**_base(), "edges": [[0, 1, 2]]},
    {**_base(), "nodes": [{**nd, "out_shape": [2.0]}
                          for nd in _base()["nodes"]]},
], ids=["raw-list", "other-schema", "not-a-mapping", "int-dtype",
        "null-flops", "inf-macs", "id-past-64-bits", "three-ended-edge",
        "float-dim"])
def test_documents_left_to_from_json(doc):
    """What the arrays cannot take exactly as ``from_json`` does comes
    back as ``None``; ``from_json`` then parses or rejects it."""
    assert from_json_arrays(doc) is None


def _invalid(kind):
    doc = _base()
    if kind == "no-nodes":
        del doc["nodes"]
    elif kind == "duplicate-id":     # the first repeat in list order
        doc["nodes"][5]["id"] = 1
        doc["nodes"][6]["id"] = 2
    elif kind == "dangling-edge":
        doc["edges"][4] = [2, 9]
    elif kind == "malformed-edge":
        doc["edges"][2] = [1]
    elif kind == "negative-dim":
        doc["nodes"][3]["out_shape"] = [4, -1]
    elif kind == "cycle":
        doc["edges"].append([6, 1])
    return doc


KINDS = ["no-nodes", "duplicate-id", "dangling-edge", "malformed-edge",
         "negative-dim", "cycle"]


def _error(parse, doc):
    with pytest.raises(GraphValidationError) as info:
        parse(doc)
    return str(info.value), info.value.node_id


@pytest.mark.parametrize("kind", KINDS)
def test_invalid_document_is_left_to_from_json(kind):
    doc = _invalid(kind)
    assert from_json_arrays(doc) is None
    _, node_id = _error(from_json, doc)
    assert {"duplicate-id": 1, "dangling-edge": 9,
            "negative-dim": 3}.get(kind) == node_id


@pytest.fixture(scope="module")
def packed_dippm():
    cfg = PMGNSConfig(hidden=32, layout="packed")
    return DIPPM.from_params(pmgns_init(jax.random.PRNGKey(0), cfg), cfg)


@pytest.mark.parametrize("kind", KINDS)
def test_submit_json_rejects_invalid_document(packed_dippm, kind):
    doc = _invalid(kind)
    want = _error(from_json, doc)
    with packed_dippm.serve(max_wait_ms=30_000.0) as svc:
        fut = svc.submit_json(doc)
        assert fut.done()
        err = fut.exception(timeout=1)
        assert isinstance(err, GraphValidationError)
        assert (str(err), err.node_id) == want
        st = svc.stats
    assert st.invalid == st.failed == st.submitted == 1
    assert st.array_parses == st.array_parse_fallbacks == 0
    assert st.queue_depth == 0 and st.batches == 0


@pytest.mark.parametrize("edges", [
    [(0, 1), (0, 2), (1, 2), (3, 4)],
    [(0, 1), (0, 1), (2, 3)],
    [(2, 3), (0, 1)],
    [(1, 0), (0, 5)],
    [(4, 4)],
    [],
], ids=["sorted", "repeat", "unsorted", "first-column-falls", "one", "none"])
def test_pad_sample_edges_are_their_unique_sorted_rows(edges):
    e = np.asarray(edges, np.int32).reshape(-1, 2)
    got = pad_sample(np.zeros((6, 32), np.float32), e,
                     np.zeros(5, np.float32)).edges
    want = np.unique(e, axis=0) if len(e) else e
    assert got.dtype == np.int32
    np.testing.assert_array_equal(got, want)
    assert not np.shares_memory(got, e)
