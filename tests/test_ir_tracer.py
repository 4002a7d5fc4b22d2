"""IR + tracer: graph extraction invariants."""
import gzip
import hashlib
import json
import random
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st
from jax import ShapeDtypeStruct as S

from repro.core.ir import (OP_VOCAB, _WL_ROUNDS, OpGraph, OpNode,
                           filter_and_preprocess)
from repro.core.tracer import trace_graph
from repro.core.frontends import from_json
from repro.zoo.families import trace_family

POOL = Path(__file__).resolve().parents[1] / "bench/pool/zoo_table2.jsonl.gz"


def _mlp_graph(depth=2, width=32, batch=4):
    def fn(params, x):
        for w, b in params:
            x = jnp.maximum(x @ w + b, 0.0)
        return x
    params = [(S((width, width), jnp.float32), S((width,), jnp.float32))
              for _ in range(depth)]
    return trace_graph(fn, params, S((batch, width), jnp.float32),
                       meta={"batch": batch})


def test_trace_is_dag_with_dense_ids():
    g = _mlp_graph()
    assert g.num_nodes == 6  # (dense, add, relu) x2
    ids = [nd.node_id for nd in g.nodes]
    assert ids == list(range(g.num_nodes))
    g.topo_order()  # raises on cycle


def test_ops_are_canonical():
    g = _mlp_graph()
    for nd in g.nodes:
        assert nd.op in OP_VOCAB


def test_macs_exact():
    g = _mlp_graph(depth=3, width=16, batch=8)
    assert g.total_macs() == pytest.approx(3 * 8 * 16 * 16)


def test_param_bytes_attributed():
    g = _mlp_graph()
    dense_nodes = [nd for nd in g.nodes if nd.op == "dense"]
    for nd in dense_nodes:
        assert nd.param_bytes == 32 * 32 * 4


def test_scan_replication_preserves_totals():
    def fn(params, x):
        def body(c, w):
            return jnp.tanh(c @ w), ()
        y, _ = jax.lax.scan(body, x, params)
        return y
    full = trace_graph(fn, S((10, 8, 8), jnp.float32),
                       S((2, 8), jnp.float32))
    capped = trace_graph(fn, S((10, 8, 8), jnp.float32),
                         S((2, 8), jnp.float32), max_scan_iters=2)
    assert full.total_macs() == pytest.approx(capped.total_macs())
    assert capped.num_nodes < full.num_nodes


def test_layout_ops_filtered():
    def fn(params, x):
        y = x.reshape(2, -1).T.reshape(x.shape)
        return y @ params
    g = trace_graph(fn, S((8, 8), jnp.float32), S((8, 8), jnp.float32))
    assert all(nd.op in OP_VOCAB for nd in g.nodes)
    assert g.op_count("dense") == 1


def test_json_roundtrip():
    g = _mlp_graph()
    g2 = OpGraph.loads(g.dumps())
    assert g2.num_nodes == g.num_nodes
    assert g2.edges == g.edges
    assert g2.fingerprint() == g.fingerprint()


def test_foreign_json_frontend_aliases():
    doc = {
        "nodes": [
            {"id": 0, "op": "Conv2D", "out_shape": [1, 8, 8, 16]},
            {"id": 1, "op": "ReLU", "out_shape": [1, 8, 8, 16]},
            {"id": 2, "op": "GEMM", "out_shape": [1, 10]},
        ],
        "edges": [[0, 1], [1, 2]],
        "meta": {"batch": 1},
    }
    g = from_json(doc)
    assert [nd.op for nd in g.nodes] == ["conv", "relu", "dense"]
    assert g.edges == [(0, 1), (1, 2)]


def test_schema_from_json_does_not_mutate_parsed_nodes():
    """Re-canonicalizing aliased op names must build new OpNodes — the
    parse must not write through to node objects the caller can see,
    and re-parsing the same doc must be stable."""
    import copy
    src = OpGraph(
        nodes=[OpNode(0, "gemm", (4, 64), flops=512.0),
               OpNode(1, "ReLU", (4, 64), flops=256.0)],
        edges=[(0, 1)], meta={"family": "external"})
    doc = src.to_json()
    pristine = copy.deepcopy(doc)
    g1 = from_json(doc)
    assert doc == pristine                       # input doc untouched
    # the caller's graph keeps its exporter-native op names
    assert [nd.op for nd in src.nodes] == ["gemm", "ReLU"]
    assert [nd.op for nd in g1.nodes] == ["dense", "relu"]
    g2 = from_json(doc)                          # re-parse: unchanged
    assert [nd.op for nd in g2.nodes] == ["dense", "relu"]
    assert g2.fingerprint() == g1.fingerprint()


@given(st.integers(1, 4), st.integers(1, 3))
@settings(max_examples=8, deadline=None)
def test_fingerprint_depends_on_structure(depth, scale):
    g1 = _mlp_graph(depth=depth, width=8 * scale)
    g2 = _mlp_graph(depth=depth, width=8 * scale)
    assert g1.fingerprint() == g2.fingerprint()


def _permuted(g, perm):
    """Relabel node ids by ``perm`` and shuffle the node list — the same
    graph as a re-parsing frontend might emit it."""
    nodes = [OpNode(perm[nd.node_id], nd.op, nd.out_shape, dtype=nd.dtype,
                    attrs=dict(nd.attrs), flops=nd.flops, macs=nd.macs)
             for nd in g.nodes]
    nodes.sort(key=lambda nd: nd.node_id)
    edges = [(perm[s], perm[d]) for s, d in g.edges]
    edges.reverse()
    return OpGraph(nodes=nodes, edges=edges, meta=dict(g.meta))


@pytest.mark.parametrize("seed", [0, 1, 2, 3, 4])
def test_fingerprint_canonical_under_node_reordering(seed):
    """The cache contract: equal graphs hash equal regardless of node
    order / id labeling (frontends' re-parse can permute both)."""
    import random
    g = _mlp_graph(depth=3, width=16)
    perm = list(range(g.num_nodes))
    random.Random(seed).shuffle(perm)
    gp = _permuted(g, {i: p for i, p in enumerate(perm)})
    assert gp.fingerprint() == g.fingerprint()
    # list-order-only permutation (ids kept) must also be invariant
    g_shuf = OpGraph(nodes=list(reversed(g.nodes)), edges=list(g.edges),
                     meta=dict(g.meta))
    assert g_shuf.fingerprint() == g.fingerprint()


def test_fingerprint_sensitive_to_rewiring_shape_and_meta():
    base = OpGraph(
        nodes=[OpNode(0, "dense", (4, 8)), OpNode(1, "relu", (4, 8)),
               OpNode(2, "add", (4, 8)), OpNode(3, "tanh", (4, 8))],
        edges=[(0, 1), (1, 2), (2, 3)], meta={"batch": 4})
    rewired = OpGraph(nodes=base.nodes,
                      edges=[(0, 1), (0, 2), (2, 3)], meta={"batch": 4})
    assert rewired.fingerprint() != base.fingerprint()
    reshaped = OpGraph(
        nodes=[OpNode(0, "dense", (4, 16))] + base.nodes[1:],
        edges=base.edges, meta={"batch": 4})
    assert reshaped.fingerprint() != base.fingerprint()
    remeta = OpGraph(nodes=base.nodes, edges=base.edges, meta={"batch": 8})
    assert remeta.fingerprint() != base.fingerprint()


def _wl_fingerprint_reference(g):
    """The per-node ``blake2b`` WL hash that ``OpGraph.fingerprint``
    replaced: slow, but the partition of graphs it induces is what the
    array version must reproduce."""
    n = len(g.nodes)
    pos = {nd.node_id: i for i, nd in enumerate(g.nodes)}

    def _h(data: bytes) -> bytes:
        return hashlib.blake2b(data, digest_size=16).digest()

    labels = [_h(f"{nd.op}|{tuple(nd.out_shape)}|{nd.dtype}".encode())
              for nd in g.nodes]
    preds = [[] for _ in range(n)]
    succs = [[] for _ in range(n)]
    edge_pos = []
    for s, d in g.edges:
        si, di = pos[s], pos[d]
        preds[di].append(si)
        succs[si].append(di)
        edge_pos.append((si, di))
    for _ in range(_WL_ROUNDS):
        labels = [
            _h(labels[i]
               + b"<" + b"".join(sorted(labels[p] for p in preds[i]))
               + b">" + b"".join(sorted(labels[q] for q in succs[i])))
            for i in range(n)
        ]
    h = hashlib.sha256()
    h.update(f"{n}|{len(g.edges)}".encode())
    for lab in sorted(labels):
        h.update(lab)
    for pair in sorted(labels[si] + labels[di] for si, di in edge_pos):
        h.update(pair)
    h.update(json.dumps(g.meta, sort_keys=True, default=str).encode())
    return h.hexdigest()


def _classes(keys):
    """Equivalence classes of positions under equal keys, as a canonical
    list of class numbers (first occurrence order)."""
    first = {}
    return [first.setdefault(k, len(first)) for k in keys]


def _relabeled(g, seed):
    """``g`` with sparse, shuffled ids and a shuffled node list."""
    rng = random.Random(seed)
    ids = rng.sample(range(10 * g.num_nodes + 10), g.num_nodes)
    nodes = [OpNode(ids[nd.node_id], nd.op, nd.out_shape, dtype=nd.dtype)
             for nd in g.nodes]
    rng.shuffle(nodes)
    edges = [(ids[s], ids[d]) for s, d in g.edges]
    rng.shuffle(edges)
    return OpGraph(nodes=nodes, edges=edges, meta=dict(g.meta))


def _pool_graphs():
    with gzip.open(POOL, "rt") as f:
        docs = [json.loads(line) for line in f]
    return [from_json(d) for d in docs[::4][:128]]


_ZOO = [("mobilenet", {"width": 0.35, "res": 64}),
        ("mobilenet", {"width": 1.0, "res": 96}),
        ("resnet", {"depths": [2, 2, 2, 2], "bottleneck": False,
                    "width": 0.5, "res": 64}),
        ("resnet", {"depths": [3, 4, 6, 3], "bottleneck": True,
                    "width": 0.5, "res": 64}),
        ("vit", {"dim": 192, "depth": 6, "patch": 16, "res": 64}),
        ("vit", {"dim": 192, "depth": 12, "patch": 16, "res": 64}),
        ("densenet", {"blocks": [3, 6, 12, 8], "growth": 16, "res": 64}),
        ("poolformer", {"dim": 32, "depths": [2, 2, 6, 2], "res": 64})]


def _zoo_graphs():
    graphs = [trace_family(fam, dict(cfg, batch=batch))
              for fam, cfg in _ZOO for batch in (1, 4)]
    # a re-trace, and the same graphs without meta (structural twins
    # across batch sizes differ only in shapes)
    graphs.append(trace_family(*_ZOO[0]))
    graphs += [OpGraph(g.nodes, g.edges, {}) for g in graphs[:4]]
    return graphs


@pytest.mark.parametrize("source", ["pool", "zoo"])
def test_fingerprint_partition_matches_reference(source):
    """Equal fingerprints ⇔ equal reference fingerprints, over real
    graphs and their relabeled copies."""
    graphs = _pool_graphs() if source == "pool" else _zoo_graphs()
    graphs += [_relabeled(g, i) for i, g in enumerate(graphs[:16])]
    new = [g.fingerprint() for g in graphs]
    ref = [_wl_fingerprint_reference(g) for g in graphs]
    assert _classes(new) == _classes(ref)
    assert len(set(new)) < len(new)          # some classes hold several


def _chain(ops, ids=None):
    ids = list(range(len(ops))) if ids is None else ids
    nodes = [OpNode(i, op, (4, 8)) for i, op in zip(ids, ops)]
    return OpGraph(nodes=nodes, edges=list(zip(ids, ids[1:])),
                   meta={"batch": 4})


def _assert_pairs(*pairs):
    """Each ``(g1, g2, equal)``: both hashes agree on ``equal``."""
    for g1, g2, equal in pairs:
        assert (g1.fingerprint() == g2.fingerprint()) is equal
        assert (_wl_fingerprint_reference(g1)
                == _wl_fingerprint_reference(g2)) is equal


def _case_sparse_unsorted_ids():
    dense = _chain(["dense", "relu", "add", "tanh"])
    sparse = _chain(["dense", "relu", "add", "tanh"], ids=[40, 7, 93, 12])
    sparse.nodes.reverse()
    _assert_pairs((dense, sparse, True))


def _case_single_node():
    one = OpGraph([OpNode(0, "dense", (4, 8))], [], {})
    again = OpGraph([OpNode(5, "dense", (4, 8))], [], {})
    other = OpGraph([OpNode(0, "dense", (4, 8), dtype="bfloat16")], [], {})
    _assert_pairs((one, again, True), (one, other, False))


def _case_no_edges():
    nodes = [OpNode(i, op, (2, i + 1)) for i, op in
             enumerate(["conv", "relu", "pool"])]
    g = OpGraph(nodes, [], {})
    _assert_pairs((g, OpGraph(list(reversed(nodes)), [], {}), True),
                  (g, OpGraph(nodes[:2], [], {}), False))


def _case_isolated_nodes():
    g = _chain(["dense", "relu", "add"])
    lone = OpGraph(g.nodes + [OpNode(3, "tanh", (4, 8))], g.edges, g.meta)
    moved = OpGraph([OpNode(0, "tanh", (4, 8))]
                    + [OpNode(i + 1, nd.op, nd.out_shape)
                       for i, nd in enumerate(g.nodes)],
                    [(s + 1, d + 1) for s, d in g.edges], g.meta)
    _assert_pairs((lone, moved, True), (lone, g, False))


def _case_duplicate_edges():
    # same nodes and edge count; the doubled edge lands on another node
    nodes = [OpNode(0, "dense", (4, 8)), OpNode(1, "relu", (4, 8)),
             OpNode(2, "tanh", (4, 8))]
    a = OpGraph(nodes, [(0, 1), (0, 1), (0, 2)], {})
    _assert_pairs((a, OpGraph(nodes, [(0, 1), (0, 2), (0, 2)], {}), False),
                  (a, OpGraph(nodes, [(0, 2), (0, 1), (0, 1)], {}), True))


def _case_fan_out():
    # predecessor-only refinement cannot tell one dense feeding both
    # relus from two denses feeding one each
    nodes = [OpNode(0, "dense", (4, 8)), OpNode(1, "dense", (4, 8)),
             OpNode(2, "relu", (4, 8)), OpNode(3, "relu", (4, 8))]
    _assert_pairs((OpGraph(nodes, [(0, 2), (0, 3)], {}),
                   OpGraph(nodes, [(0, 2), (1, 3)], {}), False))


def _case_reversed_edges():
    # the same chain read backwards is another graph
    g = _chain(["dense", "relu", "tanh"])
    back = OpGraph(g.nodes, [(d, s) for s, d in g.edges], g.meta)
    _assert_pairs((g, back, False))


def _case_shape_order():
    # the same dims in another order are another shape
    _assert_pairs((OpGraph([OpNode(0, "dense", (4, 8))], [], {}),
                   OpGraph([OpNode(0, "dense", (8, 4))], [], {}), False),
                  (OpGraph([OpNode(0, "dense", (4, 8))], [], {}),
                   OpGraph([OpNode(0, "dense", (4, 8, 1))], [], {}), False))


def _case_list_shape():
    # an out_shape given as a list hashes as the tuple would
    g = _chain(["dense", "relu"])
    listed = OpGraph([OpNode(nd.node_id, nd.op, list(nd.out_shape))
                      for nd in g.nodes], g.edges, g.meta)
    _assert_pairs((g, listed, True))


def _case_unknown_id():
    g = OpGraph([OpNode(0, "dense", (4, 8)), OpNode(1, "relu", (4, 8))],
                [(0, 1), (1, 9)], {})
    with pytest.raises(KeyError):
        g.fingerprint()
    with pytest.raises(KeyError):
        _wl_fingerprint_reference(g)
    sparse = _chain(["dense", "relu"], ids=[3, 8])
    sparse.edges.append((8, 5))
    with pytest.raises(KeyError):
        sparse.fingerprint()


def _case_hex_format():
    for g in (_chain(["dense"]), _mlp_graph(), OpGraph([], [], {})):
        fp = g.fingerprint()
        assert len(fp) == 64 and set(fp) <= set("0123456789abcdef")


_EDGE_CASES = {f.__name__[len("_case_"):]: f for f in (
    _case_sparse_unsorted_ids, _case_single_node, _case_no_edges,
    _case_isolated_nodes, _case_duplicate_edges, _case_fan_out,
    _case_reversed_edges, _case_shape_order, _case_list_shape, _case_unknown_id,
    _case_hex_format)}


@pytest.mark.parametrize("case", list(_EDGE_CASES))
def test_fingerprint_edge_cases(case):
    _EDGE_CASES[case]()


def test_filter_contracts_connectivity():
    nodes = [
        OpNode(0, "dense", (4, 4)),
        OpNode(1, "reshape", (16,)),      # layout — must vanish
        OpNode(2, "relu", (16,)),
    ]
    g = filter_and_preprocess(nodes, [(0, 1), (1, 2)])
    assert g.num_nodes == 2
    assert (0, 1) in g.edges  # dense → relu wired through the reshape
