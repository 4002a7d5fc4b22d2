"""Generalized operator-graph IR — the framework-neutral model representation.

This is the analogue of the paper's Relay IR stage (DIPPM §3.1): every
frontend (jaxpr tracer, serialized JSON graphs) lowers to :class:`OpGraph`,
and every downstream component (Node Feature Generator, Static Feature
Generator, cost model, dataset builder) consumes only :class:`OpGraph`.
The serving path's JSON parse also yields :class:`GraphArrays`, the same
graph as arrays, which the fingerprint and the feature generators read
as they read an :class:`OpGraph`.

Design notes
------------
* Nodes are *operators* with attributes and an output shape — exactly the
  information Algorithm 1 of the paper extracts from Relay.
* Non-operator nodes (constants, pure layout ops) are contracted away by
  :func:`filter_and_preprocess`, preserving dataflow connectivity, mirroring
  the paper's post-order "filter and preprocess" step.
* The op vocabulary is deliberately small and hardware-meaningful: the
  one-hot segment of the 32-dim node feature (§3.2) indexes into
  :data:`OP_VOCAB`.
"""
from __future__ import annotations

import dataclasses
import functools
import hashlib
import itertools
import json
from operator import attrgetter
from typing import (TYPE_CHECKING, Any, Dict, Iterable, List, Optional,
                    Sequence, Tuple)

import numpy as np

if TYPE_CHECKING:
    from .node_features import NodeColumns

# ---------------------------------------------------------------------------
# Operator vocabulary
# ---------------------------------------------------------------------------

#: Canonical operator kinds. Order matters: it defines the one-hot encoding.
OP_VOCAB: Tuple[str, ...] = (
    "dense",        # matmul / dot_general / batched matmul
    "conv",         # any convolution
    "add",
    "mul",
    "div",
    "relu",         # max(x, 0) family
    "gelu",         # gelu / silu / swish / other smooth activations
    "tanh",
    "exp",
    "softmax",      # detected softmax pattern or explicit op
    "reduce",       # sum/max/mean reductions (incl. norm statistics)
    "norm",         # fused layer/rms/batch norm (frontends may emit directly)
    "pool",         # avg/max pooling (reduce_window)
    "gather",       # embedding lookup / take / dynamic-slice
    "scatter",      # scatter / dynamic-update-slice / one-hot dispatch
    "elementwise",  # any other pointwise op (rsqrt, logistic, select, ...)
)

OP_INDEX: Dict[str, int] = {name: i for i, name in enumerate(OP_VOCAB)}

#: Ops treated as pure layout/bookkeeping — contracted by the filter pass.
LAYOUT_OPS: Tuple[str, ...] = (
    "reshape", "transpose", "broadcast", "convert", "slice", "concat",
    "squeeze", "pad", "copy", "iota", "constant", "rev",
)

#: Float-op weights per output element used by the per-node FLOP estimate.
_POINTWISE_FLOP_COST = {
    "add": 1.0, "mul": 1.0, "div": 4.0, "relu": 1.0, "gelu": 10.0,
    "tanh": 8.0, "exp": 8.0, "softmax": 12.0, "elementwise": 2.0,
    "norm": 8.0, "reduce": 1.0, "pool": 1.0, "gather": 0.0, "scatter": 1.0,
}

_DTYPE_BYTES = {
    "float64": 8, "float32": 4, "float16": 2, "bfloat16": 2,
    "int64": 8, "int32": 4, "int16": 2, "int8": 1, "uint8": 1, "bool": 1,
    "float8_e4m3fn": 1, "float8_e5m2": 1,
}


def dtype_bytes(dtype: str) -> int:
    return _DTYPE_BYTES.get(str(dtype), 4)


class GraphValidationError(ValueError):
    """A submitted graph document is structurally invalid.

    Raised by the frontends (``repro.core.frontends.from_json``) with
    node-level context — missing fields, dangling edge references,
    negative shape dims, cycles — instead of leaking raw ``KeyError``
    / ``IndexError`` from arbitrary user payloads. ``node_id`` carries
    the offending node when one is identifiable. The serving layer
    maps this to an immediate future rejection (the request never
    touches the queue)."""

    def __init__(self, message: str, node_id: Optional[int] = None):
        super().__init__(message)
        self.node_id = node_id


#: Weisfeiler–Lehman refinement rounds behind :meth:`OpGraph.fingerprint`.
#: Each round folds one more hop of wiring into every node label; 4 rounds
#: separate any two operator DAGs whose 4-hop neighborhoods differ, at
#: O(rounds · (n + e)) array work.
_WL_ROUNDS = 4


def _u64(x: int) -> np.ndarray:
    # a 0-d array: numpy binds it to an array operand faster than a scalar
    return np.array(x, np.uint64)


#: splitmix64's finalizer (Steele, Lea & Flood, "Fast splittable
#: pseudorandom number generators", OOPSLA 2014).
_MIX_MUL = (_u64(0xBF58476D1CE4E5B9), _u64(0x94D049BB133111EB))
_MIX_SHIFT = (_u64(30), _u64(27), _u64(31))
#: Salts that set a shape dim's position and an edge's source apart.
_DIM_SALT = _u64(0x9E3779B97F4A7C15)
_PAIR_SALT = _u64(0xD6E8FEB86659FD93)
#: Weight of the successor sum; being 3 mod 4, it keeps apart two nodes
#: whose predecessor and successor sums are swapped (unless the sums
#: agree in 63 bits).
_SUCC_MUL = _u64(0xC2B2AE3D27D4EB4F)
_CONTENT_KEY = attrgetter("op", "out_shape", "dtype")
_NODE_ID = attrgetter("node_id")


def _mix64(z: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer over a ``uint64`` array: a bijection that
    spreads every input bit over the whole word (wraps mod 2^64)."""
    z = z ^ (z >> _MIX_SHIFT[0])
    z *= _MIX_MUL[0]
    z ^= z >> _MIX_SHIFT[1]
    z *= _MIX_MUL[1]
    z ^= z >> _MIX_SHIFT[2]
    return z


@functools.lru_cache(maxsize=1024)
def _op_dtype_word(op: str, dtype: str) -> int:
    """A stable 64-bit hash of an ``(op, dtype)`` pair (graphs hold a
    few dozen distinct pairs)."""
    return int.from_bytes(hashlib.blake2b(f"{op}|{dtype}".encode(),
                                          digest_size=8).digest(), "little")


def _content_labels(keys: Sequence[Tuple[str, Sequence[int], str]]
                    ) -> np.ndarray:
    """Each node's initial WL label, a ``uint64`` hash of its content key
    ``(op, out_shape, dtype)``: the op/dtype word, the rank, and the sum
    of the dims each mixed with its position. Hashed once per distinct
    content in the graph (a few dozen across hundreds of nodes)."""
    n = len(keys)
    first: Dict[Tuple[Any, ...], int] = {}   # content → its first node
    try:
        first_of = np.fromiter(map(first.setdefault, keys, range(n)),
                               np.intp, n)
    except TypeError:  # an out_shape given as a list
        first.clear()
        keys = [(op, tuple(shape), dtype) for op, shape, dtype in keys]
        first_of = np.fromiter(map(first.setdefault, keys, range(n)),
                               np.intp, n)
    k = len(first)
    ops, shapes, dtypes = zip(*first) if k else ((), (), ())
    rank = np.fromiter(map(len, shapes), np.int64, k)
    dims = np.fromiter(itertools.chain.from_iterable(shapes), np.int64,
                       int(rank.sum())).view(np.uint64)
    owner = np.repeat(np.arange(k), rank)
    pos = np.arange(dims.size) - (np.cumsum(rank) - rank)[owner]
    shape_sum = np.zeros(k, np.uint64)
    np.add.at(shape_sum, owner,
              _mix64(dims + _mix64(pos.view(np.uint64) ^ _DIM_SALT)))
    word = np.fromiter(map(_op_dtype_word, ops, dtypes), np.uint64, k)
    labels = np.empty(n, np.uint64)
    labels[np.fromiter(first.values(), np.intp, k)] = _mix64(
        _mix64(word + rank.view(np.uint64)) + shape_sum)
    return labels[first_of]


def _edge_positions(nodes: Sequence["OpNode"],
                    edges: Sequence[Tuple[int, int]]
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """The list positions of the edges' sources and destinations;
    ``KeyError`` names an edge end that is not a node id."""
    n = len(nodes)
    ends = np.fromiter(itertools.chain.from_iterable(edges), np.int64,
                       2 * len(edges)).reshape(-1, 2)
    if list(map(_NODE_ID, nodes)) == list(range(n)):
        # ids 0..n-1 in list order (what filter_and_preprocess emits)
        if ends.size and not 0 <= ends.min() <= ends.max() < n:
            raise KeyError(int(ends[(ends < 0) | (ends >= n)][0]))
        return ends[:, 0], ends[:, 1]
    ids = np.fromiter(map(_NODE_ID, nodes), np.int64, n)
    order = np.argsort(ids, kind="stable")
    at = np.searchsorted(ids[order], ends).clip(max=n - 1)
    known = ids[order][at] == ends
    if not known.all():
        raise KeyError(int(ends[~known][0]))
    at = order[at]
    return at[:, 0], at[:, 1]


def _wl_fingerprint(keys: Sequence[Tuple[str, Sequence[int], str]],
                    src: np.ndarray, dst: np.ndarray,
                    meta: Dict[str, Any]) -> str:
    """:meth:`OpGraph.fingerprint` of a graph given as each node's
    content key and the list positions of its edges' ends."""
    n, e = len(keys), len(src)
    with np.errstate(over="ignore"):
        labels = _content_labels(keys)
        for _ in range(_WL_ROUNDS):
            mixed = _mix64(labels)
            into = np.zeros(n, np.uint64)
            np.add.at(into, dst, mixed[src])
            out = np.zeros(n, np.uint64)
            np.add.at(out, src, mixed[dst])
            labels = _mix64(labels + into + out * _SUCC_MUL)
        pairs = _mix64(_mix64(labels[src] ^ _PAIR_SALT) + labels[dst])
        sums = np.array([labels.sum(dtype=np.uint64),
                         pairs.sum(dtype=np.uint64)], "<u8")
    h = hashlib.sha256(f"{n}|{e}".encode())
    h.update(sums.tobytes())
    h.update(json.dumps(meta, sort_keys=True, default=str).encode())
    return h.hexdigest()


# ---------------------------------------------------------------------------
# Node / Graph dataclasses
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class OpNode:
    """One operator node of the generalized graph (paper Algorithm 1)."""

    node_id: int
    op: str                               # one of OP_VOCAB (post-filter)
    out_shape: Tuple[int, ...]
    dtype: str = "float32"
    attrs: Dict[str, Any] = dataclasses.field(default_factory=dict)
    #: FLOPs attributed to this node (filled by the tracer / frontend).
    flops: float = 0.0
    #: MACs for dense/conv nodes — feeds F_mac (paper eq. 1).
    macs: float = 0.0
    #: bytes read + written, roofline memory side.
    bytes_accessed: float = 0.0
    #: parameter bytes held by this node (weights), for the memory model.
    param_bytes: float = 0.0

    @property
    def out_elems(self) -> int:
        n = 1
        for d in self.out_shape:
            n *= int(d)
        return n

    @property
    def out_bytes(self) -> int:
        return self.out_elems * dtype_bytes(self.dtype)

    def to_json(self) -> Dict[str, Any]:
        return {
            "id": self.node_id, "op": self.op,
            "out_shape": list(self.out_shape), "dtype": self.dtype,
            "attrs": self.attrs, "flops": self.flops, "macs": self.macs,
            "bytes_accessed": self.bytes_accessed,
            "param_bytes": self.param_bytes,
        }

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "OpNode":
        return OpNode(
            node_id=int(d["id"]), op=str(d["op"]),
            out_shape=tuple(int(x) for x in d["out_shape"]),
            dtype=str(d.get("dtype", "float32")),
            attrs=dict(d.get("attrs", {})),
            flops=float(d.get("flops", 0.0)), macs=float(d.get("macs", 0.0)),
            bytes_accessed=float(d.get("bytes_accessed", 0.0)),
            param_bytes=float(d.get("param_bytes", 0.0)),
        )


@dataclasses.dataclass
class OpGraph:
    """Directed operator dataflow graph with metadata.

    ``edges`` are (src_id, dst_id) pairs over ``nodes`` ids; ids are dense
    [0, n) after :func:`filter_and_preprocess`.
    """

    nodes: List[OpNode]
    edges: List[Tuple[int, int]]
    #: global metadata: batch size, family name, input shapes...
    meta: Dict[str, Any] = dataclasses.field(default_factory=dict)

    # -- structural helpers -------------------------------------------------
    @property
    def num_nodes(self) -> int:
        return len(self.nodes)

    @property
    def num_edges(self) -> int:
        return len(self.edges)

    def adjacency(self) -> np.ndarray:
        """Dense adjacency matrix A[dst, src] = 1 (message flows src→dst)."""
        n = self.num_nodes
        a = np.zeros((n, n), dtype=np.float32)
        for s, d in self.edges:
            a[d, s] = 1.0
        return a

    def in_degrees(self) -> np.ndarray:
        deg = np.zeros((self.num_nodes,), dtype=np.int32)
        for _, d in self.edges:
            deg[d] += 1
        return deg

    def topo_order(self) -> List[int]:
        """Kahn topological order (graphs from tracing are DAGs)."""
        n = self.num_nodes
        indeg = [0] * n
        succ: List[List[int]] = [[] for _ in range(n)]
        for s, d in self.edges:
            indeg[d] += 1
            succ[s].append(d)
        stack = [i for i in range(n) if indeg[i] == 0]
        order: List[int] = []
        while stack:
            u = stack.pop()
            order.append(u)
            for v in succ[u]:
                indeg[v] -= 1
                if indeg[v] == 0:
                    stack.append(v)
        if len(order) != n:  # cycle — shouldn't happen for traced graphs
            raise ValueError("OpGraph has a cycle; not a DAG")
        return order

    # -- aggregate statistics (consumed by SFG + cost model) ----------------
    def total_macs(self) -> float:
        return float(sum(nd.macs for nd in self.nodes))

    def total_param_bytes(self) -> float:
        return float(sum(nd.param_bytes for nd in self.nodes))

    def op_count(self, op: str) -> int:
        return sum(1 for nd in self.nodes if nd.op == op)

    def fingerprint(self) -> str:
        """Canonical content hash — invariant under node reordering.

        Two :class:`OpGraph`\\ s describing the same model must hash
        equal even when their node lists are permuted or their ids
        relabeled — frontends that re-parse a serialized graph can emit
        nodes in a different order, and the serving layer's
        content-addressed prediction cache (``repro.serve.cache``) and
        poison quarantine key on this hash, so an order-sensitive
        fingerprint would silently miss on every re-parsed duplicate.

        Weisfeiler–Lehman refinement over the edge arrays, in numpy:

        1. every node starts from a 64-bit label of its content
           ``(op, out_shape, dtype)``;
        2. each of :data:`_WL_ROUNDS` rounds mixes into every label the
           **commutative sum** (mod 2^64) of its predecessors' mixed
           labels and, with another weight, the same sum over its
           successors. The sum is a multiset hash: it ignores neighbour
           order and counts duplicate edges with their multiplicity, so
           a node's label encodes its local wiring, not its position;
        3. ``sha256`` over the node/edge counts, the sum of the final
           labels, the sum of the mixed ``(src_label, dst_label)`` edge
           pairs (both multiset hashes, little-endian, so the value does
           not depend on the host) and the JSON-canonicalized ``meta``,
           as 64 hex digits.

        There is no sort, and each distinct content is hashed once:
        numpy lets go of the GIL in every sort (and in ``np.add.at`` and
        loops over more than 500 elements), and in a serving process the
        batcher thread then takes it in the middle of a submit. Edge
        ends must name node ids; an unknown id raises ``KeyError``.

        The array version replaced a per-node ``blake2b`` over sorted
        neighbour labels. It induces the same equivalence classes (the
        tests keep that version as a reference), but every value changed
        once: the cost model's noise realisation per graph and the
        splits of a freshly built dataset changed with it, while records
        that stash ``meta["fingerprint"]`` keep theirs.

        WL-indistinguishable non-isomorphic graphs could in principle
        collide, but operator DAGs with shaped, typed nodes don't hit
        those pathologies in practice; for cache keys the failure mode
        is astronomically unlikely.

        The hash is memoized on the instance: graphs are treated as
        immutable once built (every transform in this repo constructs a
        new ``OpGraph``), and both the serving cache and the cost
        model's noise seeding hit this per request.
        """
        memo = self.__dict__.get("_fingerprint")
        if memo is not None:
            return memo
        src, dst = _edge_positions(self.nodes, self.edges)
        fp = _wl_fingerprint(list(map(_CONTENT_KEY, self.nodes)), src, dst,
                             self.meta)
        self.__dict__["_fingerprint"] = fp
        return fp

    # -- serialization (the portable multi-frontend schema) -----------------
    def to_json(self) -> Dict[str, Any]:
        return {
            "schema": "repro.opgraph.v1",
            "nodes": [nd.to_json() for nd in self.nodes],
            "edges": [list(e) for e in self.edges],
            "meta": self.meta,
        }

    def dumps(self) -> str:
        return json.dumps(self.to_json())

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "OpGraph":
        if d.get("schema") != "repro.opgraph.v1":
            raise ValueError(f"unknown OpGraph schema: {d.get('schema')!r}")
        return OpGraph(
            nodes=[OpNode.from_json(x) for x in d["nodes"]],
            edges=[(int(a), int(b)) for a, b in d["edges"]],
            meta=dict(d.get("meta", {})),
        )

    @staticmethod
    def loads(s: str) -> "OpGraph":
        return OpGraph.from_json(json.loads(s))


@dataclasses.dataclass
class GraphArrays:
    """An operator graph held as arrays, with no ``OpNode`` per node:
    what ``frontends.from_json_arrays`` makes of a ``repro.opgraph.v1``
    document. It answers what the serving path asks of an
    :class:`OpGraph` (``num_nodes``, ``edges``, ``meta``,
    :meth:`fingerprint`, and through ``cols`` the node features) with
    the same values as the ``OpGraph`` that ``frontends.from_json``
    builds of that document."""

    #: The nodes' ``node_features.NodeColumns``.
    cols: "NodeColumns"
    #: [E, 2] int64 (src, dst) node positions, sorted and unique, with
    #: no self-loop: the edges of ``filter_and_preprocess``'s output.
    edges: np.ndarray
    #: Each node's content key ``(op, out_shape, dtype)``, node order.
    content: List[Tuple[str, Tuple[int, ...], str]]
    meta: Dict[str, Any]

    @property
    def num_nodes(self) -> int:
        return len(self.content)

    def fingerprint(self) -> str:
        """:meth:`OpGraph.fingerprint` of the same graph."""
        return _wl_fingerprint(self.content, self.edges[:, 0],
                               self.edges[:, 1], self.meta)


# ---------------------------------------------------------------------------
# Filter / preprocess  (paper Algorithm 1, lines 2-11)
# ---------------------------------------------------------------------------

def filter_and_preprocess(
    raw_nodes: Sequence[OpNode],
    raw_edges: Iterable[Tuple[int, int]],
    meta: Optional[Dict[str, Any]] = None,
) -> OpGraph:
    """Contract non-operator (layout) nodes, keep operator nodes.

    Mirrors the paper's ``filter_and_preprocess(IR)``: pure layout ops
    (reshape/transpose/...) carry no compute signal; they are removed and
    their predecessors are wired directly to their successors so dataflow
    connectivity is preserved. Node ids are re-densified.
    """
    raw_nodes = list(raw_nodes)
    id2node = {nd.node_id: nd for nd in raw_nodes}
    keep = {nd.node_id for nd in raw_nodes if nd.op in OP_INDEX}

    # predecessor lists over the raw graph
    preds: Dict[int, List[int]] = {nd.node_id: [] for nd in raw_nodes}
    for s, d in raw_edges:
        if s in id2node and d in id2node:
            preds[d].append(s)

    # resolve each raw node to its set of kept ancestors (transitively
    # skipping layout nodes); memoized DFS, post-order
    resolved: Dict[int, Tuple[int, ...]] = {}

    def resolve(nid: int) -> Tuple[int, ...]:
        if nid in resolved:
            return resolved[nid]
        resolved[nid] = ()  # cycle guard
        if nid in keep:
            resolved[nid] = (nid,)
            return resolved[nid]
        out: List[int] = []
        for p in preds[nid]:
            out.extend(resolve(p))
        resolved[nid] = tuple(dict.fromkeys(out))
        return resolved[nid]

    new_ids = {old: i for i, old in enumerate(sorted(keep))}
    edges: List[Tuple[int, int]] = []
    seen = set()
    for nid in keep:
        for p in preds[nid]:
            for src in resolve(p):
                e = (new_ids[src], new_ids[nid])
                if e not in seen and e[0] != e[1]:
                    seen.add(e)
                    edges.append(e)

    nodes = []
    for old in sorted(keep):
        nd = id2node[old]
        nodes.append(dataclasses.replace(nd, node_id=new_ids[old]))
    return OpGraph(nodes=nodes, edges=sorted(edges), meta=dict(meta or {}))
