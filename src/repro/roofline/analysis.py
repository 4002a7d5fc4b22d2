"""Roofline analysis from compiled dry-run artifacts (no real hardware).

Inputs: ``lowered.compile()`` products — ``compiled.as_text()`` (optimized
per-device HLO), ``cost_analysis()``, ``memory_analysis()``. Outputs: the
three roofline terms per the brief:

    compute term    = HLO_FLOPs        / (chips × peak_FLOP/s)
    memory term     = HLO_bytes        / (chips × HBM_bw)
    collective term = collective_bytes / (chips × link_bw)

Two XLA gotchas this module corrects:

1. ``HloCostAnalysis`` visits each computation **once** — a 60-layer
   ``lax.scan`` (= ``while`` loop) body is counted once, undercounting
   FLOPs by 60×. We parse the HLO, recover each while loop's trip count
   from its condition's comparison constant, and scale every instruction
   inside the body (nested whiles multiply).
2. collective bytes are not in ``cost_analysis`` at all — we sum operand
   sizes of every all-gather / all-reduce / reduce-scatter / all-to-all /
   collective-permute, with the same trip-count scaling.

All parsed sizes are **per-device** (SPMD prints the per-shard program),
so ``terms = per_device_quantity / per_chip_peak`` — algebraically equal
to the brief's ``global / (chips × peak)`` form.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")

#: ops that don't move data at runtime
_META_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
             "bitcast", "after-all", "partition-id", "replica-id", "iota"}

#: ops whose operand/result bytes count toward the HBM-traffic term.
#: The dry-run compiles on the CPU backend, whose HLO leaves elementwise
#: chains unfused; on the TPU target XLA fuses them into their producer,
#: so counting every unfused add/mul would overstate HBM traffic ~50×.
#: We count the ops that are real HBM round-trips on TPU: matmuls/convs,
#: fusions, data movement (slices/updates/gather/scatter/copy), reductions
#: and collectives.
_BYTES_OPS = {
    "dot", "convolution", "fusion", "custom-call",
    "dynamic-slice", "dynamic-update-slice", "gather", "scatter",
    "scatter-add", "reduce", "reduce-window", "sort", "copy",
    "copy-start", "concatenate", "pad",
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all",
    "collective-permute", "all-reduce-start", "all-gather-start",
}


@dataclasses.dataclass(frozen=True)
class Hardware:
    name: str
    peak_flops: float      # per chip
    hbm_bw: float          # per chip, bytes/s
    link_bw: float         # per chip, bytes/s
    hbm_bytes: float


#: One TPU v5e chip. Source: Google Cloud documentation, "TPU v5e" —
#: 197 TFLOP/s bf16, 16 GB HBM at 819 GB/s; ``link_bw`` is one ICI link.
V5E = Hardware("tpu-v5e", peak_flops=197e12, hbm_bw=819e9, link_bw=50e9,
               hbm_bytes=16e9)

#: Nominal envelope for the CPU container the benchmarks run in — a
#: conventional reference point (≈ a few AVX cores + dual-channel DDR),
#: NOT a measured machine. Achieved-rate percentages against it are for
#: *relative* comparison across kernels/runs on the same host; absolute
#: %-of-peak is only meaningful on a real accelerator target.
CPU_HOST = Hardware("cpu-host-nominal", peak_flops=2.0e11, hbm_bw=5.0e10,
                    link_bw=1.0e9, hbm_bytes=8e9)


#: Published peaks by ``jax.Device.device_kind``. A kind that is not
#: here has no envelope: :func:`default_hardware` raises rather than
#: guess. The CPU has none either — CPU callers that want the nominal
#: :data:`CPU_HOST` envelope name it.
PEAKS_BY_DEVICE_KIND: Dict[str, Hardware] = {
    "TPU v5 lite": V5E,        # the kind JAX reports for a v5e chip
}


def default_hardware(device=None) -> Hardware:
    """The roofline envelope of ``device`` (default: the first device),
    looked up by its ``device_kind`` in :data:`PEAKS_BY_DEVICE_KIND`."""
    import jax
    kind = (device or jax.devices()[0]).device_kind
    try:
        return PEAKS_BY_DEVICE_KIND[kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {kind!r} (known: "
            f"{sorted(PEAKS_BY_DEVICE_KIND)}); pass an explicit Hardware, "
            f"e.g. CPU_HOST for the nominal CPU envelope") from None


@dataclasses.dataclass
class RooflineReport:
    flops: float                     # per device, trip-count corrected
    bytes_accessed: float            # per device
    collective_bytes: float          # per device
    collective_breakdown: Dict[str, float]
    compute_s: float
    memory_s: float
    collective_s: float
    dominant: str
    n_collective_ops: int

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)


# ---------------------------------------------------------------------------
# HLO text parsing
# ---------------------------------------------------------------------------

_SHAPE_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3fn|f8e5m2|s64|u64|s32|u32"
                       r"|s16|u16|s8|u8|pred|c64|c128)\[([0-9,]*)\]")


def _shape_bytes(dtype: str, dims: str) -> float:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _result_bytes(line: str) -> float:
    """Sum all shape literals in the result type (LHS of the op name)."""
    rhs = line.split(" = ", 1)
    if len(rhs) != 2:
        return 0.0
    # result type is everything up to the first op token after '= '
    m = re.match(r"\s*(\(.*?\)|\S+)\s", rhs[1])
    head = m.group(1) if m else rhs[1]
    return float(sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(head)))


def _split_computations(text: str) -> Dict[str, List[str]]:
    """computation name → its instruction lines."""
    comps: Dict[str, List[str]] = {}
    cur: Optional[str] = None
    for line in text.splitlines():
        stripped = line.strip()
        if cur is None:
            m = re.match(r"(?:ENTRY\s+)?%?([\w.\-~]+)\s*(?:\([^)]*\))?.*\{",
                         line)
            if m and not line.startswith(" "):
                cur = m.group(1)
                comps[cur] = []
        else:
            if stripped == "}" or stripped.startswith("}"):
                cur = None
            elif stripped:
                comps[cur].append(stripped)
    return comps


def _while_multipliers(comps: Dict[str, List[str]]) -> Dict[str, float]:
    """computation name → execution-count multiplier from while loops."""
    mult: Dict[str, float] = {name: 1.0 for name in comps}
    # find while ops: condition=..., body=...
    edges: List[Tuple[str, str, str]] = []   # (parent, cond, body)
    for parent, lines in comps.items():
        for line in lines:
            if " while(" in line or re.search(r"\bwhile\(", line):
                mc = re.search(r"condition=%?([\w.\-~]+)", line)
                mb = re.search(r"body=%?([\w.\-~]+)", line)
                if mc and mb:
                    edges.append((parent, mc.group(1), mb.group(1)))

    def trip_count(cond_name: str) -> float:
        best = 1.0
        for line in comps.get(cond_name, []):
            for m in re.finditer(r"constant\((\d+)\)", line):
                best = max(best, float(m.group(1)))
        return best

    # propagate: body multiplier = parent multiplier × trip count.
    # iterate to fixpoint (nesting depth ≤ 3 in practice)
    for _ in range(6):
        changed = False
        for parent, cond, body in edges:
            tc = trip_count(cond)
            new = mult.get(parent, 1.0) * tc
            for target in (body, cond):
                if target in mult and mult[target] < new:
                    mult[target] = new
                    changed = True
        if not changed:
            break
    return mult


def _group_size(line: str, default: int) -> int:
    """#participants of a collective from replica_groups annotation."""
    m = re.search(r"replica_groups=\{\{([0-9,]+)\}", line)
    if m:
        return len(m.group(1).split(","))
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", line)
    if m:
        return int(m.group(2))
    return default


def parse_collectives(text: str, n_devices: int = 1
                      ) -> Tuple[float, Dict[str, float], int]:
    """→ (total per-device collective bytes, per-op-kind breakdown, #ops).

    Byte convention (operand bytes, per brief): all-reduce / all-to-all /
    collective-permute move ≈ result bytes; all-gather's operand is
    result/G; reduce-scatter's operand is result×G.
    """
    comps = _split_computations(text)
    mult = _while_multipliers(comps)
    total = 0.0
    breakdown: Dict[str, float] = {k: 0.0 for k in COLLECTIVES}
    count = 0
    for cname, lines in comps.items():
        m = mult.get(cname, 1.0)
        for line in lines:
            for kind in COLLECTIVES:
                if re.search(rf"\b{kind}(?:-start|-done)?\(", line):
                    if f"{kind}-done" in line:
                        continue  # counted at -start
                    rb = _result_bytes(line)
                    g = _group_size(line, n_devices)
                    if kind == "all-gather":
                        b = rb / max(g, 1)
                    elif kind == "reduce-scatter":
                        b = rb * g
                    else:
                        b = rb
                    total += b * m
                    breakdown[kind] += b * m
                    count += 1
                    break
    return total, breakdown, count


_DOT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")


def parse_flops_and_bytes(text: str) -> Tuple[float, float]:
    """Per-device (FLOPs, HBM bytes) from optimized HLO, trip-corrected.

    FLOPs: dot/convolution ops (2·result·K). Bytes: operands + results of
    every executed non-meta top-level instruction (post-fusion HLO reads
    each operand once and writes each result once — the roofline
    convention).
    """
    comps = _split_computations(text)
    mult = _while_multipliers(comps)

    # name → shape-bytes and name → dims for operand lookup
    shapes: Dict[str, Tuple[str, str]] = {}
    for lines in comps.values():
        for line in lines:
            m = re.match(r"%?([\w.\-~]+)\s*=\s*", line)
            if not m:
                continue
            sm = _SHAPE_RE.search(line.split(" = ", 1)[1])
            if sm:
                shapes[m.group(1)] = (sm.group(1), sm.group(2))

    def dims_of(name: str) -> List[int]:
        if name not in shapes:
            return []
        d = shapes[name][1]
        return [int(x) for x in d.split(",")] if d else []

    flops = 0.0
    byts = 0.0
    # fusion computations are *not* executed standalone; their caller
    # (the fusion op) accounts for the IO. Mark them.
    fused = {name for name in comps if name.startswith("fused_computation")
             or ".fused" in name}
    for cname, lines in comps.items():
        m = mult.get(cname, 1.0)
        in_fused = cname in fused
        for line in lines:
            lm = re.match(r"%?([\w.\-~]+)\s*=\s*", line)
            if not lm:
                continue
            opm = re.search(r"\)?\s([a-z][a-z0-9\-]*)\(", line)
            op = opm.group(1) if opm else ""
            # --- flops: count inside fusions too (they execute) ---------
            if op in ("dot", "convolution"):
                out_elems = 1
                for d in dims_of(lm.group(1)):
                    out_elems *= d
                k = 1
                operands = re.findall(r"\(%?([\w.\-~]+)[,)]", line)
                cd = _DOT_RE.search(line)
                if op == "dot" and cd and operands:
                    ldims = dims_of(operands[0])
                    if cd.group(1):
                        for i in cd.group(1).split(","):
                            if int(i) < len(ldims):
                                k *= ldims[int(i)]
                elif op == "convolution" and len(operands) > 1:
                    kd = dims_of(operands[1])
                    if kd:
                        k = max(1, int(
                            (1.0 * _prod(kd)) / max(kd[-1] if kd else 1, 1)))
                flops += 2.0 * out_elems * k * m
            # --- bytes: top-level executed instructions, fusion-aware ----
            if not in_fused and op in _BYTES_OPS:
                rb = _result_bytes(line)
                if op in ("fusion", "custom-call"):
                    # fusions in while bodies list the whole carried tuple
                    # as operands but only *read a slice*; approximate a
                    # fusion's HBM traffic as write + equal-sized read.
                    byts += 2.0 * rb * m
                else:
                    ob = 0.0
                    for operand in re.findall(
                            r"%([\w.\-~]+)", line.split(
                                "(", 1)[1] if "(" in line else ""):
                        if operand in shapes:
                            ob += _shape_bytes(*shapes[operand])
                    byts += (rb + ob) * m
    return flops, byts


def _prod(xs):
    n = 1
    for x in xs:
        n *= x
    return n


# ---------------------------------------------------------------------------
# report
# ---------------------------------------------------------------------------

def analyze_hlo(text: str, hw: Hardware = V5E,
                cost_analysis: Optional[Dict] = None,
                n_devices: int = 1) -> RooflineReport:
    flops, byts = parse_flops_and_bytes(text)
    coll, breakdown, nops = parse_collectives(text, n_devices)
    # fall back to XLA's flop count when ours comes out lower (ours skips
    # elementwise flops; XLA's skips while-loop trip counts — take the max.
    # bytes stay ours: XLA's count reflects the unfused CPU backend.)
    if cost_analysis:
        flops = max(flops, float(cost_analysis.get("flops", 0.0)))
    compute_s = flops / hw.peak_flops
    memory_s = byts / hw.hbm_bw
    collective_s = coll / hw.link_bw
    terms = {"compute": compute_s, "memory": memory_s,
             "collective": collective_s}
    dominant = max(terms, key=terms.get)
    return RooflineReport(
        flops=flops, bytes_accessed=byts, collective_bytes=coll,
        collective_breakdown=breakdown, compute_s=compute_s,
        memory_s=memory_s, collective_s=collective_s, dominant=dominant,
        n_collective_ops=nops)


def roofline_terms(report: RooflineReport) -> Dict[str, float]:
    return {"compute_s": report.compute_s, "memory_s": report.memory_s,
            "collective_s": report.collective_s,
            "dominant": report.dominant}


# ---------------------------------------------------------------------------
# analytic per-kernel traffic models (benchmarks)
# ---------------------------------------------------------------------------
# Counting convention: one HBM read per operand, one write per result,
# per *stage* — fused stages keep intermediates on-chip and therefore
# drop the inter-stage round-trips. f32 elements are 4 bytes; an edge
# row is 2×int32 = 8 bytes. These are deterministic models, not
# measurements: benchmarks use them for fused-vs-unfused traffic ratios
# (machine-independent) and to convert measured wall time into achieved
# GB/s / %-of-roofline rows.

def mp_layer_traffic(p: int, q: int, f: int, h: int, *, mode: str = "mean",
                     combine: str = "split",
                     fused: bool = False) -> Dict[str, float]:
    """FLOPs + HBM bytes of one packed message-passing layer.

    Unfused = the composed per-op pipeline (gather → mask → scatter
    [→ degree → mean] → combine → bias/act/node-mask), each stage
    round-tripping its intermediate through HBM. Fused = the megakernel:
    inputs read once, output written once, everything else in VMEM.
    """
    nw = 2 if combine == "split" else 1      # weight matmuls in combine
    flops = 2.0 * q * f                      # scatter-accumulate MACs
    flops += 2.0 * p * f * h * nw            # combine matmul(s)
    flops += p * h                           # bias + activation
    if mode == "mean":
        flops += p * f                       # degree divide
    weights = f * h * nw + h
    if fused:
        elems = (p * f                       # x, read once
                 + q                         # edge_mask
                 + 2 * p                     # node mask + self-scale
                 + weights
                 + p * h)                    # output, written once
        byts = 4.0 * elems + 8.0 * q         # + edges (2×int32)
    else:
        elems = (p * f + q * f               # gather: read x, write msgs
                 + 2.0 * q * f               # mask: rewrite msgs
                 + q * f + p * f             # scatter: read msgs, write agg
                 + 2.0 * p * f + p           # combine reads x + agg (+ss)
                 + weights + p * h           # weights, write y
                 + 2.0 * p * h + p)          # act+mask rewrite
        if mode == "mean":
            elems += (q + p                  # degree pass
                      + 2.0 * p * f + p)     # mean divide rewrite
        byts = 4.0 * elems + 8.0 * q
    return {"flops": flops, "bytes": byts}


def segment_aggregate_traffic(b: int, e: int, n: int, f: int, *,
                              mode: str = "mean") -> Dict[str, float]:
    """Two-pass sparse aggregation: gather writes ``[E, F]`` messages,
    scatter reads them back — per batch row, ×``b``."""
    flops = b * (2.0 * e * f + (n * f if mode == "mean" else 0.0))
    elems = b * (n * f + e * f               # gather: read h, write msgs
                 + e + e * f + n * f         # scatter: mask, msgs, out
                 + (e + n if mode == "mean" else 0))
    return {"flops": flops, "bytes": 4.0 * elems + 8.0 * b * e}


def segment_readout_traffic(p: int, f: int, g: int, *,
                            kind: str = "mean_max") -> Dict[str, float]:
    """Fused segment mean/max readout over the packed flat node axis."""
    out_f = 2 * f if kind == "mean_max" else f
    flops = 2.0 * p * f + g * f              # sum+max sweep, mean divide
    elems = p * f + 2.0 * p + g * out_f + g  # h, ids+mask, out, counts
    return {"flops": flops, "bytes": 4.0 * elems}


def edge_softmax_traffic(b: int, e: int, h: int, n: int) -> Dict[str, float]:
    """Two-pass online edge softmax: stats pass + normalize pass."""
    flops = b * 5.0 * e * h                  # exp, sub, mul, div, max
    elems = b * (2.0 * e * h                 # scores read twice (2 passes)
                 + 2.0 * e                   # dst + mask (per pass, int/f32)
                 + 2.0 * n * h               # write (max, denom)
                 + 2.0 * n * h               # read them back
                 + e * h)                    # output
    return {"flops": flops, "bytes": 4.0 * elems}


def dense_aggregate_traffic(b: int, n: int, f: int) -> Dict[str, float]:
    """Dense-adjacency aggregation — the O(N²) path the sparse kernels
    replace (kept for microbench comparison rows)."""
    flops = 2.0 * b * n * n * f
    elems = b * (n * n + 2.0 * n * f)
    return {"flops": flops, "bytes": 4.0 * elems}


def achieved_rates(flops: float, byts: float, wall_s: float,
                   hw: Hardware) -> Dict[str, object]:
    """Measured wall time + modeled (FLOPs, bytes) → achieved-rate row.

    ``pct_of_roofline`` is the fraction of the wall time explained by
    the binding roofline term — 100 % means the kernel runs at the
    envelope's speed-of-light for its arithmetic intensity; low values
    mean overhead (dispatch, interpret mode) dominates. Against
    :data:`CPU_HOST` the absolute number is nominal (see its docstring);
    the fused-vs-unfused *ratio* is the machine-independent signal.
    """
    wall = max(float(wall_s), 1e-12)
    compute_s = flops / hw.peak_flops
    memory_s = byts / hw.hbm_bw
    return {
        "hardware": hw.name,
        "flops": float(flops),
        "bytes": float(byts),
        "achieved_gflops": flops / wall / 1e9,
        "achieved_gb_s": byts / wall / 1e9,
        "pct_peak_flops": 100.0 * (flops / wall) / hw.peak_flops,
        "pct_peak_bw": 100.0 * (byts / wall) / hw.hbm_bw,
        "bound": "compute" if compute_s >= memory_s else "memory",
        "pct_of_roofline": 100.0 * max(compute_s, memory_s) / wall,
    }
