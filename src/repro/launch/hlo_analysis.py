"""Compiled-HLO analysis for the roofline: collective-byte inventory with
trip-count correction, plus cost/memory extraction.

XLA's cost_analysis counts a while-loop body ONCE regardless of trip count
(the layer scan runs num_superblocks×, the loss chunker S/512×, …), so raw
HLO numbers undercount scanned programs.  We therefore report BOTH:
  * raw cost_analysis numbers, and
  * trip-count-corrected collective bytes: each collective op found in the
    post-SPMD HLO text is multiplied by the trip count of the while nest it
    sits in, classified from its op_name metadata and operand shapes.
FLOPs/bytes for the roofline terms are computed analytically (graphs.py has
exact per-layer formulas) and cross-checked against single-superblock HLO
differencing in tests — DESIGN.md §6.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "c64": 8, "c128": 16}

COLLECTIVE_KINDS = ("all-gather", "all-reduce", "reduce-scatter",
                    "all-to-all", "collective-permute")

_OP_RE = re.compile(
    r"%?[\w.\-]+\s*=\s*([a-z0-9]+)\[([\d,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute)"
    r"(-start|-done)?\(")


@dataclasses.dataclass
class CollectiveOp:
    kind: str
    dtype: str
    shape: Tuple[int, ...]
    bytes_per_exec: float
    while_depth: int
    trip_mult: float
    is_dcn: bool
    line: str


def _shape_bytes(dtype: str, dims_str: str) -> Tuple[Tuple[int, ...], float]:
    dims = tuple(int(d) for d in dims_str.split(",") if d) if dims_str \
        else ()
    n = 1
    for d in dims:
        n *= d
    return dims, n * DTYPE_BYTES.get(dtype, 4)


def _is_dcn(line: str, chips_per_pod: int) -> bool:
    """Classify a collective as crossing the pod (DCN) boundary.

    Explicit replica_groups {{a,b,...}}: DCN iff some group mixes devices
    from different pods.  Iota form [g,s]<=[...]: DCN iff the group stride
    pattern spans >= chips_per_pod (conservative heuristic).
    """
    m = re.search(r"replica_groups=\{\{([^}]*)\}", line)
    if m:
        first_group = [int(x) for x in m.group(1).split(",") if x.strip()]
        pods = {d // chips_per_pod for d in first_group}
        return len(pods) > 1
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([\d,]+)\](T\(([\d,]+)\))?",
                  line)
    if m:
        ngroups, gsize = int(m.group(1)), int(m.group(2))
        total = ngroups * gsize
        if total <= chips_per_pod:
            return False
        dims = [int(x) for x in m.group(3).split(",")]
        perm = ([int(x) for x in m.group(5).split(",")]
                if m.group(5) else list(range(len(dims))))
        # Reconstruct the first group's device ids from the iota spec.
        import numpy as np
        ids = np.arange(total).reshape(dims).transpose(perm).reshape(
            ngroups, gsize)
        return bool((ids[0] // chips_per_pod != ids[0, 0]
                     // chips_per_pod).any())
    return False


def parse_collectives(hlo_text: str, *, num_superblocks: int = 1,
                      seq_len: int = 0, xent_chunk: int = 512,
                      vocab: int = 0, chips_per_pod: int = 256,
                      inner_trip: int = 1,
                      microbatches: int = 1) -> List[CollectiveOp]:
    """Inventory of collectives with trip-count multipliers.

    Loop-nest trip counts, outermost→inner (documented estimate, DESIGN.md
    §6): with gradient accumulation the outermost while is the microbatch
    loop, then the layer scan, then intra-layer chunk scans —
    ``loop_trips = [microbatches, num_superblocks, inner_trip]`` (without
    accumulation the microbatch level is absent).  mult(depth) =
    Π loop_trips[:depth].  Vocab-sized operands at any depth belong to the
    loss-chunk loop instead of the layer scan:
    mult = Π trips[:depth-1] × ceil(seq/xent_chunk).
    """
    trips = ([microbatches] if microbatches > 1 else []) + \
        [num_superblocks, max(1, inner_trip)]

    def prod(xs):
        p = 1.0
        for x in xs:
            p *= x
        return p

    out: List[CollectiveOp] = []
    for line in hlo_text.splitlines():
        m = _OP_RE.search(line)
        if not m:
            continue
        if m.group(4) == "-done":
            continue                      # count start ops only
        dtype, dims_str, kind = m.group(1), m.group(2), m.group(3)
        shape, nbytes = _shape_bytes(dtype, dims_str)
        depth = line.count("/while/")
        if vocab and depth > 0 and any(
                d == vocab or (vocab > 64 and d % vocab == 0)
                for d in shape):
            xc = max(1.0, -(-seq_len // xent_chunk)) if seq_len else 1.0
            mult = prod(trips[:depth - 1]) * xc
        else:
            mult = prod(trips[:depth])
        out.append(CollectiveOp(
            kind=kind, dtype=dtype, shape=shape, bytes_per_exec=nbytes,
            while_depth=depth, trip_mult=mult,
            is_dcn=_is_dcn(line, chips_per_pod), line=line.strip()[:600]))
    return out


def collective_bytes(ops: List[CollectiveOp]) -> Dict[str, float]:
    """Aggregate per-chip wire bytes: {ici, dcn, raw, by_kind...}.

    all-gather/reduce-scatter move (g-1)/g of the buffer per chip; ring
    all-reduce ≈ 2× that; permute moves the buffer once.  We use the
    operand-size convention from the assignment (sum operand sizes), with
    the multiplier applied.
    """
    agg = {"ici": 0.0, "dcn": 0.0, "raw_once": 0.0,
           "ici_tpu_adj": 0.0, "dcn_tpu_adj": 0.0}
    by_kind: Dict[str, float] = {}
    for op in ops:
        b = op.bytes_per_exec * op.trip_mult
        agg["raw_once"] += op.bytes_per_exec
        key = "dcn" if op.is_dcn else "ici"
        factor = 2.0 if op.kind == "all-reduce" else 1.0
        agg[key] += b * factor
        # TPU adjustment: f32 collectives adjacent to dots/gathers exist in
        # f32 only because the CPU backend upcasts bf16 matmuls — on TPU
        # the payload would be bf16 (half the bytes).
        adj = 0.5 if (op.dtype == "f32"
                      and ("dot_general" in op.line or "_take" in op.line
                           or "gather" in op.line)) else 1.0
        agg[key + "_tpu_adj"] += b * factor * adj
        by_kind[op.kind] = by_kind.get(op.kind, 0.0) + b
    agg["by_kind"] = by_kind
    return agg


_HOISTED_CONVERT_RE = re.compile(
    r"\(param_0[^:]*: bf16\[([\d,]+)\]\) -> f32\[\1\]")


def cpu_bf16_convert_bytes(hlo_text: str) -> float:
    """Bytes of f32 copies that exist ONLY because the CPU backend lowers
    bf16 dots as convert-to-f32 (and hoists the loop-invariant converts of
    params/caches out of while loops).  A TPU compile consumes bf16 natively
    in the MXU, so these buffers would not be allocated — we report
    peak_bytes raw AND adjusted (DESIGN.md §7)."""
    seen = set()
    total = 0.0
    for m in _HOISTED_CONVERT_RE.finditer(hlo_text):
        dims = m.group(1)
        if dims in seen:
            continue
        seen.add(dims)
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        if n * 4 > 64e6:          # only count large hoisted buffers
            total += n * 4
    return total


def cost_summary(compiled) -> Dict[str, float]:
    ca = compiled.cost_analysis() or {}
    out = {"flops": float(ca.get("flops", 0.0)),
           "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    for k, v in ca.items():
        if k.startswith("bytes accessed"):
            out.setdefault("bytes_detail", {})[k] = float(v)
    return out


def memory_summary(compiled) -> Dict[str, float]:
    try:
        ma = compiled.memory_analysis()
        return {
            "argument_bytes": float(ma.argument_size_in_bytes),
            "output_bytes": float(ma.output_size_in_bytes),
            "temp_bytes": float(ma.temp_size_in_bytes),
            "alias_bytes": float(ma.alias_size_in_bytes),
            "peak_bytes": float(ma.argument_size_in_bytes
                                + ma.temp_size_in_bytes
                                + ma.output_size_in_bytes
                                - ma.alias_size_in_bytes),
        }
    except Exception as e:  # pragma: no cover
        return {"error": str(e)}
