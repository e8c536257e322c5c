"""Trip-count-aware analysis of compiled (SPMD-partitioned) HLO text.

``compiled.cost_analysis()`` visits each while body **once** — for scanned
layer stacks that understates FLOPs/bytes by ~n_layers (verified in
EXPERIMENTS.md §Dry-run notes).  This module re-derives roofline inputs from
``compiled.as_text()`` with loop trip counts applied:

* per-computation symbol table (every ``%name = TYPE op(...)`` line),
* matmul FLOPs from ``dot`` ops (2 · prod(result) · prod(contract dims)),
* collective payloads (operand bytes) for all-reduce / all-gather /
  reduce-scatter / all-to-all / collective-permute, split by kind,
* an HBM-traffic estimate (operand+result bytes of non-fusion-internal ops,
  assuming perfect reuse inside a fusion),
* recursion through ``fusion``/``call``/``while``/``conditional`` with
  while trip counts read from the loop-condition constant.

All shapes in partitioned HLO are *per-device*, so every returned quantity
is per-device (roofline terms then divide by per-chip peaks — the chip
count cancels).
"""
from __future__ import annotations

import dataclasses
import re
from typing import Dict, List, Optional, Tuple

DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "s4": 1, "u4": 1, "pred": 1, "c64": 8, "c128": 16,
    "token": 0, "s2": 1, "u2": 1,
}

COLLECTIVES = ("all-reduce", "all-gather", "reduce-scatter", "all-to-all",
               "collective-permute")


def xla_cost_analysis(compiled) -> dict:
    """``Compiled.cost_analysis()`` as a dict, ``{}`` when unavailable."""
    costs = compiled.cost_analysis()
    return dict(costs) if costs else {}

_SHAPE_RE = re.compile(r"(\w+)\[([0-9,]*)\]")


def _shape_bytes(type_str: str) -> int:
    """Total bytes of a (possibly tuple) HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * DTYPE_BYTES[dtype]
    return total


def _shape_dims(type_str: str) -> Tuple[str, List[int]]:
    m = _SHAPE_RE.search(type_str)
    if not m:
        return "", []
    dims = [int(d) for d in m.group(2).split(",") if d]
    return m.group(1), dims


@dataclasses.dataclass
class Instruction:
    name: str
    type_str: str
    op: str
    operands: List[str]
    attrs: str


@dataclasses.dataclass
class Computation:
    name: str
    instructions: List[Instruction]
    table: Dict[str, str]  # %name -> type string


def _split_computations(hlo: str) -> Dict[str, Computation]:
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    head_re = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{")
    for raw in hlo.splitlines():
        line = raw.strip()
        if cur is None:
            m = head_re.match(line)
            if m:
                cur = Computation(m.group(1), [], {})
            continue
        if line == "}":
            comps[cur.name] = cur
            cur = None
            continue
        inst = _parse_instruction(line)
        if inst is not None:
            cur.instructions.append(inst)
            cur.table[inst.name] = inst.type_str
    return comps


def _parse_instruction(line: str) -> Optional[Instruction]:
    if " = " not in line:
        return None
    lhs, rhs = line.split(" = ", 1)
    name = lhs.replace("ROOT", "").strip().lstrip("%")
    rhs = rhs.strip()
    # Type: leading tuple "(...)" or single token.
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            depth += ch == "("
            depth -= ch == ")"
            if depth == 0:
                break
        type_str, rest = rhs[:i + 1], rhs[i + 1:].strip()
    else:
        parts = rhs.split(" ", 1)
        if len(parts) != 2:
            return None
        type_str, rest = parts
    p = rest.find("(")
    if p < 0:
        return None
    op = rest[:p]
    depth = 0
    for i in range(p, len(rest)):
        depth += rest[i] == "("
        depth -= rest[i] == ")"
        if depth == 0:
            break
    operand_str = rest[p + 1:i]
    attrs = rest[i + 1:]
    operands = re.findall(r"%([\w.\-]+)", operand_str)
    return Instruction(name, type_str, op, operands, attrs)


def _group_size(attrs: str) -> int:
    # Iota form: replica_groups=[groups,size]<=[...]
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]", attrs)
    if m:
        return int(m.group(2))
    # Explicit form: replica_groups={{0,1},{2,3}}
    m = re.search(r"replica_groups=\{\{([^}]*)\}", attrs)
    if m:
        return len(m.group(1).split(","))
    return 1


def _dot_flops(inst: Instruction, comp: Computation) -> float:
    _, out_dims = _shape_dims(inst.type_str)
    out_n = 1
    for d in out_dims:
        out_n *= d
    m = re.search(r"lhs_contracting_dims=\{([0-9,]*)\}", inst.attrs)
    if not m or not inst.operands:
        return 2.0 * out_n  # degenerate
    lhs_type = comp.table.get(inst.operands[0], "")
    _, lhs_dims = _shape_dims(lhs_type)
    contract = 1
    for idx in m.group(1).split(","):
        if idx and int(idx) < len(lhs_dims):
            contract *= lhs_dims[int(idx)]
    return 2.0 * out_n * contract


_SKIP_MEM_OPS = {"parameter", "constant", "tuple", "get-tuple-element",
                 "bitcast", "after-all", "partition-id", "replica-id",
                 "iota"}


@dataclasses.dataclass
class Costs:
    flops: float = 0.0
    coll_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: {k: 0.0 for k in COLLECTIVES})
    wire_bytes: float = 0.0     # ring-algorithm estimate
    mem_bytes: float = 0.0      # HBM traffic estimate
    n_collectives: float = 0.0

    def add(self, other: "Costs", times: float = 1.0):
        self.flops += other.flops * times
        for k in COLLECTIVES:
            self.coll_bytes[k] += other.coll_bytes[k] * times
        self.wire_bytes += other.wire_bytes * times
        self.mem_bytes += other.mem_bytes * times
        self.n_collectives += other.n_collectives * times

    @property
    def total_coll_bytes(self) -> float:
        return sum(self.coll_bytes.values())


class HloAnalyzer:
    def __init__(self, hlo_text: str):
        self.comps = _split_computations(hlo_text)
        self.raw = hlo_text
        self.entry = self._find_entry(hlo_text)
        self._memo: Dict[str, Costs] = {}

    @staticmethod
    def _find_entry(hlo: str) -> str:
        m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
        if m:
            return m.group(1)
        raise ValueError("no ENTRY computation found")

    def _trip(self, cond_name: str) -> int:
        """Loop trip count ≈ the largest integer constant in the condition
        (exact for jax.lax.scan-lowered counted loops)."""
        block = self._raw_block(cond_name)
        consts = [int(x) for x in re.findall(r"constant\((\d+)\)", block)]
        return max(consts) if consts else 1

    def _raw_block(self, comp_name: str) -> str:
        m = re.search(
            r"^(?:ENTRY\s+)?%?" + re.escape(comp_name) + r"\s*\(.*?\{(.*?)^\}",
            self.raw, re.M | re.S)
        return m.group(1) if m else ""

    def costs_of(self, comp_name: str) -> Costs:
        if comp_name in self._memo:
            return self._memo[comp_name]
        comp = self.comps.get(comp_name)
        out = Costs()
        if comp is None:
            self._memo[comp_name] = out
            return out
        self._memo[comp_name] = out  # break cycles defensively
        for inst in comp.instructions:
            if inst.op == "dot":
                out.flops += _dot_flops(inst, comp)
            base = inst.op.replace("-start", "")
            if base in COLLECTIVES:
                g = _group_size(inst.attrs)
                result = _shape_bytes(inst.type_str)
                if base == "all-gather":
                    operand = result / max(g, 1)
                    wire = result * (g - 1) / max(g, 1)
                elif base == "all-reduce":
                    operand = result
                    wire = 2.0 * result * (g - 1) / max(g, 1)
                elif base == "reduce-scatter":
                    operand = result * g
                    wire = operand * (g - 1) / max(g, 1)
                else:  # all-to-all / collective-permute
                    operand = result
                    wire = result
                out.coll_bytes[base] += operand
                out.wire_bytes += wire
                out.n_collectives += 1
            # HBM traffic: each materialized result is written once and (on
            # average) read once downstream — counting operands as well
            # would double-count every producer/consumer edge.
            if inst.op not in _SKIP_MEM_OPS:
                out.mem_bytes += 2 * _shape_bytes(inst.type_str)
            # Recurse into called computations.
            if inst.op == "fusion" or inst.op == "call":
                m = re.search(r"calls=%?([\w.\-]+)", inst.attrs)
                if m:
                    sub = self.costs_of(m.group(1))
                    out.flops += sub.flops
                    for k in COLLECTIVES:
                        out.coll_bytes[k] += sub.coll_bytes[k]
                    out.wire_bytes += sub.wire_bytes
                    out.n_collectives += sub.n_collectives
                    # mem: fusion output/operands already counted above.
            elif inst.op == "while":
                mb = re.search(r"body=%?([\w.\-]+)", inst.attrs)
                mc = re.search(r"condition=%?([\w.\-]+)", inst.attrs)
                trip = self._trip(mc.group(1)) if mc else 1
                if mb:
                    out.add(self.costs_of(mb.group(1)), times=trip)
            elif inst.op == "conditional":
                for m in re.finditer(r"(?:true_computation|false_computation|"
                                     r"branch_computations=\{)([^},]*)",
                                     inst.attrs):
                    sub = self.costs_of(m.group(1).strip().lstrip("%"))
                    out.add(sub, times=1.0)
        self._memo[comp_name] = out
        return out

    def analyze(self) -> Costs:
        return self.costs_of(self.entry)
