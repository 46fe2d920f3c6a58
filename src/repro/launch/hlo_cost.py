"""Trip-count-aware HLO cost extraction for the roofline analysis.

XLA's ``compiled.cost_analysis()`` counts a ``while`` body **once**
(verified in tests/test_hlo_cost.py), so any scanned model -- scan over
layers, scan over sequence chunks -- under-reports FLOPs and collective
bytes by the trip count. This module parses ``compiled.as_text()`` into a
computation call graph, extracts per-computation costs from a per-op
symbol table, recovers while-loop trip counts (from the
``known_trip_count`` backend config, falling back to the condition
computation's loop bound constant), and propagates totals bottom-up.

Outputs per program:
  flops              dot/convolution FLOPs x trip counts
  collective_bytes   operand bytes per collective kind x trip counts
  dot_bytes          dot operand+output bytes x trip counts (an HBM-traffic
                     model assuming elementwise ops fuse into the dots)

This is the profiling substrate the §Perf loop reads -- "your profile is
lowered.as_text() + cost_analysis()".
"""
from __future__ import annotations

import dataclasses
import math
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1,
}

COLLECTIVE_KINDS = (
    "all-gather", "all-reduce", "reduce-scatter", "all-to-all",
    "collective-permute",
)

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")
_OP_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(.+)$")
_CONST_RE = re.compile(r"[su]\d+\[\]\s+constant\((\d+)\)")
_TRIP_RE = re.compile(r'known_trip_count[^\d]*?"n"\s*:\s*"(\d+)"')
_HEADER_RE = re.compile(r"^(ENTRY\s+)?%([\w.\-]+)\s*\(.*\{\s*$")

Shape = Tuple[str, Tuple[int, ...]]


def _nbytes(sh: Shape) -> int:
    dt, dims = sh
    return _DTYPE_BYTES.get(dt, 4) * (math.prod(dims) if dims else 1)


def _parse_shapes(type_str: str) -> List[Shape]:
    """All dtype[dims] occurrences in a type spec (tuple-aware)."""
    out = []
    for m in _SHAPE_RE.finditer(type_str):
        dims = m.group(2)
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        out.append((m.group(1), shape))
    return out


def _split_type_and_rest(rhs: str) -> Tuple[str, str]:
    """Split 'f32[8,8]{1,0} dot(...)' or '(s32[], f32[..]) while(...)'."""
    rhs = rhs.strip()
    if rhs.startswith("("):
        depth = 0
        for i, ch in enumerate(rhs):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    return rhs[: i + 1], rhs[i + 1 :].strip()
        return rhs, ""
    m = re.match(r"(\w+\[[\d,]*\](?:\{[^}]*\})?)\s*(.*)", rhs)
    if m:
        return m.group(1), m.group(2)
    return "", rhs


def _first_paren_args(rest: str) -> str:
    lp = rest.find("(")
    if lp < 0:
        return ""
    depth = 0
    for i in range(lp, len(rest)):
        if rest[i] == "(":
            depth += 1
        elif rest[i] == ")":
            depth -= 1
            if depth == 0:
                return rest[lp + 1 : i]
    return rest[lp + 1 :]


@dataclasses.dataclass
class Computation:
    name: str
    flops: float = 0.0
    dot_bytes: float = 0.0
    collective_bytes: Dict[str, float] = dataclasses.field(
        default_factory=lambda: defaultdict(float))
    children: List[str] = dataclasses.field(default_factory=list)
    whiles: List[Tuple[str, str, Optional[int]]] = dataclasses.field(
        default_factory=list)  # (body, cond, known_trips)
    max_const: int = 0


def _split_computations(hlo: str) -> Tuple[Dict[str, List[str]], Optional[str]]:
    comps: Dict[str, List[str]] = {}
    entry: Optional[str] = None
    current: Optional[str] = None
    buf: List[str] = []
    for line in hlo.splitlines():
        stripped = line.rstrip()
        if current is None:
            m = _HEADER_RE.match(stripped.strip())
            if m:
                current = m.group(2)
                if m.group(1):
                    entry = current
                buf = []
                comps[current] = buf
            continue
        if stripped.strip() == "}" or stripped.startswith("}"):
            current = None
            continue
        buf.append(stripped.strip())
    return comps, entry


def parse(hlo: str) -> Tuple[Dict[str, Computation], Optional[str]]:
    comps_lines, entry = _split_computations(hlo)
    comps: Dict[str, Computation] = {}
    for name, lines in comps_lines.items():
        c = Computation(name=name)
        symtab: Dict[str, List[Shape]] = {}
        for line in lines:
            cm = _CONST_RE.search(line)
            if cm:
                c.max_const = max(c.max_const, int(cm.group(1)))
            m = _OP_RE.match(line)
            if not m:
                continue
            op_name, rhs = m.group(1), m.group(2)
            type_str, rest = _split_type_and_rest(rhs)
            out_shapes = _parse_shapes(type_str)
            symtab[op_name] = out_shapes
            opm = re.match(r"([\w\-]+)", rest)
            opcode = opm.group(1) if opm else ""
            args = _first_paren_args(rest)
            operand_names = re.findall(r"%([\w.\-]+)", args)

            if opcode == "dot":
                lhs_shapes = symtab.get(operand_names[0], []) if operand_names else []
                lhs = lhs_shapes[0] if lhs_shapes else ("f32", ())
                cdims = re.search(r"lhs_contracting_dims=\{([\d,]*)\}", rest)
                cdim = 1
                if cdims and cdims.group(1):
                    for d in cdims.group(1).split(","):
                        di = int(d)
                        if di < len(lhs[1]):
                            cdim *= lhs[1][di]
                out = out_shapes[0] if out_shapes else ("f32", ())
                c.flops += 2.0 * math.prod(out[1] or (1,)) * cdim
                byte_sum = _nbytes(out)
                for on in operand_names[:2]:
                    for sh in symtab.get(on, []):
                        byte_sum += _nbytes(sh)
                c.dot_bytes += byte_sum
            elif opcode == "convolution":
                out = out_shapes[0] if out_shapes else ("f32", ())
                k_shapes = symtab.get(operand_names[1], []) if len(operand_names) > 1 else []
                k_elems = math.prod(k_shapes[0][1]) if k_shapes and k_shapes[0][1] else 1
                out_elems = math.prod(out[1] or (1,))
                cout = out[1][-1] if out[1] else 1
                c.flops += 2.0 * out_elems * max(1, k_elems // max(1, cout))
                c.dot_bytes += _nbytes(out) + sum(
                    _nbytes(sh) for on in operand_names[:2] for sh in symtab.get(on, []))
            elif any(opcode.startswith(k) for k in COLLECTIVE_KINDS):
                kind = next(k for k in COLLECTIVE_KINDS if opcode.startswith(k))
                by = 0.0
                for on in operand_names:
                    for sh in symtab.get(on, []):
                        by += _nbytes(sh)
                if by == 0.0:  # operands defined in another computation scope
                    by = sum(_nbytes(sh) for sh in out_shapes)
                c.collective_bytes[kind] += by
            elif opcode == "while":
                body = re.search(r"body=%?([\w.\-]+)", rest)
                cond = re.search(r"condition=%?([\w.\-]+)", rest)
                trips = None
                tm = _TRIP_RE.search(rest)
                if tm:
                    trips = int(tm.group(1))
                if body and cond:
                    c.whiles.append((body.group(1), cond.group(1), trips))
            elif opcode == "conditional":
                for key in ("true_computation", "false_computation"):
                    mm = re.search(rf"{key}=%?([\w.\-]+)", rest)
                    if mm:
                        c.children.append(mm.group(1))
                bm = re.search(r"branch_computations=\{([^}]*)\}", rest)
                if bm:
                    for b in bm.group(1).split(","):
                        b = b.strip().lstrip("%")
                        if b:
                            c.children.append(b)
            else:
                for mm in re.finditer(r"(?:calls=|to_apply=)%?([\w.\-]+)", rest):
                    c.children.append(mm.group(1))
        comps[name] = c
    return comps, entry


@dataclasses.dataclass
class CostSummary:
    flops: float
    dot_bytes: float
    collective_bytes: Dict[str, float]

    @property
    def total_collective_bytes(self) -> float:
        return sum(self.collective_bytes.values())

    def scaled(self, k: float) -> "CostSummary":
        return CostSummary(
            flops=self.flops * k,
            dot_bytes=self.dot_bytes * k,
            collective_bytes={kk: v * k for kk, v in self.collective_bytes.items()},
        )


def _entry_name(comps: Dict[str, Computation], entry: Optional[str]) -> str:
    if entry and entry in comps:
        return entry
    referenced = set()
    for c in comps.values():
        referenced.update(c.children)
        for b, cn, _ in c.whiles:
            referenced.add(b)
            referenced.add(cn)
    for name in comps:
        if name not in referenced:
            return name
    return next(iter(comps))


_MOSAIC_TARGET = 'custom_call_target="tpu_custom_call"'
_OP_NAME_RE = re.compile(r'metadata=\{op_name="([^"]*)"')
_JIT_SCOPE_RE = re.compile(r"jit\((\w+)\)")


def mosaic_kernels(hlo: str) -> Dict[str, int]:
    """Mosaic (Pallas on TPU) kernel call sites in a compiled program's
    text, as kernel name -> number of call sites.  The name is that of
    the innermost jitted function around the ``pallas_call`` (every
    kernel entry point in :mod:`repro.kernels` is jitted, e.g.
    ``event_lif_dispatch_db``), else the custom call's instruction name.
    Empty for a program compiled off the TPU, where Pallas kernels run
    interpreted."""
    out: Dict[str, int] = defaultdict(int)
    for line in hlo.splitlines():
        m = _OP_RE.match(line)
        if not (m and _MOSAIC_TARGET in line):
            continue
        op_name = _OP_NAME_RE.search(line)
        scopes = _JIT_SCOPE_RE.findall(op_name.group(1)) if op_name else []
        out[scopes[-1] if scopes else re.sub(r"\.\d+$", "", m.group(1))] += 1
    return dict(out)


def dynamic_slices(hlo: str, trailing: Tuple[int, ...]
                   ) -> List[Tuple[int, str]]:
    """``(bytes, op_name)`` of each ``dynamic-slice`` instruction in a
    compiled program's text whose result's trailing dimensions are
    ``trailing`` -- e.g. ``(n, n)`` finds every copy of a whole ``(n, n)``
    matrix sliced out of a stack. Instructions are counted once each,
    not per loop trip."""
    out = []
    for line in hlo.splitlines():
        m = _OP_RE.match(line)
        if not m:
            continue
        type_str, rest = _split_type_and_rest(m.group(2))
        if not rest.startswith("dynamic-slice("):
            continue
        (shape,) = _parse_shapes(type_str)
        if shape[1][-len(trailing):] != tuple(trailing):
            continue
        op_name = _OP_NAME_RE.search(line)
        out.append((_nbytes(shape), op_name.group(1) if op_name else ""))
    return out


def analyze(hlo: str) -> CostSummary:
    """Whole-program cost with while-body trip-count multipliers."""
    comps, entry = parse(hlo)
    entry = _entry_name(comps, entry)
    memo: Dict[str, CostSummary] = {}

    def total(name: str) -> CostSummary:
        if name in memo:
            return memo[name]
        c = comps.get(name)
        if c is None:
            return CostSummary(0.0, 0.0, {})
        memo[name] = CostSummary(0.0, 0.0, {})  # cycle guard (HLO is a DAG)
        flops = c.flops
        dot_bytes = c.dot_bytes
        coll: Dict[str, float] = defaultdict(float, c.collective_bytes)
        for child in c.children:
            sub = total(child)
            flops += sub.flops
            dot_bytes += sub.dot_bytes
            for k, v in sub.collective_bytes.items():
                coll[k] += v
        for body, cond, trips in c.whiles:
            if trips is None:
                trips = max(1, comps.get(cond, Computation(cond)).max_const)
            sub = total(body)
            subc = total(cond)
            flops += trips * (sub.flops + subc.flops)
            dot_bytes += trips * (sub.dot_bytes + subc.dot_bytes)
            for k, v in sub.collective_bytes.items():
                coll[k] += trips * v
            for k, v in subc.collective_bytes.items():
                coll[k] += trips * v
        out = CostSummary(flops=flops, dot_bytes=dot_bytes, collective_bytes=dict(coll))
        memo[name] = out
        return out

    import sys
    sys.setrecursionlimit(max(10000, sys.getrecursionlimit()))
    return total(entry)
