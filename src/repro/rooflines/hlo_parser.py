"""Post-SPMD HLO text parser for the roofline terms.

Works on ``compiled.as_text()`` (optimized, partitioned HLO: all shapes are
PER-CHIP).  Extracts, with while-loop trip-count multiplication — XLA's own
``cost_analysis`` counts a scan body once, and the optimized while carries
``backend_config={"known_trip_count":{"n":...}}`` which we read directly:

* per-chip collective wire bytes, by op kind, using ring formulas:
    all-gather          (g-1)/g * out_bytes
    reduce-scatter      (g-1)   * out_bytes            (in = g * out)
    all-reduce          2*(g-1)/g * bytes
    all-to-all          (g-1)/g * bytes
    collective-permute  bytes
* dot FLOPs (2 * prod(result_dims) * contracted_size) — the MXU term —
  counted in every computation (CPU HLO wraps dots in called fusions);
* HBM traffic estimate: result + operand bytes of instructions in
  *sequencing* computations only (entry + while bodies) — called fusion
  bodies are represented by their call-site line.

DESIGN.md §5 (dry-run policy): extracts per-chip flops/bytes/collective
terms from partitioned HLO text.
"""
from __future__ import annotations

import dataclasses
import re

__all__ = ["HLOCost", "parse_hlo", "cost_dict"]


def cost_dict(compiled) -> dict:
    """``compiled.cost_analysis()`` as a dict (empty when the backend gives
    none)."""
    return compiled.cost_analysis() or {}


_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "s4": 1, "u4": 1,
}

_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([\d,]*)\]")
_COLL_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_WHILE_RE = re.compile(
    r"while\(.*?\), condition=%?([\w.\-]+), body=%?([\w.\-]+)")
_TRIP_RE = re.compile(r'"known_trip_count":\{"n":"(\d+)"\}')
_CALLS_RE = re.compile(r"\b(?:calls|to_apply)=%?([\w.\-]+)")
_COMP_HDR = re.compile(r"^(ENTRY\s+)?%?([\w.\-]+)\s*\(.*\)\s*->.*\{\s*$")
_DOT_RE = re.compile(r"=\s*\S+\s+dot\(")
_DOT_OPS_RE = re.compile(r"dot\(%([\w.\-]+), %([\w.\-]+)\)")
_DEF_RE = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+) = ([a-z0-9]+)\[([\d,]*)\]")
_CDIMS_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_GROUPS_BRACE = re.compile(r"replica_groups=\{\{([\d,]+)\}")
_GROUPS_IOTA = re.compile(r"replica_groups=\[(\d+),(\d+)\]")
_CONST_RE = re.compile(r"constant\((\d+)\)")
_OPCODE_RE = re.compile(r"=\s*(?:\([^=]*?\)|[a-z0-9]+\[[\d,]*\]\S*)\s+"
                        r"([a-z][\w\-]*)[\s(]")
_REF_RE = re.compile(r"%([\w.\-]+)")
# ops that move no HBM bytes of their own (views / control / plumbing)
_NO_TRAFFIC = {
    "parameter", "get-tuple-element", "tuple", "bitcast", "constant",
    "while", "conditional", "call", "after-all", "domain", "reshape",
    "partition-id", "replica-id", "opt-barrier", "add-dependency",
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def _shapes_on(line: str):
    return [(m.group(1), m.group(2)) for m in _SHAPE_RE.finditer(line)
            if m.group(1) in _DTYPE_BYTES]


@dataclasses.dataclass
class HLOCost:
    dot_flops: float = 0.0            # per-chip MXU FLOPs
    hbm_bytes: float = 0.0            # per-chip HBM traffic estimate
    coll_bytes: float = 0.0           # per-chip collective wire bytes
    coll_by_kind: dict = dataclasses.field(default_factory=dict)
    n_collectives: int = 0
    n_whiles: int = 0
    trip_counts: dict = dataclasses.field(default_factory=dict)


def _split_computations(text: str):
    comps: dict[str, list[str]] = {}
    cur = None
    entry = None
    for line in text.splitlines():
        s = line.rstrip()
        if cur is None or (s and not s.startswith(" ")):
            m = _COMP_HDR.match(s) if ("{" in s and "->" in s) else None
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
                continue
        if cur is not None:
            if s.strip() == "}":
                cur = None
            else:
                comps[cur].append(s)
    return comps, entry


def parse_hlo(text: str) -> HLOCost:
    comps, entry = _split_computations(text)
    # instruction name -> (dtype, dims) for operand-shape lookup
    defs: dict[str, tuple[str, str]] = {}
    for lines in comps.values():
        for line in lines:
            dm = _DEF_RE.match(line)
            if dm and dm.group(2) in _DTYPE_BYTES:
                defs[dm.group(1)] = (dm.group(2), dm.group(3))
    # which computations are bodies of called fusions / reducers?
    fusion_called: set[str] = set()
    for lines in comps.values():
        for line in lines:
            for m in _CALLS_RE.finditer(line):
                fusion_called.add(m.group(1))
    # multiplier fixed-point over while edges (x trip) and call edges (x 1)
    mult = dict.fromkeys(comps, 0.0)
    if entry in mult:
        mult[entry] = 1.0
    trips: dict[str, int] = {}
    for _ in range(12):
        nxt = dict.fromkeys(comps, 0.0)
        if entry in nxt:
            nxt[entry] = 1.0
        for name, lines in comps.items():
            m0 = mult.get(name, 0.0)
            if m0 == 0.0:
                continue
            for line in lines:
                wm = _WHILE_RE.search(line)
                if wm:
                    cond, body = wm.group(1), wm.group(2)
                    tm = _TRIP_RE.search(line)
                    if tm:
                        t = int(tm.group(1))
                    else:
                        consts = [int(c.group(1)) for cl in comps.get(cond, [])
                                  for c in _CONST_RE.finditer(cl)]
                        t = max(consts) if consts else 1
                    trips[body] = t
                    if body in nxt:
                        nxt[body] += m0 * t
                    if cond in nxt:
                        nxt[cond] += m0 * (t + 1)
                for cm in _CALLS_RE.finditer(line):
                    if cm.group(1) in nxt:
                        nxt[cm.group(1)] += m0
        if nxt == mult:
            break
        mult = nxt

    cost = HLOCost(trip_counts=trips)
    for name, lines in comps.items():
        m = mult.get(name, 0.0)
        if m == 0.0:
            continue
        sequencing = name not in fusion_called
        for line in lines:
            shapes = _shapes_on(line)
            if not shapes:
                continue
            cmatch = _COLL_RE.search(line)
            if cmatch and "=" in line:
                kind = cmatch.group(1)
                out_b = _shape_bytes(*shapes[0])
                gb = _GROUPS_BRACE.search(line)
                gi = _GROUPS_IOTA.search(line)
                if gb:
                    g = len(gb.group(1).split(","))
                elif gi:
                    g = int(gi.group(2))
                else:
                    g = 2
                g = max(g, 2)
                if kind == "all-gather":
                    wire = out_b * (g - 1) / g
                elif kind == "reduce-scatter":
                    wire = out_b * (g - 1)
                elif kind == "all-reduce":
                    wire = 2 * out_b * (g - 1) / g
                elif kind == "all-to-all":
                    wire = out_b * (g - 1) / g
                else:  # collective-permute
                    wire = out_b
                cost.coll_bytes += m * wire
                cost.coll_by_kind[kind] = cost.coll_by_kind.get(kind, 0.0) \
                    + m * wire
                cost.n_collectives += 1
            if _DOT_RE.search(line):
                out_dt, out_dims = shapes[0]
                ops = _DOT_OPS_RE.search(line)
                lhs = defs.get(ops.group(1), ("f32", "")) if ops \
                    else (shapes[1] if len(shapes) > 1 else ("f32", ""))
                cd = _CDIMS_RE.search(line)
                csize = 1
                if cd and lhs[1]:
                    ldims = [int(x) for x in lhs[1].split(",") if x]
                    for ci in cd.group(1).split(","):
                        if ci and int(ci) < len(ldims):
                            csize *= ldims[int(ci)]
                n_out = 1
                for d in out_dims.split(","):
                    if d:
                        n_out *= int(d)
                cost.dot_flops += m * 2.0 * n_out * csize
            if sequencing:
                om = _OPCODE_RE.search(line)
                opcode = om.group(1) if om else ""
                if opcode and opcode not in _NO_TRAFFIC:
                    # result bytes + operand bytes (resolved via defs)
                    nbytes = _shape_bytes(*shapes[0])
                    refs = _REF_RE.findall(line.split("(", 1)[1]) \
                        if "(" in line else []
                    for r in refs[:8]:
                        if r in defs:
                            nbytes += _shape_bytes(*defs[r])
                    cost.hbm_bytes += m * nbytes
        cost.n_whiles += sum(1 for l in lines if _WHILE_RE.search(l))
    return cost
