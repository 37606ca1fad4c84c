"""Trip-count-aware HLO cost analysis.

XLA's ``compiled.cost_analysis()`` visits each ``while`` body ONCE — a
scanned 48-layer model reports ~1/48th of its real FLOPs, and a
collective inside the layer loop is counted once instead of 48 times.
Since every decoder stack here scans over layers (HLO size O(1) in
depth — required to compile 61-layer 671B programs), the dry-run needs
its own analyzer.  Two sources are combined:

* **pre-optimization HLO** (``lowered.as_text("hlo")``, global shapes,
  fully-typed params, simple loop conditions) → exact matmul/conv FLOPs
  with every op weighted by the product of its enclosing while trip
  counts.  Global FLOPs / chips = per-device (up to partition padding,
  which is reported separately by the memory analysis).
* **post-optimization HLO** (``compiled.as_text()``, per-device shapes,
  fused) → collective bytes (result-buffer bytes of all-gather /
  all-reduce / reduce-scatter / all-to-all / collective-permute ×
  trip counts) and an HBM-traffic proxy (result bytes of top-level
  (post-fusion) ops × trip counts).

Trip counts are recovered from each while condition's s32[] constant
(jax lowers scans to ``compare(iv, constant), direction=LT``; after
optimization the compare may be fused but the constant stays in the
condition computation).
"""

from __future__ import annotations

import dataclasses
import re
from typing import Dict, Iterable, Iterator, List, Optional, Sequence, Tuple

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

_TYPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w\.\-]+)\s*=\s*(.+?)\s+([a-z0-9\-]+)\(")
_HDR_RE = re.compile(r"^\s*(?:ENTRY\s+)?%?([\w\.\-]+)\s*[\({]")
_PARAM_RE = re.compile(r"([\w\.\-]+):\s*([a-z0-9]+\[[0-9,]*\])")
_CONST_RE = re.compile(r"s32\[\]\s+constant\((\d+)\)")
_CALLED_RE = {
    "to_apply": re.compile(r"to_apply=%?([\w\.\-]+)"),
    "body": re.compile(r"body=%?([\w\.\-]+)"),
    "condition": re.compile(r"condition=%?([\w\.\-]+)"),
    "calls": re.compile(r"calls=%?([\w\.\-]+)"),
    # lax.cond branches: each taken a fraction of the time; weighting
    # them 1/n_branches matches the causal-skip usage exactly (half the
    # (q,kv) chunk pairs are above the diagonal).
    "branch_t": re.compile(r"true_computation=%?([\w\.\-]+)"),
    "branch_f": re.compile(r"false_computation=%?([\w\.\-]+)"),
}
_BRANCHES_RE = re.compile(r"branch_computations=\{([^}]*)\}")


def _tshape_bytes(type_str: str) -> int:
    total = 0
    for m in _TYPE_RE.finditer(type_str):
        n = _DTYPE_BYTES.get(m.group(1), 0)
        for d in m.group(2).split(","):
            if d:
                n *= int(d)
        total += n
    return total


def _elems(dims: str) -> int:
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n


@dataclasses.dataclass
class Op:
    """One HLO instruction: name, result type string, opcode, raw line."""
    name: str
    type_str: str          # full result type (may be a tuple)
    opcode: str
    line: str


@dataclasses.dataclass
class Computation:
    """A named HLO computation: its ops and param name -> type map."""
    name: str
    ops: List[Op]
    params: Dict[str, str]           # param name -> type string


def parse_hlo(text: str) -> Dict[str, Computation]:
    """Parse HLO text into ``{computation name: Computation}`` (line
    grammar only — headers end with ``{``, ops contain ``\" = \"``)."""
    comps: Dict[str, Computation] = {}
    cur: Optional[Computation] = None
    for raw in text.splitlines():
        line = raw.rstrip()
        s = line.strip()
        if not s or s.startswith("HloModule"):
            continue
        # Computation headers: "name {", "%name (a: t[..]) -> t[..] {",
        # "ENTRY %name (...) -> ... {"  — never contain " = ".
        if s.endswith("{") and " = " not in s:
            m = _HDR_RE.match(s)
            if m:
                params = {}
                if ") -> " in s:
                    params = dict(_PARAM_RE.findall(s[: s.rfind(") -> ")]))
                cur = Computation(m.group(1), [], params)
                comps[cur.name] = cur
                continue
        if cur is None or " = " not in s:
            continue
        m = _OP_RE.match(s)
        if m:
            cur.ops.append(Op(m.group(1), m.group(2), m.group(3), s))
    return comps


# ---------------------------------------------------------------------------
# Execution multipliers (product of enclosing while trip counts)
# ---------------------------------------------------------------------------
def _trip_count(cond: Computation) -> Optional[int]:
    consts = [int(m.group(1)) for op in cond.ops
              for m in [_CONST_RE.search(op.line)] if m]
    if len(consts) == 1:
        return consts[0]
    if consts:
        # multiple constants: prefer the one inside a compare op
        for op in cond.ops:
            if "compare(" in op.line:
                m = _CONST_RE.search(op.line)
                if m:
                    return int(m.group(1))
        return max(consts)
    return None


def _called(line: str) -> List[Tuple[str, str]]:
    out = []
    for kind, rx in _CALLED_RE.items():
        m = rx.search(line)
        if m:
            out.append((kind, m.group(1)))
    m = _BRANCHES_RE.search(line)
    if m:
        for b in m.group(1).split(","):
            out.append(("branch", b.strip().lstrip("%")))
    return out


def _multipliers(comps: Dict[str, Computation],
                 shard_scale: float = 1.0) -> Tuple[Dict[str, float], int]:
    """shard_scale: multiplier applied on edges INTO shard_map bodies
    (``xla.sdy.manual_computation_body*``).  Pre-optimization HLO mixes
    GLOBAL shapes (GSPMD-auto ops) with PER-SHARD shapes inside manual
    computations; scaling the latter by the device count keeps both in
    global units so a single /chips at the end is correct."""
    called_names = set()
    for c in comps.values():
        for op in c.ops:
            for _, n in _called(op.line):
                called_names.add(n)
    mult = {n: 1.0 for n in comps if n not in called_names}
    unresolved = 0
    changed, guard = True, 0
    while changed and guard < 10_000:
        changed, guard = False, guard + 1
        for cname, comp in comps.items():
            m = mult.get(cname)
            if m is None:
                continue
            for op in comp.ops:
                called = _called(op.line)
                n_branches = sum(1 for k, _ in called
                                 if k.startswith("branch"))
                for kind, target in called:
                    if target not in comps:
                        continue
                    factor = 1.0
                    if kind in ("body", "condition"):
                        condname = _CALLED_RE["condition"].search(op.line)
                        tc = None
                        if condname and condname.group(1) in comps:
                            tc = _trip_count(comps[condname.group(1)])
                        if tc is None:
                            tc, unresolved = 1, unresolved + 1
                        factor = float(tc)
                    elif kind.startswith("branch") and n_branches > 1:
                        factor = 1.0 / n_branches
                    if "manual_computation_body" in target:
                        factor *= shard_scale
                    new = m * factor
                    if mult.get(target, 0.0) < new:
                        mult[target] = new
                        changed = True
    return mult, unresolved


# ---------------------------------------------------------------------------
# FLOPs from the pre-optimization module (typed, global shapes)
# ---------------------------------------------------------------------------
_LHS_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([0-9,]*)\}")
_OPERANDS_RE = re.compile(r"\(([^)]*)\)")


def _symbol_table(comp: Computation) -> Dict[str, str]:
    tab = dict(comp.params)
    for op in comp.ops:
        tab[op.name] = op.type_str
    return tab


def _first_operands(line: str) -> List[str]:
    idx = line.find("(")
    depth, end = 1, len(line)
    inner_start = idx + 1
    for i in range(inner_start, len(line)):
        ch = line[i]
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
            if depth == 0:
                end = i
                break
    inner = line[inner_start:end]
    return [o.strip().split(" ")[-1].lstrip("%") for o in inner.split(",")
            if o.strip()]


def _resolve_dims(name: str, tab: Dict[str, str],
                  comp: Computation) -> Optional[List[int]]:
    t = tab.get(name)
    if t is None:
        return None
    # plain array type
    m = _TYPE_RE.search(t)
    if m and not t.startswith("("):
        return [int(x) for x in m.group(2).split(",") if x]
    return None


def _dot_flops(op: Op, tab: Dict[str, str], comp: Computation) -> float:
    result_elems = _elems(_TYPE_RE.search(op.type_str).group(2)) \
        if _TYPE_RE.search(op.type_str) else 0
    k = 1
    mc = _LHS_CONTRACT_RE.search(op.line)
    operands = _first_operands(op.line)
    if mc and operands:
        lhs_dims = _resolve_dims(operands[0], tab, comp)
        if lhs_dims:
            for ci in mc.group(1).split(","):
                if ci and int(ci) < len(lhs_dims):
                    k *= lhs_dims[int(ci)]
    return 2.0 * result_elems * k


def _conv_flops(op: Op, tab: Dict[str, str], comp: Computation) -> float:
    result_elems = _elems(_TYPE_RE.search(op.type_str).group(2)) \
        if _TYPE_RE.search(op.type_str) else 0
    operands = _first_operands(op.line)
    k = 1
    if len(operands) >= 2:
        rhs = _resolve_dims(operands[1], tab, comp)
        if rhs and len(rhs) >= 2:
            for d in rhs[:-1]:       # HWIO kernel: all but output feature
                k *= d
    return 2.0 * result_elems * k


def flops_from_pre(text: str, chips: int = 1) -> Tuple[float, int]:
    """(total FLOPs with loop multipliers, unresolved whiles) from the
    pre-optimization module (GLOBAL shapes; shard_map bodies are
    per-shard and get scaled up by `chips`)."""
    comps = parse_hlo(text)
    mult, unresolved = _multipliers(comps, shard_scale=float(chips))
    total = 0.0
    for cname, comp in comps.items():
        m = mult.get(cname, 1.0)
        tab = _symbol_table(comp)
        for op in comp.ops:
            if op.opcode == "dot":
                total += m * _dot_flops(op, tab, comp)
            elif op.opcode == "convolution":
                total += m * _conv_flops(op, tab, comp)
    return total, unresolved


# ---------------------------------------------------------------------------
# Bytes + collectives from the post-optimization module (per-device)
# ---------------------------------------------------------------------------
def _collective_ops(comps: Dict[str, Computation], mult: Dict[str, float]
                    ) -> Iterator[Tuple[str, Op, float]]:
    """Each collective op as (kind, op, result-buffer bytes weighted by
    its enclosing ``while`` trip counts); ``-start`` ops count as their
    kind."""
    for cname, comp in comps.items():
        m = mult.get(cname, 1.0)
        for op in comp.ops:
            base = op.opcode[:-6] if op.opcode.endswith("-start") \
                else op.opcode
            if base in _COLLECTIVES:
                yield base, op, m * _tshape_bytes(op.type_str)


# replica_groups spellings in post-optimization HLO: explicit
# ``{{0,2},{1,3}}`` lists and the iota form ``[2,2]<=[4]`` (G groups of
# S devices: the second dim is the group size).
_GROUPS_LIST_RE = re.compile(r"replica_groups=\{\{([0-9, ]+)\}")
_GROUPS_IOTA_RE = re.compile(r"replica_groups=\[(\d+),(\d+)\]")


def collectives_by_axis(texts: Iterable[str],
                        axes: Sequence[Tuple[str, int]]) -> dict:
    """Collective counts by kind, and counts and bytes-moved estimates
    by mesh axis, over post-optimization HLO ``texts`` of programs on a
    mesh of ``(name, size)`` ``axes``.

    Bytes are each collective's trip-count-weighted result-buffer size
    (as in :func:`bytes_from_post`); each op is attributed to the axes
    (of size > 1) whose size matches its replica-group size, split
    evenly when several share a size, or among all of them when none
    matches (or the groups are not spelled out)."""
    live = [(a, s) for a, s in axes if s > 1]
    per_axis = {a: {"count": 0, "bytes": 0.0} for a, _ in live}
    counts: Dict[str, int] = {}
    total = 0.0
    for text in texts:
        comps = parse_hlo(text)
        mult, _ = _multipliers(comps)
        for kind, op, moved in _collective_ops(comps, mult):
            counts[kind] = counts.get(kind, 0) + 1
            total += moved
            m = _GROUPS_LIST_RE.search(op.line)
            if m:
                group = len([x for x in m.group(1).split(",") if x.strip()])
            else:
                m = _GROUPS_IOTA_RE.search(op.line)
                group = int(m.group(2)) if m else 0
            hit = [a for a, s in live if s == group] or list(per_axis)
            for a in hit:
                per_axis[a]["count"] += 1
                per_axis[a]["bytes"] += moved / len(hit)
    return {"counts": counts, "per_axis": per_axis,
            "total_bytes": int(total)}


def bytes_from_post(text: str) -> Tuple[float, Dict[str, float], int]:
    """Trip-count-weighted (hbm_bytes, collective bytes by kind,
    unresolved-while count) from post-optimization per-device HLO."""
    comps = parse_hlo(text)
    mult, unresolved = _multipliers(comps)
    coll = {k: 0.0 for k in _COLLECTIVES}
    for kind, _, moved in _collective_ops(comps, mult):
        coll[kind] += moved
    hbm = 0.0
    # Fusions whose root is an in-place update (dynamic-update-slice /
    # scatter) alias their operand buffer — XLA writes only the updated
    # rows (e.g. a scan's per-layer KV-cache write), not the result
    # shape.  Counting their full result would claim 48× the cache per
    # decode step (measured before this fix).
    inplace_roots = set()
    for cname, comp in comps.items():
        if comp.ops and comp.ops[-1].opcode in ("dynamic-update-slice",
                                                "scatter"):
            inplace_roots.add(cname)
    skip = ("parameter", "constant", "get-tuple-element", "tuple", "bitcast",
            "after-all", "partition-id", "replica-id",
            # in-place update ops alias their operand buffer (donation /
            # XLA buffer aliasing): traffic is O(update), not O(buffer).
            # The update operand is not recoverable from the optimized
            # text, so count 0 — vs the full-buffer cost of the select-
            # based alternative, which IS a real whole-buffer rewrite.
            "scatter", "dynamic-update-slice")
    for cname, comp in comps.items():
        m = mult.get(cname, 1.0)
        if cname.startswith(("fused_", "wrapped_")):
            continue                # fusion internals don't hit HBM
        for op in comp.ops:
            if op.opcode in skip or op.opcode.endswith("-done"):
                continue
            if op.opcode == "fusion":
                called = _CALLED_RE["calls"].search(op.line)
                if called and called.group(1) in inplace_roots:
                    continue        # aliased in-place update fusion
            hbm += m * _tshape_bytes(op.type_str)
    coll["total"] = sum(coll[k] for k in _COLLECTIVES)
    return hbm, coll, unresolved


@dataclasses.dataclass
class HloCost:
    """Per-device cost rollup combining both HLO sources (see module
    docstring); ``unresolved_whiles > 0`` flags an untrusted count."""
    flops: float                       # per-device
    collective_bytes: Dict[str, float]
    hbm_bytes: float
    unresolved_whiles: int

    def as_dict(self) -> Dict:
        """JSON-serializable form for the dry-run artifacts."""
        return {"flops": self.flops,
                "collective_bytes": self.collective_bytes,
                "hbm_bytes": self.hbm_bytes,
                "unresolved_whiles": self.unresolved_whiles}


def analyze_lowered(lowered, compiled, chips: int) -> HloCost:
    """Analyze a jax ``lowered``/``compiled`` pair: exact FLOPs from
    the pre-optimization HLO, bytes from the post-optimization HLO."""
    flops_global, unres_pre = flops_from_pre(lowered.as_text("hlo"), chips)
    hbm, coll, unres_post = bytes_from_post(compiled.as_text())
    return HloCost(flops=flops_global / max(chips, 1),
                   collective_bytes=coll, hbm_bytes=hbm,
                   unresolved_whiles=unres_pre + unres_post)
