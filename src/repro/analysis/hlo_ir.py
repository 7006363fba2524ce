"""Shared HLO-text IR: computations → op lists, with loop-aware costing.

Promoted from ``launch/hlo_cost.py`` (ISSUE 8) so the roofline cost model
and the static-analysis rules parse compiled modules through ONE parser
instead of private regex copies (``hlo_cost`` and the bench scripts each
had their own).  ``launch/hlo_cost.py`` re-exports
everything under its historical names.

The model: ``compiled.as_text()`` is parsed into ``{computation name:
[Op]}``; ``while`` trip counts come from the loop-condition computation
(the compare-against-constant emitted by ``lax.scan`` / ``fori_loop``;
dynamic bounds fall back to 1 and are flagged); :func:`analyze` re-derives
per-chip FLOPs, HBM bytes and collective bytes with loop multiplication.
See the ``launch/hlo_cost.py`` docstring for the costing conventions
(fusion surface traffic, slice-only operands, dot contraction FLOPs).

Parser hardening over the pre-promotion copy (each pinned in
``tests/test_hlo_cost.py``):

  · ``/* ... */`` comments are stripped before parsing — including block
    comments spanning lines (XLA's ``/*index=N*/`` tuple markers were
    already tolerated; a multi-line comment used to desync the
    computation walker);
  · op lines without a leading ``%`` sigil parse (newer XLA dumps print
    some names unsigiled);
  · computation headers without a ``(params) -> result`` signature are
    accepted (``ENTRY main {`` style).
"""
from __future__ import annotations

import dataclasses
import re

DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16, "s4": 1, "u4": 1, "f8e4m3fn": 1, "f8e5m2": 1,
}

SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([\d,]*)\]")
_COMP_HDR_RE = re.compile(
    r"^(?:ENTRY\s+)?%?([\w.\-]+)\s+(?:\(.*->.*)?\{\s*$")
_OPERAND_RE = re.compile(r"%([\w.\-]+)")
_CALL_ATTR_RE = re.compile(r"(?:calls|to_apply|body)=%?([\w.\-]+)")
_COND_ATTR_RE = re.compile(r"condition=%?([\w.\-]+)")
_CONST_INT_RE = re.compile(r"constant\((\d+)\)")
_CONTRACT_RE = re.compile(r"lhs_contracting_dims=\{([\d,]*)\}")
_COMMENT_RE = re.compile(r"/\*.*?\*/", re.S)

COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
               "collective-permute", "ragged-all-to-all")
_MEM_OPS = {"fusion", "dot", "convolution", "copy", "gather", "scatter",
            "dynamic-slice", "dynamic-update-slice", "reduce", "sort",
            "transpose", "reshape-and-pad", "pad", "concatenate",
            "select-and-scatter", "reduce-window", "cholesky",
            "triangular-solve"}


def type_numel_bytes(type_str: str) -> tuple[int, int]:
    """(element count, byte size) summed over every shape in ``type_str``
    — tuple types contribute all their members."""
    n_total, b_total = 0, 0
    for dtype, dims in SHAPE_RE.findall(type_str):
        if dtype not in DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                n *= int(d)
        n_total += n
        b_total += n * DTYPE_BYTES[dtype]
    return n_total, b_total


@dataclasses.dataclass
class Op:
    name: str
    rtype: str
    opcode: str
    rest: str        # operand list + attributes (raw tail of the line)


def parse_op_line(line: str) -> Op | None:
    """Parse ``%name = TYPE opcode(rest`` — TYPE may be a tuple type with
    nested parens, layout braces and ``/*index=N*/`` comments; the leading
    ``%`` sigil and a ``ROOT`` marker are optional."""
    s = _COMMENT_RE.sub("", line).strip()
    if s.startswith("ROOT "):
        s = s[5:].lstrip()
    if s.startswith("%"):
        s = s[1:]
    eq = s.find(" = ")
    if eq <= 0:
        return None
    name = s[:eq]
    if not re.fullmatch(r"[\w.\-]+", name):
        return None
    rest = s[eq + 3:]
    if rest.startswith("("):          # tuple type: match parens
        depth, i = 0, 0
        for i, ch in enumerate(rest):
            if ch == "(":
                depth += 1
            elif ch == ")":
                depth -= 1
                if depth == 0:
                    break
        rtype = rest[:i + 1]
        tail = rest[i + 1:].lstrip()
    else:
        sp = rest.find(" ")
        if sp < 0:
            return None
        rtype = rest[:sp]
        tail = rest[sp + 1:].lstrip()
    par = tail.find("(")
    if par <= 0:
        return None
    opcode = tail[:par]
    if not re.fullmatch(r"[\w\-]+", opcode):
        return None
    return Op(name, rtype, opcode, tail[par + 1:])


def parse_computations(hlo: str) -> dict[str, list[Op]]:
    """HLO module text → ``{computation name: [Op]}`` (comments stripped,
    block comments may span lines)."""
    comps: dict[str, list[Op]] = {}
    current: list[Op] | None = None
    in_comment = False
    for line in hlo.splitlines():
        if in_comment:
            end = line.find("*/")
            if end < 0:
                continue
            line = line[end + 2:]
            in_comment = False
        line = _COMMENT_RE.sub("", line)
        start = line.find("/*")
        if start >= 0:                # block comment opens, no close here
            line = line[:start]
            in_comment = True
        hdr = _COMP_HDR_RE.match(line)
        if hdr and line.rstrip().endswith("{"):
            current = []
            comps[hdr.group(1)] = current
            continue
        if line.startswith("}"):
            current = None
            continue
        if current is None:
            continue
        op = parse_op_line(line)
        if op is not None:
            current.append(op)
    return comps


def trip_count(cond_ops: list[Op]) -> int | None:
    """Largest integer constant in the loop condition ≈ trip count (exact
    for ``lax.scan`` / ``fori_loop``); None when the bound is dynamic."""
    best = None
    for op in cond_ops:
        if op.opcode == "constant":
            m = _CONST_INT_RE.search("constant(" + op.rest)
            if m:
                v = int(m.group(1))
                best = v if best is None else max(best, v)
    return best


@dataclasses.dataclass
class Cost:
    flops: float = 0.0
    bytes: float = 0.0
    coll: dict = dataclasses.field(default_factory=dict)
    dynamic_loops: int = 0

    def scaled(self, k: float) -> "Cost":
        return Cost(self.flops * k, self.bytes * k,
                    {t: v * k for t, v in self.coll.items()},
                    self.dynamic_loops)

    def add(self, o: "Cost"):
        self.flops += o.flops
        self.bytes += o.bytes
        for t, v in o.coll.items():
            self.coll[t] = self.coll.get(t, 0.0) + v
        self.dynamic_loops += o.dynamic_loops


def _dot_flops(op: Op, types: dict[str, str]) -> float:
    out_numel = type_numel_bytes(op.rtype)[0]
    operands = _OPERAND_RE.findall(op.rest.split("),")[0] + ")")
    contract = 1
    cm = _CONTRACT_RE.search(op.rest)
    if cm and operands:
        lhs_type = types.get(operands[0])
        if lhs_type:
            shapes = SHAPE_RE.findall(lhs_type)
            if shapes:
                dims = [int(d) for d in shapes[0][1].split(",") if d]
                for i in (int(x) for x in cm.group(1).split(",") if x):
                    if i < len(dims):
                        contract *= dims[i]
    return 2.0 * out_numel * contract


def _fusion_surface_bytes(op: Op, operands: list[str], types: dict,
                          called: list[Op]) -> float:
    """HBM traffic of a fused kernel = its surface, EXCEPT operands the
    fusion only *slices* (scan xs arrays, embedding tables): a parameter
    consumed solely by internal dynamic-slice/gather ops is charged at the
    slice-result size, not the full array."""
    b = float(type_numel_bytes(op.rtype)[1])          # result write
    # called-computation parameter name per position
    param_names: dict[int, str] = {}
    for o in called:
        if o.opcode == "parameter":
            m = re.match(r"(\d+)\)", o.rest)
            if m:
                param_names[int(m.group(1))] = o.name
    # per-param usage inside the fusion
    slice_bytes: dict[str, float] = {}
    only_sliced: dict[str, bool] = {n: True for n in param_names.values()}
    for o in called:
        if o.opcode == "parameter":
            continue
        head = o.rest.split("),")[0]
        used = _OPERAND_RE.findall(head)
        for u in used:
            if u not in only_sliced:
                continue
            if o.opcode in ("dynamic-slice", "gather") and used and used[0] == u:
                slice_bytes[u] = slice_bytes.get(u, 0.0) \
                    + type_numel_bytes(o.rtype)[1]
            else:
                only_sliced[u] = False
    for pos, name in enumerate(operands):
        t = types.get(name)
        if t is None:
            continue
        pname = param_names.get(pos)
        if pname is not None and only_sliced.get(pname) and pname in slice_bytes:
            b += slice_bytes[pname]
        else:
            b += type_numel_bytes(t)[1]
    return b


def analyze(hlo: str, entry: str | None = None) -> Cost:
    """Loop-multiplied per-device cost terms of an HLO module (see the
    module docstring and ``launch/hlo_cost.py`` for conventions)."""
    comps = parse_computations(hlo)
    if not comps:
        return Cost()
    if entry is None:
        m = re.search(r"^ENTRY\s+%?([\w.\-]+)", hlo, re.M)
        entry = m.group(1) if m else next(iter(comps))
    memo: dict[str, Cost] = {}

    def comp_cost(name: str) -> Cost:
        if name in memo:
            return memo[name]
        memo[name] = Cost()          # break cycles defensively
        ops = comps.get(name, [])
        types = {op.name: op.rtype for op in ops}
        total = Cost()
        for op in ops:
            oc = op.opcode
            if oc == "while":
                bm = re.search(r"body=%?([\w.\-]+)", op.rest)
                cm = _COND_ATTR_RE.search(op.rest)
                body = bm.group(1) if bm else None
                cond = cm.group(1) if cm else None
                trips = trip_count(comps.get(cond, [])) if cond else None
                if trips is None:
                    trips, dyn = 1, 1
                else:
                    dyn = 0
                if body:
                    total.add(comp_cost(body).scaled(trips))
                total.dynamic_loops += dyn
                continue
            if oc in ("fusion", "call", "custom-call", "reduce", "sort",
                      "map", "scatter", "select-and-scatter", "reduce-window",
                      "conditional"):
                cm = _CALL_ATTR_RE.search(op.rest)
                if cm and cm.group(1) in comps:
                    inner = comp_cost(cm.group(1))
                    if oc in ("call", "conditional"):
                        total.add(inner)
                    else:
                        # fusion internals: count compute + collectives, but
                        # NOT bytes — the fused kernel's HBM traffic is its
                        # surface (operands + result), added below
                        surf = Cost(flops=inner.flops, bytes=0.0,
                                    coll=dict(inner.coll),
                                    dynamic_loops=inner.dynamic_loops)
                        total.add(surf)
            base = oc.replace("-start", "")
            if base in COLLECTIVES:
                if not oc.endswith("-done"):
                    b = type_numel_bytes(op.rtype)[1]
                    total.coll[base] = total.coll.get(base, 0.0) + b
                continue
            if oc == "dot":
                total.flops += _dot_flops(op, types)
            if oc == "convolution":
                # rough: 2 × out_numel × (kernel numel / out channels)
                total.flops += 2.0 * type_numel_bytes(op.rtype)[0] * 64
            if oc in _MEM_OPS:
                head = op.rest.split("),")[0]
                operands = _OPERAND_RE.findall(head)
                if oc == "fusion":
                    cm2 = _CALL_ATTR_RE.search(op.rest)
                    called = comps.get(cm2.group(1), []) if cm2 else []
                    total.bytes += _fusion_surface_bytes(op, operands, types,
                                                         called)
                    continue
                if oc == "dynamic-update-slice":
                    # in-place (XLA aliases the buffer): traffic = the update
                    # slice read + written, not the whole buffer
                    upd = types.get(operands[1]) if len(operands) > 1 else None
                    b = 2 * type_numel_bytes(upd)[1] if upd else 0
                elif oc in ("dynamic-slice", "gather"):
                    # traffic = the slice/rows actually read + written out,
                    # not the sliced-from operand
                    b = 2 * type_numel_bytes(op.rtype)[1]
                elif oc == "scatter":
                    # traffic ≈ updates read + touched region read/written
                    upd = types.get(operands[-1]) if operands else None
                    b = 3 * type_numel_bytes(upd)[1] if upd else \
                        type_numel_bytes(op.rtype)[1]
                else:
                    b = type_numel_bytes(op.rtype)[1]
                    for operand in operands:
                        t = types.get(operand)
                        if t:
                            b += type_numel_bytes(t)[1]
                total.bytes += b
        memo[name] = total
        return total

    return comp_cost(entry)
