"""Jaxpr and HLO rule passes over traced fit graphs.

All passes take a traced (NOT executed) ``ClosedJaxpr`` — obtained from
``jax.make_jaxpr`` over the shard_map'd fit drivers — and return
:class:`~repro.analysis.report.Finding` lists.  The central analysis is
*shard uniformity*: a value is uniform when every shard provably holds
the same value (replicated inputs, constants, and the results of
full-axis ``psum``/``pmax``/``pmin``/``all_gather`` are uniform;
shard_map-sharded inputs, ``axis_index``, ``ppermute`` and
``reduce_scatter`` results are not; elementwise ops preserve uniformity
of their inputs; loop carries take a monotone fixpoint).  The SPMD
deadlock class (PR 7) is exactly a *control decision that gates
collectives going non-uniform*:

  · a ``while_loop`` whose body/cond issues collectives must have a
    provably uniform exit predicate — else trip counts can diverge
    across shards and one shard blocks in a collective its peers never
    enter (GC001);
  · ``cond``/``switch`` branches with *different* collective sequences
    are only safe under a uniform predicate — shard-varying branch
    selection with divergent sequences deadlocks (GC001).

``lax.scan``/``fori_loop`` static trip counts are uniform by
construction, so collectives inside scans are fine.
"""
from __future__ import annotations

from repro.analysis.report import Finding

# jaxpr primitive names (jax 0.4.x)
UNIFORMING_COLLECTIVES = frozenset({"psum", "pmax", "pmin", "all_gather"})
OTHER_COLLECTIVES = frozenset({
    "ppermute", "pbroadcast", "all_to_all", "reduce_scatter", "pgather",
    "psum_scatter"})
COLLECTIVE_PRIMS = UNIFORMING_COLLECTIVES | OTHER_COLLECTIVES
NONUNIFORM_PRIMS = frozenset({
    "axis_index", "ppermute", "all_to_all", "reduce_scatter", "pgather",
    "psum_scatter"})
HOST_PRIMS = frozenset({
    "pure_callback", "io_callback", "debug_callback", "callback",
    "outside_call", "infeed", "outfeed", "host_callback_call"})

_F64_DTYPES = ("float64", "complex128")


# --------------------------------------------------------------- structure

def as_open(jaxpr):
    """ClosedJaxpr | Jaxpr → the open Jaxpr."""
    return getattr(jaxpr, "jaxpr", jaxpr)


def sub_jaxprs(eqn):
    """Every sub-jaxpr in an equation's params, in declaration order."""
    for key in sorted(eqn.params):
        val = eqn.params[key]
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if hasattr(v, "eqns") or hasattr(v, "jaxpr"):
                yield key, as_open(v)


def iter_eqns(jaxpr, path=""):
    """Depth-first (eqn, path) over a jaxpr and every sub-jaxpr."""
    for eqn in as_open(jaxpr).eqns:
        name = eqn.primitive.name
        yield eqn, path
        for key, sub in sub_jaxprs(eqn):
            yield from iter_eqns(sub, f"{path}/{name}.{key}")


def has_collectives(jaxpr) -> bool:
    return any(e.primitive.name in COLLECTIVE_PRIMS
               for e, _ in iter_eqns(jaxpr))


def _axes_of(params) -> tuple:
    ax = params.get("axes", params.get("axis_name", ()))
    if not isinstance(ax, tuple):
        ax = (ax,)
    return tuple(str(a) for a in ax)


def collective_signature(eqn) -> tuple:
    """(op, axes, extra-params, result shapes+dtypes) — two collectives
    with equal signatures pair up across shards."""
    extras = tuple(sorted(
        (k, str(v)) for k, v in eqn.params.items()
        if k not in ("axes", "axis_name")
        and isinstance(v, (bool, int, float, str, tuple))))
    outs = tuple((str(v.aval.dtype), tuple(v.aval.shape))
                 for v in eqn.outvars)
    return (eqn.primitive.name, _axes_of(eqn.params), extras, outs)


def collective_sequence(jaxpr) -> tuple:
    """Structural collective schedule of a jaxpr: flat signatures, with
    loops/branches as nested markers so ('while', …) ≠ an unrolled body."""
    seq = []
    for eqn in as_open(jaxpr).eqns:
        name = eqn.primitive.name
        if name in COLLECTIVE_PRIMS:
            seq.append(collective_signature(eqn))
        elif name == "while":
            seq.append(("while",
                        collective_sequence(eqn.params["cond_jaxpr"]),
                        collective_sequence(eqn.params["body_jaxpr"])))
        elif name == "cond":
            seq.append(("cond", tuple(collective_sequence(b)
                                      for b in eqn.params["branches"])))
        elif name == "scan":
            seq.append(("scan", eqn.params.get("length"),
                        collective_sequence(eqn.params["jaxpr"])))
        else:
            for _, sub in sub_jaxprs(eqn):
                inner = collective_sequence(sub)
                if inner:
                    seq.extend(inner)
    return tuple(seq)


def describe_signature(sig) -> str:
    if sig and sig[0] in ("while", "cond", "scan"):
        return sig[0]
    op, axes, _, outs = sig
    shapes = ",".join(f"{d}{list(s)}" for d, s in outs)
    return f"{op}[axis={'/'.join(axes)}; {shapes}]"


# ----------------------------------------------------- uniformity analysis

class _UniformWalker:
    """Propagates shard-uniformity through a jaxpr, emitting GC001
    findings at every control construct that gates collectives on a
    non-uniform value."""

    def __init__(self, where: str, config: str | None):
        self.where = where
        self.config = config
        self.findings: list[Finding] = []

    def _finding(self, path, msg):
        self.findings.append(Finding(
            "GC001", f"{self.where}{path}", msg, config=self.config))

    def run(self, jaxpr, in_uniform, path="") -> list[bool]:
        """Returns uniformity of the jaxpr's outputs."""
        jx = as_open(jaxpr)
        env: dict = {}

        def write(var, val):
            env[var] = bool(val)

        def read(atom):
            # Literals and constvars are baked into the program: uniform.
            return env.get(atom, True) if hasattr(atom, "aval") \
                and not hasattr(atom, "val") else True

        if len(in_uniform) != len(jx.invars):
            in_uniform = [True] * len(jx.invars)
        for var, u in zip(jx.invars, in_uniform):
            write(var, u)
        for var in jx.constvars:
            write(var, True)

        for eqn in jx.eqns:
            name = eqn.primitive.name
            ins = [read(v) for v in eqn.invars]
            epath = f"{path}/{name}"
            if name in UNIFORMING_COLLECTIVES:
                outs = [True] * len(eqn.outvars)
            elif name in NONUNIFORM_PRIMS:
                outs = [False] * len(eqn.outvars)
            elif name == "while":
                outs = self._while(eqn, ins, epath)
            elif name == "cond":
                outs = self._cond(eqn, ins, epath)
            elif name == "scan":
                outs = self._scan(eqn, ins, epath)
            elif name == "shard_map":
                outs = self._shard_map(eqn, epath)
            else:
                sub = dict(sub_jaxprs(eqn))
                if sub and len(sub) == 1:
                    inner = next(iter(sub.values()))
                    if len(inner.invars) == len(ins):
                        outs = self.run(inner, ins, epath)
                        if len(outs) != len(eqn.outvars):
                            outs = [all(ins)] * len(eqn.outvars)
                    else:
                        outs = [all(ins)] * len(eqn.outvars)
                else:
                    outs = [all(ins)] * len(eqn.outvars)
            for var, u in zip(eqn.outvars, outs):
                write(var, u)

        return [read(v) for v in jx.outvars]

    def _while(self, eqn, ins, path):
        p = eqn.params
        cn, bn = p["cond_nconsts"], p["body_nconsts"]
        cond_consts, body_consts = ins[:cn], ins[cn:cn + bn]
        carry = list(ins[cn + bn:])
        body, cond = p["body_jaxpr"], p["cond_jaxpr"]
        # Monotone fixpoint: uniformity only ever decays.
        for _ in range(len(carry) + 1):
            probe = _UniformWalker(self.where, self.config)
            out = probe.run(body, body_consts + carry, path + ".body")
            new = [a and b for a, b in zip(carry, out)]
            if new == carry:
                break
            carry = new
        # Re-run at the fixpoint, keeping nested findings exactly once.
        body_out = self.run(body, body_consts + carry, path + ".body")
        cond_out = self.run(cond, cond_consts + carry, path + ".cond")
        if (has_collectives(body) or has_collectives(cond)) \
                and not all(cond_out):
            self._finding(
                path,
                "while_loop issues collectives but its exit predicate is "
                "not provably shard-uniform — trip counts can diverge "
                "across shards and deadlock the collective schedule "
                "(derive the predicate from psum/pmax-reduced values)")
        return [a and b for a, b in zip(carry, body_out)]

    def _cond(self, eqn, ins, path):
        pred_uniform, op_ins = ins[0], ins[1:]
        branches = eqn.params["branches"]
        seqs = [collective_sequence(b) for b in branches]
        if not pred_uniform and len(set(seqs)) > 1:
            diff = " vs ".join(
                "(" + ", ".join(describe_signature(s) for s in seq) + ")"
                for seq in seqs)
            self._finding(
                path,
                "cond branches issue divergent collective sequences "
                f"{diff} under a shard-varying predicate — shards taking "
                "different branches deadlock")
        branch_outs = [self.run(b, list(op_ins), f"{path}.b{i}")
                       for i, b in enumerate(branches)]
        n = len(eqn.outvars)
        return [pred_uniform and all(bo[i] if i < len(bo) else True
                                     for bo in branch_outs)
                for i in range(n)]

    def _scan(self, eqn, ins, path):
        p = eqn.params
        nc, ncar = p["num_consts"], p["num_carry"]
        consts, carry = ins[:nc], list(ins[nc:nc + ncar])
        xs = ins[nc + ncar:]
        body = p["jaxpr"]
        n_ys = len(eqn.outvars) - ncar
        ys = [True] * n_ys
        for _ in range(ncar + 1):
            probe = _UniformWalker(self.where, self.config)
            out = probe.run(body, consts + carry + list(xs), path + ".body")
            new = [a and b for a, b in zip(carry, out[:ncar])]
            if new == carry:
                ys = [a and b for a, b in zip(ys, out[ncar:])]
                break
            carry = new
        out = self.run(body, consts + carry + list(xs), path + ".body")
        ys = [a and b for a, b in zip(ys, out[ncar:])]
        return carry + ys

    def _shard_map(self, eqn, path):
        p = eqn.params
        in_specs = p.get("in_specs")
        inner = p["jaxpr"]
        n_in = len(as_open(inner).invars)
        if in_specs is None:
            ins = [False] * n_in
        else:
            # a spec naming no mesh axis = replicated operand → uniform;
            # any named axis → sharded
            ins = [all(axes is None for axes in spec) for spec in in_specs]
            ins += [False] * (n_in - len(ins))
        outs = self.run(inner, ins, path)
        n = len(eqn.outvars)
        if len(outs) != n:
            outs = [False] * n
        return outs


# ------------------------------------------------------------------ rules

def check_collective_uniformity(jaxpr, where: str,
                                config: str | None = None) -> list[Finding]:
    """GC001 — no shard-divergent control over collectives."""
    w = _UniformWalker(where, config)
    jx = as_open(jaxpr)
    w.run(jx, [True] * len(jx.invars))
    return w.findings


def check_host_transfers(jaxpr, where: str,
                         config: str | None = None) -> list[Finding]:
    """GC002 — no host callbacks/infeed/outfeed inside loop bodies."""
    findings = []
    for eqn, path in iter_eqns(jaxpr):
        if eqn.primitive.name in HOST_PRIMS and (
                ".body" in path or "while." in path or "scan." in path):
            findings.append(Finding(
                "GC002", f"{where}{path}/{eqn.primitive.name}",
                f"host transfer '{eqn.primitive.name}' inside a loop body "
                "serialises every iteration on a host round trip",
                config=config))
    return findings


def _avals(jaxpr):
    jx = as_open(jaxpr)
    for v in list(jx.invars) + list(jx.constvars):
        yield v.aval, ""
    for eqn, path in iter_eqns(jx):
        for v in eqn.outvars:
            yield v.aval, f"{path}/{eqn.primitive.name}"


def check_fp64(jaxpr, where: str, config: str | None = None) -> list[Finding]:
    """GC003 — no float64/complex128 anywhere in the graph."""
    findings = []
    seen = set()
    for aval, path in _avals(jaxpr):
        dt = str(getattr(aval, "dtype", ""))
        if dt in _F64_DTYPES and (path or "invars") not in seen:
            seen.add(path or "invars")
            findings.append(Finding(
                "GC003", f"{where}{path or '/invars'}",
                f"{dt} value of shape {tuple(getattr(aval, 'shape', ()))} "
                "in the fit graph (fp64 halves throughput and breaks the "
                "exact-fp32 stop-stat contract)", config=config))
            if len(seen) >= 8:        # one graph full of f64 → don't spam
                break
    return findings


def check_stop_stats_precision(jaxpr, where: str,
                               config: str | None = None) -> list[Finding]:
    """GC004 — scalar stop statistics stay exact fp32: float scalars in
    while carries are f32, scalar psums reduce in f32, and no float
    scalar rides the lossy int8 ring (ppermute)."""
    findings = []
    for eqn, path in iter_eqns(jaxpr):
        name = eqn.primitive.name
        if name == "while":
            body = as_open(eqn.params["body_jaxpr"])
            for i, v in enumerate(body.outvars):
                aval = v.aval
                dt = str(getattr(aval, "dtype", ""))
                if getattr(aval, "shape", None) == () and \
                        "float" in dt and dt != "float32":
                    findings.append(Finding(
                        "GC004", f"{where}{path}/while.carry[{i}]",
                        f"float scalar loop carry is {dt}, not f32 — "
                        "stop statistics must be exact fp32",
                        config=config))
        elif name == "psum":
            for v in eqn.outvars:
                aval = v.aval
                dt = str(getattr(aval, "dtype", ""))
                if getattr(aval, "shape", None) == () and \
                        "float" in dt and dt != "float32":
                    findings.append(Finding(
                        "GC004", f"{where}{path}/psum",
                        f"scalar psum reduces in {dt}, not f32 — stop "
                        "stats must not lose precision on the wire",
                        config=config))
        elif name == "ppermute":
            for v in eqn.invars:
                aval = getattr(v, "aval", None)
                dt = str(getattr(aval, "dtype", ""))
                if aval is not None and getattr(aval, "shape", None) == () \
                        and "float" in dt:
                    findings.append(Finding(
                        "GC004", f"{where}{path}/ppermute",
                        "float scalar riding the ppermute ring — scalar "
                        "stop stats must use the exact psum path, not the "
                        "lossy compressed ring", config=config))
    return findings


# ------------------------------------------------- HLO wire-byte account

# Per-device SEND bytes per result byte, ring algorithms (matches
# distribution.compression.ring_wire_bytes and the all-reduce convention
# in launch/hlo_cost's cost model).
def _send_factor(family: str, n: int) -> float:
    if family == "all-reduce":
        return 2.0 * (n - 1) / n            # reduce-scatter + all-gather
    if family == "all-gather":
        return (n - 1) / n                  # result is the full array
    if family == "reduce-scatter":
        return float(n - 1)                 # result is one shard
    if family == "all-to-all":
        return (n - 1) / n
    if family in ("collective-permute", "ragged-all-to-all"):
        return 1.0                          # one hop sends the payload
    return 1.0


def hlo_wire_bytes(hlo: str, axis_size: int) -> dict[str, float]:
    """Per-device wire (send) bytes by collective family from compiled
    HLO text — loop-multiplied via :func:`repro.analysis.hlo_ir.analyze`."""
    from repro.analysis.hlo_ir import analyze
    cost = analyze(hlo)
    return {fam: b * _send_factor(fam, axis_size)
            for fam, b in cost.coll.items()}
