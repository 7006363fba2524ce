"""Findings, the rule catalogue, suppression, and report rendering.

Every rule has a stable id (``GC0xx`` graph-contract, ``AST00x`` source
lint), a kebab-case name, and a severity.  Suppression is explicit and
auditable: ``--suppress GC003`` on the CLI (or ``suppress=`` in the
library API) keeps the finding in the report but marks it
``suppressed: true`` and removes it from the exit-code decision; AST
findings can also be suppressed at the flagged line with a
``# repro-lint: disable=AST002`` comment (same line or the line above).

The JSON schema (``Report.to_json``) is the ``graph-lint`` CI artifact's
contract and is pinned by a golden-file test — bump ``SCHEMA_VERSION``
when it changes shape.
"""
from __future__ import annotations

import dataclasses
import json

SCHEMA_VERSION = 1

# id → (name, severity, one-line description)
RULE_CATALOGUE: dict[str, tuple[str, str, str]] = {
    "GC001": (
        "collective-uniformity", "error",
        "control flow over collectives must be shard-uniform: cond/switch "
        "branches with divergent collective sequences (op, axes, shape, "
        "dtype) need a replicated predicate, and a while_loop issuing "
        "collectives needs an exit predicate derived from collectively-"
        "reduced values — else trip counts diverge across shards and "
        "deadlock (the PR 7 int8-ring class)"),
    "GC002": (
        "host-transfer-in-loop", "error",
        "no host callbacks/infeed/outfeed inside while/scan bodies — a "
        "host round trip per iteration serialises the hot loop"),
    "GC003": (
        "fp64-in-graph", "error",
        "no float64/complex128 values anywhere in a fit graph — fp64 "
        "silently halves throughput and breaks the exact-fp32 stop-stat "
        "contract"),
    "GC004": (
        "stop-stats-precision", "error",
        "scalar stop statistics in the fit loop must be exact fp32: "
        "float scalars in while-loop carries must be f32, scalars must "
        "not ride the lossy int8 ring (ppermute), and scalar psums must "
        "reduce in f32"),
    "GC005": (
        "wire-bytes-mismatch", "error",
        "collective bytes counted in the lowered HLO of one stats "
        "reduction must equal core.engine.stats_wire_bytes's analytic "
        "accounting (the cost model the provisioning planner trusts)"),
    "GC006": (
        "recompile-config", "error",
        "every EngineConfig field must be hashable (static jit cache "
        "key) and sweeping traced arguments (h_star) must not change the "
        "traced graph — a retrace per swept value is a silent compile "
        "storm"),
    "AST001": (
        "kernel-mask-param", "error",
        "public kernel entry points taking the points array must accept "
        "a mask= keyword — the mask operand is how padding, sharding and "
        "minibatch draws compose with every backend"),
    "AST002": (
        "hardcoded-axis-name", "warning",
        "collective calls must take their axis name from config/mesh "
        "arguments, not string literals — literal names hard-couple a "
        "graph to one mesh layout and belong only under the shard_map "
        "facades"),
    "AST003": (
        "python-rng-in-traced", "error",
        "no Python/numpy RNG inside jit-traced or lax-control-flow "
        "functions — host randomness bakes one draw into the compiled "
        "graph as a constant"),
    "AST004": (
        "hardcoded-block-shape", "error",
        "kernel call sites must not hard-code integer block shapes "
        "(block_n/block_rows) — blocks resolve through "
        "layout.tile_policy() and the autotune cache; TilePolicy "
        "constructors (the defaults themselves) are exempt"),
}


def rule_name(rule_id: str) -> str:
    return RULE_CATALOGUE[rule_id][0]


def rule_severity(rule_id: str) -> str:
    return RULE_CATALOGUE[rule_id][1]


def normalize_rule_ids(ids) -> set[str]:
    """Accept ids ('GC001') or names ('collective-uniformity'), return ids."""
    by_name = {name: rid for rid, (name, _, _) in RULE_CATALOGUE.items()}
    out = set()
    for raw in ids or ():
        for token in str(raw).split(","):
            token = token.strip()
            if not token:
                continue
            rid = by_name.get(token, token.upper())
            if rid not in RULE_CATALOGUE:
                known = ", ".join(sorted(RULE_CATALOGUE))
                raise ValueError(f"unknown lint rule {token!r} "
                                 f"(known: {known})")
            out.add(rid)
    return out


@dataclasses.dataclass
class Finding:
    rule: str                    # "GC001"
    where: str                   # "fit_sharded/while/body" or "file.py:12"
    message: str
    config: str | None = None    # engine-config cell, e.g. "mode=minibatch|…"
    suppressed: bool = False

    @property
    def name(self) -> str:
        return rule_name(self.rule)

    @property
    def severity(self) -> str:
        return rule_severity(self.rule)

    def as_dict(self) -> dict:
        return {
            "rule": self.rule,
            "name": self.name,
            "severity": self.severity,
            "where": self.where,
            "config": self.config,
            "message": self.message,
            "suppressed": self.suppressed,
        }


def apply_suppressions(findings, suppress) -> list[Finding]:
    """Mark findings whose rule id is in ``suppress`` (ids or names)."""
    ids = normalize_rule_ids(suppress)
    for f in findings:
        if f.rule in ids:
            f.suppressed = True
    return list(findings)


@dataclasses.dataclass
class Report:
    findings: list = dataclasses.field(default_factory=list)
    configs: list = dataclasses.field(default_factory=list)
    rules_run: list = dataclasses.field(default_factory=list)

    def extend(self, findings):
        self.findings.extend(findings)

    def active(self) -> list:
        return [f for f in self.findings if not f.suppressed]

    def errors(self) -> list:
        return [f for f in self.active() if f.severity == "error"]

    @property
    def ok(self) -> bool:
        return not self.active()

    def summary(self) -> dict:
        return {
            "checked_configs": len(self.configs),
            "rules_run": sorted(self.rules_run),
            "findings": len(self.findings),
            "suppressed": sum(f.suppressed for f in self.findings),
            "errors": len(self.errors()),
            "ok": self.ok,
        }

    def to_json(self, indent: int = 2) -> str:
        payload = {
            "schema_version": SCHEMA_VERSION,
            "rules": {
                rid: {"name": name, "severity": sev, "description": desc}
                for rid, (name, sev, desc) in sorted(RULE_CATALOGUE.items())
                if rid in self.rules_run or not self.rules_run
            },
            "configs": list(self.configs),
            "findings": [f.as_dict() for f in self.findings],
            "summary": self.summary(),
        }
        return json.dumps(payload, indent=indent, sort_keys=False)

    def to_text(self) -> str:
        lines = []
        for f in self.findings:
            mark = "suppressed" if f.suppressed else f.severity
            cfg = f" [{f.config}]" if f.config else ""
            lines.append(f"{f.rule} {f.name} ({mark}){cfg} {f.where}: "
                         f"{f.message}")
        s = self.summary()
        lines.append(
            f"graph-lint: {s['checked_configs']} config(s), "
            f"{s['findings']} finding(s) "
            f"({s['suppressed']} suppressed, {s['errors']} error(s)) — "
            + ("OK" if self.ok else "FAIL"))
        return "\n".join(lines)
