import argparse
import os
import pathlib
import sys


def _parse_args(argv=None):
    ap = argparse.ArgumentParser(
        prog="repro.launch.lint",
        description="Graph-contract linter: statically verify collective, "
                    "dtype, transfer and recompile invariants across every "
                    "engine configuration (rules GC001-GC006), plus the "
                    "repo's AST-level source contracts (AST001-AST004).")
    ap.add_argument("--rules", default=None,
                    help="comma-separated rule ids or names to run "
                         "(default: all); e.g. GC001,GC005 or "
                         "collective-uniformity")
    ap.add_argument("--suppress", default=None,
                    help="comma-separated rule ids/names to run but not "
                         "fail on (kept in the report, suppressed=true)")
    ap.add_argument("--config-matrix", choices=("quick", "full"),
                    default="full", dest="matrix",
                    help="engine config matrix to trace: full = all 16 "
                         "(mode x kernel x compression x prefetch) cells, "
                         "quick = a 4-cell diagonal covering each option")
    ap.add_argument("--format", choices=("text", "json"), default="text")
    ap.add_argument("--out", default=None,
                    help="also write the report (in --format) to this file")
    ap.add_argument("--devices", type=int, default=8,
                    help="XLA host device count for the lint substrate "
                         "(default 8; set before jax initialises)")
    ap.add_argument("--src", default=None,
                    help="source root for the AST rules (default: the "
                         "installed repro package directory)")
    ap.add_argument("--no-restarts", action="store_true",
                    help="trace only fit_sharded, not fit_restarts_sharded "
                         "(halves lint time)")
    return ap.parse_args(argv)


def _split(csv):
    return [t for t in (csv or "").split(",") if t.strip()]


def main(argv=None) -> int:
    args = _parse_args(argv)

    # device count must be pinned before jax initialises the backend
    flag = f"--xla_force_host_platform_device_count={args.devices}"
    if "xla_force_host_platform_device_count" not in \
            os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = \
            (os.environ.get("XLA_FLAGS", "") + " " + flag).strip()

    from repro.analysis import ast_rules, engine_contracts
    from repro.analysis.report import apply_suppressions, normalize_rule_ids

    rules = sorted(normalize_rule_ids(_split(args.rules))) if args.rules \
        else sorted(engine_contracts.GRAPH_RULES) + \
        ["AST001", "AST002", "AST003", "AST004"]

    graph_rules = [r for r in rules if r.startswith("GC")]
    report = engine_contracts.run_graph_lint(
        matrix=args.matrix, rules=graph_rules,
        include_restarts=not args.no_restarts)
    report.rules_run = list(rules)

    if any(r.startswith("AST") for r in rules):
        src = pathlib.Path(args.src) if args.src else \
            pathlib.Path(ast_rules.__file__).resolve().parents[1]
        report.extend([f for f in ast_rules.check_paths(src)
                       if f.rule in rules])

    apply_suppressions(report.findings, _split(args.suppress))

    rendered = report.to_json() if args.format == "json" \
        else report.to_text()
    print(rendered)
    if args.out:
        pathlib.Path(args.out).write_text(rendered + "\n")
    # any unsuppressed finding fails the gate — warnings included; waiving
    # is always an explicit --suppress
    return 0 if report.ok else 1


if __name__ == "__main__":
    sys.exit(main())
