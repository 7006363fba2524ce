"""The graph-contract linter: every rule class must FIRE on a
deliberately-broken graph and stay quiet on the healthy engine tree.

The broken graphs reproduce the real failure classes the rules encode:
the PR 7 int8-ring deadlock (shard-divergent while trip counts over
collectives), fp64 promotion, analytic-vs-compiled wire-byte drift, and
unhashable static config fields.
"""
import json
import pathlib

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.analysis import ast_rules, engine_contracts, graph_rules
from repro.analysis.report import (Finding, Report, RULE_CATALOGUE,
                                   apply_suppressions)

GOLDEN = pathlib.Path(__file__).parent / "goldens" / "lint_report.json"


@pytest.fixture(scope="module")
def data_mesh():
    if jax.device_count() < 8:
        pytest.skip(f"needs 8 devices, have {jax.device_count()}")
    return jax.make_mesh((8,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))


def _ring_perm(n):
    return [(j, (j + 1) % n) for j in range(n)]


# ------------------------------------------------------------------ GC001

def test_gc001_fires_on_divergent_while_trip_count(data_mesh):
    """A while_loop whose exit reads shard-local data while the body
    ppermutes — the PR 7 deadlock class."""
    def broken(x):
        def body(c):
            s, i = c
            y = jax.lax.ppermute(x, "data", _ring_perm(8))
            return (s + jnp.sum(y), i + 1)
        return jax.lax.while_loop(lambda c: c[0] < 100.0, body,
                                  (jnp.sum(x), jnp.int32(0)))
    fn = jax.shard_map(broken, mesh=data_mesh, in_specs=(P("data"),),
                       out_specs=(P(), P()), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((16, 3), jnp.float32))
    findings = graph_rules.check_collective_uniformity(jaxpr, "broken")
    assert len(findings) == 1 and findings[0].rule == "GC001"
    assert "shard-uniform" in findings[0].message


def test_gc001_fires_on_divergent_cond_branches(data_mesh):
    def broken(x):
        return jax.lax.cond(jax.lax.axis_index("data") < 4,
                            lambda: jax.lax.psum(jnp.sum(x), "data"),
                            lambda: jnp.sum(x))
    fn = jax.shard_map(broken, mesh=data_mesh, in_specs=(P("data"),),
                      out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((16, 3), jnp.float32))
    findings = graph_rules.check_collective_uniformity(jaxpr, "broken")
    assert [f.rule for f in findings] == ["GC001"]
    assert "divergent collective sequences" in findings[0].message


def test_gc001_quiet_on_psum_gated_loop(data_mesh):
    """The engine's shape: collectives in the body, exit driven by the
    psum-reduced value — uniform, no finding."""
    def healthy(x):
        def body(c):
            tot = jax.lax.psum(jnp.sum(x) * 0.5, "data")
            return (tot, c[1] + 1)
        return jax.lax.while_loop(lambda c: (c[0] < 100.0) & (c[1] < 5),
                                  body, (jnp.float32(0), jnp.int32(0)))
    fn = jax.shard_map(healthy, mesh=data_mesh, in_specs=(P("data"),),
                       out_specs=(P(), P()), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((16, 3), jnp.float32))
    assert graph_rules.check_collective_uniformity(jaxpr, "ok") == []


def test_gc001_quiet_on_uniform_predicate_cond(data_mesh):
    """Divergent branch collectives are safe when every shard takes the
    same branch (replicated predicate)."""
    def gated(x, flag):
        return jax.lax.cond(flag > 0,
                            lambda: jax.lax.psum(jnp.sum(x), "data"),
                            lambda: jnp.sum(x))
    fn = jax.shard_map(gated, mesh=data_mesh, in_specs=(P("data"), P()),
                       out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((16, 3), jnp.float32),
                               jnp.int32(1))
    assert graph_rules.check_collective_uniformity(jaxpr, "ok") == []


# ------------------------------------------------------------------ GC002

def test_gc002_fires_on_callback_in_loop():
    def f(x):
        def step(c, xi):
            v = jax.pure_callback(
                lambda a: a, jax.ShapeDtypeStruct((), jnp.float32), xi)
            return c + v, None
        out, _ = jax.lax.scan(step, jnp.float32(0), x)
        return out
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32))
    findings = graph_rules.check_host_transfers(jaxpr, "cb")
    assert findings and all(f.rule == "GC002" for f in findings)


# ------------------------------------------------------------------ GC003

def test_gc003_fires_on_fp64_graph():
    jax.config.update("jax_enable_x64", True)
    try:
        def f(x):
            return jnp.asarray(x, jnp.float64) * 2.0
        jaxpr = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32))
    finally:
        jax.config.update("jax_enable_x64", False)
    findings = graph_rules.check_fp64(jaxpr, "f64")
    assert findings and all(f.rule == "GC003" for f in findings)
    assert "float64" in findings[0].message


# ------------------------------------------------------------------ GC004

def test_gc004_fires_on_low_precision_stop_scalar():
    def f(x):
        def body(c):
            return (c[0] + jnp.bfloat16(1), c[1] + 1)
        return jax.lax.while_loop(lambda c: c[1] < 3, body,
                                  (jnp.bfloat16(0), jnp.int32(0)))
    jaxpr = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32))
    findings = graph_rules.check_stop_stats_precision(jaxpr, "prec")
    assert findings and findings[0].rule == "GC004"
    assert "bfloat16" in findings[0].message


def test_gc004_fires_on_scalar_riding_ring(data_mesh):
    def f(x):
        s = jnp.sum(x)
        return jax.lax.ppermute(s, "data", _ring_perm(8))
    fn = jax.shard_map(f, mesh=data_mesh, in_specs=(P("data"),),
                       out_specs=P(), check_vma=False)
    jaxpr = jax.make_jaxpr(fn)(jnp.zeros((16,), jnp.float32))
    findings = graph_rules.check_stop_stats_precision(jaxpr, "ring")
    assert findings and findings[0].rule == "GC004"
    assert "ring" in findings[0].message


# ------------------------------------------------------------------ GC005

def test_gc005_quiet_on_real_accounting(data_mesh):
    assert engine_contracts.check_wire_bytes(
        data_mesh, algorithms=("kmeans",)) == []


def test_gc005_fires_on_drifted_accounting(data_mesh):
    findings = engine_contracts.check_wire_bytes(
        data_mesh, algorithms=("kmeans",), compressions=("int8_ef",),
        analytic_fn=lambda stats, n, comp: 0)
    assert len(findings) == 1 and findings[0].rule == "GC005"
    assert "drifted" in findings[0].message


# ------------------------------------------------------------------ GC006

def test_gc006_fires_on_unhashable_config_field():
    from repro.core.engine import EngineConfig
    cfg = EngineConfig()
    object.__setattr__(cfg, "decay", [0.1])   # frozen bypass, on purpose
    findings = engine_contracts.check_config_static(cfg)
    assert findings and all(f.rule == "GC006" for f in findings)
    assert any("decay" in f.where for f in findings)


def test_gc006_engine_config_is_static_clean():
    assert engine_contracts.check_config_static() == []


def test_gc006_h_star_sweep_does_not_retrace(data_mesh):
    assert engine_contracts.check_h_star_traced(data_mesh) == []


# ------------------------------------------------------------------ AST

BROKEN_SRC = '''
import jax
import numpy as np

def kmeans_assign(x, centroids, *, block_n=None):
    return x

def sweep(x):
    def body(c, xi):
        return c + xi + np.random.rand(), None
    return jax.lax.scan(body, 0.0, x)

def reduce_local(x):
    return jax.lax.psum(x, "data")

def reduce_waived(x):
    return jax.lax.psum(x, "data")  # repro-lint: disable=AST002
'''


def test_ast_rules_fire_and_suppress():
    findings = ast_rules.check_source(
        BROKEN_SRC, "repro/kernels/kmeans_assign/ops.py")
    rules = sorted({f.rule for f in findings})
    assert rules == ["AST001", "AST002", "AST003"]
    flagged = [f.where for f in findings if f.rule == "AST002"]
    assert len(flagged) == 1          # the waived psum produced no finding
    assert flagged[0].endswith(":14")


BLOCK_SRC = '''
from repro.kernels.kmeans_assign.ops import kmeans_assign
from repro.kernels.layout import TilePolicy

POLICY = TilePolicy(block_rows=512, row_align=8, k_align=8, d_align=128)

def tuned(x, c):
    return kmeans_assign(x, c, block_n=256)

def waived(x, c):
    return kmeans_assign(x, c, block_n=256)  # repro-lint: disable=AST004

def resolved(x, c, bn):
    return kmeans_assign(x, c, block_n=bn)
'''


def test_ast004_flags_hardcoded_block_shapes():
    findings = ast_rules.check_source(BLOCK_SRC, "repro/somewhere.py")
    hits = [f for f in findings if f.rule == "AST004"]
    # the literal fires; the waived call, the variable-resolved call and
    # the TilePolicy constructor (the defaults themselves) stay quiet
    assert len(hits) == 1 and hits[0].where.endswith(":8"), findings
    assert "block_n=256" in hits[0].message


def test_ast001_exempt_without_x_leading_param():
    src = "def chunk_bounds(n, chunks):\n    return n\n"
    assert ast_rules.check_source(
        src, "repro/kernels/kmeans_assign/ops.py") == []


def test_ast_rules_clean_on_tree():
    src_root = pathlib.Path(ast_rules.__file__).resolve().parents[1]
    assert ast_rules.check_paths(src_root) == []


# ----------------------------------------------------------- report/driver

def test_rule_catalogue_covers_all_findings():
    assert set(engine_contracts.GRAPH_RULES) <= set(RULE_CATALOGUE)
    assert {"AST001", "AST002", "AST003", "AST004"} <= set(RULE_CATALOGUE)


def test_suppression_controls_exit_decision():
    report = Report(rules_run=["GC003"])
    report.extend([Finding("GC003", "g", "fp64")])
    assert not report.ok and len(report.errors()) == 1
    apply_suppressions(report.findings, ["GC003"])
    assert report.ok and report.errors() == []
    assert report.findings[0].suppressed      # kept in the report


def _golden_report() -> Report:
    r = Report(rules_run=["GC001", "GC005"],
               configs=["kmeans|mode=full|kernel=0|comp=none|prefetch=0"])
    r.extend([
        Finding("GC001", "fit_sharded/shard_map/while",
                "while_loop exit predicate is not shard-uniform",
                config="kmeans|mode=full|kernel=0|comp=none|prefetch=0"),
        Finding("GC005", "stats_reduction[kmeans]",
                "compiled HLO moves 1792 wire bytes but the account "
                "says 448", config="kmeans|comp=int8_ef"),
    ])
    apply_suppressions(r.findings, ["GC005"])
    return r


def test_json_report_matches_golden_schema():
    """The graph-lint CI artifact's schema, pinned byte-for-byte."""
    got = json.loads(_golden_report().to_json())
    want = json.loads(GOLDEN.read_text())
    assert got == want


def test_quick_matrix_is_clean(data_mesh):
    report = engine_contracts.run_graph_lint(
        mesh=data_mesh, matrix="quick",
        rules=("GC001", "GC002", "GC003", "GC004"),
        include_restarts=False)
    assert report.ok, report.to_text()
    assert len(report.configs) == 8   # 4 cells × 2 algorithms


def test_lint_cli_json_exit_zero(tmp_path, capsys):
    from repro.launch import lint
    out = tmp_path / "report.json"
    rc = lint.main(["--rules", "GC006", "--format", "json",
                    "--out", str(out)])
    assert rc == 0
    payload = json.loads(out.read_text())
    assert payload["schema_version"] == 1
    assert payload["summary"]["ok"] is True


def test_lint_cli_fails_then_suppresses(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text("import jax\n\ndef f(x):\n"
                   "    return jax.lax.psum(x, 'data')\n")
    from repro.launch import lint
    assert lint.main(["--rules", "AST002", "--src", str(tmp_path)]) == 1
    assert lint.main(["--rules", "AST002", "--src", str(tmp_path),
                      "--suppress", "AST002"]) == 0
