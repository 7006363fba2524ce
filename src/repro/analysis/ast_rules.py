"""Repo-specific source lint (AST level) — AST001/AST002/AST003/AST004.

These are contracts the graph passes can't see (they hold at the source
layer, before tracing):

  AST001  kernel entry points in ``kernels/*/ops.py`` whose first
          parameter is the points array ``x`` must accept ``mask=`` —
          padding, sharding and minibatch draws all compose through the
          mask operand, on every backend (helpers such as
          ``chunk_bounds(n, chunks)`` lead with no ``x`` and are exempt);
  AST002  collective calls must not hard-code axis names as string
          literals — graphs take the axis from config/mesh so one
          program serves every mesh layout (warning severity: literal
          names are legitimate directly under the shard_map facades);
  AST003  no Python/numpy RNG calls inside traced functions (decorated
          with jit, passed to lax control flow / shard_map / vmap, or
          nested in one) — host randomness bakes ONE draw into the
          compiled graph as a constant;
  AST004  no hard-coded integer block shapes (``block_n=256`` and
          friends) at kernel call sites — block resolution belongs to
          ``layout.tile_policy()`` / the autotune cache, and a literal
          at the call site silently bypasses both (plus the Triton
          power-of-two constraint).  ``TilePolicy(...)`` constructor
          calls are exempt: they ARE the hand-picked defaults.

Any finding can be waived at the flagged line (or the line above) with
``# repro-lint: disable=AST002`` (comma-separated ids, or a bare
``disable`` to waive every rule on that line).
"""
from __future__ import annotations

import ast
import pathlib
import re

from repro.analysis.report import Finding

COLLECTIVE_FNS = frozenset({
    "psum", "pmax", "pmin", "pmean", "ppermute", "pbroadcast",
    "all_gather", "all_to_all", "reduce_scatter", "psum_scatter",
    "axis_index"})
_TRACING_FNS = frozenset({
    "scan", "while_loop", "fori_loop", "cond", "switch", "map",
    "associated_scan", "shard_map", "vmap", "pmap", "jit", "grad",
    "value_and_grad", "checkpoint", "remat", "custom_jvp", "custom_vjp"})
_SUPPRESS_RE = re.compile(r"#\s*repro-lint:\s*disable(?:=([\w,\s]+))?")


def _suppressed(lines: list[str], lineno: int, rule: str) -> bool:
    for ln in (lineno, lineno - 1):
        if 1 <= ln <= len(lines):
            m = _SUPPRESS_RE.search(lines[ln - 1])
            if m:
                ids = m.group(1)
                if ids is None or rule in {t.strip() for t in ids.split(",")}:
                    return True
    return False


def _fn_name(call: ast.Call) -> str:
    f = call.func
    if isinstance(f, ast.Attribute):
        return f.attr
    if isinstance(f, ast.Name):
        return f.id
    return ""


def _has_str_literal(node: ast.AST) -> bool:
    if isinstance(node, ast.Constant):
        return isinstance(node.value, str)
    if isinstance(node, (ast.Tuple, ast.List)):
        return any(_has_str_literal(e) for e in node.elts)
    return False


# ------------------------------------------------------------------ AST001

def _check_kernel_mask(tree: ast.Module, relpath: str,
                       lines: list[str]) -> list[Finding]:
    findings = []
    for node in tree.body:
        if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            continue
        if node.name.startswith("_"):
            continue
        args = node.args
        if not args.args or args.args[0].arg != "x":
            continue
        names = {a.arg for a in list(args.args) + list(args.kwonlyargs)}
        if "mask" not in names and \
                not _suppressed(lines, node.lineno, "AST001"):
            findings.append(Finding(
                "AST001", f"{relpath}:{node.lineno}",
                f"kernel entry point '{node.name}' takes the points array "
                "but has no mask= parameter — padding/sharding/minibatch "
                "composition requires the mask operand"))
    return findings


# ------------------------------------------------------------------ AST002

def _check_axis_literals(tree: ast.Module, relpath: str,
                         lines: list[str]) -> list[Finding]:
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or \
                _fn_name(node) not in COLLECTIVE_FNS:
            continue
        literal = any(_has_str_literal(a) for a in node.args) or any(
            kw.arg in ("axis_name", "axes") and _has_str_literal(kw.value)
            for kw in node.keywords)
        if literal and not _suppressed(lines, node.lineno, "AST002"):
            findings.append(Finding(
                "AST002", f"{relpath}:{node.lineno}",
                f"collective '{_fn_name(node)}' hard-codes its axis name "
                "as a string literal — take it from config/mesh "
                "(cfg.axis_name) so the graph serves every mesh layout"))
    return findings


# ------------------------------------------------------------------ AST003

_RNG_MODULES = ("random", "np.random", "numpy.random")


def _dotted(node: ast.AST) -> str:
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


def _traced_functions(tree: ast.Module) -> set[ast.AST]:
    """Function nodes that end up inside a traced graph: jit-decorated,
    passed (by name or as a lambda) to lax control flow / shard_map /
    vmap, or nested inside one of those."""
    by_name: dict[str, list[ast.AST]] = {}
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            by_name.setdefault(node.name, []).append(node)

    traced: set[ast.AST] = set()
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            for dec in node.decorator_list:
                names = {n.attr for n in ast.walk(dec)
                         if isinstance(n, ast.Attribute)}
                names |= {n.id for n in ast.walk(dec)
                          if isinstance(n, ast.Name)}
                if "jit" in names:
                    traced.add(node)
        elif isinstance(node, ast.Call) and _fn_name(node) in _TRACING_FNS:
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if isinstance(arg, ast.Lambda):
                    traced.add(arg)
                elif isinstance(arg, ast.Name):
                    traced.update(by_name.get(arg.id, ()))

    # closure: defs nested inside a traced function are traced
    grew = True
    while grew:
        grew = False
        for fn in list(traced):
            for sub in ast.walk(fn):
                if sub is not fn and isinstance(
                        sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda)) and sub not in traced:
                    traced.add(sub)
                    grew = True
    return traced


def _check_rng_in_traced(tree: ast.Module, relpath: str,
                         lines: list[str]) -> list[Finding]:
    findings = []
    seen_lines: set[int] = set()
    for fn in _traced_functions(tree):
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            dotted = _dotted(node.func)
            hit = any(dotted.startswith(mod + ".") for mod in _RNG_MODULES)
            if hit and node.lineno not in seen_lines and \
                    not _suppressed(lines, node.lineno, "AST003"):
                seen_lines.add(node.lineno)
                name = getattr(fn, "name", "<lambda>")
                findings.append(Finding(
                    "AST003", f"{relpath}:{node.lineno}",
                    f"'{dotted}' call inside traced function '{name}' — "
                    "host RNG runs once at trace time and bakes a single "
                    "draw into the compiled graph; use jax.random with a "
                    "threaded key"))
    return findings


# ------------------------------------------------------------------ AST004

_BLOCK_KWARGS = frozenset({"block_n", "block_rows"})


def _check_block_literals(tree: ast.Module, relpath: str,
                          lines: list[str]) -> list[Finding]:
    findings = []
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call) or _fn_name(node) == "TilePolicy":
            continue
        for kw in node.keywords:
            if kw.arg in _BLOCK_KWARGS and \
                    isinstance(kw.value, ast.Constant) and \
                    isinstance(kw.value.value, int) and \
                    not isinstance(kw.value.value, bool) and \
                    not _suppressed(lines, kw.value.lineno, "AST004"):
                findings.append(Finding(
                    "AST004", f"{relpath}:{kw.value.lineno}",
                    f"'{_fn_name(node)}' call hard-codes {kw.arg}="
                    f"{kw.value.value} — block shapes resolve through "
                    "layout.tile_policy() / the autotune cache; a literal "
                    "here bypasses backend alignment (incl. the Triton "
                    "power-of-two rule) and pins every backend to one "
                    "shape"))
    return findings


# ------------------------------------------------------------------ driver

def check_source(source: str, relpath: str) -> list[Finding]:
    try:
        tree = ast.parse(source)
    except SyntaxError as e:
        return [Finding("AST001", f"{relpath}:{e.lineno or 0}",
                        f"unparseable source: {e.msg}")]
    lines = source.splitlines()
    findings = []
    parts = pathlib.PurePath(relpath).parts
    if "kernels" in parts and parts[-1] == "ops.py":
        findings += _check_kernel_mask(tree, relpath, lines)
    findings += _check_axis_literals(tree, relpath, lines)
    findings += _check_rng_in_traced(tree, relpath, lines)
    findings += _check_block_literals(tree, relpath, lines)
    return findings


def check_paths(root, paths=None) -> list[Finding]:
    """Run the AST rules over ``paths`` (default: every ``*.py`` under
    ``root``), reporting locations relative to ``root``'s parent."""
    root = pathlib.Path(root)
    files = sorted(root.rglob("*.py")) if paths is None \
        else [pathlib.Path(p) for p in paths]
    findings = []
    for f in files:
        try:
            rel = f.relative_to(root.parent)
        except ValueError:
            rel = f
        findings += check_source(f.read_text(), str(rel))
    return findings
