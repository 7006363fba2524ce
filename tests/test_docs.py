"""Docs health checks (ISSUE 10 / CI `docs-check` job): no dead relative
links in docs/ or the README, and every CLI flag documented in
docs/cli.md exists in the launch module it describes."""
from __future__ import annotations

import os
import re

import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
DOCS = os.path.join(REPO, "docs")

DOC_FILES = sorted(
    [os.path.join(DOCS, f) for f in os.listdir(DOCS) if f.endswith(".md")]
) + [os.path.join(REPO, "README.md")]

_LINK = re.compile(r"\[[^\]]*\]\(([^)\s]+)\)")
_FENCE = re.compile(r"```.*?```", re.S)
_FLAG = re.compile(r"--[a-z][a-z0-9-]*")


def _read(path):
    with open(path) as f:
        return f.read()


def test_docs_tree_exists():
    names = {os.path.basename(p) for p in DOC_FILES}
    assert {"architecture.md", "cli.md", "cost_planning.md",
            "bench_schemas.md", "README.md"} <= names


@pytest.mark.parametrize("path", DOC_FILES,
                         ids=[os.path.relpath(p, REPO) for p in DOC_FILES])
def test_relative_links_resolve(path):
    text = _read(path)
    base = os.path.dirname(path)
    dead = []
    for target in _LINK.findall(text):
        if target.startswith(("http://", "https://", "mailto:", "#")):
            continue
        rel = target.split("#", 1)[0]
        if not rel:
            continue
        if not os.path.exists(os.path.normpath(os.path.join(base, rel))):
            dead.append(target)
    assert not dead, (f"dead relative links in "
                      f"{os.path.relpath(path, REPO)}: {dead}")


def _cli_sections():
    """docs/cli.md split into (section name, body) pairs — one per
    `## <entry point>` heading."""
    text = _read(os.path.join(DOCS, "cli.md"))
    parts = text.split("\n## ")[1:]
    return [(p.split("\n", 1)[0].strip(), p) for p in parts]


def test_cli_doc_covers_every_launch_entry_point():
    documented = {name for name, _ in _cli_sections()}
    launch = os.path.join(REPO, "src", "repro", "launch")
    modules = {f[:-3] for f in os.listdir(launch)
               if f.endswith(".py") and not f.startswith("_")
               and f not in ("mesh.py", "hlo_cost.py", "compile_cache.py",
                             "straggler.py")}  # libs, not CLIs
    missing = modules - documented
    assert not missing, f"launch modules undocumented in cli.md: {missing}"


@pytest.mark.parametrize("name,body", _cli_sections(),
                         ids=[n for n, _ in _cli_sections()])
def test_cli_doc_flags_exist_in_source(name, body):
    src_path = os.path.join(REPO, "src", "repro", "launch", f"{name}.py")
    assert os.path.exists(src_path), \
        f"cli.md section '{name}' has no src/repro/launch/{name}.py"
    src = _read(src_path)
    # fenced example blocks may carry env-var noise (XLA_FLAGS=...); only
    # inline-code flags are claims about the argparse surface
    prose = _FENCE.sub("", body)
    flags = set()
    for code in re.findall(r"`([^`]+)`", prose):
        flags.update(_FLAG.findall(code))
    assert flags, f"cli.md section '{name}' documents no flags"
    ghosts = [f for f in flags if f not in src]
    assert not ghosts, (f"cli.md section '{name}' documents flags missing "
                        f"from {name}.py: {sorted(ghosts)}")


def test_plan_doc_covers_all_plan_flags():
    """The reverse direction for the planner (the PR's tentpole CLI):
    every argparse flag in launch/plan.py must be documented."""
    src = _read(os.path.join(REPO, "src", "repro", "launch", "plan.py"))
    declared = set(re.findall(r"add_argument\(\s*\"(--[a-z-]+)\"", src))
    body = dict(_cli_sections())["plan"]
    documented = set(_FLAG.findall(body))
    undocumented = declared - documented
    assert not undocumented, \
        f"plan flags missing from docs/cli.md: {sorted(undocumented)}"


def test_readme_has_cost_planning_section():
    text = _read(os.path.join(REPO, "README.md"))
    assert "## Cost planning" in text
    assert "repro.launch.plan" in text
    assert "BENCH_plan.json" in text


def test_cost_planning_doc_quotes_paper_numbers():
    text = _read(os.path.join(DOCS, "cost_planning.md"))
    assert "94,687.49" in text          # the paper's US-wide saving (§5.4)
    assert "1169.46" in text            # the one-off training time (Eq. 9)


def test_bench_schema_doc_covers_committed_artifacts():
    from repro.core.planner import bench_files
    text = _read(os.path.join(DOCS, "bench_schemas.md"))
    missing = [b for b in bench_files() if b not in text]
    assert not missing, \
        f"committed BENCH artifacts undocumented in bench_schemas.md: {missing}"
