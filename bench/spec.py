"""Everything the harness runs, found by name from ``BENCHMARK.json``.

A cell names a configuration and a traffic mix.  The configuration's file
is the one ``BENCHMARK.json`` gives it; its data set ``<g>`` (its
``data.generator``) is made by ``generators/<g>.py`` and its plain
reference ``<r>`` (its ``reference``) replays a job in
``references/<r>.py``; a traffic mix ``<t>`` is the data file
``traffic/<t>.json`` that the one job generator (``traffic.py``) reads; a
per-layer metric ``<m>`` is read by ``metrics/<m>.py``; the peaks of a
device kind are an entry of ``peaks.json``.  Adding any of them is adding
files and entries, with no edit to this module or to the harness.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent


def quantity(metric: str) -> str:
    """What a metric's name measures: ``fit_s.small`` is ``fit_s``, split
    off for the cells that hold it to a bound of its own."""
    return metric.split(".", 1)[0]


class Spec:
    """``BENCHMARK.json`` and the files its entries name."""

    def __init__(self, root: Path = ROOT, bench: Path = BENCH):
        self.root = Path(root)
        self.bench = Path(bench)
        self.doc = json.loads((self.root / "BENCHMARK.json").read_text())

    def _entry(self, key: str, name: str) -> dict:
        for e in self.doc[key]:
            if e["name"] == name:
                return e
        raise KeyError(f"no {key} entry named {name!r} in BENCHMARK.json")

    def cell(self, name: str) -> dict:
        return self._entry("workloads", name)

    def config(self, name: str) -> dict:
        entry = self._entry("configs", name)
        return json.loads((self.root / entry["file"]).read_text())

    def traffic(self, name: str) -> dict:
        return json.loads((self.bench / "traffic" / f"{name}.json")
                          .read_text())

    def metrics(self, cell: str, section: str) -> list[dict]:
        """The ``end_to_end`` or ``per_layer`` metrics the cell reports."""
        return [m for m in self.doc[section]
                if cell in m.get("workloads", [cell])]

    def _module(self, kind: str, name: str):
        """The module ``<kind>/<name>.py`` under the benchmark's directory."""
        path = self.bench / kind / f"{name}.py"
        if not path.is_file():
            raise KeyError(f"no {kind} file {path}")
        mod_spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_{name}".replace("-", "_").replace(".", "_"), path)
        mod = importlib.util.module_from_spec(mod_spec)
        mod_spec.loader.exec_module(mod)
        return mod

    def reader(self, metric: str):
        """``read(ctx)`` of ``metrics/<metric>.py``; a split name
        ``<m>.<part>`` without a file of its own is read by ``<m>``'s."""
        if not (self.bench / "metrics" / f"{metric}.py").is_file():
            metric = quantity(metric)
        return self._module("metrics", metric).read

    def generator(self, name: str):
        """``make(data, seed)`` of ``generators/<name>.py``: one [N, D]
        float32 data set of a configuration's ``data`` block."""
        return self._module("generators", name).make

    def reference(self, name: str):
        """``replay(x, init_seed, config, dist)`` of ``references/<name>.py``:
        the plain (early, full) fits of one job."""
        return self._module("references", name).replay

    def peak(self, device_kind: str) -> dict:
        table = json.loads((self.bench / "peaks.json").read_text())
        if device_kind not in table:
            raise KeyError(f"no peaks for device kind {device_kind!r} in "
                           "peaks.json; add its published numbers")
        return table[device_kind]
