"""BENCHMARK.json and the data files it names: loading, look-up, refusal.

The manifest is the contract with the driver; the harness is driven by it and
by the files found through it, never by a name written in code:

* a cell (``workloads`` entry) names a configuration and a traffic mix;
* ``<path>/configs/<config>.json`` holds the configuration as it is run;
* ``<path>/traffic/<traffic>.json`` holds every number of the traffic mix and its
  ``kind``; ``<path>/kinds/<kind>.py`` drives that kind of job;
* ``<path>/e2e_metrics/<metric>.json`` and ``<path>/layer_metrics/<metric>.json``
  each name the metric's reader in ``<path>/readers/`` and its arguments; the
  manifest's entry says which cells the metric exists in.

Look-ups go through ``roots`` in order, so a test (or a later PR) can add a
cell, a configuration, a kind or a metric as files in another directory
without touching a file that exists.
"""

from __future__ import annotations

import importlib
import importlib.util
import json
import os
import re
from typing import Any, Dict, List, Optional

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(HERE)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = ("device_trace", "program_span", "program_counter", "host_clock")
FOLDERS = {"end_to_end": "e2e_metrics", "per_layer": "layer_metrics"}


class ManifestError(ValueError):
    """BENCHMARK.json, or a file it names, is outside the contract."""


def check_name(value: Any, what: str) -> str:
    if not isinstance(value, str) or not NAME.match(value):
        raise ManifestError(
            f"{what} {value!r}: a name is 1-64 of A-Z a-z 0-9 _ . - and "
            "does not start with . or -")
    return value


def check_unit(value: Any, what: str) -> str:
    if not isinstance(value, str) or not UNIT.match(value):
        raise ManifestError(
            f"{what} {value!r}: a unit is 1-16 of A-Z a-z 0-9 _ / % . -")
    return value


def check_line(value: Any, what: str) -> str:
    if (not isinstance(value, str) or not 1 <= len(value) <= 200
            or "\n" in value or "\t" in value or "\r" in value):
        raise ManifestError(f"{what}: 1-200 characters on one line, no tab")
    return value


def _check_metric(m: Dict[str, Any], per_layer: bool, cells: set) -> None:
    check_name(m.get("name"), "metric name")
    check_unit(m.get("unit"), f"unit of {m['name']}")
    if m.get("better") not in ("lower", "higher"):
        raise ManifestError(f"{m['name']}: better is 'lower' or 'higher'")
    if m.get("source") not in SOURCES:
        raise ManifestError(f"{m['name']}: source is one of {SOURCES}")
    allowed = {"name", "unit", "better", "source", "workloads"}
    if per_layer:
        allowed |= {"layer", "moves"}
        check_line(m.get("layer"), f"layer of {m['name']}")
        check_name(m.get("moves"), f"moves of {m['name']}")
    else:
        allowed |= {"bound"}
        if m["source"] not in ("host_clock", "device_trace"):
            raise ManifestError(
                f"{m['name']}: an end-to-end metric is taken by the "
                "benchmark itself (host_clock or device_trace)")
        bound = m.get("bound")
        if not isinstance(bound, (int, float)) or not 0.01 <= bound <= 0.1:
            raise ManifestError(f"{m['name']}: bound in [0.01, 0.1]")
    extra = set(m) - allowed
    if extra:
        raise ManifestError(f"{m['name']}: unknown keys {sorted(extra)}")
    for w in m.get("workloads", []):
        if w not in cells:
            raise ManifestError(f"{m['name']}: no cell named {w!r}")


def validate(doc: Dict[str, Any]) -> None:
    """Refuse what the driver would refuse, as far as a file can show it."""
    keys = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
    if set(doc) != keys:
        raise ManifestError(f"keys must be exactly {sorted(keys)}")
    if not (isinstance(doc["run_seconds"], int)
            and 1 <= doc["run_seconds"] <= 51):
        raise ManifestError("run_seconds: a whole number from 1 to 51")
    if not 1 <= len(doc["paths"]) <= 16:
        raise ManifestError("paths: 1 to 16 directories")
    for p in doc["paths"]:
        if (not re.match(r"^[A-Za-z0-9_.\-/]{1,200}$", p) or p.startswith("/")
                or ".." in p.split("/")):
            raise ManifestError(f"path {p!r}")
    if not 1 <= len(doc["command"]) <= 32:
        raise ManifestError("command: 1 to 32 strings")
    for word in doc["command"]:
        check_line(word, "command word")
    configs = {}
    for c in doc["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            raise ManifestError(f"config keys: {sorted(c)}")
        check_name(c["name"], "config name")
        check_line(c["source"], "config source")
        check_line(c["why"], "config why")
        if not any(c["file"].startswith(p.rstrip("/") + "/")
                   for p in doc["paths"]):
            raise ManifestError(f"{c['file']} is not under paths")
        if len(c["reduced"]) > 16:
            raise ManifestError("reduced: at most 16 keys")
        for k in c["reduced"]:
            check_name(k, "reduced key")
        if c["name"] in configs:
            raise ManifestError(f"two configs named {c['name']}")
        configs[c["name"]] = c
    if not 1 <= len(configs) <= 24:
        raise ManifestError("1 to 24 configs")
    cells, pairs = set(), set()
    for w in doc["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            raise ManifestError(f"workload keys: {sorted(w)}")
        check_name(w["name"], "cell name")
        check_name(w["traffic"], "traffic name")
        check_line(w["why"], f"why of {w['name']}")
        if w["config"] not in configs:
            raise ManifestError(f"{w['name']}: no config {w['config']!r}")
        if w["chips"] not in (1, 4):
            raise ManifestError(f"{w['name']}: chips is 1 or 4")
        if w["name"] in cells or (w["config"], w["traffic"]) in pairs:
            raise ManifestError(f"cell {w['name']} appears twice")
        cells.add(w["name"])
        pairs.add((w["config"], w["traffic"]))
    if not 2 <= len(cells) <= 24:
        raise ManifestError("2 to 24 cells")
    four = sum(1 for w in doc["workloads"] if w["chips"] == 4)
    if four > max(1, len(cells) // 4):
        raise ManifestError("too many four-chip cells")
    unused = set(configs) - {w["config"] for w in doc["workloads"]}
    if unused:
        raise ManifestError(f"configs no cell uses: {sorted(unused)}")
    names = set()
    for group, per_layer in (("end_to_end", False), ("per_layer", True)):
        for m in doc[group]:
            _check_metric(m, per_layer, cells)
            if m["name"] in names:
                raise ManifestError(f"two metrics named {m['name']}")
            names.add(m["name"])
    e2e = {m["name"] for m in doc["end_to_end"]}
    if "setup_s" not in e2e:
        raise ManifestError("end_to_end must hold setup_s")
    for m in doc["per_layer"]:
        if m["moves"] not in e2e:
            raise ManifestError(f"{m['name']} moves no end-to-end metric")


class Benchmark:
    """The manifest plus look-up of everything a cell needs, by name."""

    def __init__(self, root: str = REPO):
        self.root = root
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            self.doc = json.load(f)
        validate(self.doc)
        # directories searched for data and code files, first hit wins
        self.dirs = [os.path.join(root, p) for p in self.doc["paths"]]
        if HERE not in self.dirs:
            self.dirs.append(HERE)

    # -- cells --------------------------------------------------------------
    def cell(self, name: str) -> Dict[str, Any]:
        for w in self.doc["workloads"]:
            if w["name"] == name:
                return w
        raise ManifestError(
            f"no cell {name!r}; cells: "
            f"{[w['name'] for w in self.doc['workloads']]}")

    def find(self, *parts: str) -> Optional[str]:
        for d in self.dirs:
            path = os.path.join(d, *parts)
            if os.path.exists(path):
                return path
        return None

    def load_json(self, *parts: str) -> Dict[str, Any]:
        path = self.find(*parts)
        if path is None:
            raise ManifestError(f"no file {os.path.join(*parts)} under "
                                f"{self.dirs}")
        with open(path) as f:
            return json.load(f)

    def config(self, name: str) -> Dict[str, Any]:
        for c in self.doc["configs"]:
            if c["name"] == name:
                path = os.path.join(self.root, c["file"])
                if not os.path.exists(path):
                    path = self.find("configs", os.path.basename(c["file"]))
                with open(path) as f:
                    return json.load(f)
        raise ManifestError(f"no config {name!r}")

    def traffic(self, cell: Dict[str, Any]) -> Dict[str, Any]:
        return self.load_json("traffic", cell["traffic"] + ".json")

    def module(self, subdir: str, name: str):
        """``<subdir>/<name>.py`` from the first directory that has it; the
        benchmark's own are imported as ``benchmark.<subdir>.<name>``."""
        check_name(name, f"{subdir} module")
        path = self.find(subdir, name + ".py")
        if path is None:
            raise ManifestError(f"no {subdir}/{name}.py")
        if os.path.dirname(os.path.dirname(path)) == HERE:
            return importlib.import_module(f"benchmark.{subdir}.{name}")
        spec = importlib.util.spec_from_file_location(
            f"benchmark_extra_{subdir}_{name}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod

    # -- metrics ------------------------------------------------------------
    def metrics(self, group: str, cell_name: str) -> List[Dict[str, Any]]:
        """The manifest's metrics of ``group`` (``end_to_end`` or
        ``per_layer``) that exist in the cell — no ``workloads`` key: every
        cell — each joined with its file ``<group's folder>/<name>.json``,
        which names the reader and its arguments.  Which cells a metric
        exists in is the manifest's to say: a later PR extends a list there
        and edits no file."""
        out = []
        for m in self.doc[group]:
            if "workloads" in m and cell_name not in m["workloads"]:
                continue
            how = self.load_json(FOLDERS[group], m["name"] + ".json")
            check_name(how.get("reader"), f"reader of {m['name']}")
            out.append({**m, "reader": how["reader"],
                        "args": how.get("args", {})})
        return out
