"""BENCHMARK.json and the files it names -> one cell, checked before
any node starts.

The harness is driven by data: a configuration is
`configs/<config>.json` (the path BENCHMARK.json gives), a traffic mix
is `traffic/<mix>.json`, a generator kind is `generators/<kind>.py`,
a per-layer metric is `layer_metrics/<metric>.json` naming a reader
`readers/<reader>.py`. A cell whose files are missing, or that names
an unknown metric, fails here.
"""

from __future__ import annotations

import importlib.util
import json
import os
import re

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


class ManifestError(Exception):
    pass


def _load_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except OSError as e:
        raise ManifestError(f"missing file: {path} ({e.strerror})") from None
    except ValueError as e:
        raise ManifestError(f"{path}: not JSON: {e}") from None


def load_module(bench_dir: str, package: str, name: str):
    """generators/<name>.py or readers/<name>.py, by file."""
    if not NAME.match(name):
        raise ManifestError(f"bad {package} name {name!r}")
    path = os.path.join(bench_dir, package, f"{name}.py")
    if not os.path.isfile(path):
        raise ManifestError(f"missing file: {path}")
    spec = importlib.util.spec_from_file_location(f"{package}.{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def check_names(bench: dict) -> None:
    """Names and units within the contract's characters; no duplicates."""
    def name(x, what):
        if not isinstance(x, str) or not NAME.match(x):
            raise ManifestError(f"{what} {x!r}: not a name (letters, digits, "
                                f"_ . - ; at most 64)")

    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        seen = set()
        for e in bench.get(group, []):
            name(e.get("name"), f"{group} name")
            if e["name"] in seen:
                raise ManifestError(f"{group}: {e['name']!r} twice")
            seen.add(e["name"])
    for w in bench["workloads"]:
        name(w.get("config"), "config")
        name(w.get("traffic"), "traffic")
        if w.get("chips") not in (1, 4):
            raise ManifestError(f"{w['name']}: chips must be 1 or 4")
    for c in bench["configs"]:
        for k in c.get("reduced", []):
            name(k, "reduced key")
    for m in bench["end_to_end"] + bench["per_layer"]:
        if not UNIT.match(str(m.get("unit", ""))):
            raise ManifestError(f"{m['name']}: unit {m.get('unit')!r} is not "
                                f"a unit (no spaces; tokens/s, MiB/s, %)")
        if m.get("better") not in ("lower", "higher"):
            raise ManifestError(f"{m['name']}: better must be lower or higher")
        if m.get("source") not in SOURCES:
            raise ManifestError(f"{m['name']}: unknown source {m.get('source')!r}")
    cells = {w["name"] for w in bench["workloads"]}
    e2e = {m["name"]: m for m in bench["end_to_end"]}
    for m in bench["end_to_end"] + bench["per_layer"]:
        for w in m.get("workloads", []):
            if w not in cells:
                raise ManifestError(f"{m['name']}: unknown cell {w!r}")
    for m in bench["per_layer"]:
        if m.get("moves") not in e2e:
            raise ManifestError(f"{m['name']}: moves unknown metric "
                                f"{m.get('moves')!r}")
        # a per-layer metric is reported only where the metric it moves
        # is: said in its own `workloads`, not worked out by the harness
        for c in sorted(cells):
            if _in_cell(m, c) and not _in_cell(e2e[m["moves"]], c):
                raise ManifestError(
                    f"{m['name']}: reported on {c}, where {m['moves']}, "
                    f"which it should move, is not; list its cells under "
                    f"\"workloads\"")


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


class Cell:
    """One entry of `workloads` with everything it names, loaded."""

    def __init__(self, root: str, bench: dict, name: str):
        bench_dir = os.path.join(root, bench["paths"][0])
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise ManifestError(f"unknown workload {name!r}; BENCHMARK.json "
                                f"has {sorted(by_name)}")
        self.entry = by_name[name]
        self.name, self.chips = name, self.entry["chips"]
        cfg = next((c for c in bench["configs"]
                    if c["name"] == self.entry["config"]), None)
        if cfg is None:
            raise ManifestError(f"{name}: unknown config "
                                f"{self.entry['config']!r}")
        self.config = _load_json(os.path.join(root, cfg["file"]))
        self.traffic = _load_json(os.path.join(
            bench_dir, "traffic", f"{self.entry['traffic']}.json"))
        self.generator = load_module(bench_dir, "generators",
                                     str(self.traffic.get("kind")))
        self.end_to_end = [m for m in bench["end_to_end"]
                           if _in_cell(m, name)]
        have = {m["name"] for m in self.end_to_end}
        if "setup_s" not in have or len(have) < 2:
            raise ManifestError(f"{name}: needs setup_s and one more "
                                f"end-to-end metric")
        unmade = have - {"setup_s"} - set(self.generator.PRODUCES)
        if unmade:
            raise ManifestError(
                f"{name}: generator {self.traffic['kind']!r} does not "
                f"produce {sorted(unmade)}")
        self.per_layer = []
        for m in bench["per_layer"]:
            if not _in_cell(m, name):
                continue
            spec = _load_json(os.path.join(bench_dir, "layer_metrics",
                                           f"{m['name']}.json"))
            reader = load_module(bench_dir, "readers", str(spec.get("reader")))
            self.per_layer.append((m, spec.get("params", {}), reader))
        if not self.per_layer:
            raise ManifestError(f"{name}: no per-layer metric")


def load(root: str) -> dict:
    bench = _load_json(os.path.join(root, "BENCHMARK.json"))
    check_names(bench)
    return bench
