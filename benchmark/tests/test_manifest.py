"""BENCHMARK.json and the files it names: names and units within the
contract's characters, every cell's files found, an unknown metric
refused, and a cell loaded from files the harness has never named."""

import copy
import json
import os
import shutil

import pytest
from conftest import BENCH, ROOT

from lib import manifest


@pytest.fixture(scope="module")
def bench():
    return manifest.load(ROOT)


def test_every_cell_loads_with_its_three_files(bench):
    for w in bench["workloads"]:
        cell = manifest.Cell(ROOT, bench, w["name"])
        assert cell.config["name"] == w["config"]
        assert cell.traffic["kind"] and hasattr(cell.generator, "Generator")
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer, "every cell reports a per-layer metric"
        for m, _params, reader in cell.per_layer:
            assert m["moves"] in names  # reported only where what it moves is
            assert callable(reader.read)


def test_the_manifest_keeps_to_the_contract(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= bench["run_seconds"] <= 51
    assert all(w["chips"] == 1 for w in bench["workloads"])
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    for c in bench["configs"]:
        assert c["file"].startswith(bench["paths"][0] + "/")
        assert len(c["source"]) <= 200 and len(c["why"]) <= 200
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert len(w["why"]) <= 200
    used = {w["config"] for w in bench["workloads"]}
    assert used == {c["name"] for c in bench["configs"]}


@pytest.mark.parametrize("mutate, message", [
    (lambda b: b["end_to_end"][0].update(name="put MiBps"), "not a name"),
    (lambda b: b["end_to_end"][0].update(name="put_MiB/s"), "not a name"),
    (lambda b: b["end_to_end"][0].update(unit="MiB per second"), "not a unit"),
    (lambda b: b["per_layer"][0].update(unit="µs"), "not a unit"),
    (lambda b: b["per_layer"][0].update(moves="no_such_metric"), "moves unknown"),
    (lambda b: b["per_layer"][0].update(workloads=["no-such-cell"]), "unknown cell"),
    (lambda b: next(m for m in b["per_layer"]
                    if m["name"] == "s3_self_share").pop("workloads"),
     "reported on ec42-get-degraded, where put_MiBps"),
    (lambda b: next(m for m in b["per_layer"]
                    if m["name"] == "cache_hit_share")["workloads"].append(
                        "rep3-put-mp16"),
     "reported on rep3-put-mp16, where get_MiBps"),
    (lambda b: b["workloads"].append(dict(b["workloads"][0])), "twice"),
    (lambda b: b["workloads"][0].update(chips=2), "chips"),
    (lambda b: b["per_layer"][0].update(source="stopwatch"), "unknown source"),
])
def test_bad_names_units_and_references_are_refused(bench, mutate, message):
    b = copy.deepcopy(bench)
    mutate(b)
    with pytest.raises(manifest.ManifestError, match=message):
        manifest.check_names(b)


def _copy_benchmark(tmp_path, bench):
    root = tmp_path / "checkout"
    shutil.copytree(BENCH, root / "benchmark",
                    ignore=shutil.ignore_patterns("work", "out", ".cache",
                                                  "__pycache__", "tests"))
    return root, copy.deepcopy(bench)


def test_unknown_workload_and_missing_files_fail_before_any_node(tmp_path, bench):
    with pytest.raises(manifest.ManifestError, match="unknown workload"):
        manifest.Cell(ROOT, bench, "no-such-cell")
    root, b = _copy_benchmark(tmp_path, bench)
    b["per_layer"].append({"name": "made_up_metric", "unit": "%",
                           "better": "higher", "source": "program_counter",
                           "layer": "feeder", "moves": "req_p50_ms"})
    with pytest.raises(manifest.ManifestError, match="made_up_metric.json"):
        manifest.Cell(str(root), b, b["workloads"][0]["name"])
    b = copy.deepcopy(bench)
    b["workloads"][0]["traffic"] = "no-such-mix"
    with pytest.raises(manifest.ManifestError, match="no-such-mix.json"):
        manifest.Cell(str(root), b, b["workloads"][0]["name"])
    os.remove(root / "benchmark" / "generators" / "mpu_put.py")
    with pytest.raises(manifest.ManifestError, match="mpu_put.py"):
        manifest.Cell(str(root), bench, bench["workloads"][0]["name"])


def test_a_generator_must_produce_the_cells_metrics(tmp_path, bench):
    root, b = _copy_benchmark(tmp_path, bench)
    # a GET mix in a cell that BENCHMARK.json lists under put_MiBps
    b["workloads"][0]["traffic"] = "get-r16x8-2down"
    with pytest.raises(manifest.ManifestError, match="does not produce"):
        manifest.Cell(str(root), b, b["workloads"][0]["name"])


def test_a_cell_from_files_the_harness_has_never_named(tmp_path, bench):
    """A second configuration, traffic mix, generator kind, per-layer
    metric and reader, each a new file, plus entries in BENCHMARK.json:
    nothing that exists is edited."""
    root, b = _copy_benchmark(tmp_path, bench)
    bd = root / "benchmark"
    cfg = json.loads((bd / "configs" / "rep3-3n.json").read_text())
    cfg["name"] = "rep2-2n"
    (bd / "configs" / "rep2-2n.json").write_text(json.dumps(cfg))
    (bd / "traffic" / "stat-x4.json").write_text(json.dumps({
        "kind": "head_stat", "loop": "closed",
        "primary": {"method": "HEAD"}, "params": {"clients": 4}}))
    (bd / "generators" / "head_stat.py").write_text(
        'PRODUCES = ("req_p50_ms",)\n\n\nclass Generator:\n'
        '    def __init__(self, env):\n        self.env = env\n')
    (bd / "layer_metrics" / "table_get_ms.json").write_text(json.dumps({
        "reader": "span_mean_ms", "params": {"span": "table.get"}}))
    (bd / "readers" / "span_mean_ms.py").write_text(
        "def read(params, ctx):\n"
        "    d = [s['dur_us'] for s in ctx.window_spans()"
        " if s['name'] == params['span']]\n"
        "    return sum(d) / len(d) / 1e3 if d else None\n")
    b["configs"].append({"name": "rep2-2n", "source": "made up for a test",
                         "file": "benchmark/configs/rep2-2n.json",
                         "reduced": [], "why": "test"})
    b["workloads"].append({"name": "rep2-stat", "config": "rep2-2n",
                           "traffic": "stat-x4", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "table_get_ms", "unit": "ms",
                           "better": "lower", "source": "program_span",
                           "layer": "store", "moves": "req_p50_ms",
                           "workloads": ["rep2-stat"]})
    manifest.check_names(b)
    cell = manifest.Cell(str(root), b, "rep2-stat")
    assert cell.config["name"] == "rep2-2n"
    assert cell.generator.PRODUCES == ("req_p50_ms",)
    assert {m["name"] for m in cell.end_to_end} == {"req_p50_ms", "setup_s"}
    mine = [(m, p, r) for m, p, r in cell.per_layer
            if m["name"] == "table_get_ms"]
    assert len(mine) == 1

    class Ctx:
        def window_spans(self):
            return [{"name": "table.get", "dur_us": 1000},
                    {"name": "table.get", "dur_us": 3000},
                    {"name": "rpc.call", "dur_us": 9}]

    m, params, reader = mine[0]
    assert reader.read(params, Ctx()) == 2.0
    # and the cells that were there load as before, without the new metric
    old = manifest.Cell(str(root), b, bench["workloads"][0]["name"])
    assert "table_get_ms" not in {m["name"] for m, _, _ in old.per_layer}
