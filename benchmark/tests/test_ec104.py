"""The cell `ec104-put-mp16` (PR 28): erasure(10,4) on 14 holders in 4
zones. The manifest finds the cell's three files, the roofline
functions take the geometry from the configuration, the two per-layer
metrics PR 28 brought are data files for `metrics_delta` that give, on
two scrapes written out here, the value worked out by hand, and
`--rehearse` runs the cell's whole control flow on the CPU to a
`correct` last line."""

import json
import os
import subprocess
import sys

import pytest
from conftest import ROOT

from lib import manifest, roofline, scrape

CELL = "ec104-put-mp16"
PUT_CELLS = ("ec42-put-mp16", "ec42-put-1stream", "rep3-put-mp16", CELL)
SECONDS = 20.0

# node 1's /metrics as the program renders the new series: a timer is
# <name>_count/_sum/_max per label set; a deployment has one mode
SCRAPE0 = """\
# TYPE block_write_seconds_count counter
block_write_seconds_count{mode="erasure"} 1000
block_write_seconds_sum{mode="erasure"} 90.000000
block_write_seconds_max{mode="erasure"} 0.400000
s3_ingest_buf_wait_count 300
s3_ingest_buf_wait_sum 300.000000
s3_ingest_wait_seconds_count 1040
s3_ingest_wait_seconds_sum 60.000000
s3_ingest_wait_seconds_max 0.900000
"""

SCRAPE1 = """\
block_write_seconds_count{mode="erasure"} 1800
block_write_seconds_sum{mode="erasure"} 190.000000
block_write_seconds_max{mode="erasure"} 0.500000
s3_ingest_buf_wait_count 700
s3_ingest_buf_wait_sum 700.000000
s3_ingest_wait_seconds_count 1840
s3_ingest_wait_seconds_sum 180.000000
s3_ingest_wait_seconds_max 0.900000
"""

# metric -> (cells that list it, what it moves, value by hand)
WANT = {
    "write_fanout_ms": (PUT_CELLS, "req_p50_ms", 1000.0 * 100.0 / 800),
    # replicate-3 has no ingest pool
    "ingest_wait_ms": (("ec42-put-mp16", "ec42-put-1stream", CELL),
                       "put_MiBps", 1000.0 * 120.0 / 800),
}


class Ctx:
    primary_method = "PUT"

    def __init__(self, m0, m1):
        self.m0, self.m1 = m0, m1

    def scrapes(self, over):
        return self.m0, self.m1, SECONDS


@pytest.fixture(scope="module")
def bench():
    return manifest.load(ROOT)


@pytest.fixture(scope="module")
def pair():
    return scrape.parse_metrics(SCRAPE0), scrape.parse_metrics(SCRAPE1)


def test_the_cell_loads_with_its_three_files(bench):
    cell = manifest.Cell(ROOT, bench, CELL)
    assert cell.chips == 1 and cell.entry["traffic"] == "put-mp16x8"
    cfg = cell.config
    assert cfg["name"] == "ec104-14n" and cfg["nodes"] == 14
    assert cfg["toml"]["erasure_coding"] == "10,4"
    assert cfg["toml"]["block_size"] == 1048576
    assert len(cfg["zones"]) == 14 and len(set(cfg["zones"])) == 4
    assert cfg["guarantees"]["readback"]["then_kill"] == [2, 6, 10, 14]
    assert {cfg["zones"][i - 1] for i in (2, 6, 10, 14)} == {"z2"}
    assert cell.traffic["kind"] == "mpu_put"
    assert cell.traffic["params"]["part_bytes"] == 16777216
    assert {m["name"] for m in cell.end_to_end} == {
        "put_MiBps", "req_p50_ms", "setup_s"}
    mine = {m["name"] for m, _p, _r in cell.per_layer}
    assert {"write_fanout_ms", "ingest_wait_ms", "pad_share",
            "s3_self_share", "rs_encode_roofline", "blake3_roofline",
            "device_idle_share", "compiles_in_window"} <= mine
    assert "rs_decode_roofline" not in mine
    entry = next(c for c in bench["configs"] if c["name"] == "ec104-14n")
    assert entry["reduced"] == ["zone_latency", "hosts", "object_bytes"]
    assert set(entry["reduced"]) == set(cfg["reduced"])
    # a toy block for --rehearse still splits ten ways with a tail
    assert (cfg["rehearse"]["toml"]["block_size"] + 1) % 10


def test_rs_encode_10_4_counts_the_algorithm_and_hbm_binds():
    block = 1048576
    assert roofline.shard_len(block, 10) == 104858
    nbytes, ops = roofline.rs_encode(block, 10, 4)
    assert nbytes == 14 * 104858
    assert ops == 2 * 32 * 80 * 104858
    t, roof = roofline.least_seconds("rs_encode", 1, block, 10, 4,
                                     "TPU v5 lite")
    assert roof == "hbm" and t == pytest.approx(14 * 104858 / 819e9)
    # int8 would need 2*32*80*104858 / 393e12 = 1.37 us against 1.79 us
    assert ops / 393e12 < t


@pytest.mark.parametrize("metric", sorted(WANT))
def test_value_by_hand_in_every_cell_that_lists_it(bench, pair, metric):
    cells, moves, want = WANT[metric]
    for w in bench["workloads"]:
        cell = manifest.Cell(ROOT, bench, w["name"])
        found = [(m, p, r) for m, p, r in cell.per_layer
                 if m["name"] == metric]
        if w["name"] not in cells:
            assert not found, f"{metric} is not {w['name']}'s"
            continue
        (m, params, reader), = found
        assert m["source"] == "program_counter" and m["better"] == "lower"
        assert m["moves"] == moves and m["unit"] == "ms"
        assert reader.__name__ == "readers.metrics_delta"
        assert reader.read(params, Ctx(*pair)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_an_absent_series_is_none_not_zero(bench, pair, metric):
    """The parent commit exports neither series: the metric is left out
    of the line, it is not 0; and a still series is no mean either."""
    cell = manifest.Cell(ROOT, bench, CELL)
    (_m, params, reader), = [x for x in cell.per_layer
                             if x[0]["name"] == metric]
    mine = {t["series"] for side in ("num", "den") for t in params[side]}
    gone = tuple({k: v for k, v in m.items() if k[0] not in mine}
                 for m in pair)
    assert reader.read(params, Ctx(*gone)) is None
    assert reader.read(params, Ctx(pair[1], pair[1])) is None


@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_runs_to_a_correct_last_line(trace):
    """Fourteen real server processes at toy sizes on the CPU (about
    40 s): every acknowledged part correct, the sample read back through
    nodes 1 and 3 and again with zone z2 SIGKILLed, and with the trace
    on the two new metrics on the line."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "GARAGE_TPU_DEVICE", "XLA_FLAGS")}
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", str(2_800_000_100 + trace),
         "--seconds", "6", "--trace", str(trace), "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    if trace:
        assert {"write_fanout_ms", "ingest_wait_ms", "pad_share",
                "device_idle_share"} <= set(line["metrics"])
        assert line["metrics"]["write_fanout_ms"]["value"] > 0
    else:
        assert set(line["metrics"]) == {"put_MiBps", "req_p50_ms",
                                        "setup_s"}
