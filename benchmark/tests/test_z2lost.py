"""The cell `ec104-get-degraded` (PR 37): `ec104-14n`'s cluster with
zone z2 lost, read by eight clients. The manifest finds the cell's
files; the configuration is `ec104-14n`'s in everything the launcher
reads; the kills are exactly zone z2 and leave the chip's node and two
of every partition's three metadata copies up; the data set is five
times the cache tier of node 1's zone; the three per-layer metrics the
PR brought are data files for `metrics_delta` that give, on two scrapes
written out here, the value worked out by hand; and `--rehearse` runs
the cell's whole control flow on the CPU to a `correct` last line."""

import hashlib
import json
import os
import subprocess
import sys

import pytest
from conftest import DATA, ROOT

from lib import manifest, roofline, scrape

CELL = "ec104-get-degraded"
DEGRADED = ("ec42-get-degraded", CELL)
READ_CACHE = 64 << 20  # [block] cache default, a node
SECONDS = 20.0

# node 1's /metrics as the program renders the new series (a timer or
# counter is <name>_count/_sum/_max per label set) beside the read
# cache's two gauges
SCRAPE0 = """\
cache_hits 100
cache_misses 900
block_gather_seconds_count{outcome="ok"} 500
block_gather_seconds_sum{outcome="ok"} 50.000000
block_gather_seconds_max{outcome="ok"} 0.900000
block_gather_fetches_count{result="ok"} 5000
block_gather_fetches_sum{result="ok"} 5000.000000
block_gather_fetches_count{result="refused"} 1500
block_gather_fetches_count{result="cancelled"} 20
"""

SCRAPE1 = """\
cache_hits 300
cache_misses 2700
block_gather_seconds_count{outcome="ok"} 1996
block_gather_seconds_sum{outcome="ok"} 229.520000
block_gather_seconds_max{outcome="ok"} 1.900000
block_gather_seconds_count{outcome="short"} 4
block_gather_seconds_sum{outcome="short"} 240.000000
block_gather_fetches_count{result="ok"} 19960
block_gather_fetches_sum{result="ok"} 19960.000000
block_gather_fetches_count{result="refused"} 6500
block_gather_fetches_count{result="failed"} 10
block_gather_fetches_count{result="cancelled"} 80
"""

# metric -> (unit, better, what it moves, value by hand)
WANT = {
    # ok gathers only: 179.52 s over 1,496
    "gather_ms": ("ms", "lower", "req_p50_ms", 1000.0 * 179.52 / 1496),
    # every fetch (14,960 + 5,000 + 10 + 60) over gathers of either
    # outcome (1,496 + 4)
    "fetches_per_block": ("count", "lower", "req_p50_ms", 20030 / 1500),
    # gathers of either outcome over lookups (200 + 1,800)
    "gathered_share": ("%", "higher", "get_MiBps", 100.0 * 1500 / 2000),
}


class Ctx:
    primary_method = "GET"

    def __init__(self, m0, m1):
        self.m0, self.m1 = m0, m1

    def scrapes(self, over):
        return self.m0, self.m1, SECONDS


@pytest.fixture(scope="module")
def bench():
    return manifest.load(ROOT)


@pytest.fixture(scope="module")
def cell(bench):
    return manifest.Cell(ROOT, bench, CELL)


@pytest.fixture(scope="module")
def pair():
    return scrape.parse_metrics(SCRAPE0), scrape.parse_metrics(SCRAPE1)


def test_the_cell_loads_with_its_files(bench, cell):
    assert cell.chips == 1
    assert cell.entry["config"] == "ec104-14n-z2lost"
    assert cell.entry["traffic"] == "get-r16x8-z2down"
    assert cell.config["name"] == "ec104-14n-z2lost"
    assert cell.traffic["kind"] == "range_get"
    assert cell.traffic["params"] == {
        "clients": 8, "parts_per_object": 4, "part_bytes": 16777216,
        "preload_objects": 20}
    assert {m["name"] for m in cell.end_to_end} == {
        "get_MiBps", "req_p50_ms", "setup_s"}
    mine = {m["name"] for m, _p, _r in cell.per_layer}
    assert {"gather_ms", "fetches_per_block", "gathered_share",
            "cache_hit_share", "first_byte_p50_ms", "s3_body_ms",
            "rs_decode_roofline", "device_idle_share",
            "compiles_in_window"} <= mine
    assert not {"rs_encode_roofline", "blake3_roofline", "pad_share"} & mine
    entry = next(c for c in bench["configs"]
                 if c["name"] == "ec104-14n-z2lost")
    assert entry["reduced"] == ["zone_latency", "hosts", "object_bytes",
                                "dataset_bytes", "chips"]
    assert set(entry["reduced"]) == set(cell.config["reduced"])
    assert len(entry["source"]) <= 200 and len(cell.entry["why"]) <= 200
    # the premise rests on a counter every accepted tree has
    assert [r["series"] for r in cell.traffic["correct"]] == [
        "feeder_device_op_items"]


def test_the_configuration_is_ec104_14n_with_a_zone_lost(cell):
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "ec104-14n.json")) as f:
        base = json.load(f)
    cfg = cell.config
    for key in ("nodes", "zones", "toml", "chip_node", "s3_node",
                "capacity", "fixed", "rehearse"):
        assert cfg[key] == base[key], key
    assert cfg["architecture"] is None
    z2 = [i + 1 for i, z in enumerate(cfg["zones"]) if z == "z2"]
    assert cfg["state"]["nodes_lost"] == z2 == [2, 6, 10, 14]
    assert cfg["state"]["holders_up"] == 10
    assert cfg["state"]["spare_holders_a_stripe"] == 0
    k, m = map(int, cfg["toml"]["erasure_coding"].split(","))
    assert len(z2) == m and cfg["nodes"] - len(z2) == k


def layout_of(cfg: dict):
    """The layout the cluster computes for this configuration, made
    here as benchmark/lib/cluster.py makes it: node keys from the
    configuration's name, node i the i-th lowest id, one role a node.
    -> (layout version, node ids, 1-based)."""
    sys.path.insert(0, ROOT)
    from garage_tpu.net.netapp import node_key_from_bytes
    from garage_tpu.rpc.layout import LayoutHistory, NodeRole
    from garage_tpu.utils.config import parse_capacity

    ids = sorted(node_key_from_bytes(hashlib.sha256(
        f"benchmark-node/{cfg['name']}/{j}".encode()).digest())
        .public_key().public_bytes_raw() for j in range(cfg["nodes"]))
    hist = LayoutHistory.new(int(cfg["toml"]["replication_factor"]))
    for node, zone in zip(ids, cfg["zones"]):
        hist.stage_role(node, NodeRole(
            zone=zone, capacity=parse_capacity(cfg["capacity"])))
    hist.apply_staged_changes()
    return hist.current(), {i + 1: n for i, n in enumerate(ids)}


def test_the_kills_are_zone_z2_and_leave_every_quorum_standing(cell):
    from garage_tpu.block.codec import shard_nodes_of
    from garage_tpu.rpc.layout import N_PARTITIONS

    cfg = cell.config
    (fault,) = cell.traffic["faults"]
    killed = fault["kill_nodes"]
    assert killed == [i + 1 for i, z in enumerate(cfg["zones"])
                      if z == "z2"]
    assert cfg["chip_node"] not in killed and cfg["s3_node"] not in killed
    lv, node_id = layout_of(cfg)
    gone = {node_id[i] for i in killed}
    k, m = map(int, cfg["toml"]["erasure_coding"].split(","))
    lost_sets = set()
    for p in range(N_PARTITIONS):
        copies = lv.nodes_of(p)
        assert len(copies) == 3 and len(set(copies) - gone) >= 2, p
        place = shard_nodes_of(lv, bytes([p]) + bytes(31), k + m)
        assert len(place) == k + m
        lost = tuple(i for i, n in enumerate(place) if n in gone)
        assert len(lost) == m  # exactly k left, none to spare
        lost_sets.add(lost)
    # several patterns (nine, with this configuration's node ids), and
    # most of them lose a data shard: a decode
    assert len(lost_sets) > 4
    assert sum(ls[0] < k for ls in lost_sets) > len(lost_sets) // 2


def test_the_data_set_is_five_times_the_zone_cache_tier(cell):
    cfg, p = cell.config, cell.traffic["params"]
    z1 = sum(z == cfg["zones"][cfg["s3_node"] - 1] for z in cfg["zones"])
    size = p["preload_objects"] * p["parts_per_object"] * p["part_bytes"]
    assert z1 == 4 and size == 1342177280 >= 5 * z1 * READ_CACHE
    assert size == 20 * READ_CACHE
    assert f"{size} " in cfg["reduced"]["dataset_bytes"]


def test_rs_decode_10_4_counts_the_algorithm_and_int8_binds():
    """No new kernel: rs_decode_roofline reads the same program at
    k = 10, where ten output rows of eighty bit-planes each make the
    int8 roof the binding one."""
    block = 1048576
    nbytes, ops = roofline.rs_decode(block, 10, 4)
    t, roof = roofline.least_seconds("rs_decode", 1, block, 10, 4,
                                     "TPU v5 lite")
    assert roof == "int8" and t == pytest.approx(ops / 393e12)
    assert nbytes / 819e9 < t


@pytest.mark.parametrize("metric", sorted(WANT))
def test_value_by_hand_in_both_degraded_cells(bench, pair, metric):
    unit, better, moves, want = WANT[metric]
    for w in bench["workloads"]:
        c = manifest.Cell(ROOT, bench, w["name"])
        found = [(m, p, r) for m, p, r in c.per_layer
                 if m["name"] == metric]
        if w["name"] not in DEGRADED:
            assert not found, f"{metric} is not {w['name']}'s"
            continue
        (m, params, reader), = found
        assert m["source"] == "program_counter" and m["better"] == better
        assert m["moves"] == moves and m["unit"] == unit
        assert m["layer"] == "block manager"
        assert reader.__name__ == "readers.metrics_delta"
        assert reader.read(params, Ctx(*pair)) == pytest.approx(want)


@pytest.mark.parametrize("metric", sorted(WANT))
def test_on_the_parent_the_metric_is_left_out(cell, metric):
    """The accepted tree exports neither series (its /metrics at two
    ends of a window are tests/data/scrape0.txt and scrape1.txt): the
    reader returns None, the line leaves the metric out and nothing
    raises; and a still series is no mean either."""
    (_m, params, reader), = [x for x in cell.per_layer
                             if x[0]["name"] == metric]
    parent = []
    for name in ("scrape0.txt", "scrape1.txt"):
        with open(os.path.join(DATA, name)) as f:
            parent.append(scrape.parse_metrics(f.read()))
    assert not any(k[0].startswith("block_gather") for k in parent[1])
    assert reader.read(params, Ctx(*parent)) is None
    still = scrape.parse_metrics(SCRAPE1)
    assert reader.read(params, Ctx(still, still)) is None


def test_rehearsal_runs_to_a_correct_last_line():
    """Fourteen real server processes at toy sizes on the CPU, zone
    z2's four SIGKILLed after the preload (about a minute): every body
    of the window equal to the seeded bytes, blocks decoded on node 1's
    device route, and with the trace on the three new metrics on the
    line, none of them zero."""
    env = {k: v for k, v in os.environ.items()
           if k not in ("JAX_PLATFORMS", "GARAGE_TPU_DEVICE", "XLA_FLAGS")}
    r = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", CELL, "--seed", "3700000101", "--seconds", "6",
         "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-2000:]
    line = json.loads(r.stdout.splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0 and line["device"]["platform"] == "cpu"
    got = line["metrics"]
    assert {"gather_ms", "fetches_per_block", "gathered_share",
            "cache_hit_share", "s3_body_ms", "first_byte_p50_ms",
            "device_idle_share", "compiles_in_window"} <= set(got)
    assert got["gather_ms"]["value"] > 0
    assert got["fetches_per_block"]["value"] >= 10
    assert 0 < got["gathered_share"]["value"] <= 100
