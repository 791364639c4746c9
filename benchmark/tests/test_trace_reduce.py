"""The trace reducer: busy union, idle share, per-op and per-program
sums, gap labelling. On hand-made XSpaces (text protos under data/,
serialized here), on a device plane with no operation in it, and on one
small trace recorded on a TPU v5e."""

import json
import os
import types

import pytest
from conftest import BENCH, DATA

from lib import manifest, trace_reduce as tr

UNIX0 = 1_700_000_000_000_001_000  # the annotation's unix ns, at trace 1,000 ns


def xplane(tmp_path, name):
    from jax.profiler import ProfileData

    with open(os.path.join(DATA, f"{name}.xspace.txt")) as f:
        raw = ProfileData.text_proto_to_serialized_xspace(f.read())
    p = tmp_path / f"{name}.xplane.pb"
    p.write_bytes(raw)
    return str(p)


def reduce_file(tmp_path, xp, stop_unix_ns, spans=()):
    start, stop, sp, out = (tmp_path / n for n in
                            ("start.json", "stop.json", "spans.jsonl", "out.json"))
    start.write_text(json.dumps({"before_unix_ns": UNIX0 - 500,
                                 "after_unix_ns": UNIX0}))
    stop.write_text(json.dumps({"before_unix_ns": stop_unix_ns,
                                "after_unix_ns": stop_unix_ns + 10}))
    sp.write_text("".join(json.dumps(s) + "\n" for s in spans))
    assert tr.main([xp, str(start), str(stop), str(sp), str(out)]) == 0
    return json.loads(out.read_text())


def test_union_merges_overlaps_and_ignores_empty():
    total, merged = tr.union_seconds([(0, 2), (1, 3), (5, 6), (6, 6), (9, 8)])
    assert total == 4 and merged == [[0, 3], [5, 6]]


def test_gaps_include_both_edges():
    assert tr.gaps_of([[2, 3], [5, 6]], 0, 10) == [(0, 2), (3, 5), (6, 10)]
    assert tr.gaps_of([], 0, 10) == [(0, 10)]


def test_gap_label_deepest_span_that_covers_most():
    spans = [{"name": "http.request", "t0": 0.0, "t1": 10.0},
             {"name": "rpc.call", "t0": 3.9, "t1": 6.1},
             {"name": "table.get", "t0": 4.0, "t1": 4.4}]  # under half
    assert tr.label_gap((4.0, 6.0), spans) == "rpc.call"
    assert tr.label_gap((20.0, 21.0), spans) == "unattributed"


def test_handmade_trace(tmp_path):
    # window: trace 1,000 ns .. 10,000 ns (9 us); ops at 2,000-5,000
    # (two overlapping) and 8,000-9,000
    spans = [{"name": "block.put", "start_us": (UNIX0 + 4000) // 1000,
              "dur_us": 3}]
    r = reduce_file(tmp_path, xplane(tmp_path, "handmade"), UNIX0 + 9000, spans)
    assert r["device_planes"] == ["/device:TPU:0"]
    assert r["window_s"] == pytest.approx(9e-6)
    assert r["busy_s"] == pytest.approx(4e-6)  # union, not the 5 us sum
    ops = {n: (s, c) for n, s, c in r["ops"]}
    assert ops["fusion.1"] == (pytest.approx(3e-6), 2)
    assert ops["copy.2"] == (pytest.approx(2e-6), 1)
    progs = {n: s for n, s, _ in r["programs"]}
    assert progs == {"jit_apply": pytest.approx(3e-6),
                     "jit_hash_rows": pytest.approx(1e-6)}
    gaps = sorted(s for _, s in r["idle_gaps"])
    assert gaps == pytest.approx([1e-6, 1e-6, 3e-6])
    assert r["lines"]["/device:TPU:0"]["Steps"] == 1  # seen, never counted


def test_ops_are_cut_to_the_window(tmp_path):
    # stop at trace 4,000 ns: the window is 3 us, busy 2,000-4,000
    r = reduce_file(tmp_path, xplane(tmp_path, "handmade"), UNIX0 + 3000)
    assert r["busy_s"] == pytest.approx(2e-6)
    assert r["window_s"] == pytest.approx(3e-6)


def test_device_plane_with_no_operation(tmp_path):
    r = reduce_file(tmp_path, xplane(tmp_path, "empty_device"), UNIX0 + 5_000_000)
    assert r["busy_s"] == 0.0 and r["ops"] == [] and r["programs"] == []
    assert r["idle_gaps"] == [["unattributed", pytest.approx(5e-3)]]
    idle = manifest.load_module(BENCH, "readers", "trace_idle")
    assert idle.read({}, types.SimpleNamespace(trace=r)) == 100.0


def test_trace_without_the_clock_annotation_is_refused(tmp_path):
    from jax.profiler import ProfileData

    p = tmp_path / "noclock.xplane.pb"
    p.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/device:TPU:0" }'))
    for n in ("a.json", "b.json"):
        (tmp_path / n).write_text('{"before_unix_ns": 0, "after_unix_ns": 1}')
    assert tr.main([str(p), str(tmp_path / "a.json"), str(tmp_path / "b.json"),
                    "-", str(tmp_path / "o.json")]) == 3


def test_roofline_reader_on_the_handmade_trace(tmp_path):
    r = reduce_file(tmp_path, xplane(tmp_path, "handmade"), UNIX0 + 9000)
    items = ("feeder_device_op_items", (("op", "encode_put"),))
    ctx = types.SimpleNamespace(
        trace=r, scrapes=lambda over: ({items: 10.0}, {items: 13.0}, 9e-6),
        geometry=(4, 2), block_bytes=1 << 20, device_kind="TPU v5 lite",
        rehearsal=False)
    with open(os.path.join(BENCH, "layer_metrics", "rs_encode_roofline.json")) as f:
        spec = json.load(f)
    rd = manifest.load_module(BENCH, "readers", spec["reader"])
    # 3 blocks: 6 shards of ceil((1 MiB + 1) / 4) bytes each at 819 GB/s
    least = 3 * 6 * 262145 / 819e9
    assert rd.read(spec["params"], ctx) == pytest.approx(100 * least / 3e-6)
    ctx.device_kind = "TPU v9"
    with pytest.raises(KeyError):
        rd.read(spec["params"], ctx)  # unknown kind: an error, no default


def test_trace_recorded_on_a_v5e(tmp_path):
    """data/v5e_small.xplane.pb: two rounds of rs.encode (jit_apply),
    BLAKE3 hash_fn(1024) (jit__unknown) and gf_apply_batched decode
    (jit_apply) on 3 items, recorded on a TPU v5e in PR 22 and cut to
    the module events, the RS programs' ops and the hash program's six
    longest ops (the whole file is 4.5 MB of HLO text)."""
    out = tmp_path / "out.json"
    assert tr.main([os.path.join(DATA, "v5e_small.xplane.pb"),
                    os.path.join(DATA, "v5e_small_start.json"),
                    os.path.join(DATA, "v5e_small_stop.json"), "-",
                    str(out)]) == 0
    r = json.loads(out.read_text())
    assert r["device_planes"] == ["/device:TPU:0"]
    assert set(r["lines"]["/device:TPU:0"]) == {"XLA Modules", "XLA Ops"}
    assert r["window_s"] == pytest.approx(0.1366, abs=1e-3)
    progs = {n: (s, c) for n, s, c in r["programs"]}
    # the device's clock leads the host's: the first encode launch lies
    # before the annotation and is cut off, so 3 of 4 jit_apply remain
    assert progs["jit_apply"][1] == 3 and progs["jit__unknown"][1] == 2
    assert progs["jit__unknown"][0] == pytest.approx(2 * 9.114e-3, rel=1e-3)
    names = [n for n, _, _ in r["ops"]]
    assert "convert_reduce_fusion" in names  # the HLO line cut to its name
    assert not any("=" in n or n.startswith("%") for n in names)
    assert 0 < r["busy_s"] <= sum(s for s, _ in progs.values())
    assert len(r["idle_gaps"]) == 5 and r["unattributed_gaps"] == 5
