"""From node 1's profiler trace (.xplane.pb) and its span file to the
numbers the per-layer readers use. Runs in a child with
JAX_PLATFORMS=cpu after every node has exited:

    python benchmark/lib/trace_reduce.py <xplane.pb> <start.json> <stop.json>
                                         <spans.jsonl|-> <out.json> [--cpu-ops]

What is what in the trace (looked at by hand on a v5e, PERF.md §3):
a device is a plane named "/device:TPU:<n>"; on it the line
"XLA Ops" holds one event per HLO operation that ran, named by its
whole HLO line, and the line "XLA Modules" one per launched program
("jit_apply(<fingerprint>)"; "Async XLA Ops" repeats copies and slices
that "XLA Ops" already covers). Host-to-device and device-to-host
copies are not on the device plane: they are host events
("tpu::System::TransferToDevice" / "...FromDevice", with a `size`) on
"/host:CPU". The device's clock leads the host's by about a
millisecond in these traces, which gap labels can live with. Busy time
is the union of the op intervals, cut to the trace window; a
program's device time is the sum of its module events. The window is
what node_main.py stamped: from the "bench.clock <unix ns>" annotation
written right after start_trace returned to the instant before
stop_trace was called. With --cpu-ops (rehearsal only) host events
that carry an `hlo_module` stat stand in for device ops, so that the
path runs end to end on the CPU; its output is labelled cpu upstream.
"""

from __future__ import annotations

import json
import re
import sys

DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
CLOCK = re.compile(r"^bench\.clock (\d+)$")


def union_seconds(intervals: list[tuple[float, float]]) -> tuple[float, list]:
    """-> (total length of the union, the merged intervals)."""
    merged: list[list[float]] = []
    for a, b in sorted(intervals):
        if b <= a:
            continue
        if merged and a <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], b)
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


def gaps_of(merged: list, w0: float, w1: float) -> list[tuple[float, float]]:
    """The idle intervals of [w0, w1] that the merged busy ones leave."""
    out, at = [], w0
    for a, b in merged:
        if a > at:
            out.append((at, a))
        at = max(at, b)
    if w1 > at:
        out.append((at, w1))
    return out


def label_gap(gap: tuple[float, float], spans: list[dict]) -> str:
    """The program span that covers most of the gap: the largest
    overlap, the shortest span among equals (the deepest); one that
    covers under half of it does not count -> "unattributed"."""
    g0, g1 = gap
    best, best_key = "unattributed", None
    for s in spans:
        a, b = s["t0"], s["t1"]
        ov = min(b, g1) - max(a, g0)
        if ov < 0.5 * (g1 - g0):
            continue
        key = (ov, -(b - a))
        if best_key is None or key > best_key:
            best, best_key = s["name"], key
    return best


def program_name(event_name: str) -> str:
    """"jit_apply(1234)" -> "jit_apply"."""
    return event_name.split("(")[0]


def op_name(event_name: str) -> str:
    """On a TPU an op's event is named by its whole HLO line,
    "%convert_reduce_fusion = u32[3,524288,2]{...} fusion(...)": keep the
    instruction's own name, "convert_reduce_fusion"."""
    return event_name.split(" = ")[0].lstrip("%")


def load_spans(path: str) -> list[dict]:
    """The program's span file -> [{"name", "t0", "t1"}] in unix ns (ints:
    a float cannot hold nanoseconds since 1970)."""
    out = []
    if path == "-":
        return out
    try:
        with open(path) as f:
            for line in f:
                try:
                    r = json.loads(line)
                    t0 = int(r["start_us"]) * 1000
                    out.append({"name": r["name"], "t0": t0,
                                "t1": t0 + int(r["dur_us"]) * 1000})
                except (ValueError, KeyError):
                    continue
    except OSError:
        pass
    return out


def reduce_planes(planes: list[dict], w0_ns: float, w1_ns: float,
                  clock_unix_ns: int, clock_trace_ns: float,
                  spans: list[dict]) -> dict:
    """planes: [{"name", "ops": [(name, start_ns, dur_ns)],
    "modules": [...]}] on the trace's clock. The window [w0_ns, w1_ns]
    is on the trace's clock too; spans are unix ns and are moved onto
    it by the clock pair."""
    window_s = (w1_ns - w0_ns) / 1e9
    out: dict = {"window_s": window_s, "device_planes": [p["name"] for p in planes],
                 "busy_s": 0.0, "ops": [], "programs": [], "idle_gaps": [],
                 "unattributed_gaps": 0}
    if not planes or window_s <= 0:
        return out

    def clip(ev):
        name, t, d = ev
        a, b = max(t, w0_ns), min(t + d, w1_ns)
        return (name, a, b) if b > a else None

    ops: dict[str, list] = {}
    progs: dict[str, list] = {}
    busy, all_gaps = 0.0, []
    for p in planes:
        ivs = []
        for ev in filter(None, map(clip, p["ops"])):
            ivs.append((ev[1], ev[2]))
            rec = ops.setdefault(op_name(ev[0]), [0.0, 0])
            rec[0] += (ev[2] - ev[1]) / 1e9
            rec[1] += 1
        for ev in filter(None, map(clip, p["modules"])):
            rec = progs.setdefault(program_name(ev[0]), [0.0, 0])
            rec[0] += (ev[2] - ev[1]) / 1e9
            rec[1] += 1
        b, merged = union_seconds(ivs)
        busy += b / 1e9
        all_gaps += gaps_of(merged, w0_ns, w1_ns)
    n = len(planes)
    out["busy_s"] = busy / n  # averaged over the chips used
    out["ops"] = sorted(([k, v[0] / n, v[1]] for k, v in ops.items()),
                        key=lambda r: -r[1])
    out["programs"] = sorted(([k, v[0] / n, v[1]] for k, v in progs.items()),
                             key=lambda r: -r[1])
    on_trace = [{"name": s["name"],
                 "t0": s["t0"] - clock_unix_ns + clock_trace_ns,
                 "t1": s["t1"] - clock_unix_ns + clock_trace_ns}
                for s in spans]
    longest = sorted(all_gaps, key=lambda g: g[0] - g[1])[:5]
    for a, b in longest:
        label = label_gap((a, b), on_trace)
        out["idle_gaps"].append([label, (b - a) / 1e9])
        out["unattributed_gaps"] += label == "unattributed"
    return out


def read_xplane(path: str, cpu_ops: bool) -> tuple[list[dict], float | None, float | None, dict]:
    """-> (device planes as reduce_planes wants them, the clock
    annotation's unix ns and its trace ns, what lines each plane has)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    planes, seen, clock_unix, clock_trace = [], {}, None, None
    host_ops = []
    for pl in pd.planes:
        lines = seen.setdefault(pl.name, {})
        device = DEVICE_PLANE.match(pl.name)
        rec = {"name": pl.name, "ops": [], "modules": []}
        for ln in pl.lines:
            evs = list(ln.events)
            lines[ln.name] = lines.get(ln.name, 0) + len(evs)
            if device:
                key = {OPS_LINE: "ops", MODULES_LINE: "modules"}.get(ln.name)
                if key:
                    rec[key] += [(e.name, e.start_ns, e.duration_ns)
                                 for e in evs]
                continue
            for e in evs:
                m = CLOCK.match(e.name)
                if m:
                    clock_unix, clock_trace = int(m.group(1)), e.start_ns
                elif cpu_ops and e.duration_ns > 0:
                    st = dict(e.stats)
                    if "hlo_module" in st:
                        host_ops.append((e.name, e.start_ns, e.duration_ns,
                                         str(st["hlo_module"])))
        if device:
            planes.append(rec)
    if cpu_ops and not planes:
        planes.append({"name": "/host:CPU (rehearsal)",
                       "ops": [(n, t, d) for n, t, d, _ in host_ops],
                       "modules": [(mod, t, d) for _, t, d, mod in host_ops]})
    return planes, clock_unix, clock_trace, seen


def main(argv: list[str]) -> int:
    cpu_ops = "--cpu-ops" in argv
    argv = [a for a in argv if a != "--cpu-ops"]
    xplane, start_json, stop_json, span_path, out_path = argv
    with open(start_json) as f:
        start = json.load(f)
    with open(stop_json) as f:
        stop = json.load(f)
    planes, clock_unix, clock_trace, seen = read_xplane(xplane, cpu_ops)
    if clock_unix is None:
        print("trace_reduce: no bench.clock annotation in the trace",
              file=sys.stderr)
        return 3
    w0 = clock_trace
    w1 = clock_trace + (stop["before_unix_ns"] - clock_unix)
    res = reduce_planes(planes, w0, w1, clock_unix, clock_trace,
                        load_spans(span_path))
    res["lines"] = seen
    res["clock"] = {"unix_ns": clock_unix, "trace_ns": clock_trace,
                    "start_trace_s": (start["after_unix_ns"]
                                      - start["before_unix_ns"]) / 1e9}
    with open(out_path, "w") as f:
        json.dump(res, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
