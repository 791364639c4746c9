#!/usr/bin/env python3
"""The benchmark's one command.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

One cell of BENCHMARK.json per process: builds the deployment the cell's
configuration file describes (real `garage_tpu.cli.server` processes,
real directories under benchmark/work/), sets it up through the admin
API, preloads and warms up with the cell's own traffic (all of that is
`setup_s`), measures for --seconds, checks what came back against the
seed, stops every node, and prints one JSON object as the last line of
stdout: `correct`, `attempted`, `failed`, `metrics`, `device`, and with
--trace 1 `breakdown`. With --trace 0 the metrics are the cell's
end-to-end metrics, with --trace 1 its per-layer metrics (node 1 then
also writes its own profiler trace and span file; see lib/node_main.py).

This process never imports JAX: the node that owns the chip must get
it. A run that finds no TPU fails (exit code not 0, no last line).
`--rehearse` is the only way to run without a chip: toy sizes from the
configuration's `rehearse` block on the CPU, output labelled `cpu`,
never inferred. See benchmark/README.md for how cells, traffic mixes,
generator kinds and metrics are added as files.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is everything from here to the window

import argparse  # noqa: E402
import glob  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402

BENCH_DIR = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH_DIR)
sys.path.insert(0, BENCH_DIR)
sys.path.insert(1, ROOT)  # garage_tpu.net.netapp derives the node ids

from lib import manifest, scrape  # noqa: E402
from lib.cluster import BUCKET, Cluster, Failed  # noqa: E402
from lib.s3client import S3Client  # noqa: E402

TIME_LIMIT = 1150  # the contract allows a cell's first run 1200 s
TRACE_SECONDS = 5.0


def say(msg: str) -> None:
    print(f"[bench +{time.monotonic() - T_START:6.1f}s] {msg}", flush=True)


class Env:
    """What a generator may use."""

    def __init__(self, cell, cluster, seed, params, key_id, secret):
        self.config, self.cluster, self.seed = cell.config, cluster, seed
        self.params, self.bucket, self.say = params, BUCKET, say
        self.block_bytes = int(cluster.toml["block_size"])
        self._key = (key_id, secret)

    def client(self, node: int | None = None) -> S3Client:
        node = node or int(self.config["s3_node"])
        return S3Client("127.0.0.1", self.cluster.ports[node]["s3"],
                        *self._key)


class Ctx:
    """What a per-layer reader may use."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def scrapes(self, over: str):
        """-> (first scrape, second scrape, seconds between them) of the
        measured window or of the trace window."""
        if over == "trace":
            return self.mt0, self.mt1, self.trace_span_s
        return self.m0, self.m1, self.window_s

    def window_spans(self) -> list[dict]:
        """The program's spans that ended inside the measured window
        (the file is read once, whatever the number of readers)."""
        if "_spans" not in self.__dict__:
            self._spans = []
            try:
                with open(self.span_file) as f:
                    for line in f:
                        try:
                            s = json.loads(line)
                            end = (s["start_us"] + s["dur_us"]) / 1e6
                        except (ValueError, KeyError):
                            continue
                        if self.w0_unix <= end < self.w1_unix:
                            self._spans.append(s)
            except OSError:
                pass
        return self._spans


def node_device(m: dict) -> dict:
    ls = scrape.labels_of(m, "feeder_device_count")
    if not ls:
        raise Failed("the chip node's /metrics has no feeder_device_count")
    return {"platform": ls[0]["platform"], "kind": ls[0]["device_kind"],
            "count": int(scrape.total(m, "feeder_device_count"))}


def warm_up(cluster: Cluster, gen) -> dict:
    """The cell's traffic is running. Wait until every client has had a
    primary request answered (minutes, where XLA has to build what the
    first requests launch), and from then until the chip node's compile
    requests have stood still for 5 s: at least 8 s, at most 60 s. A run
    that really compiles (a cell's first run in a checkout) builds
    programs for tens of seconds each, during which the counter does not
    move, so there it must stand still for 40 s, within 600 s."""
    adm = cluster.ports[cluster.chip_node]["adm"]
    floor, still, cap = 8.0, 5.0, 60.0
    t0 = time.monotonic()
    first = scrape.scrape(adm)
    t_warm = last_rq = None
    last_change = t0
    while True:
        time.sleep(1.0)
        m = scrape.scrape(adm)
        now = time.monotonic()
        built = scrape.delta(first, m, "feeder_xla_compiles") or 0
        if built:
            still, cap = 40.0, 600.0
        if t_warm is None and gen.warm():
            t_warm = now
        rq = scrape.total(m, "feeder_xla_compile_requests")
        if rq != last_rq or t_warm is None:
            last_rq, last_change = rq, now
        if t_warm is None:
            continue  # TIME_LIMIT ends a run whose first requests never return
        if now - t_warm >= floor and now - last_change >= still:
            break
        if now - t_warm >= cap:
            say(f"warm-up: compile requests still moving after {cap:.0f} s")
            break
    return {"seconds": now - t0, "first_answers_s": t_warm - t0,
            "compile_requests": last_rq, "compiles_built": built}


def counter_rules(rules: list, m0: dict, m1: dict) -> list[str]:
    """The traffic file's premise, as conditions on window deltas of the
    chip node's counters. -> the rules that did not hold."""
    bad = []
    for r in rules:
        d = scrape.delta(m0, m1, r["series"], r.get("labels")) or 0.0
        ok = {"gt": d > r["value"], "eq": d == r["value"]}[r["op"]]
        if not ok:
            bad.append(f"{r['series']}{r.get('labels', '')} moved by {d}, "
                       f"wanted {r['op']} {r['value']}")
    return bad


def reduce_trace(cluster: Cluster, trace_dir: str, start: dict, stop: dict,
                 rehearse: bool) -> dict:
    found = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not found:
        raise Failed(f"the chip node wrote no .xplane.pb under {trace_dir}")
    files = {}
    for name, obj in (("start", start), ("stop", stop)):
        files[name] = os.path.join(cluster.work, f"trace_{name}.json")
        with open(files[name], "w") as f:
            json.dump(obj, f)
    out = os.path.join(cluster.work, "trace_reduced.json")
    spans = cluster.span_file if os.path.exists(cluster.span_file) else "-"
    argv = [sys.executable, os.path.join(BENCH_DIR, "lib", "trace_reduce.py"),
            found[0], files["start"], files["stop"], spans, out]
    if rehearse:
        argv.append("--cpu-ops")
    r = subprocess.run(argv, env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    if r.returncode != 0:
        raise Failed(f"trace_reduce failed ({r.returncode}): {r.stderr[-800:]}")
    with open(out) as f:
        res = json.load(f)
    res["xplane_bytes"] = os.path.getsize(found[0])
    return res


def run(args, cell, cluster: Cluster) -> tuple[dict, dict]:
    rehearse, trace = args.rehearse, bool(args.trace)
    steps: dict = {}
    t_step = T_START

    def step(name: str) -> None:
        nonlocal t_step
        now = time.monotonic()
        steps[name] = now - t_step
        say(f"{name} ({now - t_step:.1f} s)")
        t_step = now

    params = dict(cell.traffic.get("params", {}))
    if rehearse:
        params.update(cell.config["rehearse"].get("traffic", {}))
    step("manifest read")
    key_id, secret = cluster.bring_up()
    adm = cluster.ports[cluster.chip_node]["adm"]
    device = node_device(scrape.scrape(adm))
    say(f"the chip node holds {device}")
    want = "cpu" if rehearse else "tpu"
    if device["platform"] != want:
        raise Failed(f"the chip node runs on {device['platform']!r}, "
                     f"not {want!r}")
    if device["count"] < cell.chips:
        raise Failed(f"the cell asks for {cell.chips} chips, JAX found "
                     f"{device['count']}")
    step("nodes up, layout, key, bucket")

    gen = cell.generator.Generator(
        Env(cell, cluster, args.seed, params, key_id, secret))
    gen.prepare()
    step("generator prepared (pool, preload)")
    for fault in cell.traffic.get("faults", []):
        for i in fault["kill_nodes"]:
            cluster.kill(i)
        cluster.wait_connected(cluster.n - len(fault["kill_nodes"]), 60)
        step(f"fault: nodes {fault['kill_nodes']} SIGKILLed")

    gen.start()
    warm = warm_up(cluster, gen)
    say(f"warm-up: {warm}")
    step("warm-up under the cell's traffic")

    # ---- the measured window ------------------------------------------
    m0 = scrape.scrape(adm)
    cpu0 = os.times()
    w0, w0_unix = time.monotonic(), time.time()
    setup_s = w0 - T_START
    mt0 = mt1 = start = stop = None
    trace_dir = os.path.join(cluster.work, "trace")
    if trace:
        # the trace covers the END of the window: stop_trace takes
        # seconds and stalls the node, so it is called after the window
        # has closed and the last scrape is taken
        tlen = min(TRACE_SECONDS, args.seconds / 2.0)
        time.sleep(max(0.0, args.seconds - tlen))
        mt0 = scrape.scrape(adm)
        start = cluster.control(f"start {trace_dir}")
    time.sleep(max(0.0, w0 + args.seconds - time.monotonic()))
    w1, w1_unix = time.monotonic(), time.time()
    cpu1 = os.times()
    m1 = scrape.scrape(adm)
    if trace:
        mt1 = m1
        stop = cluster.control("stop", timeout=240)
    step(f"window of {w1 - w0:.2f} s")

    gen.stop()  # requests in flight finish, outside the window
    res = gen.measure(w0, w1)
    bad = gen.check()
    m_end = scrape.scrape(adm)
    bad += counter_rules(cell.traffic.get("correct", []), m0, m1)
    for series in ("feeder_device_errors", "feeder_host_reruns"):
        if scrape.total(m_end, series):
            bad.append(f"{series} = {scrape.total(m_end, series)} on the "
                       f"chip node")
    for i in cluster.procs:
        if i != cluster.chip_node and cluster.alive(i) and cluster.loads_jax(i):
            bad.append(f"node {i} ([tpu] enable = false) mapped a JAX library")
    peaks = [p for p in cluster.control("mem")["peaks"] if p is not None]
    device["memory_peak_bytes"] = max(peaks) if peaks else 0
    step("drain and correctness checks")
    cluster.stop_all()
    step("nodes stopped")

    res["metrics"]["setup_s"] = setup_s
    e2e = {m["name"]: {"value": res["metrics"][m["name"]], "unit": m["unit"]}
           for m in cell.end_to_end if res["metrics"].get(m["name"]) is not None}
    missing = [m["name"] for m in cell.end_to_end if m["name"] not in e2e]
    if missing:
        bad.append(f"no sample for {missing}")
    detail = {"cell": cell.name, "seed": args.seed, "seconds": args.seconds,
              "trace": int(trace), "rehearsal": rehearse, "steps": steps,
              "warm_up": warm, "samples": res.get("samples"),
              "end_to_end": e2e, "incorrect_because": bad}
    line = {"correct": not bad, "attempted": res["attempted"],
            "failed": res["failed"], "metrics": e2e, "device": device}
    if trace:
        reduced = reduce_trace(cluster, trace_dir, start, stop, rehearse)
        ec = str(cluster.toml.get("erasure_coding", "1,0")).split(",")
        ctx = Ctx(m0=m0, m1=m1, mt0=mt0, mt1=mt1, window_s=w1 - w0,
                  trace_span_s=(stop["before_unix_ns"]
                                - start["after_unix_ns"]) / 1e9,
                  trace=reduced, span_file=cluster.span_file,
                  w0_unix=w0_unix, w1_unix=w1_unix,
                  loadgen_cpu_s=(cpu1.user + cpu1.system
                                 - cpu0.user - cpu0.system),
                  primary_method=cell.traffic["primary"]["method"],
                  geometry=(int(ec[0]), int(ec[1])),
                  block_bytes=int(cluster.toml["block_size"]),
                  device_kind=device["kind"], rehearsal=rehearse,
                  generated=res["metrics"])
        layer = {}
        for m, prm, reader in cell.per_layer:
            v = reader.read(prm, ctx)
            if v is not None:  # nothing to read: left out of the line
                layer[m["name"]] = {"value": v, "unit": m["unit"]}
        line["metrics"] = layer
        device["busy_s"], device["window_s"] = (reduced["busy_s"],
                                                reduced["window_s"])
        line["breakdown"] = {
            "device_ops": [[n, s] for n, s, _c in reduced["ops"][:10]],
            "idle_gaps": reduced["idle_gaps"][:10]}
        detail.update(per_layer=layer, trace_reduced=reduced,
                      stop_trace_s=(stop["after_unix_ns"]
                                    - stop["before_unix_ns"]) / 1e9)
        step("trace reduced, per-layer metrics read")
    detail["device"] = device
    return line, detail


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], required=True)
    ap.add_argument("--rehearse", action="store_true",
                    help="toy sizes on the CPU, labelled cpu (never inferred)")
    args = ap.parse_args()
    if not os.path.isdir(os.path.join(ROOT, "garage_tpu")):
        print(f"benchmark/run.py measures the garage-tpu checkout it sits "
              f"in; there is no garage_tpu/ in {ROOT}", file=sys.stderr)
        return 2
    try:
        bench = manifest.load(ROOT)
        cell = manifest.Cell(ROOT, bench, args.workload)
    except manifest.ManifestError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2

    def on_signal(signum, _frame):
        raise KeyboardInterrupt(f"signal {signum}")

    signal.signal(signal.SIGTERM, on_signal)
    signal.signal(signal.SIGALRM, on_signal)
    signal.alarm(TIME_LIMIT)
    work = os.path.join(BENCH_DIR, "work", cell.name)
    shutil.rmtree(work, ignore_errors=True)
    os.makedirs(work)
    os.makedirs(os.path.join(BENCH_DIR, "out"), exist_ok=True)
    say(f"{'REHEARSAL on cpu' if args.rehearse else 'chip run'}: "
        f"{cell.name}, seed {args.seed}, {args.seconds:g} s, trace "
        f"{args.trace}; work dir {work}")
    cluster = Cluster(ROOT, work, cell.config, args.rehearse,
                      bool(args.trace))
    try:
        line, detail = run(args, cell, cluster)
    except (Failed, KeyboardInterrupt, OSError, RuntimeError,
            subprocess.SubprocessError) as e:
        print(f"benchmark FAILED: {type(e).__name__}: {e}\n--- chip node "
              f"log, last lines ---\n{cluster.log_tail(cluster.chip_node, 40)}",
              file=sys.stderr)
        return 1
    finally:
        signal.alarm(0)
        cluster.stop_all()
        # the bulk goes now; logs, spans and the trace stay for whoever
        # reads the run, until the next run of the cell wipes them
        for d in glob.glob(os.path.join(work, "node*", "*")):
            if os.path.isdir(d):
                shutil.rmtree(d, ignore_errors=True)
    out = os.path.join(BENCH_DIR, "out",
                       f"{cell.name}-seed{args.seed}-trace{args.trace}")
    with open(out + ".json", "w") as f:
        json.dump(detail, f, indent=1)
    if not line["correct"]:  # what the chip node said, for whoever asks why
        shutil.copy(os.path.join(cluster.dir(cluster.chip_node), "log"),
                    out + ".node.log")
    detail.pop("trace_reduced", None)
    print("detail " + json.dumps(detail), flush=True)
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
