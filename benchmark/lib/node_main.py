"""Start the node that owns the chip, with a control channel beside it.

    python benchmark/lib/node_main.py --ctl <fifo> --ack <file> -- <server argv>

Runs `garage_tpu.cli.server`'s `main` unchanged (runpy, the same argv a
user gives `python -m garage_tpu.cli.server`) in this process's main
thread. Beside it one daemon thread sleeps in a blocking read of a
FIFO — it costs nothing until the harness writes to it — and serves
three commands, one per line, because only the process that holds the
chip can answer them:

    start <dir>   jax.profiler.start_trace(dir), host and Python tracers
                  at their cheapest; then one TraceAnnotation named
                  "bench.clock <unix ns>", so the reducer can put the
                  program's own spans (unix microseconds) on the trace's
                  clock
    stop          jax.profiler.stop_trace()
    mem           peak bytes in use on the fullest device
                  (device.memory_stats()), which the program exports
                  nowhere

Every command is answered by one JSON line appended to the ack file.
"""

from __future__ import annotations

import json
import runpy
import sys
import threading
import time


def _serve(ctl: str, ack: str) -> None:
    def answer(obj: dict) -> None:
        with open(ack, "a") as f:
            f.write(json.dumps(obj) + "\n")

    while True:
        # open() blocks until the harness opens the FIFO for writing, and
        # the read ends when it closes it: no polling, no timer
        with open(ctl) as f:
            lines = f.read().splitlines()
        for line in lines:
            cmd, _, arg = line.strip().partition(" ")
            res: dict = {"cmd": cmd, "ok": False}
            try:
                import jax

                if cmd == "start":
                    opts = jax.profiler.ProfileOptions()
                    opts.python_tracer_level = 0
                    opts.host_tracer_level = 1
                    t0 = time.time_ns()
                    jax.profiler.start_trace(arg, profiler_options=opts)
                    t1 = time.time_ns()
                    with jax.profiler.TraceAnnotation(f"bench.clock {t1}"):
                        pass
                    res.update(ok=True, before_unix_ns=t0, after_unix_ns=t1)
                elif cmd == "stop":
                    t0 = time.time_ns()
                    jax.profiler.stop_trace()
                    res.update(ok=True, before_unix_ns=t0,
                               after_unix_ns=time.time_ns())
                elif cmd == "mem":
                    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use")
                             for d in jax.devices()]
                    res.update(ok=True, peaks=peaks)
                else:
                    res["error"] = f"unknown command {cmd!r}"
            except Exception as e:  # the server must outlive a failed trace
                res["error"] = f"{type(e).__name__}: {e}"[:500]
            answer(res)


def main() -> None:
    argv = sys.argv[1:]
    if "--" not in argv or argv[:1] != ["--ctl"] or argv[2:3] != ["--ack"]:
        print(__doc__, file=sys.stderr)
        sys.exit(2)
    ctl, ack = argv[1], argv[3]
    rest = argv[argv.index("--") + 1:]
    threading.Thread(target=_serve, args=(ctl, ack), daemon=True,
                     name="bench-ctl").start()
    sys.argv = ["garage_tpu.cli.server"] + rest
    runpy.run_module("garage_tpu.cli.server", run_name="__main__",
                     alter_sys=True)


if __name__ == "__main__":
    main()
