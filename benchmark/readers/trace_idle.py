"""Reader `trace_idle`: 1 - (union of device-op intervals) / (trace
window), in percent, from node 1's own profiler trace. A trace with a
device plane and no operation on it reads 100. No trace -> None."""


def read(params: dict, ctx):
    t = ctx.trace
    if not t or not t.get("device_planes") or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
