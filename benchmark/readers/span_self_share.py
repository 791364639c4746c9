"""Reader `span_self_share`: of the time inside spans called
params["root"] that ended in the measured window, the share not covered
by their direct child spans (the layer's self time), in percent. Spans
come from the program's own file (GARAGE_TPU_TRACE). No such span ->
None."""

from lib.trace_reduce import union_seconds


def read(params: dict, ctx):
    spans = ctx.window_spans()
    roots = {s["span"]: s for s in spans if s["name"] == params["root"]}
    if not roots:
        return None
    kids: dict = {}
    for s in spans:
        if s.get("parent") in roots:
            kids.setdefault(s["parent"], []).append(
                (s["start_us"], s["start_us"] + s["dur_us"]))
    total = covered = 0.0
    for sid, r in roots.items():
        a, b = r["start_us"], r["start_us"] + r["dur_us"]
        total += b - a
        covered += union_seconds([(max(x, a), min(y, b))
                                  for x, y in kids.get(sid, [])])[0]
    return 100.0 * (total - covered) / total if total else None
