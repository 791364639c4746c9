"""Reader `trace_roofline`: a kernel's share of its roofline.

params: {"program": regex on the program's name in the trace,
         "items": [{"series", "labels"}, ...] counting the items launched,
         "bytes_fn": a function of lib/roofline.py}
The least time the chip could take for the items launched while the
trace ran (bytes and operations from shapes, peaks by device kind)
over the summed device time of the matching programs, in percent. The
items are the delta of the program's own counter between the scrapes
taken at the trace's two ends. No matching program or no items -> None.
"""

import re

from lib import roofline, scrape


def read(params: dict, ctx):
    t = ctx.trace
    m0, m1, _ = ctx.scrapes("trace")
    if not t or m0 is None:
        return None
    pat = re.compile(params["program"])
    device_s = sum(sec for name, sec, _n in t["programs"] if pat.search(name))
    items = sum(scrape.delta(m0, m1, t["series"], t.get("labels")) or 0.0
                for t in params["items"])
    if not device_s or not items:
        return None
    if ctx.rehearsal:
        return None  # the CPU has no roofline here: never a device number
    k, m = ctx.geometry
    least, _bound = roofline.least_seconds(
        params["bytes_fn"], items, ctx.block_bytes, k, m, ctx.device_kind)
    return 100.0 * least / device_s
