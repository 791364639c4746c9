"""Arithmetic from request records to end-to-end metrics.

A request record is (t_start, t_end, nbytes, ok): the host's monotonic
clock around the whole exchange, the payload bytes it carried, whether
it was acknowledged and correct."""

from __future__ import annotations

from typing import Iterable, NamedTuple, Optional


class Req(NamedTuple):
    t_start: float
    t_end: float
    nbytes: int
    ok: bool
    t_first: Optional[float] = None  # first body byte received (GET)


def overlap_bytes_per_s(reqs: Iterable[Req], w0: float, w1: float) -> float:
    """Payload bytes of the acknowledged requests, each weighted by the
    share of its own duration that lies inside [w0, w1], over the
    window's seconds. A request in flight across an edge counts by its
    overlap; a failed one contributes nothing."""
    if w1 <= w0:
        raise ValueError("empty window")
    total = 0.0
    for r in reqs:
        if not r.ok:
            continue
        dur = r.t_end - r.t_start
        inside = min(r.t_end, w1) - max(r.t_start, w0)
        if inside <= 0:
            continue
        total += r.nbytes * (inside / dur if dur > 0 else 1.0)
    return total / (w1 - w0)


def ended_inside(reqs: Iterable[Req], w0: float, w1: float) -> list[Req]:
    """The acknowledged requests whose last byte arrived in [w0, w1)."""
    return [r for r in reqs if r.ok and w0 <= r.t_end < w1]


def median(values: Iterable[float]) -> Optional[float]:
    """Plain median (mean of the middle two for an even count); None
    for no values, so that a metric with no sample is left out and
    never reported as 0."""
    v = sorted(values)
    if not v:
        return None
    mid = len(v) // 2
    return v[mid] if len(v) % 2 else (v[mid - 1] + v[mid]) / 2.0


def spread(values: Iterable[float]) -> Optional[float]:
    """Distance between the quartiles over the median: what the driver
    reads as a cell's run-to-run spread. Quartiles by linear
    interpolation (numpy's default)."""
    v = sorted(values)
    if len(v) < 2:
        return None

    def q(p: float) -> float:
        x = p * (len(v) - 1)
        lo = int(x)
        hi = min(lo + 1, len(v) - 1)
        return v[lo] + (v[hi] - v[lo]) * (x - lo)

    med = q(0.5)
    return (q(0.75) - q(0.25)) / med if med else None
