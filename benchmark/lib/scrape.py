"""Node /metrics (Prometheus text) -> series, deltas and ratios."""

from __future__ import annotations

import re
import urllib.request
from typing import Optional

_SERIES = re.compile(r'^([A-Za-z_:][A-Za-z0-9_:]*)(\{(.*)\})?$')
_LABEL = re.compile(r'([A-Za-z_][A-Za-z0-9_]*)="((?:[^"\\]|\\.)*)"')


def parse_metrics(text: str) -> dict[tuple[str, tuple], float]:
    """-> {(name, ((label, value), ...) sorted): float}."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line.startswith("#"):
            continue
        series, _, val = line.rpartition(" ")
        m = _SERIES.match(series.strip())
        if not m:
            continue
        try:
            v = float(val)
        except ValueError:
            continue
        labels = tuple(sorted(_LABEL.findall(m.group(3) or "")))
        out[(m.group(1), labels)] = v
    return out


def scrape(port: int, host: str = "127.0.0.1", timeout: float = 10.0) -> dict:
    with urllib.request.urlopen(f"http://{host}:{port}/metrics",
                                timeout=timeout) as r:
        return parse_metrics(r.read().decode())


def total(m: dict, name: str, labels: Optional[dict] = None) -> Optional[float]:
    """Sum over the series called `name` whose labels include `labels`;
    None when there is no such series (absent is not 0)."""
    want = set((labels or {}).items())
    vals = [v for (n, ls), v in m.items() if n == name and want <= set(ls)]
    return sum(vals) if vals else None


def delta(m0: dict, m1: dict, name: str, labels: Optional[dict] = None) -> Optional[float]:
    """total(m1) - total(m0). A series that appears only in the second
    scrape started from 0; one absent from both gives None."""
    b = total(m1, name, labels)
    if b is None:
        return None
    return b - (total(m0, name, labels) or 0.0)


def labels_of(m: dict, name: str) -> list[dict]:
    return [dict(ls) for (n, ls) in m if n == name]
