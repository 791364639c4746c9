#!/usr/bin/env python3
"""Medians and spreads of a set of runs, as the driver reads them.

    python3 benchmark/spread.py <run log or last-line file> ...

Each file's last line is a run's JSON object. Runs are grouped by the
cell in the file name's first part (`<cell>-s<seed>-...`), and for each
metric the median and the spread (distance between the quartiles over
the median) are printed: a bound is about five times the widest spread
over the cells, never under 1 %."""

from __future__ import annotations

import json
import os
import re
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from lib import stats  # noqa: E402


def main(paths: list[str]) -> int:
    runs: dict[str, dict[str, list[float]]] = {}
    for p in paths:
        with open(p) as f:
            last = f.read().strip().splitlines()[-1]
        try:
            line = json.loads(last)
        except ValueError:
            print(f"{p}: no result line", file=sys.stderr)
            continue
        cell = re.split(r"-s(?:eed)?\d", os.path.basename(p))[0]
        if not line["correct"] or line["failed"]:
            print(f"{p}: correct={line['correct']} failed={line['failed']}")
        for name, m in line["metrics"].items():
            runs.setdefault(cell, {}).setdefault(name, []).append(m["value"])
    for cell, metrics in sorted(runs.items()):
        for name, vals in metrics.items():
            sp = stats.spread(vals)
            print(f"{cell:<22} {name:<20} n={len(vals)} median "
                  f"{stats.median(vals):10.3f}  spread "
                  f"{'   n/a' if sp is None else f'{100 * sp:6.2f} %'}  "
                  f"[{min(vals):.3f} .. {max(vals):.3f}]")
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
