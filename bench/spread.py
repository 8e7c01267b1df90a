"""Spread of repeated runs, from which the end-to-end bounds are set.

    python3 bench/spread.py SET_A_LINES SET_B_LINES

Each argument is a file of result lines (the last stdout line of each run
of one cell, one JSON object per line). For every metric it prints each
set's median, its spread (the distance between the first and the third
quartile of `statistics.quantiles(values, n=4)`, as a share of the
median), the wider of the two, and five times that, the bound the rule
asks for (never under 1 %). It also prints how far the second set's
median lies from the first's.
"""
from __future__ import annotations

import json
import statistics
import sys


def spread(values: list) -> float:
    """Inter-quartile distance as a share of the median."""
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def load(path: str) -> dict:
    """{metric: [values]} of the result lines in `path`."""
    out: dict = {}
    with open(path) as f:
        for line in f:
            line = line.strip()
            if not line:
                continue
            for name, m in json.loads(line)["metrics"].items():
                out.setdefault(name, []).append(m["value"])
    return out


def table(sets: list) -> list:
    """Rows (metric, medians, spreads, widest, bound, median shift)."""
    rows = []
    for name in sets[0]:
        vals = [s[name] for s in sets if name in s]
        meds = [statistics.median(v) for v in vals]
        sprs = [spread(v) for v in vals]
        widest = max(sprs)
        rows.append((name, meds, sprs, widest, max(5 * widest, 0.01),
                     meds[-1] / meds[0] - 1))
    return rows


def main(argv=None) -> int:
    paths = (argv or sys.argv[1:])
    for name, meds, sprs, widest, bound, shift in table(
            [load(p) for p in paths]):
        print(f"{name}: medians {meds} spreads {sprs} widest {widest:.6f} "
              f"bound {bound:.4f} second/first {shift:+.6f}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
