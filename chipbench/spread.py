#!/usr/bin/env python3
"""Spread of a set of runs, as the bounds are set from it:

    python3 chipbench/spread.py <result files of one set> [-- <files of the second set>]

Each file's last line is a run's result line.  For every metric: the
values, the median, and the spread = (third quartile - first quartile) /
median with `statistics.quantiles(values, n=4)`.  With two sets it also
prints, per metric, the wider spread, five times it (the bound to set, never
under 1 %), and how far the second set's median lies from the first's.
"""

from __future__ import annotations

import json
import statistics
import sys


def read_set(paths: list[str]) -> dict[str, list[float]]:
    out: dict[str, list[float]] = {}
    for p in paths:
        with open(p) as fh:
            lines = [ln for ln in fh.read().splitlines() if ln.strip()]
        res = json.loads(lines[-1])
        if not res["correct"]:
            print(f"NOT CORRECT: {p}: {res['compared']}")
        for name, m in res["metrics"].items():
            out.setdefault(name, []).append(m["value"])
    return out


def spread(values: list[float]) -> tuple[float, float]:
    med = statistics.median(values)
    q = statistics.quantiles(values, n=4)
    return med, (q[2] - q[0]) / med


def main() -> int:
    args = sys.argv[1:]
    sets = [[]]
    for a in args:
        if a == "--":
            sets.append([])
        else:
            sets[-1].append(a)
    data = [read_set(s) for s in sets if s]
    for name in data[0]:
        rows = []
        for k, d in enumerate(data):
            med, sp = spread(d[name])
            rows.append((med, sp))
            print(f"{name:24s} set {k + 1}: n={len(d[name])} median={med:.6g} "
                  f"spread={sp * 100:.3f}%  values={[round(v, 4) for v in d[name]]}")
        if len(rows) == 2:
            wide = max(r[1] for r in rows)
            print(f"{name:24s} wider spread {wide * 100:.3f}% -> bound "
                  f"{max(0.01, 5 * wide):.4f}; second median "
                  f"{(rows[1][0] / rows[0][0] - 1) * 100:+.3f}% of the first")
    return 0


if __name__ == "__main__":
    sys.exit(main())
