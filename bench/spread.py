"""Spread of a cell's runs, for setting and checking bounds.

    python3 bench/spread.py runs.jsonl [more.jsonl ...]

Each file holds the last stdout line of each run of one set, one JSON object
a line. For every metric it prints the median, the quartiles (Python's
``statistics.quantiles(values, n=4)``), the spread (interquartile distance
over the median) of each set, and five times the widest spread: the bound
that rule gives.
"""
from __future__ import annotations

import json
import statistics
import sys
from typing import Dict, List


def spread(values: List[float]) -> float:
    q1, _, q3 = statistics.quantiles(values, n=4)
    return (q3 - q1) / statistics.median(values)


def main(paths: List[str]) -> None:
    sets: List[Dict[str, List[float]]] = []
    for p in paths:
        by: Dict[str, List[float]] = {}
        with open(p) as f:
            for line in f:
                if line.strip():
                    r = json.loads(line)
                    for k, m in r["metrics"].items():
                        by.setdefault(k, []).append(m["value"])
        sets.append(by)
    for k in sorted({k for s in sets for k in s}):
        parts, widest = [], 0.0
        for s in sets:
            v = s.get(k, [])
            if len(v) >= 2:
                sp = spread(v)
                widest = max(widest, sp)
                parts.append(f"median {statistics.median(v):.6g} spread {sp:.4f} (n={len(v)})")
        print(f"{k}: " + "; ".join(parts) + f"; 5 x widest = {5 * widest:.4f}")


if __name__ == "__main__":
    main(sys.argv[1:])
