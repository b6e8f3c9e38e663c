#!/usr/bin/env python3
"""Quartile spread of each end-to-end metric over sets of runs.

    python3 bench/tools/spread.py set1/*.log -- set2/*.log

Each file holds one run's output; its last line is the result JSON.
For each set and metric: the median, the spread (Q3 - Q1 over the
median, Python's ``statistics.quantiles(n=4)``) and the values; then
the wider of the sets' spreads, the bound five times it would give, and
whether every run was correct.
"""

from __future__ import annotations

import json
import statistics
import sys
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))

from bench.stats import spread  # noqa: E402


def _last_json(path: str) -> dict:
    lines = [ln for ln in Path(path).read_text().splitlines() if ln.strip()]
    return json.loads(lines[-1])


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    widest: dict[str, float] = {}
    for i, files in enumerate(s for s in sets if s):
        runs = [_last_json(f) for f in files]
        print(f"set {i}: {len(runs)} runs, correct "
              f"{sum(r['correct'] for r in runs)}/{len(runs)}")
        for name in runs[0]["metrics"]:
            vals = [r["metrics"][name]["value"] for r in runs]
            sp = spread(vals) if len(vals) >= 2 else float("nan")
            widest[name] = max(widest.get(name, 0.0), sp)
            print(f"  {name}: median {statistics.median(vals)!r} "
                  f"spread {sp!r} values {vals}")
    for name, sp in widest.items():
        print(f"widest {name}: {sp!r} -> 5x = {5 * sp!r}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
