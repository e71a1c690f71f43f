"""Spread of each metric over sets of runs, as the bound's rule takes it.

    python3 benchmark/tools/spreads.py chiprun_out/s1m_*.txt -- chiprun_out/s2m_*.txt

Each file is one run's standard output (the last line is the result);
`--` separates the sets.  For every metric: each set's median and spread
(quartile distance over the median, `statistics.quantiles(n=4)`), the
spread with the run farthest from the median left out, and how far the
second set's median lies from the first's.
"""

from __future__ import annotations

import json
import os
import statistics
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

from benchmark.harness.stats import spread  # noqa: E402


def _last_line(path: str) -> dict:
    with open(path) as f:
        lines = [x for x in f.read().splitlines() if x.strip()]
    return json.loads(lines[-1])


def _trimmed(values):
    med = statistics.median(values)
    far = max(values, key=lambda v: abs(v - med))
    rest = list(values)
    rest.remove(far)
    return rest


def main(argv):
    sets, cur = [], []
    for a in argv:
        if a == "--":
            sets.append(cur)
            cur = []
        else:
            cur.append(a)
    sets.append(cur)
    runs = [[_last_line(p) for p in s] for s in sets if s]
    names = sorted({m for s in runs for r in s for m in r["metrics"]})
    for s in runs:
        bad = [r for r in s if not r["correct"]]
        print(f"set of {len(s)} runs, {len(bad)} not correct")
    for name in names:
        meds = []
        for i, s in enumerate(runs):
            vals = [r["metrics"][name]["value"] for r in s
                    if name in r["metrics"]]
            if name == "setup_s":
                vals = vals[1:] if i == 0 else vals  # the first run compiles
            if len(vals) < 3:
                continue
            meds.append(statistics.median(vals))
            print(f"{name} set {i + 1}: n {len(vals)} median {meds[-1]:.6g} "
                  f"spread {100 * spread(vals):.3f} % "
                  f"trimmed {100 * spread(_trimmed(vals)):.3f} % "
                  f"min {min(vals):.6g} max {max(vals):.6g}")
        if len(meds) == 2:
            print(f"{name}: second median {100 * (meds[1] / meds[0] - 1):+.3f} %"
                  " of the first")


if __name__ == "__main__":
    main(sys.argv[1:])
