#!/usr/bin/env python3
"""How widely a cell's runs spread: the rule the bounds are set by.

    python3 benchmark/spread.py set1.jsonl set2.jsonl

Each file holds the result lines (the last line of standard output) of one
set of runs of one cell, one line a run.  For every metric: each set's
median and its spread, the distance between the first and the third
quartile (``statistics.quantiles(values, n=4)``) as a share of the median;
the wider of the two spreads; and how far the second set's median lies from
the first's.  A bound is about five times the widest spread over the cells,
never under 1%.  ``setup_s`` leaves out each set's first run, which
compiles.
"""

import json
import statistics
import sys


def spread(values):
    if len(values) < 2:
        return float("nan")
    q = statistics.quantiles(values, n=4)
    return (q[2] - q[0]) / statistics.median(values)


def load(path):
    rows = [json.loads(ln) for ln in open(path) if ln.strip().startswith("{")]
    out = {}
    for n, row in enumerate(rows):
        for name, m in row["metrics"].items():
            if name == "setup_s" and n == 0:
                continue
            out.setdefault(name, []).append(m["value"])
    return out, rows


def main(argv):
    sets = [load(p) for p in argv]
    report = {}
    for name in sets[0][0]:
        per = [{"n": len(s[name]), "median": statistics.median(s[name]),
                "spread": spread(s[name])} for s, _ in sets if name in s]
        entry = {"sets": per, "widest_spread": max(p["spread"] for p in per)}
        if len(per) == 2:
            entry["second_over_first"] = per[1]["median"] / per[0]["median"] - 1
        report[name] = entry
    ok = all(r["correct"] for _, rows in sets for r in rows)
    print(json.dumps({"all_correct": ok, "metrics": report}, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
