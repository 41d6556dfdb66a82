"""Spreads of the runs tools/sets.py recorded, as the bounds are set.

    python3 benchmarks/chip/tools/spread.py runs/<cell>.jsonl

For each end-to-end metric: each set's median and quartile spread
((Q3 - Q1) / median, Python's statistics.quantiles), the wider spread,
five times it (the bound it asks for), and the second set's median
against the first's.  Sets are the untraced runs in order, split in two
halves.
"""
import json
import statistics
import sys


def spread(xs):
    q1, _, q3 = statistics.quantiles(xs, n=4)
    return (q3 - q1) / statistics.median(xs)


def main():
    recs = [json.loads(line) for line in open(sys.argv[1])]
    runs = [r for r in recs if not r["trace"] and r["result"]]
    half = len(runs) // 2
    sets = [runs[:half], runs[half:]]
    names = sorted({k for r in runs for k in r["result"]["metrics"]})
    for name in names:
        vals = [[r["result"]["metrics"][name]["value"] for r in s]
                for s in sets]
        sp = [spread(v) for v in vals if len(v) >= 2]
        meds = [statistics.median(v) for v in vals]
        print(json.dumps({
            "metric": name, "set_medians": meds,
            "set_spreads": sp, "widest": max(sp) if sp else None,
            "bound_5x": 5 * max(sp) if sp else None,
            "second_vs_first": meds[1] / meds[0] - 1 if len(meds) > 1
            else None, "values": vals}))
    bad = [r["seed"] for r in recs if not (r["result"] or {}).get("correct")]
    print(json.dumps({"runs": len(recs), "not_correct_seeds": bad}))


if __name__ == "__main__":
    main()
