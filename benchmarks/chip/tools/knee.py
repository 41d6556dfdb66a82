"""Pick the serving cell's rate from a sweep (tools/sweep.py output).

    python3 benchmarks/chip/tools/knee.py <sweep log> <traffic file>

The knee is the highest swept rate at which, and at every lower swept
rate, the backlog does not grow: every request finished, and the median
latency of the last quarter of requests is at most 1.5 times that of the
first quarter.  The mix's rate_per_s becomes 0.8 of the knee, rounded to
0.05 requests/s.
"""
import json
import sys


def main():
    sweeps = [json.loads(line.split("SWEEP ", 1)[1])
              for line in open(sys.argv[1]) if line.startswith("SWEEP ")]
    steady = []
    for s in sorted(sweeps, key=lambda s: s["rate"]):
        if s["failed"] or s["latency_p50_last_quarter_ms"] \
                > 1.5 * s["latency_p50_first_quarter_ms"]:
            break
        steady.append(s["rate"])
    knee = max(steady) if steady else min(s["rate"] for s in sweeps) / 2
    rate = round(0.8 * knee / 0.05) * 0.05
    mix = json.loads(open(sys.argv[2]).read())
    mix["rate_per_s"] = round(rate, 2)
    open(sys.argv[2], "w").write(json.dumps(mix, indent=2) + "\n")
    print(json.dumps({"knee": knee, "rate_per_s": mix["rate_per_s"],
                      "steady": steady}))


if __name__ == "__main__":
    main()
