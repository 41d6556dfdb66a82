"""Run a cell as the benchmark's check does: one process per run.

    python3 benchmarks/chip/tools/sets.py --cell <cell> --seconds 30 \
        --seeds 11,12,13 [--trace-seeds 21,22] [--repeat 2] \
        --out runs/<cell>.jsonl

Runs `benchmarks/chip/run.py` once per seed (the seeds in order, the
whole list --repeat times, so that two sets share their seeds), then
once per --trace-seeds seed with --trace 1.  Each run's last stdout line
(its result) and the end of its stderr go to --out, one JSON object a
line.  This process never imports JAX, so each run has the chips alone.
"""
import argparse
import json
import subprocess
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[3]


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--trace-seeds", default="")
    ap.add_argument("--repeat", type=int, default=1)
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    runs = [(int(s), 0) for _ in range(args.repeat)
            for s in args.seeds.split(",") if s]
    runs += [(int(s), 1) for s in args.trace_seeds.split(",") if s]
    with open(args.out, "a") as out:
        for i, (seed, trace) in enumerate(runs):
            t0 = time.monotonic()
            p = subprocess.run(
                [sys.executable, "benchmarks/chip/run.py", "--workload",
                 args.cell, "--seed", str(seed), "--seconds", args.seconds,
                 "--trace", str(trace)], cwd=ROOT, capture_output=True,
                text=True)
            lines = p.stdout.strip().splitlines()
            try:
                result = json.loads(lines[-1]) if lines else None
            except json.JSONDecodeError:
                result = None
            rec = {"cell": args.cell, "seed": seed, "trace": trace,
                   "index": i, "rc": p.returncode,
                   "wall_s": time.monotonic() - t0, "result": result,
                   "stderr_tail": p.stderr[-3000:]}
            out.write(json.dumps(rec) + "\n")
            out.flush()
            short = {k: v["value"] for k, v in
                     (result or {}).get("metrics", {}).items()}
            print(json.dumps({"seed": seed, "trace": trace,
                              "rc": p.returncode,
                              "wall_s": round(rec["wall_s"], 1),
                              "correct": (result or {}).get("correct"),
                              "metrics": short,
                              "compared": (result or {}).get("compared")}),
                  flush=True)


if __name__ == "__main__":
    main()
