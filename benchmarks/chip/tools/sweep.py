"""Find the serving cell's knee: its traffic at a list of fixed rates.

    python3 benchmarks/chip/tools/sweep.py --cell <cell> --seed <n> \
        --seconds 30 --rates 2,3,4,6

For each rate, a fresh engine over the same model serves the cell's mix
at that rate for --seconds (then drains), and one JSON line reports
completed tokens/s, request latency p50/p95, the requests still queued
or running when the window closed, and the chip's memory.
"""
import argparse
import json
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import run as harness  # noqa: E402


def quarter(measured, q):
    from yardstick import percentile
    lat = measured["latencies_ms"]
    n = len(lat) // 4
    return percentile(lat[q * n:(q + 1) * n], 50) if n else None


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    import jax
    from drivers import serve
    from repro.models import build_model
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    dev = jax.devices()[:1]
    model = None
    for rate in (float(r) for r in args.rates.split(",")):
        cell = harness.Cell(harness.ROOT, args.cell, args.seed, dev,
                            seconds=args.seconds, bench=bench)
        cell.traffic["rate_per_s"] = rate
        model = model or build_model(cell.arch())
        state = serve.setup(cell, log=lambda m: print(m, flush=True),
                            model=model)
        measured = serve.window(cell, state, args.seconds,
                                log=lambda m: print(m, flush=True))
        stats = dev[0].memory_stats()
        serve.release(cell, state)
        harness._free(jax)
        print("SWEEP " + json.dumps({
            "rate": rate, **measured["e2e"],
            "attempted": measured["attempted"],
            "failed": measured["failed"],
            "steps": measured["steps_in_window"],
            "completed_in_window": len(measured["completed"]),
            "latency_p50_first_quarter_ms": quarter(measured, 0),
            "latency_p50_last_quarter_ms": quarter(measured, 3),
            "peak_bytes": stats.get("peak_bytes_in_use"),
            "bytes_limit": stats.get("bytes_limit")}), flush=True)


if __name__ == "__main__":
    main()
