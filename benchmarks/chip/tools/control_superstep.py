"""Readings that set the limits of a cell driven by `drivers/superstep.py`,
at the cell's own size, on the chip.

    python3 benchmarks/chip/tools/control_superstep.py --cell <cell> \
        --seeds 1,2:51,3 [--seconds 5] [--control-seeds 1,2] \
        [--trace-seed 4 --trace-seconds 20]

For each --seeds seed, a run of the cell with a --seconds window (or
the seconds given after the seed's colon): its `correct` and the
numbers it compared.  For each --control-seeds seed,
the plain reference follows the cell's first steps, and so do, in the
program's place, the reference in fp8 (the control: one precision below
the configuration's bfloat16), the reference on half of each batch and,
on a ring, the reference with no token exchange; each is compared with
the reference as the program is.  With --trace-seed, one traced run
comes first, whose log holds the model scopes' table.

`tools/control.py` does the same for the dense decoder's cells through
`references/apibcd.py`; here the model reference is the one the
configuration's `reference` key names (`references/superstep.py`).  One
JSON line per seed and reading; nothing here runs in a benchmark run.
"""
import argparse
import gc
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import run as harness  # noqa: E402
from tools.control import emit  # noqa: E402


def control_readings(cell, log=print):
    """{kind: [(name, value, limit)]} of the fp8 control and the faults
    against the reference, over the cell's checked steps."""
    import jax
    from drivers import train
    from references import superstep

    feed = train._feed(cell)
    batches = [next(feed) for _ in range(train.CHECKED_STEPS)]
    key = jax.random.PRNGKey(cell.model_seed)
    devs = cell.devices[:cell.chips]
    reference = cell.config["reference"]

    def follow(precision="f32", faults=()):
        return superstep.run(reference, cell.model, cell.traffic, key,
                             batches, devs, precision=precision,
                             faults=faults)

    ref = follow()
    kinds = [("control_fp8", "fp8", ()), ("half_batch", "f32",
                                          ("half_batch",))]
    if cell.traffic["agents"] > 1:
        kinds.append(("no_exchange", "f32", ("no_exchange",)))
    readings = {}
    for kind, precision, faults in kinds:
        got = follow(precision, faults)
        readings[kind] = train.compare_training(
            cell, got["losses"], got["grad_norms"], got["change_norms"],
            {i: v or {} for i, v in got["token_norms"].items()}, ref,
            log=log)
        del got
        gc.collect()
    return readings


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--trace-seed", type=int)
    ap.add_argument("--trace-seconds", type=float, default=20.0)
    args = ap.parse_args()
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    entry = [w for w in bench["workloads"] if w["name"] == args.cell][0]
    devices = jax.devices()[:entry["chips"]]

    def log(msg):
        print(msg, flush=True)

    def program(seed, seconds, trace):
        t0 = time.monotonic()
        res = harness.run_cell(harness.ROOT, args.cell, seed, seconds,
                               trace, devices, bench=bench, log=log)
        emit(cell=args.cell, seed=seed,
             kind="traced" if trace else "program",
             seconds=time.monotonic() - t0, correct=res["correct"],
             compared=res["compared"], metrics=res["metrics"],
             device=res["device"])
        harness._free(jax)

    if args.trace_seed is not None:
        program(args.trace_seed, args.trace_seconds, 1)
    for spec in (s for s in args.seeds.split(",") if s):
        seed, _, seconds = spec.partition(":")
        program(int(seed), float(seconds or args.seconds), 0)
    for seed in (int(s) for s in args.control_seeds.split(",") if s):
        t0 = time.monotonic()
        cell = harness.Cell(harness.ROOT, args.cell, seed, devices,
                            bench=bench)
        for kind, checks in control_readings(cell, log).items():
            emit(cell=args.cell, seed=seed, kind=kind,
                 seconds=time.monotonic() - t0,
                 compared={n: v for n, v, _ in checks})
        harness._free(jax)


if __name__ == "__main__":
    main()
