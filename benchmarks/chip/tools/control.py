"""Readings that set a cell's limits, at the cell's own size, on the chip.

    python3 benchmarks/chip/tools/control.py --cell <cell> \
        --seeds 1,2,3 [--seconds 8] [--program 1]

Training cells: for each seed, the plain reference follows the cell's
first steps, and so do, in the program's place, the reference in fp8
(the control: one precision below the configuration's bfloat16), the
reference on half of each batch, and on the ring the reference with no
token exchange; each is compared with the reference as the program is.
With --program 1 the program's own readings come first (a run of the
cell with a --seconds window).

The serving cell: for each seed, the program serves the cell's traffic
for --seconds, and over the same seeded sample of finished requests
the reference reads the widest gap of the served tokens (the program)
and of the tokens the fp8 reference puts first (the control).

One JSON line per seed and reading; nothing here runs in a benchmark
run.
"""
import argparse
import gc
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))

import run as harness  # noqa: E402


def emit(**kw):
    print("READING " + json.dumps(kw), flush=True)


def train_seed(cell, seed, program, seconds):
    import jax
    from drivers import train
    from references import apibcd

    if program:
        res = harness.run_cell(harness.ROOT, cell.name, seed, seconds, 0,
                               cell.devices, bench=cell.bench,
                               log=lambda m: print(m, flush=True))
        emit(cell=cell.name, seed=seed, kind="program",
             compared=res["compared"], metrics=res["metrics"])
    c = harness.Cell(harness.ROOT, cell.name, seed, cell.devices,
                     bench=cell.bench)
    feed = train._feed(c)
    batches = [next(feed) for _ in range(train.CHECKED_STEPS)]
    key = jax.random.PRNGKey(c.model_seed)
    devs = c.devices[:c.chips]
    ref = apibcd.run(c.model, c.traffic, key, batches, devs)
    kinds = [("control_fp8", "fp8", ()), ("half_batch", "f32",
                                          ("half_batch",))]
    if c.traffic["agents"] > 1:
        kinds.append(("no_exchange", "f32", ("no_exchange",)))
    for kind, precision, faults in kinds:
        t0 = time.monotonic()
        got = apibcd.run(c.model, c.traffic, key, batches, devs,
                         precision=precision, faults=faults)
        checks = train.compare_training(
            c, got["losses"], got["grad_norms"], got["change_norms"],
            {i: v or {} for i, v in got["token_norms"].items()}, ref,
            log=lambda m: print(m, flush=True))
        emit(cell=c.name, seed=seed, kind=kind,
             seconds=time.monotonic() - t0,
             compared={n: v for n, v, _ in checks})
        gc.collect()


def serve_seed(cell, seed, seconds, model):
    from drivers import serve

    c = harness.Cell(harness.ROOT, cell.name, seed, cell.devices,
                     seconds=seconds, bench=cell.bench)
    state = serve.setup(c, log=lambda m: print(m, flush=True), model=model)
    measured = serve.window(c, state, seconds,
                            log=lambda m: print(m, flush=True))
    kept = serve.release(c, state)
    harness._free(__import__("jax"))
    picked = serve.sample(kept["requests"], kept["outputs"], seed)
    t0 = time.monotonic()
    gaps = serve.reference_gaps(c, kept["requests"], kept["outputs"],
                                picked, precisions=("f32", "fp8"))
    emit(cell=c.name, seed=seed, kind="program_and_control",
         seconds=time.monotonic() - t0, requests=len(picked),
         served=sum(len(kept["outputs"][i]) for i in picked),
         program=max(gaps["f32"]), control=max(gaps["fp8"]),
         program_all=gaps["f32"], control_all=gaps["fp8"],
         failed=measured["failed"], e2e=measured["e2e"])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8.0)
    ap.add_argument("--program", type=int, default=0)
    args = ap.parse_args()
    import jax
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    bench = harness.read_json(harness.ROOT / "BENCHMARK.json")
    entry = [w for w in bench["workloads"] if w["name"] == args.cell][0]
    cell = harness.Cell(harness.ROOT, args.cell, 0,
                        jax.devices()[:entry["chips"]], bench=bench)
    model = None
    for seed in (int(s) for s in args.seeds.split(",")):
        if cell.traffic["driver"] == "train":
            train_seed(cell, seed, args.program, args.seconds)
        else:
            if model is None:
                from repro.models import build_model
                model = build_model(cell.arch())
            serve_seed(cell, seed, args.seconds, model)
        harness._free(jax)


if __name__ == "__main__":
    main()
