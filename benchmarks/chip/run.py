"""Run one benchmark cell on the chip and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

Everything a cell is made of is found by name, from the cell's entry in
`BENCHMARK.json` at the root of the checkout:

    configs/<config>.json     model sizes, the program's config id and
                              the plain reference that checks it
    traffic/<traffic>.json    the traffic mix, and the driver that
                              sends it (drivers/<driver>.py)
    cells/<cell>.json         the limits of the comparison that decides
                              `correct`, and what the cell alone sets
    metrics/<metric>.py       one reader per per-layer metric

A run sets up (weights and state from the seed, every shape of the
cell compiled or read from the compile cache at <checkout>/.jax_cache,
the first steps that the reference follows), measures for --seconds,
compares what the timed path produced with the plain reference, and
prints one JSON line last.  --trace 1 profiles the window and reports
the per-layer metrics instead of the end-to-end ones.  Without a TPU,
or with fewer chips than the cell asks for, it exits non-zero and
prints no result.
"""
import time

PROCESS_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
TRACE_DIR = ROOT / ".bench_traces"


class BenchError(RuntimeError):
    """The cell cannot be run here."""


def load_module(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def read_json(path):
    path = Path(path)
    if not path.is_file():
        raise BenchError(f"missing {path}")
    return json.loads(path.read_text())


class CompileLog:
    """Counts XLA compilations (persistent-cache hits included) and sums
    the seconds spent tracing, lowering and compiling (copied from
    chip_smoke.py)."""

    _SPANS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._SPANS:
            self.seconds += duration
        if event == self._SPANS[-1]:
            self.count += 1


class Cell:
    """What a driver is given: the cell's files, the seed and the
    devices.  `span(name)` writes a host annotation into the trace."""

    def __init__(self, root, name, seed, devices, *, seconds=0.0,
                 bench=None, data=HERE):
        bench = bench or read_json(Path(root) / "BENCHMARK.json")
        entry = [w for w in bench["workloads"] if w["name"] == name]
        if not entry:
            raise BenchError(f"no workload {name!r} in BENCHMARK.json")
        self.entry = entry[0]
        self.name = name
        self.bench = bench
        self.seed = int(seed)
        self.window_seconds = float(seconds)
        self.devices = list(devices)
        self.chips = int(self.entry["chips"])
        cfg_entry = [c for c in bench["configs"]
                     if c["name"] == self.entry["config"]][0]
        self.config = read_json(Path(root) / cfg_entry["file"])
        self.traffic = read_json(
            Path(data) / "traffic" / f"{self.entry['traffic']}.json")
        self.limits = read_json(Path(data) / "cells" / f"{name}.json")
        self.model = self.config["model"]

    @property
    def model_seed(self):
        """A 31-bit seed for `jax.random.PRNGKey`, mixed from --seed."""
        import numpy as np
        return int(np.random.SeedSequence(self.seed).generate_state(1)[0]
                   & 0x7FFFFFFF)

    def arch(self):
        """The program's ArchConfig at the sizes of the config file."""
        from repro.configs.base import ArchConfig
        return ArchConfig(**self.model)

    def span(self, name):
        import jax
        return jax.profiler.TraceAnnotation(name)

    def metric_names(self, key):
        """The cell's metrics of BENCHMARK.json's `key` list."""
        return [m for m in self.bench[key]
                if "workloads" not in m or self.name in m["workloads"]]


def _free(jax):
    gc.collect()
    for d in jax.live_arrays():
        d.delete()
    gc.collect()


def run_cell(root, name, seed, seconds, trace, devices, *, bench=None,
             data=HERE, log=print):
    """Set up, measure and check one cell; returns the result dict."""
    import jax

    cell = Cell(root, name, seed, devices, seconds=seconds, bench=bench,
                data=data)
    driver = load_module(HERE / "drivers" / f"{cell.traffic['driver']}.py",
                         f"bench_driver_{cell.traffic['driver']}")
    compiles = CompileLog(jax)
    state = driver.setup(cell, log=log)
    setup_s = time.monotonic() - PROCESS_START
    mark = (compiles.count, compiles.seconds)
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(str(TRACE_DIR))
    with cell.span("bench.window"):
        measured = driver.window(cell, state, float(seconds), log=log)
    if trace:
        jax.profiler.stop_trace()
    in_window = compiles.count - mark[0]
    log(f"compiles_in_window={in_window} "
        f"compile_s_in_window={compiles.seconds - mark[1]:.6f} "
        f"compiles_total={compiles.count} "
        f"compile_s_total={compiles.seconds:.6f}")
    used = cell.devices[:cell.chips]
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in used)
    # the program's state goes before the reference runs, so that the
    # reference neither sets the peak nor runs out of memory
    counters = driver.release(cell, state)
    _free(jax)
    checks = driver.check(cell, counters, log=log)
    correct = all(v is not None and v <= lim for _, v, lim in checks)
    device = {"platform": used[0].platform, "kind": used[0].device_kind,
              "count": len(used), "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct),
              "attempted": int(measured["attempted"]),
              "failed": int(measured["failed"])}
    if trace:
        from devtrace import breakdown, load, mean_busy_s
        tr = load(TRACE_DIR)
        device["busy_s"] = mean_busy_s(tr)
        device["window_s"] = tr.window_s
        metrics = {}
        for m in cell.metric_names("per_layer"):
            reader = load_module(HERE / "metrics" / f"{m['name']}.py",
                                 f"bench_metric_{m['name']}")
            value = reader.read(cell, tr, measured)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        result["breakdown"] = breakdown(tr)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
    else:
        metrics = {m["name"]: {"value": measured["e2e"][m["name"]],
                               "unit": m["unit"]}
                   for m in cell.metric_names("end_to_end")
                   if m["name"] != "setup_s"}
        metrics["setup_s"] = {"value": setup_s, "unit": "s"}
    result.update(metrics=metrics, device=device)
    result["compared"] = {n: {"value": v, "limit": lim}
                          for n, v, lim in checks}
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = read_json(ROOT / "BENCHMARK.json")
    entry = [w for w in bench["workloads"] if w["name"] == args.workload]
    if not entry:
        raise BenchError(f"no workload {args.workload!r} in BENCHMARK.json")
    if not (ROOT / "src" / "repro").is_dir():
        raise BenchError(f"{ROOT} holds no program (src/repro)")
    sys.path.insert(0, str(ROOT / "src"))
    sys.path.insert(0, str(HERE))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise BenchError(f"needs a TPU; JAX found {devices[0].platform!r}")
    chips = int(entry[0]["chips"])
    if len(devices) < chips:
        raise BenchError(f"the cell needs {chips} chips; JAX found "
                         f"{len(devices)}")
    from repro.utils.compile_cache import enable_compile_cache
    enable_compile_cache()

    def log(msg):
        print(msg, file=sys.stderr, flush=True)

    result = run_cell(ROOT, args.workload, args.seed, args.seconds,
                      args.trace, devices[:chips], bench=bench, log=log)
    for name, c in result["compared"].items():
        log(f"compared {name}={c['value']!r} limit={c['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except BenchError as e:
        print(f"benchmark: {e}", file=sys.stderr)
        sys.exit(2)
