"""A tiny copy of the benchmark's cells for tests on the CPU: the same
cell names, drivers, generators, comparisons and limits, with small
model sizes and short traffic.  It adds two cells that the benchmark has
files for but does not run yet (see PERF.md, Open questions), so that
their paths stay tested: SERVE, the serving cell, with its
configuration, mix, limits and metrics as they would enter
BENCHMARK.json, and RING, four agents on a ring with two walks, under
the one-agent cell's limits."""
from __future__ import annotations

import json
import shutil
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parents[1]
ROOT = HERE.parents[1]
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MODEL = {"num_layers": 2, "d_model": 64, "num_heads": 4,
              "num_kv_heads": 2, "head_dim": 16, "d_ff": 128,
              "vocab_size": 256}
TINY_TRAIN = {"batch_per_agent": 4, "seq": 16}
TINY_SERVE = {"rate_per_s": 12.0, "max_batch": 4,
              "prompt_tokens": {"median": 24, "sigma": 1.0, "min": 16,
                                "max": 48},
              "output_tokens": {"median": 6, "sigma": 1.0, "min": 2,
                                "max": 20}}
TINY_POOL = {"num_blocks": 64}
SERVE = "internlm2-1.8b.serve-chat"
_SERVE_ONLY = {"workloads": [SERVE]}
SERVE_ENTRIES = {
    "configs": [{"name": "internlm2-1.8b",
                 "source": "https://arxiv.org/abs/2403.17297",
                 "file": "benchmarks/chip/configs/internlm2-1.8b.json",
                 "reduced": [], "why": "tests"}],
    "workloads": [{"name": SERVE, "config": "internlm2-1.8b",
                   "traffic": "serve-chat", "chips": 1, "why": "tests"}],
    "end_to_end": [
        dict(name="serve_tokens_per_s", unit="tokens/s", better="higher",
             bound=0.25, source="host_clock", **_SERVE_ONLY),
        dict(name="request_latency_p95_ms", unit="ms", better="lower",
             bound=0.25, source="host_clock", **_SERVE_ONLY),
        dict(name="itl_p95_ms", unit="ms", better="lower", bound=0.25,
             source="host_clock", **_SERVE_ONLY)],
    "per_layer": [
        dict(name=n, unit=u, better=b, source=src, layer=layer,
             moves="itl_p95_ms", **_SERVE_ONLY)
        for n, u, b, src, layer in (
            ("serve_sched_host_ms_per_step", "ms", "lower",
             "program_counter", "scheduler"),
            ("serve_step_device_ms", "ms", "lower", "device_trace",
             "jitted serving steps"),
            ("serve_step_mfu_pct", "%", "higher", "host_clock",
             "whole serving step"),
            ("device_idle_pct.serve", "%", "lower", "device_trace",
             "device"))]}
RING = "qwen2-0.5b.apibcd-ring4"
RING_TRAFFIC = {"driver": "train", "agents": 4, "walks": 2, "tau": 0.05,
                "rho": 20.0}


def bench():
    """BENCHMARK.json as committed."""
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def full_bench():
    """BENCHMARK.json with the serving cell's entries added."""
    b = bench()
    for key, entries in SERVE_ENTRIES.items():
        names = {e["name"] for e in b[key]}
        b[key] += [dict(e) for e in entries if e["name"] not in names]
    return b


def tiny_root(tmp_path):
    """(root, data dir) of a tiny benchmark under tmp_path."""
    tmp_path = Path(tmp_path)
    b = full_bench()
    (tmp_path / "traffic").mkdir(exist_ok=True)
    (tmp_path / "cells").mkdir(exist_ok=True)
    b["workloads"].append({"name": RING, "config": "qwen2-0.5b",
                           "traffic": "ring", "chips": 4, "why": "tests"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "qwen2-0.5b.apibcd-a1" in m.get("workloads", []):
            m["workloads"].append(RING)
    for c in b["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        cfg["model"].update(TINY_MODEL)
        if "serve" in cfg:
            cfg["serve"].update(TINY_POOL)
        path = tmp_path / f"{c['name']}.json"
        path.write_text(json.dumps(cfg))
        c["file"] = str(path)
    for w in b["workloads"]:
        if w["name"] == RING:
            t = dict(RING_TRAFFIC)
            limits = HERE / "cells" / "qwen2-0.5b.apibcd-a1.json"
        else:
            t = json.loads((HERE / "traffic" / f"{w['traffic']}.json")
                           .read_text())
            limits = HERE / "cells" / f"{w['name']}.json"
        t.update(TINY_TRAIN if t["driver"] == "train" else TINY_SERVE)
        (tmp_path / "traffic" / f"{w['traffic']}.json").write_text(
            json.dumps(t))
        shutil.copy(limits, tmp_path / "cells" / f"{w['name']}.json")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp_path, tmp_path


def run_tiny(tmp_path, cell, seed=7, seconds=1.0, trace=0):
    import jax
    import run as harness

    root, data = tiny_root(tmp_path)
    return harness.run_cell(root, cell, seed, seconds, trace,
                            jax.devices(), bench=json.loads(
                                (root / "BENCHMARK.json").read_text()),
                            data=data, log=lambda *_: None)
