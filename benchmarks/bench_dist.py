"""Mesh-runtime superstep benchmark: wall-time per gAPI-BCD superstep for
A in {4, 8} agents on forced host devices, written to BENCH_dist.json so
the perf trajectory of the dist trainer starts populating.

    PYTHONPATH=src python benchmarks/bench_dist.py [--out BENCH_dist.json]

Each agent count runs in its own subprocess (jax pins the host device
count at first init), timing a tiny dense LM so the number measures the
superstep machinery (ring, masking, fused prox kernel in interpret mode)
rather than model math.
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

_CHILD = r"""
import os, sys, time, json
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=%(devices)d "
    + os.environ.get("XLA_FLAGS", ""))
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, %(src)r)

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh

from repro.configs.base import ArchConfig, TrainConfig
from repro.data.tokens import agent_batches
from repro.dist.trainer import init_train_state, make_train_step
from repro.models import build_model

A = %(agents)d
cfg = ArchConfig(name="bench-tiny", family="dense", source="bench",
                 num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
                 head_dim=32, d_ff=256, vocab_size=512,
                 tie_embeddings=True)
model = build_model(cfg)
mesh = Mesh(np.array(jax.devices()).reshape(A, 1, 1),
            ("agent", "replica", "model"))
tcfg = TrainConfig(num_agents=A, model_parallel=1, num_walks=2,
                   tau=0.05, rho=20.0)
state = init_train_state(model, tcfg, key=jax.random.PRNGKey(0))
step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
batches = agent_batches(cfg.vocab_size, A, 2, 64, seed=0)

toks, targs = next(batches)
batch = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(targs)}
with jax.set_mesh(mesh):
    t0 = time.monotonic()
    state, m = step_fn(state, batch, jnp.int32(0))
    jax.block_until_ready(m["loss"])
    compile_s = time.monotonic() - t0
    steps = 10
    t0 = time.monotonic()
    for s in range(1, steps + 1):
        state, m = step_fn(state, batch, jnp.int32(s))
    jax.block_until_ready(m["loss"])
    step_ms = (time.monotonic() - t0) / steps * 1e3

print(json.dumps({"agents": A, "devices": %(devices)d,
                  "compile_s": round(compile_s, 2),
                  "superstep_ms": round(step_ms, 2),
                  "loss": float(m["loss"])}))
"""


def bench(agents: int):
    src = os.path.join(os.path.dirname(__file__), "..", "src")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    env.setdefault("TPU_SKIP_MDS_QUERY", "1")
    code = _CHILD % {"agents": agents, "devices": agents,
                     "src": os.path.abspath(src)}
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    if res.returncode != 0:
        raise RuntimeError(res.stdout + res.stderr)
    return json.loads(res.stdout.strip().splitlines()[-1])


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="BENCH_dist.json")
    ap.add_argument("--agents", type=int, nargs="*", default=[4, 8])
    args = ap.parse_args()

    results = {"benchmark": "dist_superstep",
               "timestamp": time.strftime("%Y-%m-%dT%H:%M:%S"),
               "runs": []}
    for a in args.agents:
        r = bench(a)
        print(f"A={a}: superstep {r['superstep_ms']:.2f} ms "
              f"(compile {r['compile_s']:.1f}s)")
        results["runs"].append(r)

    with open(args.out, "w") as f:
        json.dump(results, f, indent=1)
    print("wrote", args.out)


if __name__ == "__main__":
    main()
