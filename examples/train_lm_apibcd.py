"""End-to-end driver: decentralized LM training with API-BCD on a mesh.

Presets:
  tiny  (default) — ~6M-param qwen2-family model, 60 steps, CPU-feasible
                    (forces 8 host devices: 4 agents x 2-way FSDP).
  paper           — ~100M-param model, 300 steps (sized for a real slice;
                    runs on CPU too if you have hours to spare).

    PYTHONPATH=src python examples/train_lm_apibcd.py
    PYTHONPATH=src python examples/train_lm_apibcd.py --preset paper
"""
import argparse
import os

ap = argparse.ArgumentParser()
ap.add_argument("--preset", choices=["tiny", "paper"], default="tiny")
ap.add_argument("--steps", type=int, default=0)
ap.add_argument("--baseline", action="store_true",
                help="also run the synchronous all-reduce DP baseline")
args = ap.parse_args()

os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=8 "
                           + os.environ.get("XLA_FLAGS", ""))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
from jax.sharding import Mesh  # noqa: E402

from repro.configs.base import ArchConfig, TrainConfig  # noqa: E402
from repro.data.tokens import agent_batches  # noqa: E402
from repro.dist.trainer import (init_train_state,  # noqa: E402
                                make_dp_baseline_step, make_train_step)
from repro.models import build_model  # noqa: E402
from repro.optim import adamw, constant  # noqa: E402

if args.preset == "tiny":
    cfg = ArchConfig(name="lm-tiny", family="dense", source="examples",
                     num_layers=4, d_model=256, num_heads=4, num_kv_heads=2,
                     head_dim=64, d_ff=512, vocab_size=2048,
                     tie_embeddings=True)
    steps, seq, bpa = args.steps or 60, 128, 4
else:
    cfg = ArchConfig(name="lm-100m", family="dense", source="examples",
                     num_layers=12, d_model=768, num_heads=12,
                     num_kv_heads=4, head_dim=64, d_ff=2048,
                     vocab_size=32768, tie_embeddings=True)
    steps, seq, bpa = args.steps or 300, 512, 8

model = build_model(cfg)
a, mp = 4, 1
mesh = Mesh(np.array(jax.devices()).reshape(a, 2, mp),
            ("agent", "replica", "model"))
tcfg = TrainConfig(num_agents=a, model_parallel=mp, num_walks=2,
                   tau=0.05, rho=20.0)
print(f"API-BCD: {cfg.name}, agents={a}, walks={tcfg.num_walks}, "
      f"steps={steps}")

state = init_train_state(model, tcfg, key=jax.random.PRNGKey(0))
step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
batches = agent_batches(cfg.vocab_size, a, bpa, seq, seed=0)

losses = []
with jax.set_mesh(mesh):
    for step in range(steps):
        toks, targs = next(batches)
        batch = {"tokens": jnp.asarray(toks), "targets": jnp.asarray(targs)}
        state, metrics = step_fn(state, batch, jnp.int32(step))
        losses.append(float(metrics["loss"]))
        if step % 10 == 0 or step == steps - 1:
            print(f"step {step:4d}  loss {losses[-1]:.4f}")

first, last = np.mean(losses[:10]), np.mean(losses[-10:])
print(f"\nloss: first-10 avg {first:.4f} -> last-10 avg {last:.4f} "
      f"({'improved' if last < first else 'NOT improved'})")

if args.baseline:
    print("\nall-reduce DP baseline:")
    opt = adamw(weight_decay=0.0)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    bstep = jax.jit(make_dp_baseline_step(model, opt, constant(3e-4)))
    batches = agent_batches(cfg.vocab_size, a, bpa, seq, seed=0)
    with jax.set_mesh(mesh):
        for step in range(steps):
            toks, targs = next(batches)
            batch = {"tokens": jnp.asarray(toks.reshape(-1, seq)),
                     "targets": jnp.asarray(targs.reshape(-1, seq))}
            params, opt_state, metrics = bstep(params, opt_state, batch,
                                               step)
            if step % 10 == 0 or step == steps - 1:
                print(f"step {step:4d}  loss {float(metrics['loss']):.4f}")
