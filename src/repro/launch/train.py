"""End-to-end API-BCD decentralized LM training driver.

The agent ring is laid over the devices it is given (all of
`jax.devices()` from the command line); on CPU, `--devices` forces a
host device count so the ring exists (demo scale). Example:

    PYTHONPATH=src python -m repro.launch.train \
        --arch qwen2-0.5b --smoke --agents 4 --walks 2 --steps 50 \
        --batch-per-agent 4 --seq 128 --devices 8

`Superstep` is the same path as a callable that takes its device list
(`chip_smoke.py` drives it on one chip). Writes checkpoints and a loss
log, and with --profile-dir a profiler trace (docs/dist.md, "Profiling
the superstep").
"""
import argparse
import contextlib
import os

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, SingleDeviceSharding

from repro.configs.base import TrainConfig
from repro.data.tokens import agent_batches
from repro.dist.sharding import state_shardings, train_batch_shardings
from repro.dist.trainer import init_train_state, make_train_step
from repro.models import build_model
from repro.utils.hotpath import hot_loop


class Superstep:
    """The API-BCD superstep over `devices`, driven one step at a time.

    The agents are laid on an ("agent", "replica", "model") mesh over
    the devices; on a single device there is no mesh and the agents are
    vmapped there.  The state is initialised straight into its layout
    (no full copy on one device first) and donated to every step.
    place=False leaves `state` abstract (shapes with their shardings),
    so `lower(abstract_batch())` compiles the step without allocating.

    Under `jax.profiler` each dispatch is a host span "apibcd.step"
    (`StepTraceAnnotation`, numbered by the step) and each upload in
    `next_batch` an "apibcd.batch_upload" span; inside the compiled step
    the phases carry `apibcd.*` scopes (`repro.dist.trainer`).
    """

    def __init__(self, cfg, devices, *, agents, walks, model_parallel=1,
                 batch_per_agent=4, seq=128, tau=0.05, rho=20.0,
                 paper_faithful=False, seed=0, place=True):
        devices = list(devices)
        a, mp = agents, model_parallel
        self.model = build_model(cfg)
        self.tcfg = TrainConfig(num_agents=a, model_parallel=mp,
                                num_walks=walks, tau=tau, rho=rho,
                                accumulate_between_visits=not paper_faithful)
        self.batches = agent_batches(cfg.vocab_size, a, batch_per_agent, seq,
                                     seed=seed)
        init = lambda key: init_train_state(self.model, self.tcfg, key)
        shapes = jax.eval_shape(init, jax.random.PRNGKey(seed))
        batch = {k: jax.ShapeDtypeStruct((a, batch_per_agent, seq),
                                         jnp.int32)
                 for k in ("tokens", "targets")}
        if len(devices) == 1:
            self.mesh = None
            one = SingleDeviceSharding(devices[0])
            st_sh = jax.tree.map(lambda _: one, shapes)
            self._batch_sh = jax.tree.map(lambda _: one, batch)
        else:
            self.mesh = agent_mesh(devices, a, mp)
            st_sh = state_shardings(self.mesh, shapes)
            self._batch_sh = train_batch_shardings(self.mesh, batch)
        self._batch_shapes = batch
        if place:
            self.state = jax.jit(init, out_shardings=st_sh)(
                jax.random.PRNGKey(seed))
        else:
            self.state = _abstract(shapes, st_sh)
        self.train_step = jax.jit(make_train_step(self.model, self.tcfg),
                                  out_shardings=(st_sh, None),
                                  donate_argnums=(0,))

    def _mesh_ctx(self):
        if self.mesh is None:
            return contextlib.nullcontext()
        return jax.set_mesh(self.mesh)

    def next_batch(self):
        with jax.profiler.TraceAnnotation("apibcd.batch_upload"):
            toks, targs = next(self.batches)
            return jax.device_put({"tokens": toks, "targets": targs},
                                  self._batch_sh)

    def abstract_batch(self):
        return _abstract(self._batch_shapes, self._batch_sh)

    def lower(self, batch, step=0):
        with self._mesh_ctx():
            return self.train_step.lower(self.state, batch, jnp.int32(step))

    @hot_loop
    def step(self, step, batch=None):
        """One superstep; returns its metrics (device arrays) without
        waiting for them."""
        if batch is None:
            batch = self.next_batch()
        span = jax.profiler.StepTraceAnnotation("apibcd.step", step_num=step)
        with span, self._mesh_ctx():
            self.state, metrics = self.train_step(self.state, batch,
                                                  jnp.int32(step))
        return metrics


def agent_mesh(devices, agents, model_parallel):
    """("agent", "replica", "model") mesh over `devices`; the replica
    axis takes what the agents and model parallelism leave."""
    replica = len(devices) // (agents * model_parallel)
    assert agents * model_parallel * replica == len(devices), (
        agents, model_parallel, len(devices))
    return Mesh(np.array(devices).reshape(agents, replica, model_parallel),
                ("agent", "replica", "model"))


def _abstract(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def _run_baseline(args, model, mesh):
    """The synchronous all-reduce data-parallel baseline on the mesh."""
    from repro.dist.trainer import make_dp_baseline_step
    from repro.optim import adamw, constant

    opt = adamw(weight_decay=0.0)
    params = model.init(jax.random.PRNGKey(0))
    opt_state = opt.init(params)
    step_fn = jax.jit(make_dp_baseline_step(model, opt, constant(3e-4)))
    batches = agent_batches(model.cfg.vocab_size, args.agents,
                            args.batch_per_agent, args.seq, seed=0)
    with jax.set_mesh(mesh):
        for step in range(args.steps):
            toks, targs = next(batches)
            batch = {"tokens": jnp.asarray(toks.reshape(-1, args.seq)),
                     "targets": jnp.asarray(targs.reshape(-1, args.seq))}
            params, opt_state, metrics = step_fn(params, opt_state,
                                                 batch, step)
            if step % args.log_every == 0:
                print(f"step {step:4d}  loss {float(metrics['loss']):.4f}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true",
                    help="use the reduced smoke config (CPU-feasible)")
    ap.add_argument("--agents", type=int, default=4)
    ap.add_argument("--walks", type=int, default=2)
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch-per-agent", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--tau", type=float, default=0.05)
    ap.add_argument("--rho", type=float, default=20.0)
    ap.add_argument("--devices", type=int, default=0,
                    help="force host device count (CPU demo); 0 = real")
    ap.add_argument("--baseline", action="store_true",
                    help="run the synchronous all-reduce DP baseline "
                         "instead of API-BCD")
    ap.add_argument("--paper-faithful", action="store_true",
                    help="disable gradient accumulation between visits "
                         "(idle agents, as in the paper)")
    ap.add_argument("--checkpoint-dir", default=None)
    ap.add_argument("--log-dir", default=None,
                    help="write JSONL metrics here")
    ap.add_argument("--log-every", type=int, default=5)
    ap.add_argument("--profile-dir", default=None,
                    help="trace every step after the first (which "
                         "compiles) with jax.profiler into this "
                         "directory, with a Perfetto copy")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    from repro.checkpoint import save_checkpoint
    from repro.configs import get_config, get_smoke
    from repro.utils.compile_cache import enable_compile_cache
    from repro.utils.logging import MetricLogger

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    devices = jax.devices()
    a, mp = args.agents, args.model_parallel
    print(f"mesh: agents={a} replica={len(devices) // (a * mp)} "
          f"model={mp}  arch={cfg.name}")

    if args.baseline:
        return _run_baseline(args, build_model(cfg),
                             agent_mesh(devices, a, mp))

    run = Superstep(cfg, devices, agents=a, walks=args.walks,
                    model_parallel=mp, batch_per_agent=args.batch_per_agent,
                    seq=args.seq, tau=args.tau, rho=args.rho,
                    paper_faithful=args.paper_faithful)
    logger = MetricLogger(args.log_dir, echo_every=args.log_every)
    for step in range(args.steps):
        if step == 1 and args.profile_dir:
            jax.block_until_ready(run.state)
            jax.profiler.start_trace(args.profile_dir,
                                     create_perfetto_trace=True)
        metrics = run.step(step)
        logger.log(step, loss=metrics["loss"], nll=metrics["nll"])
    if args.profile_dir and args.steps > 1:
        jax.block_until_ready(run.state)
        jax.profiler.stop_trace()
        print("profile written to", args.profile_dir)
    logger.close()

    if args.checkpoint_dir:
        save_checkpoint(args.checkpoint_dir, run.state, step=args.steps,
                        metadata={"arch": cfg.name})
        print("checkpoint written to", args.checkpoint_dir)


if __name__ == "__main__":
    main()
