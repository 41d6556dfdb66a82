"""End-to-end behaviour tests for the paper's system.

These exercise the full pipeline the way a user would: decentralized
training end-to-end (data -> graph -> walks -> method -> metric), the
serving loop, and the example entry points.
"""
import subprocess
import sys
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (
    APIBCD, CyclicWalk, centralized_solution, hamiltonian_cycle,
    random_graph, simulate_incremental,
)
from repro.core import losses as L
from repro.data import make_problem


def test_end_to_end_decentralized_regression():
    """Full paper pipeline: surrogate data -> network -> async API-BCD
    simulation -> NMSE within 3x of the centralized solution."""
    problem = make_problem("cpusmall", num_agents=10, subsample=1024)
    net = random_graph(10, zeta=0.7, seed=0)
    order = hamiltonian_cycle(net)
    method = APIBCD(problem, tau=0.05, num_walks=5)
    walks = [CyclicWalk(order) for _ in range(5)]
    res = simulate_incremental(method, net, walks, max_iterations=300,
                               eval_every=20)
    final = res.trace[-1].metric
    x_star = centralized_solution(problem)
    best = L.evaluate(problem, x_star)
    assert final < max(3 * best, 0.15), (final, best)


def test_end_to_end_lm_training_improves():
    """Decentralized LM training on a simulated mesh improves the loss
    (subprocess: needs 8 host devices)."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    code = r"""
import os, sys
sys.path.insert(0, "src")
import numpy as np, jax, jax.numpy as jnp
from jax.sharding import Mesh
from repro.configs.base import ArchConfig, TrainConfig
from repro.data.tokens import agent_batches
from repro.dist.trainer import init_train_state, make_train_step
from repro.models import build_model

cfg = ArchConfig(name="t", family="dense", source="test", num_layers=2,
                 d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                 d_ff=256, vocab_size=512, tie_embeddings=True)
model = build_model(cfg)
mesh = Mesh(np.array(jax.devices()).reshape(4, 2, 1),
            ("agent", "replica", "model"))
tcfg = TrainConfig(num_agents=4, model_parallel=1, num_walks=2,
                   tau=0.05, rho=20.0)
state = init_train_state(model, tcfg, key=jax.random.PRNGKey(0))
step_fn = jax.jit(make_train_step(model, tcfg), donate_argnums=(0,))
batches = agent_batches(cfg.vocab_size, 4, 4, 64, seed=0)
losses = []
with jax.set_mesh(mesh):
    for step in range(40):
        toks, targs = next(batches)
        state, m = step_fn(state, {"tokens": jnp.asarray(toks),
                                   "targets": jnp.asarray(targs)},
                           jnp.int32(step))
        losses.append(float(m["loss"]))
first, last = sum(losses[:8]) / 8, sum(losses[-8:]) / 8
print("FIRST", first, "LAST", last)
assert last < first - 0.05, (first, last)
print("LM_E2E_OK")
"""
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "LM_E2E_OK" in res.stdout, res.stdout + res.stderr


def test_end_to_end_serving_greedy_decode():
    """Prefill + multi-step greedy decode stays finite and matches
    teacher-forced prefill on the generated prefix."""
    from functools import partial
    from repro.configs import get_smoke
    from repro.models import build_model

    cfg = get_smoke("internlm2-1.8b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    b, p, n_new = 2, 12, 6
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (b, p)), jnp.int32)

    prefill = jax.jit(partial(model.prefill, cache_len=p + n_new))
    decode = jax.jit(model.decode_step)
    logits, caches = prefill(params, {"tokens": toks})
    token = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    generated = [token]
    for i in range(n_new - 1):
        logits, caches = decode(params, token, caches, jnp.int32(p + i))
        assert bool(jnp.isfinite(logits).all())
        token = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        generated.append(token)

    # teacher-forcing the full generated prefix reproduces the last step
    full = jnp.concatenate([toks] + generated[:-1], axis=1)
    logits_full, _ = jax.jit(model.prefill)(params, {"tokens": full})
    _, caches2 = jax.jit(partial(model.prefill, cache_len=full.shape[1]))(
        params, {"tokens": full[:, :-1]})
    logits_step, _ = decode(params, full[:, -1:], caches2,
                            jnp.int32(full.shape[1] - 1))
    np.testing.assert_allclose(
        np.asarray(logits_step[:, 0]), np.asarray(logits_full[:, -1]),
        rtol=3e-2, atol=3e-2)


def test_quickstart_example_runs():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["PYTHONPATH"] = "src"
    res = subprocess.run(
        [sys.executable, "examples/quickstart.py"], env=env,
        capture_output=True, text=True, timeout=900,
        cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "API-BCD" in res.stdout and "simulated time" in res.stdout, (
        res.stdout + res.stderr)
