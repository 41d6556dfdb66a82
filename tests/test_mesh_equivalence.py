"""Exact numerical equivalence: the mesh API-BCD superstep (SPMD, vmap
over agents, token ring) vs a transparent numpy re-implementation of the
same semantics, on a convex quadratic where everything is analytic.

Runs in a subprocess with 8 host devices (tests are pinned to 1 device).
"""
import os
import subprocess
import sys


CODE = r"""
import sys
sys.path.insert(0, "src")
import numpy as np
import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from repro.configs.base import TrainConfig
from repro.dist.trainer import init_train_state, make_train_step

P = 8          # model dim
A, M = 4, 2    # agents, walks
TAU, RHO = 0.3, 2.0

rng = np.random.default_rng(0)
A_data = rng.standard_normal((A, 16, P)).astype(np.float32)
b_data = rng.standard_normal((A, 16)).astype(np.float32)


class QuadModel:
    '''Quadratic "LM": loss_i(w) = 0.5 mean (A_i w - b_i)^2.'''

    def init(self, key):
        del key
        return {"w": jnp.zeros((P,), jnp.float32)}

    def train_loss(self, params, batch):
        r = batch["a"] @ params["w"] - batch["b"]
        loss = 0.5 * jnp.mean(r * r)
        return loss, {"nll": loss, "aux": jnp.zeros(())}


model = QuadModel()
tcfg = TrainConfig(num_agents=A, model_parallel=1, num_walks=M,
                   tau=TAU, rho=RHO, accumulate_between_visits=False)
mesh = Mesh(np.array(jax.devices()).reshape(A, 2, 1),
            ("agent", "replica", "model"))
state = init_train_state(model, tcfg, key=jax.random.PRNGKey(0))
step_fn = jax.jit(make_train_step(model, tcfg))

batch = {"a": jnp.asarray(A_data), "b": jnp.asarray(b_data)}

# ---- numpy re-implementation of the superstep semantics ----
x = np.zeros((A, P), np.float32)
tok = np.zeros((A, P), np.float32)
zh = np.zeros((A, M, P), np.float32)
period = A // M

def np_step(x, tok, zh, step):
    grads = np.stack([
        (A_data[i].T @ (A_data[i] @ x[i] - b_data[i])) / A_data[i].shape[0]
        for i in range(A)])
    rel = (np.arange(A) - step) % A
    active = (rel % period) == 0
    walk_id = rel // period
    x_new = x.copy()
    for i in range(A):
        if active[i]:
            zsum = zh[i].sum(axis=0)
            x_new[i] = (RHO * x[i] - grads[i] + TAU * zsum) / (RHO + TAU * M)
    tok_new = tok + (x_new - x) / A
    zh_new = zh.copy()
    for i in range(A):
        if active[i]:
            zh_new[i, walk_id[i]] = tok_new[i]
    tok_new = np.roll(tok_new, 1, axis=0)
    return x_new, tok_new, zh_new

with jax.set_mesh(mesh):
    for step in range(3 * A):
        state, metrics = step_fn(state, batch, jnp.int32(step))
        x, tok, zh = np_step(x, tok, zh, step)

        np.testing.assert_allclose(np.asarray(state["params"]["w"]), x,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(state["token"]["w"]), tok,
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_allclose(np.asarray(state["zhat"]["w"]), zh,
                                   rtol=2e-5, atol=2e-5)

print("MESH_EQUIV_OK")
"""


def test_mesh_superstep_matches_numpy_reference():
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    res = subprocess.run([sys.executable, "-c", CODE], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "MESH_EQUIV_OK" in res.stdout, res.stdout + res.stderr
