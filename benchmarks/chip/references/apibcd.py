"""Plain reference of the API-BCD superstep (gAPI-BCD, eq. 15 and 12b).

Agents are kept one by one, each on its own device, as plain arrays:
the local model x_i, the token value held at ring slot i, the M token
copies zhat_i, and the gradient accumulated between visits.  Each step:

  * every agent takes the gradient of its loss on its own batch;
  * the agents that hold a token this step ((i - t) mod A is a multiple
    of A / M) apply the mean of the gradients accumulated since their
    last visit:  x <- (rho x - g + tau sum_m zhat_m) / (rho + tau M),
    credit (x_new - x) / A to the token they hold, and copy the token
    into zhat for that walk; the others accumulate their gradient;
  * every token moves one hop on the ring (slot i -> slot i + 1).

`faults` plant what a broken program would do, for the control runs:
"half_batch" takes the loss over the first half of each batch only, and
"no_exchange" leaves the tokens where they are.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from references import dense_gqa


@functools.partial(jax.jit, static_argnums=(0, 4, 5))
def _value_and_grad(cfg_items, params, tokens, targets, precision, half):
    cfg = dict(cfg_items)
    if half:
        tokens, targets = tokens[: tokens.shape[0] // 2], \
            targets[: targets.shape[0] // 2]
    return jax.value_and_grad(
        lambda p: dense_gqa.loss(cfg, p, tokens, targets, precision))(params)


@functools.partial(jax.jit, donate_argnums=(0,),
                   static_argnames=("rho", "tau", "walks", "agents",
                                    "period"))
def _visit(x, g, zhats, token, *, rho, tau, walks, agents, period):
    """One token-holding visit: apply the mean accumulated gradient g /
    period to x (eq. 15) and credit (x_new - x) / agents to the token
    (eq. 12b).  zhats: the agent's non-zero token copies; token None
    is all zeros.  Returns (x_new, token_new)."""
    def leaf(x, g, token, *zs):
        zsum = sum(zs) if zs else 0.0
        x_new = (rho * x - g / period + tau * zsum) / (rho + tau * walks)
        credit = (x_new - x) / agents
        return x_new, credit if token is None else token + credit
    tok = token if token is not None else jax.tree.map(lambda _: None, x)
    pairs = jax.tree.map(leaf, x, g, tok, *zhats,
                         is_leaf=lambda v: v is None)
    is_pair = lambda p: isinstance(p, tuple)
    return (jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair),
            jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair))


def leaf_norms(tree):
    """{leaf name: L2 norm}; layer-stacked leaves give one per layer."""
    out = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(tree):
        name = jax.tree_util.keystr(path)
        if "segments" in name:
            n = jnp.sqrt(jnp.sum(jnp.square(leaf.astype(jnp.float32)),
                                 axis=tuple(range(1, leaf.ndim))))
            for i, v in enumerate(np.asarray(n, np.float64)):
                out[f"{name}[{i}]"] = float(v)
        else:
            out[name] = float(jnp.sqrt(jnp.sum(jnp.square(
                leaf.astype(jnp.float32)))))
    return out


def run(cfg, train, key, batches, devices, *, precision="f32", faults=()):
    """Follow the first len(batches) steps from the seeded init.

    cfg: the model sizes; train: {"agents", "walks", "tau", "rho"};
    batches: [(tokens [A, B, S], targets [A, B, S]), ...] as fed to
    the program.  Returns {"losses": [mean loss per step],
    "grad_norms": {agent: {leaf: norm}} of the gradient each agent
    active at step 0 applies, "change_norms": {agent: {leaf: norm}} of
    x after the last step minus x at the start, "token_norms": {ring
    slot: {leaf: norm} of the token value it holds after the last
    step's exchange, or None where it is still all zeros}}."""
    a, m = int(train["agents"]), int(train["walks"])
    rho, tau = float(train["rho"]), float(train["tau"])
    period = a // m
    cfg_items = tuple(sorted(cfg.items()))
    half = "half_batch" in faults
    dev = [devices[i % len(devices)] for i in range(a)]
    x0 = jax.jit(lambda k: dense_gqa.init(cfg, k))(key)
    x0_host = jax.tree.map(np.asarray, x0)
    x = [jax.device_put(x0, d) for d in dev]
    del x0
    token = [None] * a          # None: all zeros, not yet materialised
    zhat = [[None] * m for _ in range(a)]
    gacc = [None] * a
    losses, grad_norms = [], {}
    for t, (toks, targs) in enumerate(batches):
        rel = [(i - t) % a for i in range(a)]
        step_losses = []
        for i in range(a):
            loss, g = _value_and_grad(
                cfg_items, x[i], jax.device_put(toks[i], dev[i]),
                jax.device_put(targs[i], dev[i]), precision, half)
            step_losses.append(float(loss))
            if gacc[i] is not None:
                g = jax.tree.map(jnp.add, gacc[i], g)
                gacc[i] = None
            if rel[i] % period:
                gacc[i] = g
                continue
            if t == 0:
                grad_norms[i] = leaf_norms(
                    jax.tree.map(lambda v: v / period, g))
            zs = tuple(z for z in zhat[i] if z is not None)
            x[i], token[i] = _visit(x[i], g, zs, token[i], rho=rho, tau=tau,
                                    walks=float(m), agents=float(a),
                                    period=float(period))
            del g, zs
            zhat[i][rel[i] // period] = token[i]
        losses.append(float(np.mean(step_losses)))
        if "no_exchange" not in faults and a > 1:
            token = [None if token[i - 1] is None
                     else jax.device_put(token[i - 1], dev[i])
                     for i in range(a)]
    tokens = {i: (None if token[i] is None else
                  _host_change_norms(jax.tree.map(np.asarray, token[i]),
                                     None))
              for i in range(a)}
    del token, zhat, gacc
    change = {}
    for i in range(a):
        xi = jax.tree.map(np.asarray, x[i])
        x[i] = None
        change[i] = _host_change_norms(xi, x0_host)
    return {"losses": losses, "grad_norms": grad_norms,
            "change_norms": change, "token_norms": tokens}


def _host_change_norms(x, x0):
    """leaf_norms of x - x0 (of x where x0 is None), on the host in
    float64."""
    out = {}
    flat = jax.tree_util.tree_leaves_with_path(x)
    base = [None] * len(flat) if x0 is None else jax.tree.leaves(x0)
    for (path, a), b in zip(flat, base):
        name = jax.tree_util.keystr(path)
        d = np.asarray(a, np.float64)
        if b is not None:
            d = d - np.asarray(b, np.float64)
        if "segments" in name:
            n = np.sqrt(np.sum(np.square(d).reshape(d.shape[0], -1), 1))
            for j, v in enumerate(n):
                out[f"{name}[{j}]"] = float(v)
        else:
            out[name] = float(np.sqrt(np.sum(np.square(d))))
    return out
