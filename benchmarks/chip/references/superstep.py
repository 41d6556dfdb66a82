"""Plain reference of the API-BCD superstep for any model reference.

`references/apibcd.py` follows the superstep with the dense decoder of
`references/dense_gqa.py`; this one takes the model reference by name,
as a configuration's `reference` key gives it ("references/mla_moe.py"),
so that a configuration whose model dict nests groups (moe, mla) can be
followed.  The visit (eq. 15 and 12b) and the norms are apibcd's own;
the schedule, the faults ("half_batch", "no_exchange") and what is
returned are those of `apibcd.run`.
"""
from __future__ import annotations

import functools
import importlib
import json

import jax
import jax.numpy as jnp
import numpy as np

from references.apibcd import _host_change_norms, _visit, leaf_norms


def model_reference(path):
    """The module a config's `reference` names ("references/x.py")."""
    return importlib.import_module(
        path.removesuffix(".py").replace("/", "."))


@functools.partial(jax.jit, static_argnums=(0, 1, 5, 6))
def _value_and_grad(ref, cfg_json, params, tokens, targets, precision,
                    half):
    cfg = json.loads(cfg_json)
    if half:
        tokens, targets = tokens[: tokens.shape[0] // 2], \
            targets[: targets.shape[0] // 2]
    return jax.value_and_grad(
        lambda p: ref.loss(cfg, p, tokens, targets, precision))(params)


def run(reference, cfg, train, key, batches, devices, *, precision="f32",
        faults=()):
    """`apibcd.run` with the model reference `reference` (a config's
    `reference` path): the first len(batches) steps from the seeded
    init; returns {"losses", "grad_norms", "change_norms",
    "token_norms"} as `apibcd.run` does."""
    ref = model_reference(reference)
    a, m = int(train["agents"]), int(train["walks"])
    rho, tau = float(train["rho"]), float(train["tau"])
    period = a // m
    cfg_json = json.dumps(cfg, sort_keys=True)
    half = "half_batch" in faults
    dev = [devices[i % len(devices)] for i in range(a)]
    x0 = jax.jit(lambda k: ref.init(cfg, k))(key)
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
                ref, cfg_json, x[i], jax.device_put(toks[i], dev[i]),
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
