"""Drive the API-BCD superstep (`repro.launch.train.Superstep`).

setup    builds one Superstep at the cell's agents and walks on the
         cell's chips (state initialised on the device from the seed),
         and drives it through its first CHECKED_STEPS steps with the
         window's own feed; the state before, after the first and after
         the last of them is copied to the host for the check.
window   keeps stepping that same object for the window's seconds,
         with at most IN_FLIGHT steps queued ahead of the device, and
         times the whole window to the completion of the last step.
check    follows the checked steps with the plain reference
         (`references.apibcd`) and compares each step's loss, the first
         gradient the update applied, and the change of every leaf.
"""
from __future__ import annotations

import time
from collections import deque

import jax
import numpy as np

from compare import host_leaf_norms, kept_leaves, leaf_gap, token_slots_gap
from gen.tokens import agent_batches

CHECKED_STEPS = 3
IN_FLIGHT = 2


def _feed(cell):
    t = cell.traffic
    return agent_batches(cell.model["vocab_size"], t["agents"],
                         t["batch_per_agent"], t["seq"], seed=cell.seed)


def _agent(tree, i):
    return {k: v[i] for k, v in tree.items()}


def _host_params(run, part="params"):
    """One part of the agents' state on the host: {leaf path: [A, ...]
    array}."""
    flat = jax.tree_util.tree_leaves_with_path(run.state[part])
    return {jax.tree_util.keystr(p): np.asarray(v) for p, v in flat}


def setup(cell, log=print):
    from repro.launch.train import Superstep

    t = cell.traffic
    run = Superstep(cell.arch(), cell.devices[:cell.chips],
                    agents=t["agents"], walks=t["walks"],
                    batch_per_agent=t["batch_per_agent"], seq=t["seq"],
                    tau=t["tau"], rho=t["rho"], seed=cell.model_seed)
    shardings = jax.tree.map(lambda s: s.sharding, run.abstract_batch())
    feed = _feed(cell)
    state = {"run": run, "feed": feed, "shardings": shardings,
             "batches": [], "losses": [], "step": 0}
    state["x0"] = _host_params(run)
    for i in range(CHECKED_STEPS):
        toks, targs = next(feed)
        state["batches"].append((toks, targs))
        batch = jax.device_put({"tokens": toks, "targets": targs}, shardings)
        metrics = run.step(i, batch)
        state["losses"].append(float(metrics["loss"]))
        if i == 0:
            state["x1"] = _host_params(run)
    state["x_last"] = _host_params(run)
    state["token"] = _host_params(run, "token")
    state["step"] = CHECKED_STEPS
    log(f"train: checked-step losses {state['losses']}")
    return state


def window(cell, state, seconds, log=print):
    run, feed, sh = state["run"], state["feed"], state["shardings"]
    t = cell.traffic
    tokens_per_step = t["agents"] * t["batch_per_agent"] * t["seq"]
    pending = deque()
    steps = 0
    t0 = time.monotonic()
    while time.monotonic() - t0 < seconds:
        with cell.span("bench.batch_upload"):
            toks, targs = next(feed)
            batch = jax.device_put({"tokens": toks, "targets": targs}, sh)
        with cell.span("bench.step"):
            pending.append(run.step(state["step"], batch))
        state["step"] += 1
        steps += 1
        if len(pending) > IN_FLIGHT:
            with cell.span("bench.wait"):
                # the queue bound: wait for the step IN_FLIGHT behind
                jax.block_until_ready(pending.popleft())
    with cell.span("bench.wait"):
        jax.block_until_ready(list(pending))
        jax.block_until_ready(run.state)
    wall = time.monotonic() - t0
    loss = float(pending[-1]["loss"]) if pending else float("nan")
    log(f"train: {steps} steps in {wall:.6f}s, last loss {loss:.6f}")
    return {"e2e": {"train_tokens_per_s": steps * tokens_per_step / wall},
            "attempted": steps,
            "failed": 0 if np.isfinite(loss) else steps,
            "steps": steps, "wall_s": wall,
            "tokens_per_step": tokens_per_step}


def release(cell, state):
    """Drop the program's state; keep what the check needs."""
    keep = {k: state[k] for k in ("batches", "losses", "x0", "x1",
                                  "x_last", "token")}
    state.clear()
    return keep


def _program_readings(cell, kept):
    """Per-agent leaf norms of the program's first applied gradient
    (worked out from the update, eq. 15, with zhat = 0 at the start)
    and of each agent's change over the checked steps."""
    t = cell.traffic
    a, m = t["agents"], t["walks"]
    rho, tau = float(t["rho"]), float(t["tau"])
    period = a // m
    active0 = [i for i in range(a) if i % period == 0]
    grads, change, tokens = {}, {}, {}
    for i in range(a):
        tokens[i] = host_leaf_norms({k: v[i] for k, v in
                                     kept["token"].items()})
        x0 = {k: v[i] for k, v in kept["x0"].items()}
        if i in active0:
            x1 = {k: v[i] for k, v in kept["x1"].items()}
            grads[i] = host_leaf_norms(
                {k: rho * x0[k].astype(np.float64)
                 - (rho + tau * m) * x1[k].astype(np.float64)
                 for k in x0})
        change[i] = host_leaf_norms(
            {k: kept["x_last"][k][i].astype(np.float64)
             - x0[k].astype(np.float64) for k in x0})
    return grads, change, tokens


def check(cell, kept, log=print, precision="f32", faults=()):
    from references import apibcd

    t = cell.traffic
    ref = apibcd.run(cell.model, t, jax.random.PRNGKey(cell.model_seed),
                     kept["batches"], cell.devices[:cell.chips],
                     precision=precision, faults=faults)
    grads, change, tokens = _program_readings(cell, kept)
    return compare_training(cell, kept["losses"], grads, change, tokens,
                            ref, log)


def compare_training(cell, losses, grads, change, tokens, ref, log=print):
    """[(name, value, limit)] of the training comparison."""
    lim = cell.limits["limits"]
    loss_gap = max(abs(p - r) / abs(r) for p, r in zip(losses,
                                                       ref["losses"]))
    keep = kept_leaves(ref["grad_norms"].values())
    grad_gap, grad_leaf = max(
        (leaf_gap(grads[i], ref["grad_norms"][i], keep) + (i,)
         for i in ref["grad_norms"]), key=lambda g: g[0])[:2]
    change_gap, change_leaf = max(
        (leaf_gap(change[i], ref["change_norms"][i], keep) + (i,)
         for i in ref["change_norms"]), key=lambda g: g[0])[:2]
    token_gap, token_leaf = token_slots_gap(tokens, ref["token_norms"],
                                            keep)
    log(f"train check: losses {losses} reference {ref['losses']}; "
        f"worst gradient leaf {grad_leaf}, worst change leaf "
        f"{change_leaf}, worst token leaf {token_leaf}; {len(keep)} "
        f"leaves compared")
    return [("loss_gap", loss_gap, lim["loss_gap"]),
            ("grad_gap", grad_gap, lim["grad_gap"]),
            ("change_gap", change_gap, lim["change_gap"]),
            ("token_gap", token_gap, lim["token_gap"])]
