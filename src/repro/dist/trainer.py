"""The sharded API-BCD mesh trainer (gAPI-BCD superstep, eq. 15 + 12b).

Realizes the fresh-token synchronous logical view of Algorithm 2 that
Theorems 2/3 analyze, as one SPMD program over the ("agent", "replica",
"model") mesh:

  * every state leaf carries a leading agent axis ([A, ...]; token copies
    zhat are [A, M, ...]),
  * each superstep, the M tokens sit at M of the A ring slots; the
    round-robin schedule `(slot - step) % (A/M) == 0` marks the
    token-holding agents active,
  * active agents apply the closed-form gAPI-BCD update (eq. 15) through
    the fused Pallas kernel in `repro.kernels.prox_update` (one VMEM pass
    produces both x_new and the token credit delta (x_new - x)/A,
    eq. 12b),
  * tokens then move one hop on the agent ring via `jax.lax.ppermute`:
    under `jax.vmap(axis_name="agent")` with no mesh (all agents on one
    device), and per device under `jax.shard_map` when a mesh is set
    (`jax.set_mesh`), where it is a collective-permute between chips.
    The kernel runs per device shard the same way.

Paper-faithful mode (`accumulate_between_visits=False`) leaves the
A - M non-holding agents bit-untouched — the invariant
`tests/dist_check_script.py` asserts.  The beyond-paper default
accumulates every agent's gradient between visits and applies the mean
at the next activation, so no batch is wasted on idle agents.

Every line of the step sits under one `jax.named_scope`, which the
compiled program keeps as each operation's `op_name`: apibcd.grad (the
local gradients), .accumulate, .zsum, .prox (eq. 15 and 12b), .select
(only token holders move), .token (12c) and .exchange (the ring hop);
docs/dist.md reads them in a profiler trace.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.dist.sharding import stacked_spec
from repro.kernels.ops import prox_update


def _mesh_axes():
    """{axis: size} of the mesh set by `jax.set_mesh`, or None when
    there is no mesh (or one device): the agents are then vmapped."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return None
    return dict(mesh.shape)


def _on_shards(fn, axes, *leaves):
    """Run `fn` on each device's own shard of agent-stacked `leaves`, in
    the layout `state_shardings` gives them (the mesh's agent axis holds
    one agent per index).  Used where GSPMD would move whole stacks: a
    Pallas call is opaque to it (every device would gather every
    agent's operands) and a vmapped ppermute lowers to a gather (every
    device would receive every agent's token)."""
    spec = stacked_spec(leaves[0].shape,
                        {a: axes[a] for a in ("replica", "model")
                         if a in axes})
    # check_vma=False: a Pallas call declares no varying mesh axes for its
    # outputs (and the CPU interpreter would trip over the check)
    return jax.shard_map(fn, in_specs=(spec,) * len(leaves),
                         out_specs=spec, check_vma=False)(*leaves)


def _prox_update_tree(params, g_eff, zsum, **kw):
    """The fused kernel over agent-stacked trees -> (x_full, d_full).
    It is elementwise, so running it per shard is exact."""
    axes = _mesh_axes()
    local = lambda x, g, z: prox_update(x, g, z, **kw)
    if axes is not None:
        local = functools.partial(_on_shards, local, axes)
    pairs = jax.tree.map(local, params, g_eff, zsum)
    is_pair = lambda p: isinstance(p, tuple)
    return (jax.tree.map(lambda p: p[0], pairs, is_leaf=is_pair),
            jax.tree.map(lambda p: p[1], pairs, is_leaf=is_pair))


def _broadcast(mask, leaf):
    """[A] mask -> [A, 1, 1, ...] matching leaf's rank."""
    return mask.reshape((mask.shape[0],) + (1,) * (leaf.ndim - 1))


def init_train_state(model, tcfg, key=None):
    """Build the API-BCD train state: {"params", "token", "zhat", "gacc"}.

    params: [A, ...] per-agent models x_i, replicated from one model.init
            (the paper's common initialization; tokens then start at 0 so
            z and zhat agree with eq. (6) relative to the common init).
    token:  [A, ...] value of the token currently at each ring slot.
    zhat:   [A, M, ...] local token copies zhat_{i,m}.
    gacc:   [A, ...] gradient accumulator (between-visit accumulation).

    key=None returns ShapeDtypeStructs (abstract — safe for 100B-scale
    configs in the dry-run); pass a PRNGKey to materialize.
    """
    a, m = tcfg.num_agents, tcfg.num_walks
    assert a % m == 0, (a, m)

    def build(k):
        p0 = model.init(k)
        params = jax.tree.map(
            lambda x: jnp.tile(x[None], (a,) + (1,) * x.ndim), p0)
        token = jax.tree.map(
            lambda x: jnp.zeros((a,) + x.shape, jnp.float32), p0)
        zhat = jax.tree.map(
            lambda x: jnp.zeros((a, m) + x.shape, jnp.float32), p0)
        gacc = jax.tree.map(
            lambda x: jnp.zeros((a,) + x.shape, jnp.float32), p0)
        return {"params": params, "token": token, "zhat": zhat,
                "gacc": gacc}

    if key is None:
        return jax.eval_shape(lambda: build(jax.random.PRNGKey(0)))
    return build(key)


def make_train_step(model, tcfg):
    """Build the jit-able SPMD superstep: (state, batch, step) ->
    (new_state, metrics).

    batch leaves are [A, ...] (per-agent shards); step is a scalar int32.
    Semantics match the transparent numpy reference in
    tests/test_mesh_equivalence.py exactly.
    """
    a, m = tcfg.num_agents, tcfg.num_walks
    assert a % m == 0, (a, m)
    period = a // m
    tau, rho = float(tcfg.tau), float(tcfg.rho)
    accumulate = bool(tcfg.accumulate_between_visits)

    grad_fn = jax.value_and_grad(model.train_loss, has_aux=True)

    perm = [(i, (i + 1) % a) for i in range(a)]

    def ring_shift(leaf):
        # one hop on the agent ring: slot i receives slot i-1's token
        hop = lambda t: jax.lax.ppermute(t, "agent", perm)
        axes = _mesh_axes()
        if axes is None:
            return jax.vmap(hop, axis_name="agent")(leaf)
        assert axes["agent"] == a, (axes, a)
        return _on_shards(hop, axes, leaf)

    def step_fn(state, batch, step):
        params, token = state["params"], state["token"]
        zhat, gacc = state["zhat"], state["gacc"]

        with jax.named_scope("apibcd.grad"):
            (losses, metr), grads = jax.vmap(grad_fn)(params, batch)
            metrics = {"loss": jnp.mean(losses),
                       "nll": jnp.mean(metr["nll"]),
                       "aux": jnp.mean(metr["aux"])}

        with jax.named_scope("apibcd.select"):
            rel = jnp.mod(jnp.arange(a) - step, a)
            active = (rel % period) == 0         # [A] token-holding agents
            walk_id = rel // period              # which token sits here

        with jax.named_scope("apibcd.accumulate"):
            if accumulate:
                gsum = jax.tree.map(jnp.add, gacc, grads)
                # mean over the visit period (steady-state visit interval)
                g_eff = jax.tree.map(lambda g: g / period, gsum)
                gacc_new = jax.tree.map(
                    lambda g: jnp.where(_broadcast(active, g), 0.0, g), gsum)
            else:
                g_eff = grads
                gacc_new = gacc

        with jax.named_scope("apibcd.zsum"):
            zsum = jax.tree.map(lambda z: z.sum(axis=1), zhat)

        # fused closed-form update (eq. 15) + token credit (eq. 12b);
        # with one agent per walk every agent is active, nothing reads
        # the old x after the update, and x_new can take its buffer
        with jax.named_scope("apibcd.prox"):
            x_full, d_full = _prox_update_tree(
                params, g_eff, zsum, tau=tau, rho=rho, num_walks=m,
                num_agents=a, in_place=period == 1)

        # only token-holding agents move; inactive rows stay bit-identical
        with jax.named_scope("apibcd.select"):
            params_new = jax.tree.map(
                lambda xf, x: jnp.where(_broadcast(active, x), xf, x),
                x_full, params)
            delta = jax.tree.map(
                lambda d: jnp.where(_broadcast(active, d), d, 0.0), d_full)

        # the token credit, then zhat_{i, walk_id[i]} <- z (12c) for
        # active slots only
        with jax.named_scope("apibcd.token"):
            token_new = jax.tree.map(jnp.add, token, delta)
            wmask = active[:, None] & (jnp.arange(m)[None, :]
                                       == walk_id[:, None])   # [A, M]
            zhat_new = jax.tree.map(
                lambda zh, t: jnp.where(
                    wmask.reshape((a, m) + (1,) * (zh.ndim - 2)),
                    t[:, None], zh),
                zhat, token_new)

        with jax.named_scope("apibcd.exchange"):
            token_out = jax.tree.map(ring_shift, token_new)

        return ({"params": params_new, "token": token_out,
                 "zhat": zhat_new, "gacc": gacc_new}, metrics)

    return step_fn


def make_dp_baseline_step(model, opt, schedule):
    """Synchronous all-reduce data-parallel baseline (what API-BCD
    replaces): one parameter set, global-batch gradient, optimizer step.

    Returns (params, opt_state, batch, step) -> (params, opt_state,
    metrics).  Under a sharded global batch XLA inserts the gradient
    all-reduce automatically.
    """
    from repro.optim.optimizers import apply_updates

    grad_fn = jax.value_and_grad(model.train_loss, has_aux=True)

    def step_fn(params, opt_state, batch, step):
        (loss, metr), grads = grad_fn(params, batch)
        lr = schedule(step)
        updates, opt_state = opt.update(grads, opt_state, params, lr)
        params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, **metr}

    return step_fn
