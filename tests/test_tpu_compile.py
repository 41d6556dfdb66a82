"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode lets through: block shapes
not aligned to the tiling, kernels that overrun fast memory, programs
that do not fit the device.  These tests compile the prox_update kernel,
the API-BCD superstep (on one chip, and as the four-chip ring) and the
serving engine's decode step at qwen2-0.5b's published widths for v5e
chips.  Nothing runs, so they say nothing about results or speed.

Only one process at a time may load the TPU library, and every test
worker imports this file: the topology is described inside a
module-scoped fixture, never at import time.
"""
import contextlib
import math
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.ops import prox_update
from repro.launch.train import Superstep
from repro.models import build_model

V5E_HBM_BYTES = 16 * 10**9      # one v5e chip (Google Cloud, "TPU v5e")


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@contextlib.contextmanager
def _on_tpu():
    """Trace as the chip run does: in 32-bit mode (the test suite turns
    on float64 for the convex reference code), and on the TPU branch of
    `jax.default_backend()`, which the program reads to pick compiled
    kernels and which here is the CPU."""
    with pytest.MonkeyPatch.context() as mp, jax.enable_x64(False):
        mp.setattr(jax, "default_backend", lambda: "tpu")
        yield


@pytest.fixture(autouse=True)
def as_on_tpu():
    with _on_tpu():
        yield


def _planned_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


@pytest.fixture(scope="module")
def full_width_step(topo):
    """qwen2-0.5b's A=1, M=1 superstep at published widths and depth,
    lowered and compiled for one chip: (Superstep, lowered, compiled)."""
    with _on_tpu():
        run = Superstep(get_config("qwen2-0.5b"), topo.devices[:1],
                        agents=1, walks=1, batch_per_agent=4, seq=128,
                        place=False)
        lowered = run.lower(run.abstract_batch())
        return run, lowered, lowered.compile()


# qwen2-0.5b's leaves as the A=1 superstep holds them: the tied
# embedding, an MLP weight, the final norm, and the stacked K projection
# (21504 rows: its last 2048-row block is ragged); then a leaf whose
# second-minor dim is off the 8-row tile (leading dim in the grid), one
# wider than a column block (ragged columns), and one narrower than a
# lane tile with many rows (rwkv6's stacked w_lora_a: 64 of 128 lanes);
# then deepseek-v2-lite's stacked router (64 lanes), latent projection
# wkv_a (576 wide) and routed expert stack (8 held a layer, 1408 wide)
@pytest.mark.parametrize("shape", [(1, 151936, 896), (1, 24, 896, 4864),
                                   (1, 896), (1, 24, 896, 128),
                                   (3, 5, 257), (2, 40000),
                                   (1, 24, 2048, 64), (1, 4, 2048, 64),
                                   (1, 5, 2048, 576),
                                   (1, 4, 8, 2048, 1408)])
def test_prox_update_kernel_compiles(one_chip, shape):
    x = jax.ShapeDtypeStruct(shape, jnp.float32, sharding=one_chip)
    step = jax.jit(partial(prox_update, tau=0.05, rho=20.0, num_walks=1,
                           num_agents=1, interpret=False))
    compiled = step.lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_train_superstep_compiles_full_width(full_width_step):
    """A=1, M=1 at published widths and depth: the step holds the
    compiled kernel and plans to fit one chip."""
    _, lowered, compiled = full_width_step
    assert "tpu_custom_call" in lowered.as_text()
    assert _planned_bytes(compiled) < V5E_HBM_BYTES


def _kernel_calls(hlo):
    """[(shape, aliased)] of the step's prox_update kernels, each
    checked to read three and write two arrays of one f32 shape."""
    import re

    shape_of = dict(re.findall(r"%([\w.-]+) = (f32\[[\d,]*\])", hlo))
    calls = []
    for ln in hlo.splitlines():
        if 'custom_call_target="tpu_custom_call"' not in ln:
            continue
        result, operands = ln.split(" custom-call(", 1)
        outs = re.findall(r"f32\[[\d,]*\]", result)
        ins = [shape_of[n] for n in
               re.findall(r"%([\w.-]+)", operands.split(")", 1)[0])]
        assert len(outs) == 2 and len(ins) == 3, ln[:160]
        assert len(set(outs + ins)) == 1, ln[:160]
        calls.append((outs[0],
                      "output_to_operand_aliasing={{0}: (0, {})}" in ln))
    return calls


def _folded(leaves, agents_per_chip):
    """Each leaf's shape as a chip's kernel sees it: its agents' share,
    leading dims folded into rows (a bitcast: every second-minor dim
    fills whole 8-row tiles)."""
    assert all(s.shape[-2] % 8 == 0 for s in leaves if s.ndim > 2)
    return sorted(f"f32[{agents_per_chip * math.prod(s.shape[1:-1])},"
                  f"{s.shape[-1]}]" for s in leaves)


def test_train_superstep_updates_each_leaf_in_its_own_layout(
        full_width_step):
    """One prox_update kernel per parameter leaf (14), each reading x, g
    and zsum and writing x_new and the credit in the leaf's own shape,
    and x_new in x's buffer (one walk per agent: every agent is active);
    nothing in the step is re-tiled as [rows, 1024]."""
    import re

    run, _, compiled = full_width_step
    hlo = compiled.as_text()
    calls = _kernel_calls(hlo)
    assert all(aliased for _, aliased in calls)
    assert sorted(shape for shape, _ in calls) == \
        _folded(jax.tree.leaves(run.state["params"]), 1)
    assert not re.search(r"f32\[(\d+,)*1024\]", hlo)


def test_train_ring_compiles_full_width_per_shard(topo):
    """The four-chip ring (A=4, M=2, one agent per chip) at published
    widths and depth: under `shard_map` each chip's kernels update its
    own agent's [1, ...] shard of each leaf in the leaf's layout, x_new
    in a buffer of its own (with two walks the select still reads x),
    and the step plans to fit one chip."""
    import re

    with _on_tpu():
        run = Superstep(get_config("qwen2-0.5b"), topo.devices[:4],
                        agents=4, walks=2, batch_per_agent=4, seq=128,
                        place=False)
        with jax.set_mesh(run.mesh):
            compiled = run.train_step.lower(
                run.state, run.abstract_batch(),
                jax.ShapeDtypeStruct((), jnp.int32)).compile()
    hlo = compiled.as_text()
    calls = _kernel_calls(hlo)
    assert not any(aliased for _, aliased in calls)
    assert sorted(shape for shape, _ in calls) == \
        _folded(jax.tree.leaves(run.state["params"]), 1)
    assert not re.search(r"f32\[(\d+,)*1024\]", hlo)
    assert _planned_bytes(compiled) < V5E_HBM_BYTES


def test_mla_moe_superstep_compiles_at_its_cut_widths(topo):
    """The deepseek-v2-lite cell's step (1 dense + 4 MoE layers, 8 of 64
    experts held, 12800 vocabulary rows, published widths; A=1, M=1,
    8 x 1024 tokens), built from the benchmark's configuration as the
    harness builds it, plans under 15 GB of the chip, with one update
    kernel per parameter leaf."""
    import json
    from pathlib import Path

    from repro.configs.base import ArchConfig

    path = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
            / "configs" / "deepseek-v2-lite.json")
    cfg = ArchConfig(**json.loads(path.read_text())["model"])
    run = Superstep(cfg, topo.devices[:1], agents=1, walks=1,
                    batch_per_agent=8, seq=1024, place=False)
    compiled = run.lower(run.abstract_batch()).compile()
    calls = _kernel_calls(compiled.as_text())
    assert len(calls) == len(jax.tree.leaves(run.state["params"]))
    assert _planned_bytes(compiled) < 15 * 10**9


def test_train_superstep_kernel_sits_under_its_phase_scope(topo):
    """Compiled for the chip, each prox_update kernel (the Pallas call,
    `tpu_custom_call`, named after it) carries the apibcd.prox scope and
    each layer scan apibcd.grad's (the smoke config: the same program at
    small widths)."""
    import re

    from repro.configs import get_smoke
    run = Superstep(get_smoke("qwen2-0.5b"), topo.devices[:1], agents=2,
                    walks=2, batch_per_agent=2, seq=16, place=False)
    hlo = run.lower(run.abstract_batch()).compile().as_text()
    kernels = [ln for ln in hlo.splitlines()
               if 'custom_call_target="tpu_custom_call"' in ln]
    assert kernels
    for ln in kernels:
        assert re.match(r"\s*%prox_update\.\d+ = ", ln), ln[:80]
        assert 'op_name="jit(step_fn)/apibcd.prox/prox_update/' in ln
    loops = [ln for ln in hlo.splitlines() if " while(" in ln
             and "model.blocks" in ln]
    assert len(loops) == 2
    assert all(re.search(r'op_name="[^"]*/apibcd\.grad/[^"]*model\.blocks',
                         ln) for ln in loops)


def test_engine_decode_step_compiles_full_width(one_chip):
    """The slot-arena decode step the engine jits (`decode_rows_tokens`,
    arena donated) at published widths, 4 slots of 512 positions."""
    model = build_model(get_config("qwen2-0.5b"))
    put = lambda tree: jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=one_chip),
        tree)
    params = put(jax.eval_shape(model.init, jax.random.PRNGKey(0)))
    arena = put(jax.eval_shape(
        lambda: model.init_arena(4, 512, dtype=jnp.bfloat16)))
    rows = jax.ShapeDtypeStruct((4,), jnp.int32, sharding=one_chip)
    step = jax.jit(model.decode_rows_tokens, donate_argnums=(2,))
    compiled = step.lower(params, rows, arena, rows).compile()
    assert _planned_bytes(compiled) < V5E_HBM_BYTES
