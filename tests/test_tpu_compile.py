"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode lets through: block shapes
not aligned to the tiling, kernels that overrun fast memory, programs
that do not fit the device.  These tests compile the prox_update kernel,
the API-BCD superstep and the serving engine's decode step at
qwen2-0.5b's published widths for one v5e chip.  Nothing runs, so they
say nothing about results or speed.

Only one process at a time may load the TPU library, and every test
worker imports this file: the topology is described inside a
module-scoped fixture, never at import time.
"""
import os
from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.prox_update import LANE, prox_update_2d
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


@pytest.fixture(autouse=True)
def as_on_tpu(monkeypatch):
    """Trace as the chip run does: in 32-bit mode (the test suite turns
    on float64 for the convex reference code), and on the TPU branch of
    `jax.default_backend()`, which the program reads to pick compiled
    kernels and which here is the CPU."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    with jax.enable_x64(False):
        yield


def _planned_bytes(compiled):
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


# 1 row; qwen2-0.5b's tied embedding (151936 x 896 = 132944 rows of
# 1024); and a row count whose last 256-row block is ragged
@pytest.mark.parametrize("rows", [1, 132944, 132958])
def test_prox_update_kernel_compiles(one_chip, rows):
    x = jax.ShapeDtypeStruct((rows, LANE), jnp.float32, sharding=one_chip)
    step = jax.jit(partial(prox_update_2d, tau=0.05, rho=20.0, num_walks=1,
                           num_agents=1, interpret=False))
    compiled = step.lower(x, x, x).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_train_superstep_compiles_full_width(topo):
    """A=1, M=1 at published widths and depth: the step holds the
    compiled kernel and plans to fit one chip."""
    run = Superstep(get_config("qwen2-0.5b"), topo.devices[:1], agents=1,
                    walks=1, batch_per_agent=4, seq=128, place=False)
    lowered = run.lower(run.abstract_batch())
    assert "tpu_custom_call" in lowered.as_text()
    assert _planned_bytes(lowered.compile()) < V5E_HBM_BYTES


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
