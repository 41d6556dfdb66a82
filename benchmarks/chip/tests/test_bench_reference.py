"""The plain references against the program at a tiny size on the CPU:
the same weights from the same seed, bit for bit; the same loss,
gradients and logits when the program also computes in float32; and the
control (the reference in fp8) far from the reference."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
from references import apibcd, dense_gqa


def _cfgs(tied):
    from repro.configs.base import ArchConfig

    m = dict(name="tiny", family="dense", source="test", qkv_bias=tied,
             tie_embeddings=tied, rope_theta=1e6, param_dtype="float32",
             compute_dtype="float32", **bench_tiny.TINY_MODEL)
    return m, ArchConfig(**m)


@pytest.mark.parametrize("tied", [True, False])
def test_reference_init_is_the_programs_bit_for_bit(tied):
    from repro.models import build_model

    m, arch = _cfgs(tied)
    key = jax.random.PRNGKey(1234567)
    prog = build_model(arch).init(key)
    ref = dense_gqa.init(m, key)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.mark.parametrize("tied", [True, False])
def test_reference_matches_the_program_in_float32(tied):
    from repro.models import build_model

    m, arch = _cfgs(tied)
    model = build_model(arch)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, m["vocab_size"], (2, 12)), jnp.int32)
    targs = jnp.asarray(rng.integers(0, m["vocab_size"], (2, 12)),
                        jnp.int32)
    with jax.default_matmul_precision("highest"):
        (pl, _), pg = jax.value_and_grad(model.train_loss, has_aux=True)(
            params, {"tokens": toks, "targets": targs})
        plog, _ = model.prefill(params, {"tokens": toks[:1]})
    rl, rg = jax.value_and_grad(
        lambda p: dense_gqa.loss(m, p, toks, targs))(params)
    rlog = dense_gqa.logits(m, params, toks[0])
    assert float(pl) == pytest.approx(float(rl), rel=1e-5)
    np.testing.assert_allclose(np.asarray(plog[0, -1]),
                               np.asarray(rlog[-1]), rtol=1e-4, atol=1e-5)
    for a, b in zip(jax.tree.leaves(pg), jax.tree.leaves(rg)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=1e-6)


def test_control_fails_the_training_limits():
    import run as harness
    from drivers import train

    cell = harness.Cell(bench_tiny.ROOT, "qwen2-0.5b.apibcd-a1", 5,
                        jax.devices())
    cell.model = dict(cell.model, **bench_tiny.TINY_MODEL)
    cell.traffic = dict(cell.traffic, **bench_tiny.TINY_TRAIN)
    feed = train._feed(cell)
    batches = [next(feed) for _ in range(train.CHECKED_STEPS)]
    key = jax.random.PRNGKey(cell.model_seed)
    ref = apibcd.run(cell.model, cell.traffic, key, batches, jax.devices())
    got = apibcd.run(cell.model, cell.traffic, key, batches, jax.devices(),
                     precision="fp8")
    checks = train.compare_training(
        cell, got["losses"], got["grad_norms"], got["change_norms"],
        {i: v or {} for i, v in got["token_norms"].items()}, ref,
        log=lambda *_: None)
    assert any(v > lim for _, v, lim in checks), checks


def test_control_fails_the_serving_limit():
    import run as harness
    from compare import widest_logit_gap

    cell = harness.Cell(bench_tiny.ROOT, bench_tiny.SERVE, 5,
                        jax.devices(), bench=bench_tiny.full_bench())
    m = dict(cell.model, **bench_tiny.TINY_MODEL)
    params = dense_gqa.init(m, jax.random.PRNGKey(9))
    rng = np.random.default_rng(1)
    worst = 0.0
    for _ in range(4):
        toks = jnp.asarray(rng.integers(0, m["vocab_size"], 64), jnp.int32)
        ref = np.asarray(dense_gqa.logits(m, params, toks))
        low = np.asarray(dense_gqa.logits(m, params, toks, "fp8"))
        worst = max(worst, widest_logit_gap(ref, low.argmax(-1)))
    assert worst > cell.limits["limits"]["logit_gap"], worst
