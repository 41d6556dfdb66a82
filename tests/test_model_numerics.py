"""Numerical equivalence of the optimized model paths vs their simple
reference forms (the beyond-paper lowering optimizations must not change
the math)."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.kernels import ref
from repro.models.rwkv6 import wkv_chunked


def test_wkv_chunked_matches_sequential():
    rng = np.random.default_rng(0)
    b, h, s, hd = 2, 3, 256, 64
    r = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.2, 0.999, (b, h, s, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, hd)), jnp.float32)
    st = jnp.asarray(rng.standard_normal((b, h, hd, hd)), jnp.float32) * 0.1

    out_c, s_c = wkv_chunked(r, k, v, w, u, st, chunk=64)
    out_r, s_r = ref.rwkv6(r, k, v, w, u, state=st)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(s_c), np.asarray(s_r),
                               rtol=2e-4, atol=2e-4)


def test_wkv_chunked_strong_decay_stable():
    """Decay ratios stay <= 1: no overflow even with aggressive decay."""
    rng = np.random.default_rng(1)
    b, h, s, hd = 1, 2, 128, 64
    r = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, h, s, hd)), jnp.float32)
    w = jnp.asarray(rng.uniform(0.01, 0.2, (b, h, s, hd)), jnp.float32)
    u = jnp.asarray(rng.standard_normal((h, hd)), jnp.float32)
    st = jnp.zeros((b, h, hd, hd), jnp.float32)
    out_c, s_c = wkv_chunked(r, k, v, w, u, st, chunk=64)
    assert bool(jnp.isfinite(out_c).all()) and bool(jnp.isfinite(s_c).all())
    out_r, _ = ref.rwkv6(r, k, v, w, u, state=st)
    np.testing.assert_allclose(np.asarray(out_c), np.asarray(out_r),
                               rtol=5e-4, atol=5e-4)


def test_rwkv_block_chunked_equals_sequential_path():
    """The full rwkv block gives the same output whether time_mix takes
    the chunked (S % 64 == 0) or sequential path."""
    from repro.models import rwkv6 as RW
    cfg = get_smoke("rwkv6-1.6b")
    params = RW.rwkv_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((2, 128, cfg.d_model)), jnp.float32)
    st = RW.init_state(cfg, 2)

    out_chunked, st_c = RW.time_mix(params, cfg, x, st)
    os.environ["REPRO_RWKV_SEQUENTIAL"] = "1"
    try:
        out_seq, st_s = RW.time_mix(params, cfg, x, st)
    finally:
        del os.environ["REPRO_RWKV_SEQUENTIAL"]
    np.testing.assert_allclose(np.asarray(out_chunked),
                               np.asarray(out_seq), rtol=1e-3, atol=1e-3)
    np.testing.assert_allclose(np.asarray(st_c["wkv"]),
                               np.asarray(st_s["wkv"]),
                               rtol=1e-3, atol=1e-3)


def test_gshard_moe_matches_scatter_dispatch():
    """GShard einsum dispatch == sort/scatter dispatch when nothing is
    dropped (generous capacity)."""
    from repro.configs.base import ArchConfig, MoEConfig
    from repro.models import moe as MOE

    cfg = ArchConfig(
        name="moe-test", family="moe", source="test", num_layers=1,
        d_model=64, num_heads=2, num_kv_heads=2, head_dim=32, d_ff=64,
        vocab_size=128,
        moe=MoEConfig(num_experts=4, top_k=2, num_shared_experts=0,
                      d_ff_expert=64, capacity_factor=8.0))
    params = MOE.moe_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 16, 64)), jnp.float32)

    out_g, aux_g, _ = MOE.moe_apply(params, cfg, x)
    out_s, aux_s, _ = MOE.moe_apply_scatter(params, cfg, x)
    np.testing.assert_allclose(np.asarray(out_g), np.asarray(out_s),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(float(aux_g), float(aux_s), rtol=1e-5)


def test_flash_suffix_accounting():
    """hlo_flops kernel-adjusted bytes <= raw bytes and excludes
    score-shaped tiles."""
    from repro.utils.hlo_flops import analyze
    hlo = """
ENTRY %main (a: f32[4,512,512]) -> f32[4,512,512] {
  %a = f32[4,512,512]{2,1,0} parameter(0)
  %b = f32[4,512,512]{2,1,0} fusion(%a), kind=kLoop, calls=%fc
  ROOT %c = f32[4,128]{1,0} dot(%b, %b), lhs_contracting_dims={1,2}, rhs_contracting_dims={1,2}
}
"""
    r = analyze(hlo)
    assert r["bytes_kernel_adjusted"] <= r["bytes"]
