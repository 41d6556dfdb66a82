"""Kernel validation: interpret-mode Pallas vs pure-jnp oracles, swept
over shapes and dtypes."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref


def _rand(rng, shape, dtype):
    return jnp.asarray(rng.standard_normal(shape), dtype)


TOL = {jnp.float32: dict(rtol=1e-5, atol=1e-5),
       jnp.bfloat16: dict(rtol=3e-2, atol=3e-2)}


# ---------------------------------------------------------------------------
# prox_update
# ---------------------------------------------------------------------------


# (): a scalar; (2, 3, 16, 256): a stacked leaf folded into rows;
# (3, 5, 257): second-minor dim off the 8-row tile, leading dim in the
# grid; (3, 33000): last dim wider than one column block (ragged);
# (2, 293, 896): ragged last row block under a grid-walked leading dim;
# (4104, 64): a last dim narrower than a lane tile, in two row blocks
@pytest.mark.parametrize("shape", [(64,), (300,), (8, 130), (3, 5, 257),
                                   (), (2, 3, 16, 256), (3, 33000),
                                   (2, 293, 896), (4104, 64)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("in_place", [False, True])
def test_prox_update(shape, dtype, in_place):
    rng = np.random.default_rng(0)
    x = _rand(rng, shape, dtype)
    g = _rand(rng, shape, dtype)
    z = _rand(rng, shape, dtype)
    args = dict(tau=0.1, rho=1.0, num_walks=4, num_agents=16)
    xr, dr = ref.prox_update(x, g, z, **args)
    xk, dk = jax.jit(lambda *a: ops.prox_update(
        *a, **args, in_place=in_place, interpret=True),
        donate_argnums=0 if in_place else ())(x, g, z)
    np.testing.assert_allclose(np.asarray(xk, np.float32),
                               np.asarray(xr, np.float32), **TOL[dtype])
    np.testing.assert_allclose(np.asarray(dk), np.asarray(dr),
                               **TOL[dtype])


# deepseek-v2-lite's leaves as its one-agent superstep holds them (1 dense
# + 4 MoE layers): the stacked router (64 of 128 lanes), the latent
# projection wkv_a (576 wide: 4.5 lane tiles) and a routed expert stack
# (8 held experts a layer, 1408 wide: 11 lane tiles)
@pytest.mark.parametrize("shape", [(1, 4, 2048, 64), (1, 5, 2048, 576),
                                   (1, 4, 8, 2048, 1408)])
def test_prox_update_equals_the_unfused_update_bit_for_bit(shape):
    rng = np.random.default_rng(1)
    x, g, z = (jnp.asarray(rng.standard_normal(shape, np.float32))
               for _ in range(3))
    args = dict(tau=0.05, rho=20.0, num_walks=1, num_agents=1)
    xr, dr = jax.jit(lambda *a: ref.prox_update(*a, **args))(x, g, z)
    xk, dk = jax.jit(lambda *a: ops.prox_update(*a, **args,
                                                interpret=True))(x, g, z)
    np.testing.assert_array_equal(np.asarray(xk), np.asarray(xr))
    np.testing.assert_array_equal(np.asarray(dk), np.asarray(dr))


@pytest.mark.parametrize("rows,cols", [(151936, 896), (21504, 4864),
                                       (1, 896), (49152, 64), (5, 257),
                                       (1, 1), (3, 40000), (8, 32769)])
def test_prox_update_block_fits_its_vmem_budget(rows, cols):
    """A block, padded as VMEM holds it (rows to 8, the last dim to
    whole 128-lane tiles), stays within BLOCK_ELEMS, and is a legal TPU
    block: rows a multiple of 8 or all of them, columns a multiple of
    128 or all of them."""
    from repro.kernels.prox_update import BLOCK_ELEMS, _block_shape
    br, bc = _block_shape(rows, cols)
    assert br == rows or br % 8 == 0
    assert bc == cols or bc % 128 == 0
    assert -(-br // 8) * 8 * (-(-bc // 128) * 128) <= BLOCK_ELEMS


@pytest.mark.parametrize("backend,interpret", [("cpu", True),
                                               ("tpu", False),
                                               ("gpu", None)])
def test_interpret_mode_only_on_cpu(monkeypatch, backend, interpret):
    """Kernels are interpreted on the CPU, compiled on the TPU, and
    refused elsewhere rather than silently interpreted."""
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert ops._interpret_default(False) is False
    if interpret is None:
        with pytest.raises(NotImplementedError, match="gpu"):
            ops._interpret_default(None)
    else:
        assert ops._interpret_default(None) is interpret


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,t,h,kv,hd,window", [
    (128, 128, 4, 4, 64, 0),       # MHA causal
    (256, 256, 4, 2, 64, 0),       # GQA
    (256, 256, 4, 1, 32, 64),      # MQA sliding window
    (96, 96, 2, 2, 64, 0),         # non-multiple of block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(s, t, h, kv, hd, window, dtype):
    rng = np.random.default_rng(1)
    b = 2
    q = _rand(rng, (b, s, h, hd), dtype)
    k = _rand(rng, (b, t, kv, hd), dtype)
    v = _rand(rng, (b, t, kv, hd), dtype)
    out = ops.flash_attention(q, k, v, causal=True, window=window,
                              block_q=64, block_k=64, interpret=True)
    # oracle works in [B,H,S,hd]
    q2 = q.transpose(0, 2, 1, 3)
    k2 = k.transpose(0, 2, 1, 3)
    v2 = v.transpose(0, 2, 1, 3)
    want = ref.attention(q2, k2, v2, causal=True, window=window)
    want = want.transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_flash_attention_matches_model_reference():
    """The model's chunked_attention and the kernel agree (same math)."""
    from repro.models.attention import chunked_attention
    rng = np.random.default_rng(2)
    b, s, kv, g, hd = 2, 128, 2, 3, 32
    q = _rand(rng, (b, s, kv, g, hd), jnp.float32)
    k = _rand(rng, (b, s, kv, hd), jnp.float32)
    v = _rand(rng, (b, s, kv, hd), jnp.float32)
    want = chunked_attention(q, k, v, causal=True, q_chunk=64, kv_chunk=64)
    qk = q.reshape(b, s, kv * g, hd)
    out = ops.flash_attention(qk, k, v, causal=True, block_q=64,
                              block_k=64, interpret=True)
    out = out.reshape(b, s, kv, g, hd)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# decode attention
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("t,h,kv,hd,valid", [
    (512, 8, 8, 64, None),
    (512, 8, 2, 64, None),
    (384, 4, 1, 128, 200),        # partial ring + MQA
    (1000, 4, 2, 64, 1000),       # non-multiple of block
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(t, h, kv, hd, valid, dtype):
    rng = np.random.default_rng(3)
    b = 2
    q = _rand(rng, (b, h, hd), dtype)
    k = _rand(rng, (b, t, kv, hd), dtype)
    v = _rand(rng, (b, t, kv, hd), dtype)
    out = ops.decode_attention(q, k, v, valid_len=valid, block_k=128,
                               interpret=True)
    want = ref.decode_attention(q, k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), valid_len=valid)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_per_row_lengths(dtype):
    """Slot-arena decode: every batch row attends to its own valid KV
    length (one kernel launch over slots at different decode depths)."""
    rng = np.random.default_rng(8)
    b, t, h, kv, hd = 3, 384, 4, 2, 64
    q = _rand(rng, (b, h, hd), dtype)
    k = _rand(rng, (b, t, kv, hd), dtype)
    v = _rand(rng, (b, t, kv, hd), dtype)
    lengths = jnp.asarray([1, 200, 384], jnp.int32)
    out = ops.decode_attention(q, k, v, lengths=lengths, block_k=128,
                               interpret=True)
    want = ref.decode_attention(q, k.transpose(0, 2, 1, 3),
                                v.transpose(0, 2, 1, 3), valid_len=lengths)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])
    # each row matches a solo scalar-length call (per-row masking exact)
    for i, n in enumerate([1, 200, 384]):
        solo = ops.decode_attention(q[i:i + 1], k[i:i + 1], v[i:i + 1],
                                    valid_len=n, block_k=128, interpret=True)
        np.testing.assert_allclose(np.asarray(solo[0], np.float32),
                                   np.asarray(out[i], np.float32),
                                   **TOL[dtype])


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_paged(dtype):
    """Block-table decode: each row's KV is scattered across a shared
    block pool; the kernel must match the gather-then-linear oracle."""
    rng = np.random.default_rng(9)
    b, h, kv, hd = 3, 4, 2, 64
    bs, w = 8, 6                       # block_size, table width
    nb = 1 + b * w                     # null block + enough for all rows
    q = _rand(rng, (b, h, hd), dtype)
    k_pool = _rand(rng, (nb, bs, kv, hd), dtype)
    v_pool = _rand(rng, (nb, bs, kv, hd), dtype)
    # rows own disjoint random (non-contiguous) blocks; trailing entries
    # of short rows point at the null block 0
    perm = rng.permutation(nb - 1) + 1
    tables = perm[:b * w].reshape(b, w).astype(np.int32)
    lengths = np.asarray([1, 19, w * bs], np.int32)
    for i, n in enumerate(lengths):
        tables[i, (int(n) + bs - 1) // bs:] = 0
    tables = jnp.asarray(tables)
    out = ops.decode_attention_paged(q, k_pool, v_pool, tables,
                                     jnp.asarray(lengths), interpret=True)
    want = ref.decode_attention_paged(q, k_pool, v_pool, tables,
                                      jnp.asarray(lengths))
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_decode_attention_paged_degenerate_arena():
    """With an identity block table the paged kernel IS the linear
    kernel: same inputs, same per-row lengths, same outputs (the slot
    arena is the 1-contiguous-run-of-blocks special case)."""
    rng = np.random.default_rng(10)
    b, t, h, kv, hd = 2, 256, 4, 2, 64
    bs = 64
    q = _rand(rng, (b, h, hd), jnp.float32)
    k = _rand(rng, (b, t, kv, hd), jnp.float32)
    v = _rand(rng, (b, t, kv, hd), jnp.float32)
    lengths = jnp.asarray([100, 256], jnp.int32)
    linear = ops.decode_attention(q, k, v, lengths=lengths, block_k=bs,
                                  interpret=True)
    # pool = the same caches cut into contiguous blocks (plus null 0)
    w = t // bs
    pool_k = jnp.concatenate(
        [jnp.zeros((1, bs, kv, hd)), k.reshape(b * w, bs, kv, hd)])
    pool_v = jnp.concatenate(
        [jnp.zeros((1, bs, kv, hd)), v.reshape(b * w, bs, kv, hd)])
    tables = 1 + jnp.arange(b * w, dtype=jnp.int32).reshape(b, w)
    paged = ops.decode_attention_paged(q, pool_k, pool_v, tables, lengths,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(linear),
                               rtol=1e-6, atol=1e-6)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention_ring(dtype):
    """Ring-table decode: each row's last min(length, window) tokens
    live in a fixed ring of blocks (position p at ring slot p % window)
    with a per-row table rotation; the kernel must match the
    unrotate-then-linearize oracle across unwrapped, part-filled and
    fully wrapped rows."""
    rng = np.random.default_rng(11)
    b, h, kv, hd = 3, 4, 2, 64
    bs, window = 8, 40
    w = (window + bs - 1) // bs
    nb = 1 + b * w
    q = _rand(rng, (b, h, hd), dtype)
    k_pool = _rand(rng, (nb, bs, kv, hd), dtype)
    v_pool = _rand(rng, (nb, bs, kv, hd), dtype)
    perm = rng.permutation(nb - 1) + 1
    tables = jnp.asarray(perm[:b * w].reshape(b, w).astype(np.int32))
    lengths = jnp.asarray([1, 25, 100], jnp.int32)   # wraps only in row 2
    starts = jnp.asarray([0, 2, 4], jnp.int32)
    out = ops.decode_attention_ring(q, k_pool, v_pool, tables, starts,
                                    lengths, window=window, interpret=True)
    want = ref.decode_attention_ring(q, k_pool, v_pool, tables, starts,
                                     lengths, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_decode_attention_ring_rotation_invariant():
    """Rotating (table, start) together is bitwise a no-op: the mask is
    keyed to ring-slot indices, so a host that rotates tables in place
    (no block copies) changes nothing in the output."""
    rng = np.random.default_rng(12)
    b, h, kv, hd = 2, 4, 2, 64
    bs, window = 8, 32
    w = window // bs
    nb = 1 + b * w
    q = _rand(rng, (b, h, hd), jnp.float32)
    k_pool = _rand(rng, (nb, bs, kv, hd), jnp.float32)
    v_pool = _rand(rng, (nb, bs, kv, hd), jnp.float32)
    ring = (rng.permutation(nb - 1) + 1)[:b * w].reshape(b, w)
    lengths = jnp.asarray([17, 77], jnp.int32)
    base = ops.decode_attention_ring(
        q, k_pool, v_pool, jnp.asarray(ring.astype(np.int32)),
        jnp.zeros(b, jnp.int32), lengths, window=window, interpret=True)
    for s in range(1, w):
        # entry (s + bi) % w must hold ring block bi -> roll right by s
        rot = np.roll(ring, s, axis=1).astype(np.int32)
        out = ops.decode_attention_ring(
            q, k_pool, v_pool, jnp.asarray(rot),
            jnp.full(b, s, jnp.int32), lengths, window=window,
            interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(base))


def test_decode_attention_ring_degenerate_paged():
    """While no row has wrapped (length <= window), the ring kernel IS
    the paged kernel: identical tables, identical DMA schedule,
    identical mask — the monotone table is the degenerate ring."""
    rng = np.random.default_rng(13)
    b, h, kv, hd = 2, 4, 2, 64
    bs, window = 8, 32
    w = window // bs
    nb = 1 + b * w
    q = _rand(rng, (b, h, hd), jnp.float32)
    k_pool = _rand(rng, (nb, bs, kv, hd), jnp.float32)
    v_pool = _rand(rng, (nb, bs, kv, hd), jnp.float32)
    tables = jnp.asarray(
        (rng.permutation(nb - 1) + 1)[:b * w].reshape(b, w).astype(np.int32))
    lengths = jnp.asarray([9, 32], jnp.int32)        # <= window: no wrap
    ring = ops.decode_attention_ring(q, k_pool, v_pool, tables,
                                     jnp.zeros(b, jnp.int32), lengths,
                                     window=window, interpret=True)
    paged = ops.decode_attention_paged(q, k_pool, v_pool, tables, lengths,
                                       interpret=True)
    np.testing.assert_allclose(np.asarray(ring), np.asarray(paged),
                               rtol=1e-6, atol=1e-6)


# ---------------------------------------------------------------------------
# rwkv6
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,hd,chunk", [(64, 32, 32), (130, 64, 64),
                                        (96, 64, 128)])
@pytest.mark.parametrize("dtype", [jnp.float32])
def test_rwkv6_scan(s, hd, chunk, dtype):
    rng = np.random.default_rng(4)
    b, h = 2, 3
    r = _rand(rng, (b, h, s, hd), dtype)
    k = _rand(rng, (b, h, s, hd), dtype)
    v = _rand(rng, (b, h, s, hd), dtype)
    w = jnp.asarray(rng.uniform(0.2, 0.99, (b, h, s, hd)), dtype)
    u = _rand(rng, (h, hd), dtype)
    out = ops.rwkv6_scan(r, k, v, w, u, chunk=chunk, interpret=True)
    want, _ = ref.rwkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


def test_rwkv6_scan_bf16():
    rng = np.random.default_rng(5)
    b, h, s, hd = 1, 2, 64, 64
    r = _rand(rng, (b, h, s, hd), jnp.bfloat16)
    k = _rand(rng, (b, h, s, hd), jnp.bfloat16)
    v = _rand(rng, (b, h, s, hd), jnp.bfloat16)
    w = jnp.asarray(rng.uniform(0.5, 0.99, (b, h, s, hd)), jnp.bfloat16)
    u = _rand(rng, (h, hd), jnp.bfloat16)
    out = ops.rwkv6_scan(r, k, v, w, u, chunk=32, interpret=True)
    want, _ = ref.rwkv6(r, k, v, w, u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               rtol=1e-1, atol=1e-1)


# ---------------------------------------------------------------------------
# rg-lru
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("s,w,chunk,block_w", [
    (64, 256, 32, 128), (100, 130, 64, 512), (256, 512, 128, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rglru_scan(s, w, chunk, block_w, dtype):
    rng = np.random.default_rng(6)
    b = 2
    a = jnp.asarray(rng.uniform(0.3, 0.999, (b, s, w)), dtype)
    u = _rand(rng, (b, s, w), dtype)
    out = ops.rglru_scan(a, u, chunk=chunk, block_w=block_w,
                         interpret=True)
    want, _ = ref.rglru(a, u)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32),
                               **TOL[dtype])


# ---------------------------------------------------------------------------
# model-integration oracle checks: the model blocks implement the same
# math the kernels implement (transitively: model == kernel)
# ---------------------------------------------------------------------------


def test_rwkv_model_block_matches_kernel_math():
    from repro.configs import get_smoke
    from repro.models import rwkv6 as RW
    cfg = get_smoke("rwkv6-1.6b")
    params = RW.rwkv_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    rng = np.random.default_rng(7)
    b, s, d = 2, 16, cfg.d_model
    hd = cfg.rwkv_head_dim
    h = d // hd
    x = _rand(rng, (b, s, d), jnp.float32)
    state = RW.init_state(cfg, b)

    out_model, _ = RW.time_mix(params, cfg, x, state)

    # reproduce projections, then compare the recurrence core to the kernel
    xs = RW._token_shift(x, state["shift"], params["mu"])
    r = (xs["r"] @ params["wr"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    k = (xs["k"] @ params["wk"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    v = (xs["v"] @ params["wv"]).reshape(b, s, h, hd).transpose(0, 2, 1, 3)
    w = params["w0"] + jnp.tanh(
        xs["w"] @ params["w_lora_a"]) @ params["w_lora_b"]
    w = jnp.exp(-jnp.exp(w.astype(jnp.float32)))
    w = w.reshape(b, s, h, hd).transpose(0, 2, 1, 3)

    core_kernel = ops.rwkv6_scan(r, k, v, w, params["u"], chunk=16,
                                 interpret=True)
    core_ref, _ = ref.rwkv6(r, k, v, w, params["u"])
    np.testing.assert_allclose(np.asarray(core_kernel),
                               np.asarray(core_ref), rtol=2e-4, atol=2e-4)
