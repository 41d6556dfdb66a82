"""DeepSeek-V2's latent attention and routed experts as deepseek-v2-lite
needs them: MLA without a query LoRA, YaRN rope and its softmax scale,
an expert layer told which experts it holds, gates left unnormalised,
the sequence-wise balance loss, configurations read from JSON, and the
MLA configurations that were there unchanged bit for bit."""
import dataclasses
import hashlib
import json
import math
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config, get_smoke
from repro.configs.base import ArchConfig, MLAConfig, MoEConfig, RopeScaling
from repro.models import attention as A
from repro.models import build_model
from repro.models import moe as MOE
from repro.models.layers import rope_frequencies

LITE_JSON = (Path(__file__).resolve().parents[1] / "benchmarks" / "chip"
             / "configs" / "deepseek-v2-lite.json")


# ---------------------------------------------------------------------------
# the MLA configurations that were there: outputs unchanged bit for bit
# ---------------------------------------------------------------------------

# sha256 (first 16 hex digits) of each output, taken on the CPU before
# MLA learnt the query without LoRA, YaRN and the one scale helper, and
# before the expert layer learnt its share, gate and balance-loss options
DIGESTS = {"train_moe": "ca264111490ae22c", "serve_moe": "826e4a39508c0651",
           "train_dense": "e5fef455c5736947",
           "serve_arena": "d9a3fc61fc3ba62e",
           "serve_paged": "9ba2c1f5fdd79c4e"}


def _digest(tree):
    h = hashlib.sha256()
    for leaf in jax.tree.leaves(tree):
        a = np.asarray(leaf)
        h.update(str((a.dtype, a.shape)).encode())
        h.update(a.tobytes())
    return h.hexdigest()[:16]


def _train(model, params, batch):
    def lossf(p):
        loss, m = model.train_loss(p, batch)
        return loss, (m["nll"], m["aux"])
    return jax.jit(jax.value_and_grad(lossf, has_aux=True))(params)


@pytest.fixture(scope="module")
def mla_outputs():
    """deepseek-v2-236b's smoke config (MLA + MoE): a training step and
    prefill + decode; a dense MLA stack: a training step and the
    engine's arena, paged and mixed steps."""
    out = {}
    cfg = get_smoke("deepseek-v2-236b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(5)
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    targs = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 16)), jnp.int32)
    out["train_moe"] = _train(model, params,
                              {"tokens": toks, "targets": targs})
    logits, caches = jax.jit(lambda p, b: model.prefill(p, b, cache_len=20))(
        params, {"tokens": toks})
    dl, _ = jax.jit(model.decode_step)(params, toks[:, :1], caches,
                                       jnp.int32(16))
    out["serve_moe"] = (logits, caches, dl)

    cfg = ArchConfig(name="mla-digest", family="dense", source="test",
                     num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                     d_ff=128, vocab_size=256, tie_embeddings=True,
                     mla=MLAConfig(kv_lora_rank=16, q_lora_rank=32,
                                   qk_nope_head_dim=16, qk_rope_head_dim=8,
                                   v_head_dim=16))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    toks = jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 12)), jnp.int32)
    out["train_dense"] = _train(model, params,
                                {"tokens": toks, "targets": toks[:, ::-1]})
    arena = model.init_arena(2, 16, dtype=jnp.bfloat16)
    lg, arena = jax.jit(model.prefill_into_slot)(
        params, toks[:1, :8], jnp.int32(6), jnp.int32(1), arena)
    dl, arena2 = jax.jit(model.decode_rows)(
        params, toks[:, :1], arena, jnp.array([3, 6], jnp.int32))
    mixed = jax.jit(model.mixed_step_tokens)(
        params, toks[:, 0], arena2, jnp.array([4, 7], jnp.int32),
        toks[1:, :8], jnp.int32(5), jnp.int32(0))
    out["serve_arena"] = (lg, arena, dl, arena2, mixed)
    pool = model.init_pool(8, 4, dtype=jnp.bfloat16)
    table = jnp.array([1, 2, 3, 0], jnp.int32)
    cl, pool = jax.jit(model.prefill_chunk_into_blocks)(
        params, toks[:1, :4], jnp.int32(4), jnp.int32(0), table, pool)
    tables = jnp.array([[1, 2, 3, 0], [0, 0, 0, 0]], jnp.int32)
    pl_, pool2 = jax.jit(model.decode_rows_paged)(
        params, toks[:, :1], pool, tables, jnp.array([4, 0], jnp.int32))
    mp = jax.jit(model.mixed_step_paged_tokens)(
        params, toks[:, 1], pool2, tables, jnp.array([5, 0], jnp.int32),
        toks[1:, :4], jnp.int32(4), jnp.int32(0),
        jnp.array([4, 5, 0, 0], jnp.int32))
    out["serve_paged"] = (cl, pool, pl_, pool2, mp)
    return out


@pytest.mark.parametrize("part", sorted(DIGESTS))
def test_existing_mla_configs_are_unchanged_bit_for_bit(mla_outputs, part):
    assert _digest(mla_outputs[part]) == DIGESTS[part]


# ---------------------------------------------------------------------------
# YaRN and the MLA softmax scale
# ---------------------------------------------------------------------------

LITE_YARN = RopeScaling(factor=40, original_max_position_embeddings=4096,
                        beta_fast=32, beta_slow=1, mscale=0.707,
                        mscale_all_dim=0.707)


def _deepseek_yarn(dim, base, factor, original, beta_fast, beta_slow):
    """DeepSeek-V2's DeepseekV2YarnRotaryEmbedding frequencies, written
    out in numpy float32 from its reference code."""
    def corr_dim(rot):
        return (dim * math.log(original / (rot * 2 * math.pi))) \
            / (2 * math.log(base))
    low = max(math.floor(corr_dim(beta_fast)), 0)
    high = min(math.ceil(corr_dim(beta_slow)), dim - 1)
    pos = np.arange(0, dim, 2, dtype=np.float32) / dim
    extra = 1.0 / (base ** pos)
    inter = 1.0 / (factor * base ** pos)
    ramp = np.clip((np.arange(dim // 2, dtype=np.float32) - low)
                   / (high - low), 0, 1)
    mask = 1.0 - ramp
    return inter * (1 - mask) + extra * mask, (low, high)


def test_yarn_frequencies_are_deepseeks():
    got = np.asarray(rope_frequencies(64, 10000.0, LITE_YARN))
    want, (low, high) = _deepseek_yarn(64, 10000.0, 40, 4096, 32, 1)
    assert (low, high) == (10, 23)
    np.testing.assert_allclose(got, want, rtol=2e-6)
    # the fast dims keep theta's frequency, the slow ones take it / 40
    plain = np.asarray(rope_frequencies(64, 10000.0))
    assert np.array_equal(got[:low], plain[:low])
    np.testing.assert_allclose(got[high:], plain[high:] / 40, rtol=1e-6)


def test_mla_softmax_scale_takes_yarns_mscale_squared():
    lite = get_config("deepseek-v2-lite")
    mscale = 0.1 * 0.707 * math.log(40) + 1.0
    assert A._mla_scale(lite) == pytest.approx(mscale ** 2 / math.sqrt(192),
                                               rel=1e-12)
    assert A._mla_scale(get_config("deepseek-v2-236b")) == \
        float(1.0 / np.sqrt(192))


def test_rope_scaling_is_refused_without_mla():
    with pytest.raises(NotImplementedError, match="MLA"):
        ArchConfig(name="g", family="dense", source="t", num_layers=1,
                   d_model=32, num_heads=2, num_kv_heads=2, d_ff=32,
                   vocab_size=64, rope_scaling=LITE_YARN)


def test_mla_without_query_lora_projects_queries_once():
    cfg = get_smoke("deepseek-v2-lite")
    m = cfg.mla
    p = A.mla_init(jax.random.PRNGKey(0), cfg, jnp.float32)
    assert set(p) == {"wq", "wkv_a", "kv_norm", "wk_b", "wv_b", "wo"}
    assert p["wq"].shape == (cfg.d_model, cfg.num_heads
                             * (m.qk_nope_head_dim + m.qk_rope_head_dim))


# ---------------------------------------------------------------------------
# configurations
# ---------------------------------------------------------------------------


def test_deepseek_v2_lite_is_published_widths():
    c = get_config("deepseek-v2-lite")
    assert (c.num_layers, c.d_model, c.num_heads, c.d_ff, c.vocab_size) == (
        27, 2048, 16, 10944, 102400)
    assert c.layer_types == ("attn",) + ("moe",) * 26
    assert (c.moe.num_experts, c.moe.top_k, c.moe.num_shared_experts,
            c.moe.d_ff_expert, c.moe.experts_held) == (64, 6, 2, 1408, 64)
    assert not c.moe.norm_topk_prob and c.moe.aux_kind == "seq"
    assert (c.mla.q_lora_rank, c.mla.kv_lora_rank) == (0, 512)
    assert c.rope_scaling == LITE_YARN and not c.tie_embeddings


def test_a_json_model_builds_its_nested_groups():
    """The benchmark's configuration as `Cell.arch()` builds it: nested
    groups become their dataclasses, lists tuples, null rank 0."""
    model = json.loads(LITE_JSON.read_text())["model"]
    cfg = ArchConfig(**model)
    assert isinstance(cfg.moe, MoEConfig) and isinstance(cfg.mla, MLAConfig)
    assert isinstance(cfg.rope_scaling, RopeScaling)
    assert cfg.layer_types == ("attn",) + ("moe",) * 4
    assert (cfg.moe.num_experts, cfg.moe.experts_held) == (64, 8)
    nulled = dict(model, mla=dict(model["mla"], q_lora_rank=None))
    assert ArchConfig(**nulled).mla.q_lora_rank == 0
    hash(cfg)


# ---------------------------------------------------------------------------
# the expert layer
# ---------------------------------------------------------------------------


def _moe_cfg(**moe):
    base = dict(num_experts=64, top_k=6, num_shared_experts=2,
                d_ff_expert=16, capacity_factor=1.25, router_aux_loss=0.001,
                norm_topk_prob=False, aux_kind="seq")
    return ArchConfig(name="moe-share", family="moe", source="test",
                      num_layers=1, d_model=32, num_heads=2, num_kv_heads=2,
                      d_ff=32, vocab_size=64,
                      moe=MoEConfig(**dict(base, **moe)))


def _share(cfg, held, offset):
    return dataclasses.replace(cfg, moe=dataclasses.replace(
        cfg.moe, experts_held=held, expert_offset=offset))


def test_eight_shares_add_up_to_the_whole_layer():
    """Each of 8 chips holds 8 of the 64 experts: their outputs, with
    the shared experts (computed on every chip) counted once, add up to
    the layer that holds all 64 of those experts; the balance loss is
    the same on every share, and the assignments dropped over capacity
    add up to the whole layer's."""
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(11)
    shares = [_share(cfg, 8, 8 * i) for i in range(8)]
    params = [MOE.moe_init(key, c, jnp.float32) for c in shares]
    whole = dict(params[0], **{
        k: jnp.concatenate([p[k] for p in params])
        for k in ("w_gate", "w_up", "w_down")})
    rng = np.random.default_rng(4)
    # repeated tokens load some experts past capacity
    x = jnp.asarray(rng.standard_normal((3, 40, 32)), jnp.float32)
    x = x.at[:, 20:].set(x[:, :20])
    out, aux, dropped = MOE.moe_apply(whole, cfg, x)
    assert int(dropped) > 0
    parts = [MOE.moe_apply(p, c, x)
             for p, c in zip(params, shares)]
    shared = MOE._shared(whole, x)
    total = sum(o - shared for o, _, _ in parts) + shared
    np.testing.assert_allclose(np.asarray(total), np.asarray(out),
                               rtol=1e-5, atol=1e-6)
    assert all(float(a) == float(aux) for _, a, _ in parts)
    assert sum(int(d) for _, _, d in parts) == int(dropped)


def test_a_share_draws_the_experts_of_its_indices():
    cfg = _moe_cfg()
    key = jax.random.PRNGKey(2)
    a = MOE.moe_init(key, _share(cfg, 8, 16), jnp.float32)
    b = MOE.moe_init(key, _share(cfg, 4, 20), jnp.float32)
    assert a["w_up"].shape == (8, 32, 16)
    np.testing.assert_array_equal(a["w_up"][4:], b["w_up"])
    np.testing.assert_array_equal(a["router"], b["router"])


def _route_by_hand(x, router, k, norm):
    probs = np.asarray(jax.nn.softmax(x @ router, axis=-1), np.float64)
    top = np.argsort(-probs, axis=-1)[..., :k]
    gates = np.take_along_axis(probs, top, -1)
    if norm:
        gates = gates / gates.sum(-1, keepdims=True)
    return probs, top, gates


@pytest.mark.parametrize("norm", [False, True])
def test_gates_are_renormalised_only_if_asked(norm):
    """With room for every token, the routed output is each token's
    top-k experts weighted by their scores, summed to 1 only with
    norm_topk_prob."""
    cfg = _moe_cfg(num_experts=8, top_k=3, num_shared_experts=0,
                   capacity_factor=8.0, norm_topk_prob=norm)
    p = MOE.moe_init(jax.random.PRNGKey(5), cfg, jnp.float32)
    x = jnp.asarray(np.random.default_rng(6).standard_normal((2, 6, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        out, _, _ = MOE.moe_apply(p, cfg, x)
        _, top, gates = _route_by_hand(x, p["router"], 3, norm)
        want = np.zeros(x.shape)
        for b in range(2):
            for s in range(6):
                for e, g in zip(top[b, s], gates[b, s]):
                    h = jax.nn.silu(x[b, s] @ p["w_gate"][e]) \
                        * (x[b, s] @ p["w_up"][e])
                    want[b, s] += g * np.asarray(h @ p["w_down"][e])
    np.testing.assert_allclose(np.asarray(out), want, rtol=1e-4, atol=1e-5)


def test_sequence_balance_loss_is_deepseeks():
    """alpha sum_i f_i P_i per sequence, f_i = E / (k S) times expert i's
    picks, P_i its mean score, averaged over the sequences."""
    cfg = _moe_cfg(num_experts=8, top_k=3, router_aux_loss=0.5)
    p = MOE.moe_init(jax.random.PRNGKey(7), cfg, jnp.float32)
    x = jnp.asarray(np.random.default_rng(8).standard_normal((3, 10, 32)),
                    jnp.float32)
    with jax.default_matmul_precision("highest"):
        _, aux, _ = MOE.moe_apply(p, cfg, x)
        probs, top, _ = _route_by_hand(x, p["router"], 3, False)
    want = []
    for b in range(3):
        f = np.bincount(top[b].ravel(), minlength=8) * 8 / (3 * 10)
        want.append(0.5 * np.sum(f * probs[b].mean(0)))
    assert float(aux) == pytest.approx(np.mean(want), rel=1e-5)


def test_the_superstep_counts_dropped_assignments():
    """The step's metrics carry moe_dropped where the model has expert
    layers, and nothing of it where it has none."""
    from repro.launch.train import Superstep

    for arch, has in (("deepseek-v2-lite", True), ("qwen2-0.5b", False)):
        run = Superstep(get_smoke(arch), jax.devices()[:1], agents=2,
                        walks=1, batch_per_agent=2, seq=16)
        metrics = run.step(0)
        assert ("moe_dropped" in metrics) == has
        if has:
            assert 0 <= int(metrics["moe_dropped"]) <= 2 * 2 * 16 * 3 * 2
