"""Serving tests: the continuous-batching slot engine (repro.serve) and
the deprecated wave-batching shim kept on top of it (BatchedServer)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from proptest import property_sweep
from repro.configs import get_smoke
from repro.models import build_model
from repro.serve import (Engine, FamilyCaps, bucket_length, num_buckets,
                         probe_family_caps)

with warnings.catch_warnings():
    warnings.simplefilter("ignore", DeprecationWarning)
    from repro.dist.server import BatchedServer


@pytest.fixture(scope="module")
def served():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    return cfg, model, params


def test_wave_batching_drains_queue(served):
    cfg, model, params = served
    with pytest.warns(DeprecationWarning, match="BatchedServer"):
        srv = BatchedServer(model, params, max_batch=3)
    rng = np.random.default_rng(0)
    uids = [srv.submit(rng.integers(0, cfg.vocab_size, (int(n),)),
                       max_new_tokens=5)
            for n in (4, 7, 5, 6, 3)]          # 2 waves (3 + 2)
    done = srv.run()
    assert srv.pending == 0
    assert sorted(r.uid for r in done) == sorted(uids)
    for r in done:
        assert r.output is not None and 1 <= len(r.output) <= 5
        assert (r.output >= 0).all() and (r.output < cfg.vocab_size).all()


def test_batched_decode_matches_solo_decode(served):
    """A prompt served inside a same-length wave must produce the same
    greedy continuation as served alone (batching is semantically inert)."""
    cfg, model, params = served
    rng = np.random.default_rng(1)
    a = rng.integers(0, cfg.vocab_size, (6,))
    b = rng.integers(0, cfg.vocab_size, (6,))

    with pytest.warns(DeprecationWarning):
        alone = BatchedServer(model, params, max_batch=1)
    alone.submit(a, max_new_tokens=4)
    ref = alone.run()[0].output

    with pytest.warns(DeprecationWarning):
        batched = BatchedServer(model, params, max_batch=2)
    uid = batched.submit(a, max_new_tokens=4)
    batched.submit(b, max_new_tokens=4)
    outs = {r.uid: r.output for r in batched.run()}
    np.testing.assert_array_equal(outs[uid], ref)


def test_mixed_lengths_bucket_into_waves(served):
    cfg, model, params = served
    rng = np.random.default_rng(3)
    with pytest.warns(DeprecationWarning):
        srv = BatchedServer(model, params, max_batch=4)
    lens = [4, 4, 7, 4, 7]
    uids = [srv.submit(rng.integers(0, cfg.vocab_size, (n,)),
                       max_new_tokens=3) for n in lens]
    first_wave = srv.step()
    assert [len(r.prompt) for r in first_wave] == [4, 4, 4]
    done = srv.run()      # _done accumulates across steps (incl. wave 1)
    assert sorted(r.uid for r in done) == sorted(uids)


def test_eos_truncates(served):
    cfg, model, params = served
    rng = np.random.default_rng(2)
    prompt = rng.integers(0, cfg.vocab_size, (6,))
    # find which token greedy decode emits first, then use it as "EOS"
    with pytest.warns(DeprecationWarning):
        probe = BatchedServer(model, params, max_batch=1)
    probe.submit(prompt, max_new_tokens=3)
    first_tok = int(probe.run()[0].output[0])

    with pytest.warns(DeprecationWarning):
        srv = BatchedServer(model, params, max_batch=1)
    srv.submit(prompt, max_new_tokens=10, eos_id=first_tok)
    out = srv.run()[0].output
    assert out[-1] == first_tok and len(out) <= 10


# ---------------------------------------------------------------------------
# continuous-batching engine (repro.serve.Engine)
# ---------------------------------------------------------------------------


def test_engine_continuous_drains_mixed_lengths(served):
    """Mixed prompt lengths AND budgets drain in one engine — no waves."""
    cfg, model, params = served
    eng = Engine(model, params, max_batch=3, max_len=32)
    rng = np.random.default_rng(10)
    uids = [eng.submit(rng.integers(0, cfg.vocab_size, (int(n),)),
                       max_new_tokens=int(b))
            for n, b in ((4, 2), (7, 9), (5, 1), (6, 4), (3, 6))]
    done = eng.run()
    assert eng.pending == 0 and eng.num_active == 0
    assert sorted(r.uid for r in done) == sorted(uids)
    for r in done:
        assert r.output is not None and 1 <= len(r.output) <= r.max_new_tokens
        assert (r.output >= 0).all() and (r.output < cfg.vocab_size).all()


def test_engine_mixed_admission_bit_identity(served):
    """A request admitted into a half-full decode batch (another slot is
    mid-generation) produces bit-identical tokens to the same request
    served alone — admission timing is semantically inert."""
    cfg, model, params = served
    rng = np.random.default_rng(11)
    long_p = rng.integers(0, cfg.vocab_size, (6,))
    short_p = rng.integers(0, cfg.vocab_size, (5,))

    ref = Engine(model, params, max_batch=2, max_len=32)
    ref.submit(short_p, max_new_tokens=5)
    want_short = ref.run()[0].output
    ref2 = Engine(model, params, max_batch=2, max_len=32)
    ref2.submit(long_p, max_new_tokens=12)
    want_long = ref2.run()[0].output

    eng = Engine(model, params, max_batch=2, max_len=32)
    uid_long = eng.submit(long_p, max_new_tokens=12)
    for _ in range(4):                      # long request is mid-decode...
        eng.step()
    assert eng.num_active == 1
    uid_short = eng.submit(short_p, max_new_tokens=5)   # ...then admit
    outs = {r.uid: r.output for r in eng.run()}
    np.testing.assert_array_equal(outs[uid_short], want_short)
    np.testing.assert_array_equal(outs[uid_long], want_long)


@property_sweep(num_cases=4, base_seed=100)
def test_engine_slot_reuse_never_leaks(rng):
    """Property: a slot freed by one request and reused by another must
    not leak KV state — output on a reused arena == output on a fresh
    arena, for random prompts/budgets."""
    cfg, model, params = _SHARED["served"]
    eng = _SHARED["reused_engine"]          # slots reused across cases
    plen = int(rng.integers(2, 11))
    budget = int(rng.integers(1, 7))
    prompt = rng.integers(0, cfg.vocab_size, (plen,))
    # keep both slots busy so reuse interleaves with live decodes
    eng.submit(rng.integers(0, cfg.vocab_size, (int(rng.integers(2, 9)),)),
               max_new_tokens=int(rng.integers(1, 7)))
    uid = eng.submit(prompt, max_new_tokens=budget)
    outs = {r.uid: r.output for r in eng.run()}

    fresh = Engine(model, params, max_batch=2, max_len=32)
    fresh.submit(prompt, max_new_tokens=budget)
    np.testing.assert_array_equal(outs[uid], fresh.run()[0].output)


_SHARED = {}


@pytest.fixture(autouse=True)
def _shared_engine(served):
    if "served" not in _SHARED:
        _SHARED["served"] = served
        _SHARED["reused_engine"] = Engine(served[1], served[2],
                                          max_batch=2, max_len=32)
    yield


def test_engine_eos_truncates(served):
    cfg, model, params = served
    rng = np.random.default_rng(12)
    prompt = rng.integers(0, cfg.vocab_size, (6,))
    probe = Engine(model, params, max_batch=1, max_len=32)
    probe.submit(prompt, max_new_tokens=3)
    first_tok = int(probe.run()[0].output[0])

    eng = Engine(model, params, max_batch=1, max_len=32)
    eng.submit(prompt, max_new_tokens=10, eos_id=first_tok)
    out = eng.run()[0].output
    assert out[-1] == first_tok and len(out) <= 10


def test_engine_rejects_longer_than_slot(served):
    cfg, model, params = served
    eng = Engine(model, params, max_batch=1, max_len=16)
    with pytest.raises(ValueError, match="slot capacity"):
        eng.submit(np.arange(10, dtype=np.int32) % cfg.vocab_size,
                   max_new_tokens=20)


def test_engine_eos_on_prefill_token(served):
    """EOS emitted by the prefill forward itself (the request's very
    first generated token) finishes the request during admission — it
    never occupies a decode step, and the slot is immediately
    reusable."""
    cfg, model, params = served
    rng = np.random.default_rng(16)
    prompt = rng.integers(0, cfg.vocab_size, (6,))
    probe = Engine(model, params, max_batch=1, max_len=32)
    probe.submit(prompt, max_new_tokens=1)
    first_tok = int(probe.run()[0].output[0])

    eng = Engine(model, params, max_batch=1, max_len=32)
    eng.submit(prompt, max_new_tokens=10, eos_id=first_tok)
    other = eng.submit(rng.integers(0, cfg.vocab_size, (4,)),
                       max_new_tokens=2)
    done = eng.step()                   # admission finishes request 0
    assert [len(r.output) for r in done if r.uid != other] == [1]
    assert eng.run()[-1].uid == other   # slot was recycled


def test_bucket_length_floor_and_boundaries():
    """Pow2 boundaries and the floor clamp (satellite coverage for the
    admission bucketing)."""
    assert [bucket_length(n) for n in (1, 2, 3, 4, 8, 9, 16, 17)] == \
        [1, 2, 4, 4, 8, 16, 16, 32]
    assert bucket_length(3, floor=8) == 8       # floor clamps small lengths
    assert bucket_length(8, floor=8) == 8       # floor itself is a bucket
    assert bucket_length(9, floor=8) == 16      # floor does not cap large
    assert bucket_length(0) == 1                # degenerate inputs
    assert num_buckets(16, floor=16) == 1


# ---------------------------------------------------------------------------
# paged KV (block-pool) engine
# ---------------------------------------------------------------------------


# Greedy tokens from two different compiled paths (the paged engine and
# the raw prefill + decode_step loop, or the paged and arena engines) may
# part where two logits tie to within bf16 rounding: the logits come out
# of a bf16 unembedding (8 significand bits), and a path that rounds an
# intermediate differently moves a logit by one unit in the last place.
# Measured on the smoke model: the raw loop picks token 440 at 0.6016
# where the paged engine picks 395 at 0.5977, a margin of one ulp.  Such
# paths are therefore compared by teacher forcing: a path's tokens are
# fed through the raw loop, and at every step the token's logit must be
# within BF16_TIE_ULPS ulps (of that step's top logit) of the maximum.
# Bitwise checks stay where the same compiled programs rerun.
BF16_TIE_ULPS = 2


def _teacher_forced_logits(model, params, prompt, tokens):
    """[len(tokens), V] f32 logits of the raw loop when it is fed
    `tokens`: row i scores the choice of tokens[i]."""
    from functools import partial
    plen = len(prompt)
    prefill = jax.jit(partial(model.prefill, cache_len=plen + len(tokens)))
    decode = jax.jit(model.decode_step)
    logits, caches = prefill(params, {"tokens": jnp.asarray(prompt[None])})
    rows = [np.asarray(logits[0, -1])]
    for i in range(1, len(tokens)):
        tok = jnp.asarray([[tokens[i - 1]]], jnp.int32)
        logits, caches = decode(params, tok, caches, jnp.int32(plen + i - 1))
        rows.append(np.asarray(logits[0, -1]))
    return np.stack(rows)


def _assert_near_greedy(model, params, prompt, tokens):
    """Every token is the raw loop's argmax up to a bf16 tie."""
    tokens = np.asarray(tokens)
    logits = _teacher_forced_logits(model, params, prompt, tokens)
    top = logits.max(-1)
    gap = top - logits[np.arange(len(tokens)), tokens]
    ulp = 2.0 ** (np.floor(np.log2(np.abs(top))) - 7)
    bad = np.nonzero(gap > BF16_TIE_ULPS * ulp)[0]
    assert bad.size == 0, (
        f"tokens at {bad.tolist()} trail the raw loop's top logit by "
        f"{gap[bad].tolist()} (> {BF16_TIE_ULPS} bf16 ulps)")


def test_engine_paged_longer_than_slot_gqa(served):
    """Acceptance: plen + max_new_tokens > slot capacity (but within the
    pool budget) completes through Engine(paged=True), greedy under the
    raw single-request decode loop up to bf16 ties — with another
    request in flight so pool scatter/gather interleaves across rows."""
    cfg, model, params = served
    rng = np.random.default_rng(20)
    prompt = rng.integers(0, cfg.vocab_size, (10,))
    budget = 20                         # 10 + 20 = 30 > capacity 16

    eng = Engine(model, params, max_batch=2, max_len=16, paged=True,
                 block_size=8, prefill_chunk=4)
    assert eng.paged and eng.num_blocks * eng.block_size >= 30
    with pytest.raises(ValueError):     # pool budget still bounds requests
        eng.submit(prompt, max_new_tokens=10_000)
    uid = eng.submit(prompt, max_new_tokens=budget)
    eng.submit(rng.integers(0, cfg.vocab_size, (5,)), max_new_tokens=6)
    outs = {r.uid: r.output for r in eng.run()}
    assert len(outs[uid]) == budget
    _assert_near_greedy(model, params, prompt, outs[uid])
    assert eng.free_blocks == eng.num_blocks    # all blocks returned


def test_engine_paged_longer_than_slot_mla():
    """Same acceptance bar on an MLA (latent-cache) config: GQA and MLA
    share the paged code path."""
    from repro.configs.base import ArchConfig, MLAConfig
    cfg = ArchConfig(name="mla-paged-t", family="dense", source="test",
                     num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                     d_ff=128, vocab_size=256, tie_embeddings=True,
                     mla=MLAConfig(kv_lora_rank=16, q_lora_rank=32,
                                   qk_nope_head_dim=16, qk_rope_head_dim=8,
                                   v_head_dim=16))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(21)
    prompt = rng.integers(0, cfg.vocab_size, (9,))
    budget = 18                         # 9 + 18 = 27 > capacity 16

    eng = Engine(model, params, max_batch=2, max_len=16, paged=True,
                 block_size=4, prefill_chunk=4)
    assert eng.paged
    uid = eng.submit(prompt, max_new_tokens=budget)
    eng.submit(rng.integers(0, cfg.vocab_size, (5,)), max_new_tokens=8)
    outs = {r.uid: r.output for r in eng.run()}
    assert len(outs[uid]) == budget
    _assert_near_greedy(model, params, prompt, outs[uid])
    assert eng.free_blocks == eng.num_blocks


def test_engine_paged_matches_arena_mixed_lengths(served):
    """Paged and arena engines on a mixed-length workload that fits
    both: every request gets its full budget from each, and both are
    greedy under the raw loop up to bf16 ties — the storage backend is
    semantically inert."""
    cfg, model, params = served
    rng = np.random.default_rng(22)
    reqs = [(rng.integers(0, cfg.vocab_size, (int(n),)), int(b))
            for n, b in ((4, 2), (7, 9), (5, 1), (6, 4), (3, 6), (8, 8))]
    arena = Engine(model, params, max_batch=3, max_len=32)
    paged = Engine(model, params, max_batch=3, max_len=32, paged=True,
                   block_size=8)
    ua = [arena.submit(p, max_new_tokens=b) for p, b in reqs]
    up = [paged.submit(p, max_new_tokens=b) for p, b in reqs]
    oa = {r.uid: r.output for r in arena.run()}
    op = {r.uid: r.output for r in paged.run()}
    for (prompt, budget), a, b in zip(reqs, ua, up):
        assert len(oa[a]) == len(op[b]) == budget
        _assert_near_greedy(model, params, prompt, oa[a])
        _assert_near_greedy(model, params, prompt, op[b])
    assert paged.free_blocks == paged.num_blocks


def test_engine_paged_admission_waits_for_blocks(served):
    """FIFO under block scarcity in "reserve" mode: a pool with room for
    ~one live request still drains a deeper queue (finished requests
    free their blocks, the head is admitted next), never deadlocks, and
    never preempts."""
    cfg, model, params = served
    rng = np.random.default_rng(23)
    eng = Engine(model, params, max_batch=4, max_len=16, paged=True,
                 block_size=8, num_blocks=4,     # 32 pooled tokens
                 preemption="reserve")
    reqs = [(rng.integers(0, cfg.vocab_size, (6,)), 12) for _ in range(3)]
    uids = [eng.submit(p, max_new_tokens=b) for p, b in reqs]
    eng.step()
    # worst case 3 blocks each: only one fits alongside another's reserve
    assert eng.num_active < 3 and eng.pending >= 1
    done = eng.run()
    assert eng.num_preemptions == 0     # reserve mode never evicts
    assert sorted(r.uid for r in done) == sorted(uids)
    for (p, b), u in zip(reqs, uids):
        want = {r.uid: r.output for r in done}[u]
        ref_eng = Engine(model, params, max_batch=1, max_len=32)
        ref_eng.submit(p, max_new_tokens=b)
        np.testing.assert_array_equal(want, ref_eng.run()[0].output)


# ---------------------------------------------------------------------------
# preempt-and-recompute (paged, preemption="recompute")
# ---------------------------------------------------------------------------


def _drain_capped(eng, max_steps=600):
    """run() with a step cap: a livelock fails the test instead of
    hanging the suite."""
    done = []
    for _ in range(max_steps):
        done.extend(eng.step())
        if not (eng.pending or eng.num_active):
            return done
    raise AssertionError(
        f"engine did not drain in {max_steps} steps "
        f"(pending={eng.pending}, active={eng.num_active})")


def test_engine_paged_preemption_bit_identity_gqa(served):
    """Acceptance: a request that is preempted mid-generation and
    recomputed produces a final token sequence bitwise identical to the
    same request run unpreempted.  Pool sized so two hungry requests
    cannot coexist at peak — optimistic admission takes both, then the
    younger is evicted (LIFO) and recomputed."""
    cfg, model, params = served
    rng = np.random.default_rng(30)
    pa = rng.integers(0, cfg.vocab_size, (8,))
    pb = rng.integers(0, cfg.vocab_size, (8,))

    refs = {}
    for key, p in (("a", pa), ("b", pb)):
        r = Engine(model, params, max_batch=2, max_len=32)
        r.submit(p, max_new_tokens=20)
        refs[key] = r.run()[0].output

    # worst case 4 blocks each (8 + 20 - 1 = 27 tokens), pool holds 6:
    # reserve would serialize, recompute admits both then evicts B
    eng = Engine(model, params, max_batch=2, max_len=32, paged=True,
                 block_size=8, num_blocks=6, prefill_chunk=4)
    assert eng.paged and eng.preemption == "recompute"
    ua = eng.submit(pa, max_new_tokens=20)
    ub = eng.submit(pb, max_new_tokens=20)
    outs = {r.uid: r for r in _drain_capped(eng)}
    assert eng.num_preemptions >= 1
    assert outs[ub].preemptions >= 1        # LIFO: the younger is evicted
    assert outs[ua].preemptions == 0        # the older never is
    np.testing.assert_array_equal(outs[ua].output, refs["a"])
    np.testing.assert_array_equal(outs[ub].output, refs["b"])
    assert eng.free_blocks == eng.num_blocks    # eviction leaked nothing


def test_engine_paged_preemption_bit_identity_mla():
    """Same acceptance bar on an MLA (latent-cache) config: recompute
    prefill shares the paged path with GQA."""
    from repro.configs.base import ArchConfig, MLAConfig
    cfg = ArchConfig(name="mla-preempt-t", family="dense", source="test",
                     num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                     d_ff=128, vocab_size=256, tie_embeddings=True,
                     mla=MLAConfig(kv_lora_rank=16, q_lora_rank=32,
                                   qk_nope_head_dim=16, qk_rope_head_dim=8,
                                   v_head_dim=16))
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(6))
    rng = np.random.default_rng(31)
    pa = rng.integers(0, cfg.vocab_size, (6,))
    pb = rng.integers(0, cfg.vocab_size, (6,))

    refs = {}
    for key, p in (("a", pa), ("b", pb)):
        r = Engine(model, params, max_batch=2, max_len=32)
        r.submit(p, max_new_tokens=15)
        refs[key] = r.run()[0].output

    eng = Engine(model, params, max_batch=2, max_len=32, paged=True,
                 block_size=4, num_blocks=7, prefill_chunk=4)
    assert eng.paged
    ua = eng.submit(pa, max_new_tokens=15)  # worst 5 blocks each, pool 7
    ub = eng.submit(pb, max_new_tokens=15)
    outs = {r.uid: r for r in _drain_capped(eng)}
    assert eng.num_preemptions >= 1 and outs[ub].preemptions >= 1
    np.testing.assert_array_equal(outs[ua].output, refs["a"])
    np.testing.assert_array_equal(outs[ub].output, refs["b"])
    assert eng.free_blocks == eng.num_blocks


def test_engine_paged_preemption_fifo_fairness(served):
    """Never-preempted requests keep FIFO completion order under
    pressure (equal budgets): eviction re-queues victims at the head,
    so younger requests cannot overtake older ones."""
    cfg, model, params = served
    rng = np.random.default_rng(32)
    eng = Engine(model, params, max_batch=3, max_len=32, paged=True,
                 block_size=8, num_blocks=6, prefill_chunk=4)
    reqs = [(rng.integers(0, cfg.vocab_size, (6,)), 14) for _ in range(5)]
    uids = [eng.submit(p, max_new_tokens=b) for p, b in reqs]
    done = _drain_capped(eng)
    assert sorted(r.uid for r in done) == sorted(uids)
    assert all(len(r.output) == 14 for r in done)   # no eos: full budgets
    never_preempted = [r.uid for r in done if r.preemptions == 0]
    assert never_preempted == sorted(never_preempted)
    assert eng.num_preemptions >= 1     # the workload did apply pressure
    assert eng.free_blocks == eng.num_blocks


def test_engine_paged_preemption_queue_stays_uid_sorted(served):
    """Eviction re-queues victims in uid position, so even when an
    older evictee is already waiting (double-preemption cascades) the
    queue never lets a younger request ahead of an older one."""
    cfg, model, params = served
    rng = np.random.default_rng(33)
    eng = Engine(model, params, max_batch=3, max_len=32, paged=True,
                 block_size=4, num_blocks=8, prefill_chunk=4)
    uids = [eng.submit(rng.integers(0, cfg.vocab_size, (5,)),
                       max_new_tokens=16) for _ in range(6)]
    for _ in range(600):
        eng.step()
        qs = [r.uid for r in eng._queue]
        assert qs == sorted(qs), f"queue out of uid order: {qs}"
        if not (eng.pending or eng.num_active):
            break
    else:
        raise AssertionError("engine did not drain")
    assert eng.num_preemptions >= 2        # cascades actually happened
    assert sorted(r.uid for r in eng._done) == sorted(uids)


@property_sweep(num_cases=3, base_seed=300)
def test_engine_paged_preemption_scarcity_sweep(rng):
    """Property: random workloads on pools barely larger than one
    request's worst case always drain (no deadlock/livelock — every
    submitted request completes) with outputs matching a solo arena
    run, and the pool ends fully free."""
    cfg, model, params = _SHARED["served"]
    plens = [int(rng.integers(2, 11)) for _ in range(5)]
    budgets = [int(rng.integers(2, 9)) for _ in range(5)]
    prompts = [rng.integers(0, cfg.vocab_size, (n,)) for n in plens]
    worst_tokens = max(n + b - 1 for n, b in zip(plens, budgets))
    eng = Engine(model, params, max_batch=3, max_len=32, paged=True,
                 block_size=4, prefill_chunk=4,
                 num_blocks=max(3, -(-worst_tokens // 4) + 1))
    uids = [eng.submit(p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    outs = {r.uid: r.output for r in _drain_capped(eng)}
    assert sorted(outs) == sorted(uids)
    assert eng.free_blocks == eng.num_blocks
    for p, b, u in zip(prompts, budgets, uids):
        ref = Engine(model, params, max_batch=1, max_len=32)
        ref.submit(p, max_new_tokens=b)
        np.testing.assert_array_equal(outs[u], ref.run()[0].output)


def test_engine_preemption_during_replay_bit_identity(served):
    """A slot evicted while it is still REPLAYING a previous eviction's
    tokens (`_replay` non-empty) must re-admit cleanly: gen_prefix is
    not duplicated (the interrupted replay contributed nothing to
    `_gen`) and the final output is bitwise identical to an unpreempted
    run of the same paged configuration (the same compiled programs).
    Scenario: an older long request keeps crossing block boundaries, so
    the younger request is evicted, re-admitted, and evicted again
    before its replay drains."""
    cfg, model, params = served
    rng = np.random.default_rng(34)
    pa = rng.integers(0, cfg.vocab_size, (4,))
    pb = rng.integers(0, cfg.vocab_size, (4,))
    budget = 24
    geometry = dict(max_batch=2, max_len=32, paged=True, block_size=4,
                    num_blocks=7, prefill_chunk=4)

    # alone, a request's worst case (4 + 24 - 1 = 27 tokens, 7 blocks)
    # fits the pool: the references are never evicted
    refs = {}
    for key, p in (("a", pa), ("b", pb)):
        r = Engine(model, params, **geometry)
        r.submit(p, max_new_tokens=budget)
        refs[key] = r.run()[0].output
        assert r.num_preemptions == 0

    # together, optimistic admission takes both, then A's growth
    # repeatedly evicts B (LIFO) — including while B is mid-replay
    eng = Engine(model, params, **geometry)
    assert eng.paged and eng.preemption == "recompute"
    ua = eng.submit(pa, max_new_tokens=budget)
    ub = eng.submit(pb, max_new_tokens=budget)

    mid_replay_evictions = 0
    done = []
    for _ in range(600):
        b_slot = next((s for s in range(eng.max_batch)
                       if eng._slot_req[s] is not None
                       and eng._slot_req[s].uid == ub), None)
        b_replaying = b_slot is not None and bool(eng._replay[b_slot])
        pre = eng.num_preemptions
        done.extend(eng.step())
        if b_replaying and eng.num_preemptions > pre \
                and any(r.uid == ub for r in eng._queue):
            mid_replay_evictions += 1
        if not (eng.pending or eng.num_active):
            break
    else:
        raise AssertionError("engine did not drain")

    assert mid_replay_evictions >= 1, (
        "scenario failed to evict a mid-replay slot; retune the pool")
    outs = {r.uid: r for r in done}
    assert outs[ub].preemptions >= 2
    # no duplication: output length is exactly the budget …
    assert len(outs[ua].output) == budget
    assert len(outs[ub].output) == budget
    # … and the tokens are bitwise those of an unpreempted run
    np.testing.assert_array_equal(outs[ua].output, refs["a"])
    np.testing.assert_array_equal(outs[ub].output, refs["b"])
    assert eng.free_blocks == eng.num_blocks


def test_engine_long_replay_bit_identity(served):
    """Regression for the O(n²) replay drain: `_replay` held a list and
    `pop(0)` shifted every remaining element each decode step.  It is a
    deque now; a request evicted LATE in a long generation (hundreds of
    queued replay tokens) must drain it popleft-by-popleft and still
    reproduce the unpreempted output bitwise.  References are solo
    *paged* runs with the same geometry: at this length the paged and
    arena backends legitimately argmax-tie-flip on this random-weight
    model, and the property under test is replay, not backend parity."""
    cfg, model, params = served
    rng = np.random.default_rng(35)
    pa = rng.integers(0, cfg.vocab_size, (8,))
    pb = rng.integers(0, cfg.vocab_size, (8,))
    budget = 96

    refs = {}
    for key, p in (("a", pa), ("b", pb)):
        r = Engine(model, params, max_batch=2, max_len=128, paged=True,
                   block_size=8, num_blocks=40, prefill_chunk=8)
        r.submit(p, max_new_tokens=budget)
        refs[key] = r.run()[0].output

    # worst case 13 blocks each (8 + 96 - 1 = 103 tokens / 8); pool 18
    # admits both optimistically, exhausts when the pair holds ~144
    # tokens, so B is evicted ~60 tokens deep → a long replay queue
    eng = Engine(model, params, max_batch=2, max_len=128, paged=True,
                 block_size=8, num_blocks=18, prefill_chunk=8)
    assert eng.paged and eng.preemption == "recompute"
    from collections import deque
    assert all(isinstance(q, deque) for q in eng._replay)
    ua = eng.submit(pa, max_new_tokens=budget)
    ub = eng.submit(pb, max_new_tokens=budget)
    outs = {r.uid: r for r in _drain_capped(eng, max_steps=1200)}
    assert outs[ub].preemptions >= 1
    assert eng.stats["replayed_tokens"] >= 50, eng.stats["replayed_tokens"]
    np.testing.assert_array_equal(outs[ua].output, refs["a"])
    np.testing.assert_array_equal(outs[ub].output, refs["b"])
    assert eng.free_blocks == eng.num_blocks


def test_engine_preemption_arg_validated(served):
    cfg, model, params = served
    with pytest.raises(ValueError, match="preemption"):
        Engine(model, params, max_batch=2, max_len=16, paged=True,
               preemption="swap")


@pytest.mark.parametrize("arch,reason", [
    ("rwkv6-1.6b", "recurrent state has no pages"),
    ("deepseek-v2-236b", "moe chunking changes routing capacity"),
])
def test_engine_paged_auto_selects_arena(arch, reason):
    """paged=True on families that cannot page falls back to the arena
    and still serves correctly."""
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(4))
    rng = np.random.default_rng(24)
    eng = Engine(model, params, max_batch=2, max_len=32, paged=True)
    assert not eng.paged, reason
    prompt = rng.integers(0, cfg.vocab_size, (5,))
    uid = eng.submit(prompt, max_new_tokens=4)
    ref = Engine(model, params, max_batch=2, max_len=32)
    ref.submit(prompt, max_new_tokens=4)
    np.testing.assert_array_equal(
        {r.uid: r.output for r in eng.run()}[uid], ref.run()[0].output)


@pytest.fixture(scope="module")
def served_windowed():
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg, window=16)
    params = model.init(jax.random.PRNGKey(5))
    return cfg, model, params


def test_engine_ring_paged_sliding_window_bitwise(served_windowed):
    """Sliding-window GQA now PAGES: the window becomes a fixed block
    ring (position p at ring slot p % window, eviction = overwrite), so
    Engine(paged=True) serves it instead of falling back to the arena —
    bit-identical to the arena sliding-window path, longer-than-window
    prompts and generations included."""
    cfg, model, params = served_windowed
    rng = np.random.default_rng(44)
    prompts = [rng.integers(0, cfg.vocab_size, (int(n),))
               for n in (5, 23, 11, 3)]     # incl. longer-than-window

    ref = Engine(model, params, max_batch=2, max_len=128)
    assert not ref.paged and not ref.overlap    # windowed arena: serialized
    for p in prompts:
        ref.submit(p, max_new_tokens=30)
    want = {r.uid: r.output for r in ref.run()}

    eng = Engine(model, params, max_batch=2, max_len=128, paged=True,
                 block_size=8, num_blocks=24, prefill_chunk=32)
    assert eng.paged and eng.window == 16
    assert eng.prefill_chunk == 16              # clamped to the ring
    for p in prompts:
        eng.submit(p, max_new_tokens=30)
    outs = {r.uid: r.output for r in eng.run()}
    assert set(outs) == set(want)
    for u in want:
        np.testing.assert_array_equal(outs[u], want[u])
    assert eng.free_blocks == eng.num_blocks


def test_engine_ring_paged_zero_alloc_long_generation(served_windowed):
    """The ring cap is the whole point: a windowed generation never
    occupies more than ceil(window / block_size) blocks per slot,
    however far past the window it runs (BlockAllocator telemetry —
    the uncapped accounting would have reserved 14 blocks here)."""
    cfg, model, params = served_windowed
    rng = np.random.default_rng(45)
    eng = Engine(model, params, max_batch=1, max_len=64, paged=True,
                 block_size=8, num_blocks=32, prefill_chunk=8)
    assert eng.paged
    eng.submit(rng.integers(0, cfg.vocab_size, (10,)), max_new_tokens=100)
    out = eng.run()[0].output
    assert len(out) == 100
    ring = -(-eng.window // eng.block_size)     # 2
    assert eng._allocator.peak_in_use <= ring, eng._allocator.peak_in_use
    assert eng.free_blocks == eng.num_blocks


def test_engine_ring_paged_preemption_bitwise(served_windowed):
    """Preempt-and-recompute through the ring: a starved pool evicts a
    windowed request mid-generation; its recompute prompt re-prefill
    and token replay run through the ring-aware steps and the output
    stays bitwise identical to an unstarved run."""
    cfg, model, params = served_windowed
    rng = np.random.default_rng(46)
    pa = rng.integers(0, cfg.vocab_size, (9,))
    pb = rng.integers(0, cfg.vocab_size, (12,))
    budget = 40

    refs = {}
    for key, p in (("a", pa), ("b", pb)):
        r = Engine(model, params, max_batch=2, max_len=64, paged=True,
                   block_size=4, num_blocks=16, prefill_chunk=8)
        r.submit(p, max_new_tokens=budget)
        refs[key] = r.run()[0].output

    # ring = 4 blocks per slot; pool 7 admits both optimistically and
    # runs dry as they wrap, evicting the newer request mid-generation
    eng = Engine(model, params, max_batch=2, max_len=64, paged=True,
                 block_size=4, num_blocks=7, prefill_chunk=8)
    assert eng.paged and eng.preemption == "recompute"
    ua = eng.submit(pa, max_new_tokens=budget)
    ub = eng.submit(pb, max_new_tokens=budget)
    outs = {r.uid: r for r in _drain_capped(eng, max_steps=800)}
    assert outs[ub].preemptions >= 1
    assert eng.stats["replayed_tokens"] > 0
    np.testing.assert_array_equal(outs[ua].output, refs["a"])
    np.testing.assert_array_equal(outs[ub].output, refs["b"])
    assert eng.free_blocks == eng.num_blocks


def test_family_capability_flags_windowed(served_windowed):
    """The sliding-window caps matrix: windowed GQA opts into paging /
    chunked prefill / mixed step (the ring), while windowed MLA and
    recurrent stacks keep degrading to the arena with serialized
    admission — and the engine resolution follows the backend: the
    SAME windowed GQA model overlaps when paged, serializes on the
    arena (its exact-length prefill has no fused-step shape)."""
    cfg, model, params = served_windowed
    caps = probe_family_caps(model, max_batch=2, capacity=32)
    assert caps == FamilyCaps(pad_prompts=False, supports_paging=True,
                              supports_chunked_prefill=True,
                              supports_mixed_step=True)
    arena = Engine(model, params, max_batch=1, max_len=32)
    assert not arena.paged and not arena.overlap
    assert arena.stats["overlap_mode"] == ""
    paged = Engine(model, params, max_batch=1, max_len=32, paged=True)
    assert paged.paged and paged.overlap
    assert paged.stats["overlap_mode"] == "fused"

    mla = build_model(_mla_cfg(), window=16)
    assert probe_family_caps(mla, max_batch=2, capacity=32) == FamilyCaps(
        pad_prompts=False, supports_paging=False,
        supports_chunked_prefill=False, supports_mixed_step=False)

    rec = build_model(get_smoke("rwkv6-1.6b"), window=16)
    assert probe_family_caps(rec, max_batch=2, capacity=32) == FamilyCaps(
        pad_prompts=False, supports_paging=False,
        supports_chunked_prefill=False, supports_mixed_step=False)


def test_probe_family_caps_memoized():
    """probe_family_caps eval_shape-traces several entry points; one
    Engine construction per cache bucket must not re-pay that — probes
    are memoized per (model, signature), weakly keyed by the Model."""
    from repro.serve.engine import _CAPS_CACHE
    model = build_model(get_smoke("qwen2-0.5b"))
    c1 = probe_family_caps(model, max_batch=2, capacity=32)
    assert probe_family_caps(model, max_batch=2, capacity=32) is c1
    assert probe_family_caps(model, max_batch=2, capacity=64) is not c1
    assert model in _CAPS_CACHE


def test_bucketing_bounds_compiles(served):
    """Distinct plen+budget combos collapse into O(log max_len) buckets:
    the shim keeps ONE engine for caps 9..12 (all bucket to 16), and the
    engine's admitted prefill shapes are powers of two."""
    cfg, model, params = served
    assert [bucket_length(n) for n in (3, 8, 9, 16, 17)] == [4, 8, 16, 16, 32]
    assert num_buckets(32) == 6                 # {1, 2, 4, 8, 16, 32}
    assert num_buckets(1024, floor=8) == 8      # O(log max_len)
    rng = np.random.default_rng(13)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore", DeprecationWarning)
        srv = BatchedServer(model, params, max_batch=2)
    for plen, budget in ((4, 5), (5, 5), (6, 6), (7, 5)):   # caps 9..12
        srv.submit(rng.integers(0, cfg.vocab_size, (plen,)), budget)
    srv.run()
    assert list(srv._engines) == [16]
    (eng,) = srv._engines.values()
    assert eng.prefill_shapes <= {8, 16}    # pow2 prompt buckets only


# ---------------------------------------------------------------------------
# token-returning steps + host-loop telemetry
# ---------------------------------------------------------------------------


def test_token_step_entry_points_return_ids_not_logits(served):
    """The jitted serving steps must hand the host int32 token ids:
    [B] for the row-wise decode steps (plus advanced positions/lengths
    for the device feedback loop), [] for the batch-1 admission
    prefills.  This is the per-step transfer contract the mesh engine
    relies on — never [B, 1, vocab] logits."""
    cfg, model, params = served
    b, cap = 3, 32
    arena = jax.eval_shape(lambda: model.init_arena(b, cap))
    out = jax.eval_shape(
        model.decode_rows_tokens,
        jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32)),
        jax.ShapeDtypeStruct((b,), jnp.int32), arena,
        jax.ShapeDtypeStruct((b,), jnp.int32))
    toks, _, pos = out
    assert toks.shape == (b,) and toks.dtype == jnp.int32
    assert pos.shape == (b,) and pos.dtype == jnp.int32

    pool = jax.eval_shape(lambda: model.init_pool(8, 8))
    toks, _, lens = jax.eval_shape(
        model.decode_rows_paged_tokens,
        jax.eval_shape(model.init, jax.ShapeDtypeStruct((2,), jnp.uint32)),
        jax.ShapeDtypeStruct((b,), jnp.int32), pool,
        jax.ShapeDtypeStruct((b, 4), jnp.int32),
        jax.ShapeDtypeStruct((b,), jnp.int32))
    assert toks.shape == (b,) and toks.dtype == jnp.int32
    assert lens.shape == (b,) and lens.dtype == jnp.int32


def test_engine_stats_and_steady_state_uploads(served):
    """Telemetry: the recorded per-decode-step fetch is [max_batch]
    int32, and in steady-state decode (no admission / finish / block
    boundary) the engine re-uploads NOTHING — tokens and lengths feed
    back device-side, tables stay cached."""
    cfg, model, params = served
    rng = np.random.default_rng(40)
    eng = Engine(model, params, max_batch=2, max_len=64, paged=True,
                 block_size=32)          # one block covers the whole run
    assert eng.paged
    eng.submit(rng.integers(0, cfg.vocab_size, (4,)), max_new_tokens=12)
    eng.step()                           # admission + first decode step
    base = eng.stats
    assert base["admissions"] == 1 and base["decode_steps"] == 1
    assert base["decode_fetch_elems"] == 2      # [max_batch] ids ...
    assert base["decode_fetch_dtype"] == "int32"    # ... not logits
    assert base["admit_host_s"] > 0 and base["decode_s"] > 0
    for _ in range(5):                   # steady state: same block, no events
        eng.step()
    after = eng.stats
    assert after["decode_steps"] == 6
    assert after["h2d_uploads"] == base["h2d_uploads"], (
        "steady-state decode must not re-upload tables/lengths/tokens")
    eng.run()
    # arena engines have no pool: free_blocks must be None, not 0
    assert Engine(model, params, max_batch=1, max_len=16).free_blocks is None
    assert eng.free_blocks == eng.num_blocks


# ---------------------------------------------------------------------------
# engine over other cache families: MLA (absorbed latent cache) and
# recurrent state (rwkv; exact-length prefill, no padding)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("arch", ["deepseek-v2-236b", "rwkv6-1.6b"])
def test_engine_other_families_bit_identical(arch):
    cfg = get_smoke(arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(1))
    rng = np.random.default_rng(14)
    a = rng.integers(0, cfg.vocab_size, (5,))
    b = rng.integers(0, cfg.vocab_size, (7,))

    ref = Engine(model, params, max_batch=2, max_len=32)
    ref.submit(a, max_new_tokens=4)
    want = ref.run()[0].output

    eng = Engine(model, params, max_batch=2, max_len=32)
    eng.submit(b, max_new_tokens=8)
    eng.step()
    eng.step()
    uid = eng.submit(a, max_new_tokens=4)   # admitted mid-flight
    outs = {r.uid: r.output for r in eng.run()}
    np.testing.assert_array_equal(outs[uid], want)
    # neither family may pad prompts: recurrent state folds padding in,
    # and moe capacity dropping depends on the static sequence length
    assert eng.prefill_shapes == {5, 7}


def test_engine_on_production_mesh_subprocess():
    """Engine(mesh=...) serves on a ("data", "model") mesh via the
    slot-arena sharding specs; mid-flight admission stays bit-identical
    to a same-mesh engine serving the request alone.  The paged backend
    (pool_shardings + chunked prefill) must also complete a
    longer-than-slot request on the mesh, matching the host arena
    reference (subprocess: needs 4 forced host devices)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = r"""
import sys
sys.path.insert(0, "src")
import numpy as np, jax
from jax.sharding import Mesh
from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.serve import Engine

cfg = ArchConfig(name="t", family="dense", source="test", num_layers=2,
                 d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                 d_ff=256, vocab_size=512, tie_embeddings=True)
model = build_model(cfg)
params = model.init(jax.random.PRNGKey(0))
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
rng = np.random.default_rng(0)
a = rng.integers(0, cfg.vocab_size, (5,))
b = rng.integers(0, cfg.vocab_size, (7,))

ref = Engine(model, params, max_batch=2, max_len=32, mesh=mesh)
ref.submit(a, max_new_tokens=4)
want = ref.run()[0].output

eng = Engine(model, params, max_batch=2, max_len=32, mesh=mesh)
eng.submit(b, max_new_tokens=8)
eng.step(); eng.step()
uid = eng.submit(a, max_new_tokens=4)
outs = {r.uid: r.output for r in eng.run()}
np.testing.assert_array_equal(outs[uid], want)

# paged on the mesh: longer-than-slot generation, vs a same-mesh arena
# reference with a big enough slot and the SAME max_batch (sharding is
# shape-dependent: host-vs-mesh or cross-batch-size bitwise comparison
# is out of scope — sharded reductions reorder float ops)
mesh_ref = Engine(model, params, max_batch=2, max_len=32, mesh=mesh)
mesh_ref.submit(a, max_new_tokens=20)            # 5 + 20 > capacity 16
want_long = mesh_ref.run()[0].output
pg = Engine(model, params, max_batch=2, max_len=16, mesh=mesh, paged=True,
            block_size=8, prefill_chunk=4)
assert pg.paged
uid = pg.submit(a, max_new_tokens=20)
pg.submit(b, max_new_tokens=6)
outs = {r.uid: r.output for r in pg.run()}
np.testing.assert_array_equal(outs[uid], want_long)
print("MESH_ENGINE_OK")
"""
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "MESH_ENGINE_OK" in res.stdout, res.stdout + res.stderr


def test_engine_ring_paged_on_mesh_subprocess():
    """Ring-paged sliding window on a ("data", "model") mesh: the paged
    windowed engine (async overlapped admission, starved pool forcing a
    mid-generation preemption + ring replay) must match the same-mesh
    arena windowed reference bitwise (subprocess: 4 forced host
    devices)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    code = r"""
import sys
sys.path.insert(0, "src")
import numpy as np, jax
from jax.sharding import Mesh
from repro.configs.base import ArchConfig
from repro.models import build_model
from repro.serve import Engine

cfg = ArchConfig(name="t", family="dense", source="test", num_layers=2,
                 d_model=128, num_heads=4, num_kv_heads=2, head_dim=32,
                 d_ff=256, vocab_size=512, tie_embeddings=True)
model = build_model(cfg, window=16)
params = model.init(jax.random.PRNGKey(0))
mesh = Mesh(np.array(jax.devices()).reshape(2, 2), ("data", "model"))
rng = np.random.default_rng(3)
pa = rng.integers(0, cfg.vocab_size, (9,))
pb = rng.integers(0, cfg.vocab_size, (12,))
budget = 24                                 # wraps the 16-token ring

ref = Engine(model, params, max_batch=2, max_len=64, mesh=mesh)
assert not ref.paged and not ref.overlap    # windowed arena: serialized
for p in (pa, pb):
    ref.submit(p, max_new_tokens=budget)
want = {r.uid: r.output for r in ref.run()}

# ring = 4 blocks per slot; pool 7 admits both then runs dry as they
# wrap, evicting the younger request mid-generation (ring replay)
eng = Engine(model, params, max_batch=2, max_len=64, mesh=mesh,
             paged=True, block_size=4, num_blocks=7, prefill_chunk=8)
assert eng.paged and eng.window == 16
assert eng.overlap and eng.overlap_mode == "async"
ua = eng.submit(pa, max_new_tokens=budget)
ub = eng.submit(pb, max_new_tokens=budget)
outs = {r.uid: r for r in eng.run()}
assert outs[ub].preemptions >= 1, outs[ub].preemptions
for u in want:
    np.testing.assert_array_equal(outs[u].output, want[u])
assert eng.free_blocks == eng.num_blocks
print("MESH_RING_OK")
"""
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900,
                         cwd=os.path.join(os.path.dirname(__file__), ".."))
    assert "MESH_RING_OK" in res.stdout, res.stdout + res.stderr


def test_engine_sliding_window_exact_prefill():
    """A model with an attention ring smaller than the slot capacity
    must prefill at exact prompt lengths: pow2 padding would evict real
    context from the windowed ring and count the pad slots valid.  The
    engine output must match the raw prefill/decode loop."""
    from functools import partial
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg, window=16)
    params = model.init(jax.random.PRNGKey(2))
    rng = np.random.default_rng(15)
    plen, budget = 20, 4
    prompt = rng.integers(0, cfg.vocab_size, (plen,))

    eng = Engine(model, params, max_batch=2, max_len=32)
    assert not eng._pad_prompts          # ring 16 < capacity 32
    uid = eng.submit(prompt, max_new_tokens=budget)
    out = {r.uid: r.output for r in eng.run()}[uid]

    prefill = jax.jit(partial(model.prefill, cache_len=plen + budget))
    decode = jax.jit(model.decode_step)
    logits, caches = prefill(params, {"tokens": jnp.asarray(prompt[None])})
    tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    want = [int(tok[0, 0])]
    for i in range(1, budget):
        logits, caches = decode(params, tok, caches, jnp.int32(plen + i - 1))
        tok = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
        want.append(int(tok[0, 0]))
    np.testing.assert_array_equal(out, np.asarray(want, np.int32))


# ---------------------------------------------------------------------------
# overlapped admission: capability flags, serialized-vs-overlapped bit
# identity (preemption-during-overlap included), stats schema
# ---------------------------------------------------------------------------


def _mla_cfg():
    from repro.configs.base import ArchConfig, MLAConfig
    return ArchConfig(name="mla-overlap-t", family="dense", source="test",
                      num_layers=2, d_model=64, num_heads=4, num_kv_heads=4,
                      d_ff=128, vocab_size=256, tie_embeddings=True,
                      mla=MLAConfig(kv_lora_rank=16, q_lora_rank=32,
                                    qk_nope_head_dim=16, qk_rope_head_dim=8,
                                    v_head_dim=16))


# (prompt_len, budget, arrival_step) — staggered so admissions land
# while other rows decode (the serialized scheduler would stall them)
_STAGGER = [(9, 6, 0), (5, 8, 0), (7, 5, 2), (4, 7, 3), (6, 6, 5)]


def _run_staggered(model, cfg, params, *, paged, overlap,
                   overlap_mode="auto", num_blocks=None, snapshots=None):
    """Drive `_STAGGER` through a fresh engine; returns (outputs in
    submit order, final stats)."""
    eng = Engine(model, params, max_batch=2, max_len=24, paged=paged,
                 block_size=4, prefill_chunk=4, overlap=overlap,
                 overlap_mode=overlap_mode, num_blocks=num_blocks)
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, cfg.vocab_size, (int(n),)), int(b))
            for n, b, _ in _STAGGER]
    outs, uids, nxt, step_i = {}, [], 0, 0
    while nxt < len(reqs) or eng.num_active or eng.pending:
        while nxt < len(reqs) and _STAGGER[nxt][2] <= step_i:
            p, b = reqs[nxt]
            uids.append(eng.submit(p, max_new_tokens=b))
            nxt += 1
        for r in eng.step():
            outs[r.uid] = list(r.output)
        if snapshots is not None:
            snapshots.append(eng.stats)
        step_i += 1
    return [outs[u] for u in uids], eng.stats


def test_family_capability_flags():
    """The monolithic fallback table is now piecewise caps: a dense
    full-attention stack opts into everything, a recurrent stack into
    nothing — and the engine degrades to exactly the caps it probed."""
    cfg = get_smoke("qwen2-0.5b")
    model = build_model(cfg)
    caps = probe_family_caps(model, max_batch=2, capacity=32)
    assert caps == FamilyCaps(pad_prompts=True, supports_paging=True,
                              supports_chunked_prefill=True,
                              supports_mixed_step=True)

    rcfg = get_smoke("rwkv6-1.6b")
    rmodel = build_model(rcfg)
    rcaps = probe_family_caps(rmodel, max_batch=2, capacity=32)
    assert rcaps == FamilyCaps(pad_prompts=False, supports_paging=False,
                               supports_chunked_prefill=False,
                               supports_mixed_step=False)
    # engine resolution follows the caps: paged + overlap silently off
    eng = Engine(rmodel, rmodel.init(jax.random.PRNGKey(0)),
                 max_batch=1, max_len=16, paged=True)
    assert not eng.paged and not eng.overlap
    assert eng.stats["overlap_mode"] == ""


@pytest.mark.parametrize("paged", [False, True])
@pytest.mark.parametrize("family", ["gqa", "mla"])
def test_overlap_vs_serialized_bit_identity(served, family, paged):
    """The house gate for the overlapped scheduler: byte-for-byte the
    serialized baseline's outputs, arena + paged, GQA + MLA — with the
    paged pool starved (num_blocks=6) so preemption fires while
    overlapped admissions are in flight."""
    if family == "gqa":
        cfg, model, params = served
    else:
        cfg = _mla_cfg()
        model = build_model(cfg)
        params = model.init(jax.random.PRNGKey(0))
    kw = {"num_blocks": 6} if paged else {}
    ser, st_s = _run_staggered(model, cfg, params, paged=paged,
                               overlap=False, **kw)
    ov, st_o = _run_staggered(model, cfg, params, paged=paged,
                              overlap=True, **kw)
    assert ser == ov
    assert st_o["overlap_mode"] == "fused"      # host: auto picks fused
    assert st_o["mixed_steps"] > 0
    assert st_o["overlapped_admissions"] > 0
    assert st_s["mixed_steps"] == st_s["overlapped_admissions"] == 0
    if paged:
        # the pool is tight enough that BOTH schedulers preempted —
        # identity above covers preemption-during-overlap
        assert st_s["preemptions"] > 0 and st_o["preemptions"] > 0


def test_overlap_async_mode_bit_identity(served):
    """overlap_mode="async" (what auto picks on data-sharded meshes,
    forced here on host) reuses the serialized graphs — identity must
    hold with zero mixed launches."""
    cfg, model, params = served
    ser, _ = _run_staggered(model, cfg, params, paged=True,
                            overlap=False, num_blocks=6)
    ov, st = _run_staggered(model, cfg, params, paged=True, overlap=True,
                            overlap_mode="async", num_blocks=6)
    assert ser == ov
    assert st["overlap_mode"] == "async"
    assert st["mixed_steps"] == 0
    assert st["overlapped_admissions"] > 0


def test_overlap_mode_validated(served):
    cfg, model, params = served
    with pytest.raises(ValueError, match="overlap_mode"):
        Engine(model, params, max_batch=1, max_len=16,
               overlap_mode="eager")


def test_engine_stats_schema_and_monotone(served):
    """Every stats key is present in every snapshot, counters never
    decrease across steps, and the decode timing split is exact:
    decode_s == decode_dispatch_s + decode_fetch_s."""
    import math

    cfg, model, params = served
    snaps = []
    _run_staggered(model, cfg, params, paged=True, overlap=True,
                   snapshots=snaps)
    keys = {"admissions", "admit_host_s", "prefill_wait_s",
            "decode_steps", "decode_s", "decode_dispatch_s",
            "decode_fetch_s", "topup_host_s", "h2d_uploads",
            "replayed_tokens", "mixed_steps", "overlapped_admissions",
            "decode_fetch_elems", "decode_fetch_dtype", "preemptions",
            "overlap_mode"}
    counters = keys - {"decode_fetch_elems", "decode_fetch_dtype",
                       "overlap_mode"}
    assert snaps and all(keys <= set(s) for s in snaps)
    for prev, cur in zip(snaps, snaps[1:]):
        for k in counters:
            assert cur[k] >= prev[k], f"{k} went backwards"
    last = snaps[-1]
    assert math.isclose(last["decode_s"], last["decode_dispatch_s"]
                        + last["decode_fetch_s"], rel_tol=1e-9)
    assert last["mixed_steps"] <= last["decode_steps"]
    assert last["overlapped_admissions"] <= last["admissions"]
    assert last["overlap_mode"] in ("fused", "async", "")


def test_chunks_needed_boundaries():
    """Exact chunk multiples must not round up an extra launch."""
    from repro.serve import chunks_needed
    for c in (1, 4, 16, 32):
        for k in (1, 2, 5):
            assert chunks_needed(k * c, c) == k          # exact multiple
            assert chunks_needed(k * c + 1, c) == k + 1  # one past
            if c > 1:
                assert chunks_needed(k * c - 1, c) == k  # one short
    assert chunks_needed(1, 4) == 1
