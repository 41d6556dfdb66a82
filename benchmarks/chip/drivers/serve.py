"""Drive the serving engine (`repro.serve.Engine`) under an open loop.

setup    makes the weights on the device from the seed, builds the
         engine as the cell's config file states, makes the window's
         requests from the seed, and warms up, through the engine's
         public submit/step API, every prefill, decode and mixed-step
         table width those requests can reach (`warm_plan`).
window   submits each request when it falls due, steps the engine,
         and records every step's return time and active rows and
         every request's completion; after the window it keeps
         stepping, submitting nothing, until every request due in the
         window is done (or DRAIN_S passes: the rest count as failed).
check    runs the plain reference over a seeded sample of the finished
         requests, the longest among them, and reads how far each
         served token's reference logit lies below the reference's
         best at its position.
"""
from __future__ import annotations

import time

import jax
import numpy as np

from compare import widest_logit_gap
from gen.requests import requests
from yardstick import percentile, weighted_percentile

DRAIN_S = 120.0
SAMPLE_TOKENS = 400      # served tokens the check reads at least
SAMPLE_MIN = 4           # requests it reads at least, where there are
SAMPLE_MAX = 12          # requests it reads at most
REF_BUCKET = 256         # reference sequences are padded to a multiple


def warm_plan(reqs, width):
    """[(table width, long prompt, extra tokens)] for `_warm`, and the
    prompt lengths whose prefill width no long prompt reaches.

    width(n) is the engine's block-table width for n tokens.  Every width
    from the shortest prompt's first decode to the longest context gets
    a long request that decodes at it: the shortest prompt whose first
    decode lands there, or where the prompts are too short, the longest
    prompt and `extra` decoded tokens to reach it."""
    plens = sorted({len(r.prompt) for r in reqs})
    hi_ctx = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    widths = sorted({width(n) for n in range(plens[0] + 1, hi_ctx + 1)})
    plan, prefills = [], set()
    for w in widths:
        fits = [p for p in range(plens[0], plens[-1] + 1)
                if width(p + 1) == w]
        p = fits[0] if fits else plens[-1]
        extra = 0
        while width(p + extra + 1) < w:
            extra += 1
        plan.append((w, p, extra))
        prefills.add(width(p))
    extra = []
    for p in plens:
        if width(p) not in prefills:
            extra.append(p)
            prefills.add(width(p))
    return plan, extra


# The engine's jitted steps see their small operands either freshly
# uploaded from the host or fed back from the previous step, and JAX
# compiles each such mix apart.  At one width, this script runs the
# decode step and the mixed step under each mix the engine produces:
# fresh lengths and tokens after an admission resolves, fresh lengths
# alone after a row finishes, and neither while rows decode on.  Each
# entry submits (prompt, new tokens) requests, then steps once; the
# long request A decodes through all of it.
_SCRIPT = (
    (),                     # A prefills alone, decodes: fresh, fresh
    (),                     # decode: fed back
    (("short", 2),),        # B streams in a mixed step: fed back
    (),                     # B resolves: decode fresh; B finishes
    (),                     # decode: fresh lengths
    (("short", 2),),        # E streams: mixed, fed back
    (),                     # E resolves and finishes in the decode
    (("short", 1),),        # F streams: mixed, fresh lengths
    (("short", 1),),        # F resolves and finishes, G streams:
)                           # mixed, fresh


def _warm(engine, reqs, seed, vocab):
    from repro.serve.bucketing import table_width

    rng = np.random.default_rng(seed)
    plan, extra = warm_plan(reqs, lambda n: table_width(
        n, engine.block_size, engine.num_blocks, window=engine.window))
    short = min(len(r.prompt) for r in reqs)

    def prompt(n):
        return rng.integers(0, vocab, n, dtype=np.int32)

    # the pool starts as a host-made array and is fed back from then on:
    # one request first, so that the script runs on the fed-back pool
    engine.submit(prompt(short), 1)
    engine.run()
    for _, p, pre in plan:
        engine.submit(prompt(p), pre + len(_SCRIPT) + 4)
        for _ in range(pre):
            engine.step()
        for subs in _SCRIPT:
            for _, new in subs:
                engine.submit(prompt(short), new)
            engine.step()
        engine.run()
    for p in extra:
        engine.submit(prompt(p), 2)
        engine.run()
    return plan, extra


def setup(cell, log=print, model=None):
    from repro.models import build_model
    from repro.serve import Engine

    mix, dep = cell.traffic, cell.config["serve"]
    dev = cell.devices[0]
    model = model or build_model(cell.arch())
    params = jax.jit(model.init,
                     out_shardings=jax.sharding.SingleDeviceSharding(dev))(
        jax.random.PRNGKey(cell.model_seed))
    reqs = requests(mix, cell.model["vocab_size"], cell.seed,
                    cell.window_seconds)
    max_ctx = max(len(r.prompt) + r.max_new_tokens for r in reqs)
    engine = Engine(model, params, max_batch=mix["max_batch"],
                    max_len=max_ctx, paged=True,
                    block_size=dep["block_size"],
                    num_blocks=dep["num_blocks"],
                    prefill_chunk=dep["prefill_chunk"])
    assert engine.paged and engine.overlap_mode == "fused", (
        engine.paged, engine.overlap_mode)
    plan = _warm(engine, reqs, cell.seed + 1, cell.model["vocab_size"])
    log(f"serve: {len(reqs)} requests due, warm-up (width, prompt, "
        f"extra tokens) and prefill-only prompts {plan}")
    return {"engine": engine, "params": params, "requests": reqs}


def window(cell, state, seconds, log=print):
    eng, reqs = state["engine"], state["requests"]
    stats0 = eng.stats
    uid_of = {}
    done_at = {}
    outputs = {}
    steps = []          # (return time, gap since previous return, rows)
    nxt = 0
    t0 = time.monotonic()
    prev = 0.0
    deadline = seconds + DRAIN_S
    while True:
        now = time.monotonic() - t0
        while nxt < len(reqs) and reqs[nxt].due_s <= now:
            with cell.span("bench.submit"):
                uid_of[eng.submit(reqs[nxt].prompt,
                                  reqs[nxt].max_new_tokens)] = nxt
            nxt += 1
        if len(done_at) == len(reqs) or now > deadline:
            break
        if not eng.num_active and not eng.pending and nxt < len(reqs):
            with cell.span("bench.idle"):
                time.sleep(min(0.0005, max(reqs[nxt].due_s - now, 0.0)))
            prev = time.monotonic() - t0
            continue
        rows = eng.num_active
        with cell.span("bench.engine_step"):
            finished = eng.step()
        t = time.monotonic() - t0
        steps.append((t, t - prev, rows))
        prev = t
        for r in finished:
            i = uid_of[r.uid]
            done_at[i] = t
            outputs[i] = np.asarray(r.output)
    stats1 = eng.stats
    state["outputs"] = outputs
    in_window = [i for i, t in done_at.items() if t < seconds]
    tokens = sum(len(outputs[i]) for i in in_window)
    lat = [(done_at.get(i, float("inf")) - r.due_s) * 1e3
           for i, r in enumerate(reqs)]
    gaps = [(g * 1e3, rows) for t, g, rows in steps if t < seconds]
    failed = len(reqs) - len(done_at)
    log(f"serve: {len(reqs)} due, {len(done_at)} done "
        f"({len(in_window)} inside the window), {len(steps)} steps, "
        f"{tokens} tokens inside the window, failed {failed}")
    return {"e2e": {"serve_tokens_per_s": tokens / seconds,
                    "request_latency_p95_ms": percentile(lat, 95),
                    "itl_p95_ms": weighted_percentile(gaps, 95)},
            "attempted": len(reqs), "failed": failed,
            "latencies_ms": lat,
            "window_s": seconds,
            "steps_in_window": sum(1 for s in steps if s[0] < seconds),
            "stats": {k: stats1[k] - stats0[k]
                      for k in ("admit_host_s", "topup_host_s",
                                "decode_steps", "mixed_steps")},
            "completed": [(len(reqs[i].prompt), len(outputs[i]))
                          for i in in_window]}


def release(cell, state):
    kept = {k: state[k] for k in ("requests", "outputs")}
    state.clear()
    return kept


def sample(reqs, outputs, seed):
    """Seeded sample of finished requests, the longest output first,
    until SAMPLE_TOKENS served tokens in SAMPLE_MIN requests or more,
    or SAMPLE_MAX requests."""
    done = sorted(outputs)
    if not done:
        return []
    longest = max(done, key=lambda i: (len(outputs[i]), -i))
    order = [longest] + [int(i) for i in np.random.default_rng(seed)
                         .permutation(done) if i != longest]
    picked, served = [], 0
    for i in order:
        if len(picked) >= SAMPLE_MAX or (served >= SAMPLE_TOKENS
                                          and len(picked) >= SAMPLE_MIN):
            break
        picked.append(i)
        served += len(outputs[i])
    return picked


def reference_gaps(cell, reqs, outputs, picked, precisions=("f32",)):
    """{precision: [T] logits rows} are not kept; returns per picked
    request the f32 reference logits' widest gap of the served tokens,
    and for a lower precision the gap of the tokens it puts first."""
    from references import dense_gqa

    cfg = cell.model
    dev = cell.devices[0]
    params = jax.jit(lambda k: dense_gqa.init(cfg, k),
                     out_shardings=jax.sharding.SingleDeviceSharding(dev))(
        jax.random.PRNGKey(cell.model_seed))
    fns = {p: jax.jit(lambda w, t, p=p: dense_gqa.logits(cfg, w, t, p))
           for p in precisions}
    out = {p: [] for p in precisions}
    for i in picked:
        prompt, served = reqs[i].prompt, outputs[i]
        seq = np.concatenate([prompt, served[:-1]]).astype(np.int32)
        n = len(seq)
        padded = np.zeros(-(-n // REF_BUCKET) * REF_BUCKET, np.int32)
        padded[:n] = seq
        pos = np.arange(len(prompt) - 1, n)
        ref = np.asarray(fns["f32"](params, padded))[pos]
        out["f32"].append(widest_logit_gap(ref, served))
        for p in precisions[1:]:
            low = np.asarray(fns[p](params, padded))[pos]
            out[p].append(widest_logit_gap(ref, low.argmax(-1)))
    return out


def check(cell, kept, log=print):
    outputs = kept["outputs"]
    picked = sample(kept["requests"], outputs, cell.seed)
    lim = cell.limits["limits"]
    unfinished = len(kept["requests"]) - len(outputs)
    if not picked:
        return [("unfinished", unfinished, 0),
                ("logit_gap", None, lim["logit_gap"])]
    gaps = reference_gaps(cell, kept["requests"], outputs, picked)["f32"]
    log(f"serve check: {len(picked)} requests, "
        f"{sum(len(outputs[i]) for i in picked)} served tokens, "
        f"gaps {gaps}")
    return [("unfinished", unfinished, 0),
            ("logit_gap", max(gaps), lim["logit_gap"])]
