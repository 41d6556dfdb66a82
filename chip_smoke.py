"""Run the serving engine and the API-BCD superstep end to end on a TPU.

    python chip_smoke.py              # one chip: serve (arena, paged), train
    python chip_smoke.py --chips 4    # four chips: the agent ring only

The one-chip run builds qwen2-0.5b at its published widths (24 layers,
d_model 896, 14 heads with 2 KV heads, d_ff 4864, vocab 151936) with
random weights from --seed, on `jax.devices()[0]` alone:

  serve  drives `repro.serve.Engine`, the entry point of
         `repro.launch.serve`, once with the slot arena and once with the
         paged pool: 8 seeded requests with prompts of 32-200 tokens and
         budgets of 16-64 at max_batch 4, so admission into freed slots,
         chunked prefill and the fused mixed step all run.  Every request
         must finish with exactly its budget of in-vocabulary tokens, and
         its first token must be the argmax of a separate `model.prefill`
         up to a bf16 tie (FIRST_TOKEN_TIE_ULPS).  Each backend runs the
         workload twice: the second run must compile nothing and
         reproduce the first bit for bit.
  train  runs the superstep of `repro.launch.train` (`Superstep`) with
         one agent and one walk for TRAIN_STEPS steps: the loss must be
         finite at every step, the step must hold the compiled
         prox_update kernel (`tpu_custom_call`), and nothing may compile
         after the first step.

--chips 4 runs only the superstep with four agents, one per chip, and
two walks: first at the smoke widths against the same jitted step on
one device with the agents vmapped and no mesh (STATE_TOL_ULPS), then at
published widths, where each chip must hold one agent's share of the
state and the loss must stay finite.

Depth is cut only where the compiled step's `memory_analysis` does not
fit the chip, and every cut is printed.  The seconds and tokens/s
printed are information, not benchmark results.  The last line of
stdout is the JSON verdict; a failed phase exits non-zero without it.
There is no CPU fallback: without a TPU the script exits non-zero.
"""
import argparse
import dataclasses
import gc
import json
import math
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
ARCH = "qwen2-0.5b"

N_REQUESTS, MAX_BATCH = 8, 4
PROMPT_LENS, BUDGETS = (32, 200), (16, 64)
# The engine's first token and the reference prefill come from different
# compiled programs (bucket-padded or chunked prefill against one
# exact-length prefill).  Their logits come out of a bf16 unembedding (8
# significand bits), and rounding that differs in any of 24 layers moves
# a logit by a few units in its last place; on the smoke model two such
# paths were measured to part at a one-ulp margin.  A first token that
# differs from the reference argmax fails only where it trails the top
# reference logit by more than this many ulps of that logit.
FIRST_TOKEN_TIE_ULPS = 8

TRAIN_STEPS = 4
BATCH_PER_AGENT, SEQ = 4, 128
# The 4-agent ring against the same step on one device: both compute in
# bf16 with f32 accumulation, and the partitioned program may round an
# activation differently, which moves a gradient element by about one
# bf16 ulp (2**-8 relative).  The update divides gradients by rho, so
# every state leaf must agree to STATE_TOL_ULPS bf16 ulps of its largest
# magnitude, and the mean loss (over 2048 tokens) to LOSS_RTOL.
STATE_TOL_ULPS = 4
LOSS_RTOL = 1e-3
COMPARE_STEPS = 3
# A compiled step may plan to use this share of a chip's free memory.
MEMORY_HEADROOM = 0.95
CUT_LAYERS = 2      # layers dropped per depth cut


class SmokeError(RuntimeError):
    """A phase did not do what it must."""


def check(cond, msg):
    if not cond:
        raise SmokeError(msg)


class CompileLog:
    """Counts XLA compilations (persistent-cache hits included) and sums
    the seconds spent tracing, lowering and compiling."""

    _SPANS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.count = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event in self._SPANS:
            self.seconds += duration
        if event == self._SPANS[-1]:
            self.count += 1

    def mark(self):
        return self.count, self.seconds

    def since(self, mark):
        return self.count - mark[0], self.seconds - mark[1]


def report(phase, kind, **fields):
    text = " ".join(f"{k}={v}" for k, v in fields.items())
    print(f"[{phase}] device_kind={kind!r} {text}", flush=True)


def bf16_ulp(x):
    return 2.0 ** (math.floor(math.log2(abs(x))) - 7)


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def workload(vocab, seed):
    rng = np.random.default_rng(seed)
    plens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, N_REQUESTS)
    budgets = rng.integers(BUDGETS[0], BUDGETS[1] + 1, N_REQUESTS)
    prompts = [rng.integers(0, vocab, (int(n),), dtype=np.int32)
               for n in plens]
    return prompts, [int(b) for b in budgets]


def reference_logits(jax, model, params, prompts):
    """Last-position f32 logits of an exact-length `model.prefill`."""
    prefill = jax.jit(model.prefill)     # one compile per prompt length
    out = []
    for p in prompts:
        logits, _ = prefill(params, {"tokens": p[None]})
        out.append(np.asarray(logits[0, -1], np.float32))
    return out


def serve_backend(jax, model, params, prompts, budgets, refs, *, paged,
                  kind, compiles):
    from repro.serve import Engine, bucket_length

    name = "serve-paged" if paged else "serve-arena"
    vocab = model.cfg.vocab_size
    max_len = bucket_length(max(len(p) + b for p, b in zip(prompts, budgets)))
    runs = []
    for attempt in ("cold", "warm"):
        eng = Engine(model, params, max_batch=MAX_BATCH, max_len=max_len,
                     paged=paged)
        check(eng.paged == paged, f"{name}: engine resolved paged={eng.paged}")
        check(eng.overlap_mode == "fused",
              f"{name}: overlap mode {eng.overlap_mode!r}, not the fused "
              "mixed step")
        mark = compiles.mark()
        t0 = time.monotonic()
        uids = [eng.submit(p, max_new_tokens=b)
                for p, b in zip(prompts, budgets)]
        done = {r.uid: r for r in eng.run()}
        wall = time.monotonic() - t0
        n_compiles, compile_s = compiles.since(mark)
        outs = [np.asarray(done[u].output) for u in uids]
        stats = eng.stats
        del eng, done
        gc.collect()
        tokens = sum(len(o) for o in outs)
        report(name, kind, run=attempt, compiles=n_compiles,
               compile_s=f"{compile_s:.3f}", wall_s=f"{wall:.3f}",
               tokens=tokens, tokens_per_s=f"{tokens / wall:.3f}",
               admissions=stats["admissions"],
               mixed_steps=stats["mixed_steps"],
               decode_steps=stats["decode_steps"],
               preemptions=stats["preemptions"])
        runs.append((outs, n_compiles, stats))

    (outs, _, stats), (warm_outs, warm_compiles, _) = runs
    check(stats["admissions"] == N_REQUESTS,
          f"{name}: {stats['admissions']} admissions for {N_REQUESTS}")
    check(stats["mixed_steps"] > 0, f"{name}: the fused mixed step never ran")
    check(warm_compiles == 0,
          f"{name}: the second run compiled {warm_compiles} programs")
    for i, (out, b) in enumerate(zip(outs, budgets)):
        check(len(out) == b, f"{name}: request {i} got {len(out)} tokens, "
                             f"budget {b}")
        check(((out >= 0) & (out < vocab)).all(),
              f"{name}: request {i} emitted an out-of-vocabulary token")
        check(np.array_equal(out, warm_outs[i]),
              f"{name}: request {i} differs between two runs of the same "
              "compiled programs")
    for i, (out, ref) in enumerate(zip(outs, refs)):
        order = np.argsort(ref)
        top, second = ref[order[-1]], ref[order[-2]]
        gap = float(top - ref[out[0]])
        tol = FIRST_TOKEN_TIE_ULPS * bf16_ulp(top)
        verdict = "match" if out[0] == order[-1] else (
            "tie" if gap <= tol else "MISMATCH")
        print(f"[{name}] request {i}: plen={len(prompts[i])} "
              f"budget={budgets[i]} first={int(out[0])} "
              f"ref_argmax={int(order[-1])} top2_margin={top - second:.6g} "
              f"gap={gap:.6g} tol={tol:.6g} {verdict}", flush=True)
        check(verdict != "MISMATCH",
              f"{name}: request {i}'s first token trails the reference "
              f"top logit by {gap:.6g} > {tol:.6g}")


def serve_phase(jax, cfg, device, seed, kind, compiles):
    from repro.models import build_model

    model = build_model(cfg)
    mark = compiles.mark()
    params = jax.jit(model.init, out_shardings=jax.sharding.
                     SingleDeviceSharding(device))(jax.random.PRNGKey(seed))
    prompts, budgets = workload(cfg.vocab_size, seed)
    refs = reference_logits(jax, model, params, prompts)
    n, secs = compiles.since(mark)
    report("serve-reference", kind, compiles=n, compile_s=f"{secs:.3f}")
    for paged in (False, True):
        serve_backend(jax, model, params, prompts, budgets, refs,
                      paged=paged, kind=kind, compiles=compiles)
    for leaf in jax.tree.leaves(params):
        leaf.delete()


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------


def cut_depth(cfg, layers):
    return dataclasses.replace(cfg, num_layers=layers,
                               layer_types=cfg.layer_types[:layers])


def step_bytes(superstep):
    mem = superstep.lower(superstep.abstract_batch()).compile() \
        .memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def fit_depth(cfg, devices, phase, kind, **kw):
    """The deepest cut of cfg (published depth first) whose compiled step
    fits every chip's free memory, by `memory_analysis`."""
    from repro.launch.train import Superstep

    free = min(d.memory_stats()["bytes_limit"]
               - d.memory_stats()["bytes_in_use"] for d in devices)
    layers = cfg.num_layers
    while True:
        probe = Superstep(cut_depth(cfg, layers), devices, place=False, **kw)
        need = step_bytes(probe)
        report(phase, kind, layers=layers, step_bytes=need, free_bytes=free,
               fits=need <= MEMORY_HEADROOM * free)
        if need <= MEMORY_HEADROOM * free:
            break
        check(layers > CUT_LAYERS,
              f"{phase}: the step does not fit at {layers} layers")
        layers -= CUT_LAYERS
    if layers != cfg.num_layers:
        print(f"[{phase}] DEPTH CUT: {cfg.num_layers} -> {layers} layers "
              "(memory_analysis of the compiled step)", flush=True)
    return cut_depth(cfg, layers)


def train_steps(run, phase, kind, compiles, steps):
    """Drive `steps` supersteps; returns the losses."""
    a, b, s = run.abstract_batch()["tokens"].shape
    losses = []
    t1 = mark = None
    t0 = time.monotonic()
    first = compiles.mark()
    for step in range(steps):
        if step == 1:
            t1, mark = time.monotonic(), compiles.mark()
        loss = float(run.step(step)["loss"])
        check(math.isfinite(loss), f"{phase}: loss {loss} at step {step}")
        losses.append(loss)
    now = time.monotonic()
    n_first, compile_s = compiles.since(first)
    n_after, _ = compiles.since(mark)
    check(n_after == 0, f"{phase}: {n_after} compiles after the first step")
    report(phase, kind, steps=steps, compiles_after_first_step=n_after,
           compile_s=f"{compile_s:.3f}", wall_s=f"{now - t0:.3f}",
           tokens_per_s=f"{a * b * s * (steps - 1) / (now - t1):.3f}",
           losses=[f"{x:.6f}" for x in losses])
    return losses


def train_phase(jax, cfg, devices, seed, kind, compiles):
    from repro.launch.train import Superstep

    kw = dict(agents=1, walks=1, batch_per_agent=BATCH_PER_AGENT, seq=SEQ,
              seed=seed)
    cfg = fit_depth(cfg, devices, "train", kind, **kw)
    run = Superstep(cfg, devices, **kw)
    check("tpu_custom_call" in run.lower(run.abstract_batch()).as_text(),
          "train: the step holds no tpu_custom_call (prox_update did not "
          "compile as a kernel)")
    train_steps(run, "train", kind, compiles, TRAIN_STEPS)


# ---------------------------------------------------------------------------
# four chips: the agent ring
# ---------------------------------------------------------------------------


def ring_compare(jax, cfg, devices, seed, kind):
    """The 4-agent ring on four chips against the same step on one."""
    from repro.launch.train import Superstep

    kw = dict(agents=4, walks=2, batch_per_agent=BATCH_PER_AGENT, seq=SEQ,
              seed=seed)
    losses = {}
    states = {}
    for name, devs in (("one-device", devices[:1]), ("ring", devices)):
        run = Superstep(cfg, devs, **kw)
        losses[name] = [float(run.step(i)["loss"])
                        for i in range(COMPARE_STEPS)]
        states[name] = jax.tree.map(np.asarray, run.state)
        del run
        gc.collect()
    for i, (l1, l4) in enumerate(zip(losses["one-device"], losses["ring"])):
        check(abs(l1 - l4) <= LOSS_RTOL * abs(l1),
              f"ring-compare: step {i} loss {l4} vs one device {l1}")
    worst = 0.0
    flat1 = jax.tree_util.tree_leaves_with_path(states["one-device"])
    flat4 = jax.tree.leaves(states["ring"])
    for (path, x1), x4 in zip(flat1, flat4):
        scale = float(np.abs(x1).max())
        err = float(np.abs(x1 - x4).max())
        if scale == 0.0:
            check(err == 0.0, f"ring-compare: {jax.tree_util.keystr(path)} "
                              f"should be zero, differs by {err}")
            continue
        ratio = err / (STATE_TOL_ULPS * 2.0 ** -8 * scale)
        worst = max(worst, ratio)
        check(ratio <= 1.0,
              f"ring-compare: {jax.tree_util.keystr(path)} differs by "
              f"{err} (> {STATE_TOL_ULPS} bf16 ulps of {scale})")
    report("ring-compare", kind, layers=cfg.num_layers,
           d_model=cfg.d_model, steps=COMPARE_STEPS,
           losses_one_device=losses["one-device"], losses_ring=losses["ring"],
           worst_leaf_error_over_tol=f"{worst:.6g}",
           bitwise=all(np.array_equal(a, b) for a, b in zip(
               jax.tree.leaves(states["one-device"]), flat4)))


def ring_full(jax, cfg, devices, seed, kind, compiles):
    from repro.launch.train import Superstep

    kw = dict(agents=4, walks=2, batch_per_agent=BATCH_PER_AGENT, seq=SEQ,
              seed=seed)
    cfg = fit_depth(cfg, devices, "ring", kind, **kw)
    run = Superstep(cfg, devices, **kw)
    check("tpu_custom_call" in run.lower(run.abstract_batch()).as_text(),
          "ring: the step holds no tpu_custom_call")
    share = sum(x.nbytes for x in jax.tree.leaves(run.state)) / len(devices)
    in_use = [d.memory_stats()["bytes_in_use"] for d in devices]
    for d, n in zip(devices, in_use):
        print(f"[ring] device {d.id}: bytes_in_use={n} "
              f"({n / share:.4f} of one agent's state share {share:.0f})",
              flush=True)
    check(all(0.95 * share <= n <= 1.25 * share for n in in_use),
          f"ring: per-chip bytes_in_use {in_use} is not one agent's share "
          f"({share:.0f}) each")
    train_steps(run, "ring", kind, compiles, TRAIN_STEPS)


# ---------------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        sys.exit(f"chip_smoke: {ROOT} is not a checkout of this repository "
                 "(no src/repro)")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU; JAX found "
                 f"{devices[0].platform!r} devices")
    if len(devices) < args.chips:
        sys.exit(f"chip_smoke: --chips {args.chips} but JAX found "
                 f"{len(devices)} devices")
    devices = devices[:args.chips]
    kind = devices[0].device_kind

    from repro.configs import get_config, get_smoke
    from repro.utils.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    compiles = CompileLog(jax)
    cfg = get_config(ARCH)
    print(f"chip_smoke: {len(devices)} x {kind}, {cfg.name} "
          f"({cfg.num_layers} layers, d_model {cfg.d_model}, vocab "
          f"{cfg.vocab_size}), compile cache {cache}", flush=True)
    t0 = time.monotonic()
    if args.chips == 1:
        serve_phase(jax, cfg, devices[0], args.seed, kind, compiles)
        gc.collect()
        print(f"[train] device 0 bytes_in_use after serve: "
              f"{devices[0].memory_stats()['bytes_in_use']}", flush=True)
        train_phase(jax, cfg, devices, args.seed, kind, compiles)
    else:
        ring_compare(jax, get_smoke(ARCH), devices, args.seed, kind)
        gc.collect()
        ring_full(jax, cfg, devices, args.seed, kind, compiles)
    print(f"chip_smoke: all phases passed in {time.monotonic() - t0:.3f}s, "
          f"{compiles.count} compiles, {compiles.seconds:.3f}s compiling",
          flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
