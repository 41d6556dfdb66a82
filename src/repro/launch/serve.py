"""Serving driver over the continuous-batching engine (repro.serve).

    PYTHONPATH=src python -m repro.launch.serve \
        --arch qwen2-0.5b --smoke --requests 8 --max-batch 4 \
        --prompt-len 32 --new-tokens 16

A/B the schedulers on the same workload:

    --continuous   slot-arena engine, admission between decode steps
                   (default)
    --wave         deprecated equal-prompt-length waves (BatchedServer
                   shim) — long generations convoy short ones
    --mixed        interleave short/long budgets so the convoy effect
                   is visible in the latency spread
    --paged        paged-KV backend: shared block pool, per-slot block
                   tables, chunked prefill (admission against free
                   blocks instead of full-length slots)
    --preemption   paged admission policy: "recompute" (optimistic,
                   preempt-and-recompute under pressure; default) or
                   "reserve" (worst-case reservation, never preempts)
                   — see docs/serving.md

Encoder-decoder families (whisper) and VLMs (whose prompts carry a
patch prefix the engine's token-only submit cannot express yet) keep a
hand-rolled prefill/decode loop.

Multi-process mesh serving lives in `repro.launch.serve_mesh` (one
engine per process over a shared ("data", "model") mesh, deterministic
lockstep scheduling, per-step telemetry) — see docs/dist.md.
"""
import argparse
import os


def _percentile(xs, p):
    import numpy as np
    return float(np.percentile(np.asarray(xs), p))


def _serve_raw(args, cfg, model, params):
    """Legacy raw loop for families the engine cannot serve: encdec
    (no slot-arena entry points) and vlm (patch-prefix prompts)."""
    import time
    from functools import partial

    import jax
    import jax.numpy as jnp
    import numpy as np

    rng = np.random.default_rng(0)
    b, p = args.requests, args.prompt_len
    prompt = {"tokens": jnp.asarray(
        rng.integers(0, cfg.vocab_size, (b, p)), jnp.int32)}
    prefix = 0
    if cfg.family in ("audio", "encdec"):
        prompt["frames"] = jnp.asarray(
            rng.standard_normal((b, cfg.encoder_seq, cfg.d_model)),
            jnp.float32)
    if cfg.family == "vlm":
        prompt["patches"] = jnp.asarray(
            rng.standard_normal((b, cfg.num_patches, cfg.d_model)),
            jnp.float32)
        prefix = cfg.num_patches

    total = p + prefix + args.new_tokens
    prefill = jax.jit(partial(model.prefill, cache_len=total))
    decode = jax.jit(model.decode_step)
    t0 = time.monotonic()
    logits, caches = prefill(params, prompt)
    logits.block_until_ready()
    print(f"prefill: {b}x{p} tokens in {time.monotonic() - t0:.3f}s")
    token = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    t0 = time.monotonic()
    for i in range(args.new_tokens):
        logits, caches = decode(params, token, caches,
                                jnp.int32(p + prefix + i))
        token = jnp.argmax(logits[:, -1], -1)[:, None].astype(jnp.int32)
    token.block_until_ready()
    dt = time.monotonic() - t0
    print(f"decode: {args.new_tokens} x batch {b} in {dt:.3f}s "
          f"({args.new_tokens * b / dt:.1f} tok/s)")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-0.5b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--mixed", action="store_true",
                    help="interleave short (new_tokens//4) and long budgets")
    ap.add_argument("--devices", type=int, default=0)
    mode = ap.add_mutually_exclusive_group()
    mode.add_argument("--continuous", dest="mode", action="store_const",
                      const="continuous", default="continuous",
                      help="slot-arena continuous batching (default)")
    mode.add_argument("--wave", dest="mode", action="store_const",
                      const="wave", help="deprecated wave batching")
    ap.add_argument("--paged", action="store_true",
                    help="paged KV: shared block pool + block tables + "
                         "chunked prefill (continuous mode only; "
                         "auto-falls back to the arena for families "
                         "that cannot page)")
    ap.add_argument("--block-size", type=int, default=16,
                    help="paged KV block size in tokens")
    ap.add_argument("--preemption", choices=("recompute", "reserve"),
                    default="recompute",
                    help="paged admission policy (docs/serving.md)")
    args = ap.parse_args()

    if args.devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices} "
            + os.environ.get("XLA_FLAGS", ""))

    import time
    import warnings

    import jax
    import numpy as np

    from repro.configs import get_config, get_smoke
    from repro.models import build_model
    from repro.serve import Engine, bucket_length
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_smoke(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)

    if cfg.family in ("audio", "encdec", "vlm"):
        print(f"[{cfg.name}] {cfg.family}: raw prefill/decode loop "
              "(engine serves token-only prompts)")
        return _serve_raw(args, cfg, model, params)

    short = max(1, args.new_tokens // 4)
    budgets = [short if (args.mixed and i % 2 == 0) else args.new_tokens
               for i in range(args.requests)]
    prompts = [rng.integers(0, cfg.vocab_size, (args.prompt_len,))
               for _ in range(args.requests)]
    max_len = bucket_length(args.prompt_len + max(budgets))

    if args.mode == "continuous":
        srv = Engine(model, params, max_batch=args.max_batch,
                     max_len=max_len, paged=args.paged,
                     block_size=args.block_size,
                     preemption=args.preemption)
        if args.paged and not srv.paged:
            print(f"[{cfg.name}] cannot page this family; using the "
                  "slot arena")
    else:
        with warnings.catch_warnings():
            warnings.simplefilter("ignore", DeprecationWarning)
            from repro.dist.server import BatchedServer
            srv = BatchedServer(model, params, max_batch=args.max_batch)

    t0 = time.monotonic()
    uids = [srv.submit(p, max_new_tokens=b)
            for p, b in zip(prompts, budgets)]
    latency = {}
    while srv.pending or getattr(srv, "num_active", 0):
        for r in srv.step():
            latency[r.uid] = time.monotonic() - t0
    total = time.monotonic() - t0
    done = {r.uid: r for r in srv.run()}

    toks = sum(len(done[u].output) for u in uids)
    lats = [latency[u] for u in uids]
    print(f"[{cfg.name}] {args.mode}: {args.requests} reqs "
          f"(budgets {sorted(set(budgets))}), max_batch {args.max_batch}")
    print(f"  {toks} tokens in {total:.3f}s ({toks / total:.1f} tok/s); "
          f"latency p50 {_percentile(lats, 50):.3f}s "
          f"p99 {_percentile(lats, 99):.3f}s")
    for u in uids[: min(4, len(uids))]:
        print("  ", done[u].output.tolist())


if __name__ == "__main__":
    main()
