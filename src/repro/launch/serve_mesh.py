"""Multi-process mesh serving driver: one Engine, N host processes.

    PYTHONPATH=src python -m repro.launch.serve_mesh \
        --processes 2 --local-devices 2 --model-parallel 2 \
        --requests 8 --max-batch 4 [--paged] [--no-overlap] \
        [--arrival-rate R] [--num-blocks N] [--out stats.json]

Run with no `--process-id`, the script is the *parent*: it picks a free
coordinator port, spawns `--processes` copies of itself (one jax
process each, `--local-devices` forced host CPU devices per process —
the `tests/dist_check_script.py` pattern, but across process
boundaries), streams their output, and verifies every process computed
the **identical** result (an output digest printed by each child must
match across processes).  On real multi-host hardware the parent is the
cluster launcher instead and each host runs the child entry point with
its own `--process-id`.

Every child process runs the *same deterministic scheduler*: the
engine's host state is plain numpy advanced only by (a) the submitted
workload, identical by construction (seeded), and (b) token ids fetched
from **fully-replicated** device arrays, identical on every process by
SPMD semantics.  No process ever communicates scheduling decisions —
lockstep falls out of determinism, exactly like the superstep trainer.
That only works because the engine's jitted steps return replicated
`[B]` int32 token ids rather than model-sharded logits: each process
reads its local copy, and the per-step device→host transfer is B * 4
bytes regardless of vocab size or process count (`docs/dist.md`).

The child reports `Engine.stats` (admission host time vs prefill wait
vs decode step time, dispatch/fetch split, mixed-step and
overlapped-admission counters, preemptions); process 0 writes them to
`--out` for `benchmarks/bench_mesh_serving.py`.  `--arrival-rate R`
submits the workload on a seeded step-indexed Poisson schedule instead
of all up front — the load pattern where overlapped admission
(`--no-overlap` to disable) earns its keep, since prefills then land
while decode batches are busy rather than in one initial burst.

This driver is a CPU program by design, not a chip path: each child
forces `JAX_PLATFORMS=cpu` and a host device count, and its collectives
run over jax's gloo backend (`jax_cpu_collectives_implementation`).  A
chip holds one process at a time, so these children could not share
one; serving on a TPU runs `repro.serve.Engine` in one process
(`chip_smoke.py`, `repro.launch.serve`).
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import socket
import subprocess
import sys
import time


def _build_parser():
    ap = argparse.ArgumentParser()
    ap.add_argument("--processes", type=int, default=2)
    ap.add_argument("--local-devices", type=int, default=2,
                    help="forced host CPU devices per process")
    ap.add_argument("--model-parallel", type=int, default=2,
                    help='"model" mesh axis; the rest becomes "data"')
    ap.add_argument("--arch", default="tiny",
                    help='"tiny" (built-in bench config) or a smoke arch')
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--mixed", action="store_true",
                    help="interleave short (new_tokens//4) and long budgets")
    ap.add_argument("--arrival-rate", type=float, default=0.0,
                    help="mean Poisson arrivals per engine step (seeded, "
                         "step-indexed — identical schedule on every "
                         "process and across overlap modes); 0 submits "
                         "the whole workload up front")
    ap.add_argument("--no-overlap", action="store_true",
                    help="serialized admission (overlap=False): block on "
                         "each prefill's first token before decoding")
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--block-size", type=int, default=16)
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="paged pool size (default: engine sizes the pool "
                         "to max_batch worst-case rows)")
    ap.add_argument("--preemption", choices=("recompute", "reserve"),
                    default="recompute")
    ap.add_argument("--out", default=None,
                    help="process 0 writes engine stats JSON here")
    ap.add_argument("--timeout", type=int, default=600)
    # internal (set by the parent when spawning children)
    ap.add_argument("--process-id", type=int, default=None)
    ap.add_argument("--coordinator", default=None)
    return ap


def _tiny_cfg():
    from repro.configs.base import ArchConfig
    return ArchConfig(name="mesh-serve-tiny", family="dense", source="bench",
                      num_layers=2, d_model=128, num_heads=4, num_kv_heads=2,
                      head_dim=32, d_ff=256, vocab_size=512,
                      tie_embeddings=True)


def _workload(cfg, args):
    import numpy as np
    rng = np.random.default_rng(0)
    short = max(1, args.new_tokens // 4)
    return [(rng.integers(0, cfg.vocab_size, (args.prompt_len,)),
             short if (args.mixed and i % 2 == 0) else args.new_tokens)
            for i in range(args.requests)]


def _arrival_steps(n, rate):
    """Engine-step index at which request i is submitted.

    Poisson arrivals, but *step-indexed* rather than wall-clock: gaps
    are drawn once from a fixed seed and floored onto step numbers, so
    every process — and, crucially, the serialized and overlapped runs
    being compared — replays the identical arrival schedule and their
    output digests stay bitwise comparable."""
    import numpy as np
    if rate <= 0:
        return [0] * n
    rng = np.random.default_rng(1234)
    gaps = rng.exponential(1.0 / rate, size=n)
    return np.floor(np.cumsum(gaps)).astype(int).tolist()


def _digest(done):
    h = hashlib.sha256()
    for r in sorted(done, key=lambda r: r.uid):
        h.update(f"{r.uid}:{r.output.tolist()}".encode())
    return h.hexdigest()[:16]


def run_child(args) -> int:
    # a CPU child by design (see the module docstring); env must be set
    # before jax initializes a backend
    os.environ.setdefault("TPU_SKIP_MDS_QUERY", "1")
    os.environ["JAX_PLATFORMS"] = "cpu"
    os.environ["XLA_FLAGS"] = (
        f"--xla_force_host_platform_device_count={args.local_devices} "
        + os.environ.get("XLA_FLAGS", ""))
    import jax
    jax.config.update("jax_cpu_collectives_implementation", "gloo")
    jax.distributed.initialize(coordinator_address=args.coordinator,
                               num_processes=args.processes,
                               process_id=args.process_id)
    import numpy as np
    from jax.sharding import Mesh

    from repro.configs import get_smoke
    from repro.models import build_model
    from repro.serve import Engine, bucket_length

    pid = args.process_id
    devs = np.array(jax.devices())
    mp = args.model_parallel
    assert devs.size % mp == 0, (devs.size, mp)
    mesh = Mesh(devs.reshape(devs.size // mp, mp), ("data", "model"))
    print(f"[proc {pid}] {jax.process_count()} processes, "
          f"{devs.size} devices, mesh data={devs.size // mp} model={mp}",
          flush=True)

    cfg = _tiny_cfg() if args.arch == "tiny" else get_smoke(args.arch)
    model = build_model(cfg)
    # identical params on every process (same key, same CPU init);
    # numpy leaves so Engine's device_put can lay them out across
    # processes without cross-process resharding of a committed array
    params = jax.tree.map(np.asarray, model.init(jax.random.PRNGKey(0)))

    reqs = _workload(cfg, args)
    max_len = bucket_length(args.prompt_len + args.new_tokens)
    eng = Engine(model, params, max_batch=args.max_batch, max_len=max_len,
                 mesh=mesh, paged=args.paged, block_size=args.block_size,
                 num_blocks=args.num_blocks, preemption=args.preemption,
                 overlap=not args.no_overlap)
    backend = "paged" if eng.paged else "arena"

    def _run_workload():
        """Submit `reqs` on the arrival schedule and drain; returns
        {uid: Request} for this pass only."""
        uids, done, nxt, step_i = [], {}, 0, 0
        while nxt < len(reqs) or eng.num_active or eng.pending:
            while nxt < len(reqs) and arrive[nxt] <= step_i:
                p, b = reqs[nxt]
                uids.append(eng.submit(p, max_new_tokens=b))
                nxt += 1
            for r in eng.step():
                done[r.uid] = r
            step_i += 1
        return {u: r for u, r in done.items() if u in set(uids)}

    # warm up by replaying the EXACT timed loop once: the engine is
    # deterministic, so the same arrival schedule reproduces the same
    # launch sequence and the timed pass hits only cached executables.
    # An all-up-front warm-up would miss the overlap scheduler's mixed
    # prefill+decode variants (an idle engine admits through the plain
    # cold-start path, never a mixed step).
    arrive = _arrival_steps(len(reqs), args.arrival_rate)
    _run_workload()
    eng._done.clear()
    warm = eng.stats

    t0 = time.perf_counter()
    done = _run_workload()
    wall_s = time.perf_counter() - t0
    stats = eng.stats
    delta = {k: (stats[k] - warm[k]
                 if isinstance(stats[k], (int, float))
                 and not isinstance(stats[k], str) else stats[k])
             for k in stats}
    # gauges, not counters: report the live values
    delta["decode_fetch_elems"] = stats["decode_fetch_elems"]
    delta["decode_fetch_dtype"] = stats["decode_fetch_dtype"]

    digest = _digest(done.values())
    toks = sum(len(r.output) for r in done.values())
    adm = max(delta["admissions"], 1)
    dsteps = max(delta["decode_steps"], 1)
    derived = {
        "admit_host_ms_per_admission": 1e3 * delta["admit_host_s"] / adm,
        "prefill_wait_ms_per_admission":
            1e3 * delta["prefill_wait_s"] / adm,
        "admission_ms_per_admission":
            1e3 * (delta["admit_host_s"] + delta["prefill_wait_s"]) / adm,
        "decode_step_ms": 1e3 * delta["decode_s"] / dsteps,
        "admission_over_decode_step":
            (delta["admit_host_s"] + delta["prefill_wait_s"]) / adm
            / max(delta["decode_s"] / dsteps, 1e-12),
        "h2d_uploads_per_decode_step": delta["h2d_uploads"] / dsteps,
        "throughput_tok_s": toks / max(wall_s, 1e-12),
    }
    print(f"[proc {pid}] {backend}"
          f"[{'overlap' if eng.overlap else 'serialized'}]: "
          f"{len(done)}/{len(reqs)} requests, "
          f"{toks} tokens in {wall_s:.2f}s; "
          f"admission {derived['admission_ms_per_admission']:.2f} ms/req "
          f"(host {derived['admit_host_ms_per_admission']:.2f} + wait "
          f"{derived['prefill_wait_ms_per_admission']:.2f}), decode step "
          f"{derived['decode_step_ms']:.2f} ms, fetch "
          f"[{delta['decode_fetch_elems']}] {delta['decode_fetch_dtype']}, "
          f"mixed_steps {delta['mixed_steps']}, "
          f"overlapped_admissions {delta['overlapped_admissions']}",
          flush=True)

    if args.out and pid == 0:
        payload = {
            "backend": backend,
            "num_processes": jax.process_count(),
            "devices": int(devs.size),
            "mesh": {"data": int(devs.size // mp), "model": int(mp)},
            "arch": cfg.name,
            "workload": {"requests": args.requests,
                         "prompt_len": args.prompt_len,
                         "new_tokens": args.new_tokens,
                         "mixed": bool(args.mixed),
                         "max_batch": args.max_batch,
                         "arrival_rate": args.arrival_rate,
                         "overlap": bool(eng.overlap),
                         "preemption": args.preemption
                         if backend == "paged" else None},
            "completed": len(done),
            "tokens": toks,
            "wall_s": round(wall_s, 4),
            # None in arena mode (no pool), block count in paged mode —
            # a drained paged engine must have returned every block
            "free_blocks": eng.free_blocks,
            "num_blocks": eng.num_blocks if backend == "paged" else None,
            "engine_stats": {k: (round(v, 6) if isinstance(v, float) else v)
                             for k, v in delta.items()},
            "derived": {k: round(v, 4) for k, v in derived.items()},
            "output_digest": digest,
        }
        with open(args.out, "w") as f:
            json.dump(payload, f, indent=1)
        print(f"[proc {pid}] wrote {args.out}", flush=True)

    # the parent asserts these digests agree across all processes
    print(f"SERVE_MESH_OK process={pid} digest={digest}", flush=True)
    return 0


def run_parent(args, argv) -> int:
    with socket.socket() as s:
        s.bind(("localhost", 0))
        port = s.getsockname()[1]
    procs = []
    for i in range(args.processes):
        procs.append(subprocess.Popen(
            [sys.executable, "-m", "repro.launch.serve_mesh", *argv,
             "--process-id", str(i), "--coordinator", f"localhost:{port}"],
            stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True))
    outs, rcs = [], []
    deadline = time.monotonic() + args.timeout
    for i, p in enumerate(procs):
        try:
            out, _ = p.communicate(timeout=max(1, deadline - time.monotonic()))
        except subprocess.TimeoutExpired:
            for q in procs:
                q.kill()
            out, _ = p.communicate()
            out += "\n[parent] TIMEOUT"
        outs.append(out)
        rcs.append(p.returncode)
        for line in out.splitlines():
            print(f"  p{i}| {line}")
    digests = []
    for out in outs:
        digests += [ln.split("digest=")[1] for ln in out.splitlines()
                    if ln.startswith("SERVE_MESH_OK")]
    ok = (all(rc == 0 for rc in rcs)
          and len(digests) == args.processes
          and len(set(digests)) == 1)
    if ok:
        print(f"[parent] {args.processes} processes agree "
              f"(digest {digests[0]})")
        return 0
    print(f"[parent] FAILED: rcs={rcs} digests={digests}")
    return 1


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    args = _build_parser().parse_args(argv)
    if args.process_id is not None:
        sys.exit(run_child(args))
    sys.exit(run_parent(args, argv))


if __name__ == "__main__":
    main()
