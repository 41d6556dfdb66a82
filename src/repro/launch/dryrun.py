"""Multi-pod dry-run: lower + compile every (arch x input shape) on the
production mesh, proving the distribution config is coherent, and extract
the roofline terms from the compiled artifact.

MUST be run as a module in its own process:
    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-8b \
        --shape train_4k [--multi-pod] [--out results.json]

The XLA_FLAGS line below must execute before ANY other jax-touching import
(jax locks the device count on first init); keep it at the very top.
"""
import os
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

# flake8: noqa: E402
import argparse
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import INPUT_SHAPES, get_config, get_train
from repro.dist.serving import (data_axes, make_decode_step,
                                make_prefill_step, serve_param_shardings)
from repro.dist.sharding import (cache_shardings, state_shardings,
                                 train_batch_shardings, batch_shardings)
from repro.dist.trainer import (init_train_state, make_dp_baseline_step,
                                make_train_step)
from repro.optim import adamw, constant
from repro.launch.mesh import make_production_mesh, make_training_mesh
from repro.models import build_model
from repro.models.model import input_specs
from repro.utils.hlo_flops import analyze
from repro.utils.roofline import (Roofline, active_params, count_params,
                                  model_flops)


def _sds_with(shapes, shardings):
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        shapes, shardings)


def _expert_param_count(params_shapes):
    total = 0
    def visit(path, leaf):
        nonlocal total
        names = [getattr(k, "key", getattr(k, "idx", None)) for k in path]
        if any(n == "moe" for n in names) and leaf.ndim >= 3:
            total += int(leaf.size)
        return leaf
    jax.tree_util.tree_map_with_path(visit, params_shapes)
    return total


def _skip(cfg, shape):
    if shape.name == "long_500k" and not cfg.supports_long_context:
        return ("SKIP: enc-dec decoder (whisper) has no 500k decode use "
                "(trained context << 500k); see DESIGN.md")
    return None


def lower_combo(arch: str, shape_name: str, multi_pod: bool = False,
                verbose: bool = True, baseline_dp: bool = False):
    cfg = get_config(arch)
    shape = INPUT_SHAPES[shape_name]
    reason = _skip(cfg, shape)
    if reason:
        return {"arch": arch, "shape": shape_name,
                "multi_pod": multi_pod, "skipped": reason}

    # long-context decode on full-attention archs -> sliding-window variant
    window = 0
    if shape.name == "long_500k" and cfg.family not in ("ssm", "hybrid"):
        window = cfg.long_context_window
    model = build_model(cfg, window=window)

    chips = 512 if multi_pod else 256
    t0 = time.monotonic()

    if shape.kind == "train" and baseline_dp:
        # synchronous all-reduce data-parallel baseline (what API-BCD
        # replaces): one parameter set, gradient all-reduce every step
        tcfg = get_train(arch)
        mesh = make_training_mesh(1, tcfg.model_parallel,
                                  multi_pod=multi_pod)
        opt = adamw(weight_decay=0.0)
        step_fn = make_dp_baseline_step(model, opt, constant(3e-4))
        params_shapes = jax.eval_shape(
            model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
        opt_shapes = jax.eval_shape(opt.init, params_shapes)
        axes = {"replica": mesh.shape["replica"],
                "model": mesh.shape["model"]}
        from repro.dist.sharding import param_shardings
        p_sh = param_shardings(mesh, params_shapes, leading_axis=None,
                               axes=axes)
        o_sh = param_shardings(mesh, opt_shapes, leading_axis=None,
                               axes=axes)
        raw_batch = input_specs(cfg, shape)
        b_sh = batch_shardings(mesh, raw_batch,
                               batch_axes=("agent", "replica"))
        with jax.set_mesh(mesh):
            lowered = jax.jit(
                step_fn,
                in_shardings=(p_sh, o_sh, b_sh, None),
                out_shardings=(p_sh, o_sh, None),
                donate_argnums=(0, 1),
            ).lower(_sds_with(params_shapes, p_sh),
                    _sds_with(opt_shapes, o_sh),
                    _sds_with(raw_batch, b_sh),
                    jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
        n_params = count_params(params_shapes)
        n_expert = _expert_param_count(params_shapes)

    elif shape.kind == "train":
        tcfg = get_train(arch)
        mesh = make_training_mesh(tcfg.num_agents, tcfg.model_parallel,
                                  multi_pod=multi_pod)
        a = tcfg.num_agents
        train_step = make_train_step(model, tcfg)

        state_shapes = init_train_state(model, tcfg)
        raw_batch = input_specs(cfg, shape)
        batch_shapes = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(
                (a, s.shape[0] // a) + s.shape[1:], s.dtype), raw_batch)

        st_sh = state_shardings(mesh, state_shapes)
        b_sh = train_batch_shardings(mesh, batch_shapes)

        with jax.set_mesh(mesh):
            lowered = jax.jit(
                train_step,
                in_shardings=(st_sh, b_sh, None),
                out_shardings=(st_sh, None),
                donate_argnums=(0,),
            ).lower(_sds_with(state_shapes, st_sh),
                    _sds_with(batch_shapes, b_sh),
                    jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
        params_shapes = state_shapes["params"]
        # params carry the agent axis; count one replica
        n_params = count_params(params_shapes) // tcfg.num_agents
        n_expert = _expert_param_count(params_shapes) // tcfg.num_agents

    elif shape.kind == "prefill":
        mesh = make_production_mesh(multi_pod=multi_pod)
        batch_shapes = input_specs(cfg, shape)
        with jax.set_mesh(mesh):
            fn, (p_sh, b_sh) = make_prefill_step(model, mesh, batch_shapes)
            params_shapes = jax.eval_shape(
                model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
            lowered = fn.lower(_sds_with(params_shapes, p_sh),
                               _sds_with(batch_shapes, b_sh))
            compiled = lowered.compile()
        n_params = count_params(params_shapes)
        n_expert = _expert_param_count(params_shapes)

    else:  # decode
        mesh = make_production_mesh(multi_pod=multi_pod)
        token_shapes = input_specs(cfg, shape)["token"]
        cache_shapes = jax.eval_shape(
            lambda: model.init_cache(shape.global_batch, shape.seq_len))
        with jax.set_mesh(mesh):
            fn, (p_sh, t_sh, c_sh) = make_decode_step(
                model, mesh, token_shapes, cache_shapes)
            params_shapes = jax.eval_shape(
                model.init, jax.ShapeDtypeStruct((2,), jnp.uint32))
            lowered = fn.lower(_sds_with(params_shapes, p_sh),
                               _sds_with(token_shapes, t_sh),
                               _sds_with(cache_shapes, c_sh),
                               jax.ShapeDtypeStruct((), jnp.int32))
            compiled = lowered.compile()
        n_params = count_params(params_shapes)
        n_expert = _expert_param_count(params_shapes)

    compile_s = time.monotonic() - t0

    # structural HLO cost model (loop-corrected; per-device) -> global
    hlo = compiled.as_text()
    stats = analyze(hlo)
    flops = float(stats["flops"]) * chips
    hbm = float(stats["bytes"]) * chips
    coll_total = float(stats["collective_bytes"]) * chips
    coll_by_op = {k: v * chips for k, v in stats["collectives"].items()}
    coll_counts = stats["collective_counts"]
    xla_cost = compiled.cost_analysis() or {}

    mem = compiled.memory_analysis()
    mem_info = {}
    if mem is not None:
        for attr in ("argument_size_in_bytes", "output_size_in_bytes",
                     "temp_size_in_bytes", "generated_code_size_in_bytes",
                     "alias_size_in_bytes"):
            if hasattr(mem, attr):
                mem_info[attr] = int(getattr(mem, attr))

    act = active_params(cfg, n_params, n_expert)
    mflops = model_flops(cfg, shape, n_params, act)
    rl = Roofline(flops, hbm, coll_total, chips)
    hbm_kernel = float(stats.get("bytes_kernel_adjusted", stats["bytes"])) \
        * chips
    rl_kernel = Roofline(flops, hbm_kernel, coll_total, chips)

    result = {
        "arch": arch,
        "shape": shape_name,
        "mode": "baseline_dp" if baseline_dp else "apibcd",
        "multi_pod": multi_pod,
        "mesh": ("(2,16,16) pod,data,model" if multi_pod
                 else "(16,16) data,model"),
        "window": window,
        "compile_s": round(compile_s, 1),
        "params": int(n_params),
        "active_params": int(act),
        "model_flops": mflops,
        "roofline": rl.as_dict(),
        "roofline_kernel_adjusted": rl_kernel.as_dict(),
        "useful_flop_ratio": (mflops / flops) if flops else None,
        "collectives": coll_by_op,
        "collective_counts": coll_counts,
        "memory_analysis": mem_info,
        "xla_cost_analysis_flops_per_device": float(
            xla_cost.get("flops", 0.0)),
        "hlo_bytes": len(hlo),
    }
    if verbose:
        print(f"[{arch} x {shape_name} x "
              f"{'512(2pod)' if multi_pod else '256(1pod)'}] "
              f"compile {compile_s:.0f}s  flops {flops:.3e}  "
              f"hbm {hbm:.3e}  coll {coll_total:.3e}  "
              f"dominant={rl.dominant}")
        print("memory_analysis:", mem_info)
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=list(INPUT_SHAPES))
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--baseline-dp", action="store_true",
                    help="lower the synchronous all-reduce DP baseline "
                         "instead of the API-BCD step")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    res = lower_combo(args.arch, args.shape, args.multi_pod,
                      baseline_dp=args.baseline_dp)
    if args.out:
        with open(args.out, "w") as f:
            json.dump(res, f, indent=1)
    else:
        print(json.dumps(res, indent=1))


if __name__ == "__main__":
    main()
