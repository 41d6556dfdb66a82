"""The deepseek-v2-lite cell at a tiny size on the CPU: the plain
reference (`references/mla_moe.py`) against the program, bit for bit at
initialisation and in float32 through the loss and every leaf's
gradient; a whole run through the harness, sound and broken underneath;
the control; the readers of the model's scopes and the MFU."""
import json
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import bench_tiny
import devtrace as dt
import model_scopes
import phases
import run as harness
from devtrace import Event, Trace
from references import mla_moe, superstep
from test_bench_train_faults import FAULTS, _wrap_step

LITE = "deepseek-v2-lite.apibcd-a1-b8s1024"
# the cell's model at a tiny size: one dense and two expert layers, MLA
# without a query LoRA and with YaRN, a share of 4 of 16 experts held
# from expert 4, gates left unnormalised, the sequence balance loss
TINY_LITE = {
    "num_layers": 3, "layer_types": ["attn", "moe", "moe"], "d_model": 64,
    "num_heads": 4, "num_kv_heads": 4, "d_ff": 128, "vocab_size": 256,
    "rope_scaling": {"type": "yarn", "factor": 4,
                     "original_max_position_embeddings": 64,
                     "beta_fast": 32, "beta_slow": 1, "mscale": 0.707,
                     "mscale_all_dim": 0.707},
    "mla": {"kv_lora_rank": 16, "q_lora_rank": 0, "qk_nope_head_dim": 16,
            "qk_rope_head_dim": 8, "v_head_dim": 16},
    "moe": {"num_experts": 16, "top_k": 3, "num_shared_experts": 2,
            "d_ff_expert": 32, "capacity_factor": 1.25,
            "router_aux_loss": 0.001, "experts_held": 4, "expert_offset": 4,
            "norm_topk_prob": False, "aux_kind": "seq"}}


def _model(**over):
    cfg = json.loads((bench_tiny.ROOT / "benchmarks" / "chip" / "configs"
                      / "deepseek-v2-lite.json").read_text())
    return dict(cfg["model"], **TINY_LITE, **over)


def lite_root(tmp_path, **over):
    """bench_tiny's root with the cell at TINY_LITE (and `over`) and
    tiny traffic."""
    root, data = bench_tiny.tiny_root(tmp_path)
    path = root / "deepseek-v2-lite.json"
    cfg = json.loads(path.read_text())
    cfg["model"] = _model(**over)
    path.write_text(json.dumps(cfg))
    traffic = data / "traffic" / "apibcd-a1-b8s1024.json"
    t = json.loads(traffic.read_text())
    t.update(driver="superstep", **bench_tiny.TINY_TRAIN)
    traffic.write_text(json.dumps(t))
    return root, data


def run_lite(tmp_path, seed=7, trace=0):
    """A whole run of the tiny cell.  The program computes in float32
    here: at 64 wide, 16 experts and 16 tokens a sequence, bfloat16's
    rounding flips so large a share of the routing decisions (a token's
    k-th expert, and so who is dropped over capacity) that its gaps
    reach the limits set for the cell's size (PERF.md section 4); in
    float32 the sound run sits at rounding and every broken one far
    outside."""
    root, data = lite_root(tmp_path, compute_dtype="float32")
    return harness.run_cell(root, LITE, seed, 1.0, trace, jax.devices(),
                            bench=json.loads((root / "BENCHMARK.json")
                                             .read_text()),
                            data=data, log=lambda *_: None)


# ---------------------------------------------------------------------------
# the reference against the program
# ---------------------------------------------------------------------------


def test_reference_init_is_the_programs_bit_for_bit():
    from repro.configs.base import ArchConfig
    from repro.models import build_model

    m = _model()
    key = jax.random.PRNGKey(1234567)
    prog = build_model(ArchConfig(**m)).init(key)
    ref = mla_moe.init(m, key)
    assert jax.tree.structure(prog) == jax.tree.structure(ref)
    for a, b in zip(jax.tree.leaves(prog), jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and np.array_equal(a, b)


@pytest.fixture(scope="module")
def float32_pair():
    """(reference, program, fp8 reference) loss and gradients at the tiny
    size, the program computing in float32 at HIGHEST."""
    from repro.configs.base import ArchConfig
    from repro.models import build_model

    m = _model(compute_dtype="float32")
    model = build_model(ArchConfig(**m))
    params = model.init(jax.random.PRNGKey(3))
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, 256, (3, 24)), jnp.int32)
    targs = jnp.asarray(rng.integers(0, 256, (3, 24)), jnp.int32)
    with jax.default_matmul_precision("highest"):
        (pl, pm), pg = jax.value_and_grad(model.train_loss, has_aux=True)(
            params, {"tokens": toks, "targets": targs})
    assert int(pm["moe_dropped"]) > 0       # capacity is exercised
    ref = [jax.value_and_grad(lambda p: mla_moe.loss(m, p, toks, targs,
                                                     prec))(params)
           for prec in ("f32", "fp8")]
    return ref[0], (pl, pg), ref[1]


def _gaps(a, b):
    """(loss gap, worst leaf gap) of b against a: a leaf's gap is the
    largest element gap over the leaf's largest element."""
    (la, ga), (lb, gb) = a, b
    leaf = max(float(np.max(np.abs(np.asarray(x) - np.asarray(y)))
                     / max(float(np.max(np.abs(np.asarray(x)))), 1e-30))
               for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)))
    return abs(float(la) - float(lb)) / abs(float(la)), leaf


# Both sides compute in float32 at HIGHEST, so they differ by rounding
# alone (~1e-6 of a value), and a routing decision flips only on a
# near-tie, which the seeds here do not hold.  The limits leave two
# orders of magnitude to rounding; the fp8 control, one precision below
# the configurations' bfloat16, is far outside them.
LOSS_TOL, LEAF_TOL = 1e-5, 1e-3


def test_reference_matches_the_program_in_float32(float32_pair):
    ref, prog, _ = float32_pair
    loss_gap, leaf_gap = _gaps(ref, prog)
    assert loss_gap < LOSS_TOL and leaf_gap < LEAF_TOL, (loss_gap, leaf_gap)


def test_control_fails_the_float32_tolerances(float32_pair):
    ref, _, fp8 = float32_pair
    loss_gap, leaf_gap = _gaps(ref, fp8)
    assert loss_gap > LOSS_TOL and leaf_gap > LEAF_TOL, (loss_gap, leaf_gap)


def test_control_fails_the_cells_limits():
    from drivers import train

    cell = harness.Cell(bench_tiny.ROOT, LITE, 5, jax.devices())
    cell.model = _model()
    cell.traffic = dict(cell.traffic, **bench_tiny.TINY_TRAIN)
    feed = train._feed(cell)
    batches = [next(feed) for _ in range(train.CHECKED_STEPS)]
    key = jax.random.PRNGKey(cell.model_seed)
    reference = cell.config["reference"]
    ref = superstep.run(reference, cell.model, cell.traffic, key, batches,
                        jax.devices())
    got = superstep.run(reference, cell.model, cell.traffic, key, batches,
                        jax.devices(), precision="fp8")
    checks = train.compare_training(
        cell, got["losses"], got["grad_norms"], got["change_norms"],
        {i: v or {} for i, v in got["token_norms"].items()}, ref,
        log=lambda *_: None)
    assert any(v > lim for _, v, lim in checks), checks


def test_the_control_tool_reads_the_control_and_the_fault_over_the_limits():
    """`tools/control_superstep.py` follows the tiny cell with the fp8
    control and half of each batch, each past a limit; a ring would add
    no_exchange, one agent does not."""
    from tools.control_superstep import control_readings

    cell = harness.Cell(bench_tiny.ROOT, LITE, 5, jax.devices())
    cell.model = _model()
    cell.traffic = dict(cell.traffic, **bench_tiny.TINY_TRAIN)
    readings = control_readings(cell, log=lambda *_: None)
    assert set(readings) == {"control_fp8", "half_batch"}
    for kind, checks in readings.items():
        assert any(v > lim for _, v, lim in checks), (kind, checks)


# ---------------------------------------------------------------------------
# the cell through the harness
# ---------------------------------------------------------------------------


def test_sound_run_is_correct(tmp_path):
    res = run_lite(tmp_path)
    assert res["correct"], res["compared"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["metrics"]) == {"train_tokens_per_s", "setup_s"}
    assert set(res["compared"]) == {"loss_gap", "grad_gap", "change_gap",
                                    "token_gap"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_broken_step_is_not_correct(tmp_path, monkeypatch, fault):
    _wrap_step(monkeypatch, FAULTS[fault])
    res = run_lite(tmp_path)
    assert not res["correct"], res["compared"]


def test_the_driver_logs_the_dropped_assignments(tmp_path):
    from drivers import superstep as driver

    root, data = lite_root(tmp_path)
    cell = harness.Cell(root, LITE, 3, jax.devices(), data=data,
                        bench=json.loads((root / "BENCHMARK.json")
                                         .read_text()))
    lines = []
    state = driver.setup(cell, log=lines.append)
    driver.window(cell, state, 0.5, log=lines.append)
    counted = [ln for ln in lines if "moe_dropped" in ln]
    assert len(counted) == 2, lines
    assert "checked steps [" in counted[0] and "last window step [" \
        in counted[1]
    assert len(json.loads(counted[0].split("checked steps ")[1])) == \
        driver.train.CHECKED_STEPS


# ---------------------------------------------------------------------------
# readers
# ---------------------------------------------------------------------------

DEV = "/device:TPU:0"
STEP = "jit(step_fn)/apibcd.grad/vmap(transpose(jvp(model.blocks)))/while/body"
HLO = f"""HloModule jit_step_fn, is_scheduled=true

ENTRY %main.9 (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0), metadata={{op_name="x"}}
  %fusion.1 = f32[8]{{0}} fusion(%x), kind=kLoop, metadata={{op_name="{STEP}/checkpoint/rematted_computation/model.attn/dot_general"}}
  %fusion.2 = f32[8]{{0}} fusion(%x), kind=kLoop, metadata={{op_name="{STEP}/model.moe.router/top_k"}}
  %fusion.3 = f32[8]{{0}} fusion(%x), kind=kLoop, metadata={{op_name="{STEP}/model.moe.dispatch/cumsum"}}
  %fusion.4 = f32[8]{{0}} fusion(%x), kind=kLoop, metadata={{op_name="{STEP}/model.moe.experts/dot_general"}}
  %fusion.5 = f32[8]{{0}} fusion(%x), kind=kLoop, metadata={{op_name="{STEP}/model.moe.shared/dot_general"}}
  %fusion.6 = f32[8]{{0}} fusion(%x), kind=kLoop, metadata={{op_name="{STEP}/model.mlp/dot_general"}}
  ROOT %fusion.7 = f32[8]{{0}} fusion(%x), kind=kLoop, metadata={{op_name="jit(step_fn)/apibcd.prox/add"}}
}}
"""


def _trace():
    ops = [Event(f"%fusion.{i} = f32[8]{{0}} fusion(%x)", float(i), 0.5)
           for i in range(1, 8)]
    return Trace({DEV: ops}, {DEV: [Event("jit_step_fn(3)", 0.5, 8.0)]},
                 [Event("bench.window", 0.0, 10.0)])


def _reader(name):
    return harness.load_module(bench_tiny.HERE / "metrics" / f"{name}.py",
                               f"reader_{name}")


def _cell():
    cell = harness.Cell(bench_tiny.ROOT, LITE, 1, [])
    cell.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    return cell


@pytest.fixture
def given_hlo(monkeypatch):
    given = {"hlo": HLO}
    monkeypatch.setattr(phases, "step_hlo", lambda cell: given["hlo"])
    return given


@pytest.mark.parametrize("op_name,scope", [
    (f"{STEP}/model.attn/dot_general", "attn"),
    ("model.moe.dispatch/reduce_sum", "moe_dispatch"),
    (f"{STEP}/checkpoint/rematted_computation/model.moe.experts/mul",
     "moe_experts"),
    (f"{STEP}/model.mlp/transpose(jvp(silu))", "mlp"),
    (f"{STEP}/dot_general", None),
    ("jit(step_fn)/apibcd.prox/add", None),
    ("model.attention_sink/add", None)])
def test_an_op_takes_its_last_model_scope(op_name, scope):
    assert model_scopes.model_scope(op_name) == scope


def test_the_scope_readers(given_hlo, capsys):
    tr = _trace()
    got = {n: _reader(n).read(_cell(), tr, {"steps": 1})
           for n in ("model_attn_ms", "moe_dispatch_ms", "moe_experts_ms")}
    assert got == pytest.approx({"model_attn_ms": 500.0,
                                 "moe_dispatch_ms": 1000.0,
                                 "moe_experts_ms": 1000.0})
    # the table went to stderr once, for the first reader
    err = capsys.readouterr().err
    assert err.count("model scopes /device:TPU:0: 1 steps") == 1
    assert "model.attn" in err and "recompute" in err
    assert "model.mlp" in err and "backward" in err


def test_the_scope_readers_read_none_without_scopes(given_hlo):
    given_hlo["hlo"] = HLO.replace("model.", "")
    assert _reader("model_attn_ms").read(_cell(), _trace(), {}) is None
    given_hlo["hlo"] = HLO
    empty = Trace({DEV: []}, {DEV: []}, [Event("bench.window", 0.0, 10.0)])
    assert _reader("moe_experts_ms").read(_cell(), empty, {}) is None


def test_the_compiled_step_names_every_scope_each_way(tmp_path):
    """The tiny cell's step compiled on the CPU as the harness compiles
    it: each model scope has forward, backward and recomputed work."""
    root, data = lite_root(tmp_path)
    cell = harness.Cell(root, LITE, 5, jax.devices(), data=data,
                        bench=json.loads((root / "BENCHMARK.json")
                                         .read_text()))
    _, names = phases.op_names(phases.step_hlo(cell))
    seen = {(model_scopes.model_scope(n), phases.scope(n)[2])
            for n in names.values()}
    for sc in ("attn", "mlp", "moe_router", "moe_dispatch", "moe_experts",
               "moe_shared"):
        for way in ("forward", "backward", "recompute"):
            assert (sc, way) in seen, (sc, way)


def test_mla_moe_flops_from_the_cells_shapes():
    """Per token of the cell: 5 MLA layers of 13.76 M projection
    parameters, the 67.24 M dense MLP, 4 expert layers of router
    (0.13 M), shared experts (17.30 M) and 0.75 of a held expert
    (8.65 M each), and the 26.2 M head; attention at qk 192 and v 128
    over the causal keys."""
    from yardstick_mla_moe import (attention_params, matmul_params_per_token,
                                   train_step_flops)

    m = _cell().model
    expert = 3 * 2048 * 1408
    assert attention_params(m) == (2048 * 16 * 192 + 2048 * 576
                                   + 512 * 16 * 256 + 16 * 128 * 2048)
    per_token = (5 * attention_params(m) + 3 * 2048 * 10944
                 + 4 * (2048 * 64 + 2 * expert + 0.75 * expert)
                 + 12800 * 2048)
    assert matmul_params_per_token(m) == per_token
    attn = 2 * 5 * 16 * (192 + 128) * 1024 * 1025 / 2
    assert train_step_flops(m, 1, 8, 1024) == pytest.approx(
        3 * 8 * (2 * per_token * 1024 + attn))


def test_the_mfu_reader():
    r = _reader("train_step_mfu_pct.mla_moe")
    cell = _cell()
    tr = Trace({DEV: []}, {DEV: []}, [Event("bench.window", 0.0, 10.0)])
    assert r.read(cell, tr, {"steps": 0}) is None
    # 50 steps of 13.32 TFLOP in 10 s on one 197 TFLOP/s chip: 33.8%
    assert r.read(cell, tr, {"steps": 50}) == pytest.approx(33.82, abs=0.01)


@pytest.mark.parametrize("model", ["cell", "tiny"])
def test_all_params_counts_every_leaf_of_the_program(model):
    """The update's parameter count equals the program's leaves, at the
    cell's cut widths (535,060,992) and at the tiny size."""
    from repro.configs.base import ArchConfig
    from repro.models import transformer
    from yardstick_mla_moe import all_params, prox_update_bytes

    m = _cell().model if model == "cell" else _model()
    shapes = jax.eval_shape(
        lambda k: transformer.transformer_init(ArchConfig(**m), k),
        jax.random.PRNGKey(0))
    count = sum(int(np.prod(v.shape)) for v in jax.tree.leaves(shapes))
    assert all_params(m) == count
    if model == "cell":
        assert count == 535_060_992
    assert prox_update_bytes(m) == 20 * count


def test_the_kernel_roofline_reader():
    """The kernels are found by name, as `prox_update_kernel_roofline`
    finds them; the least time is 20 bytes of each of the cell's
    parameters at 819 GB/s."""
    from test_bench_kernel_reader import FUSION, NATIVE, OTHER_KERNEL

    r = _reader("prox_update_kernel_roofline.mla_moe")
    least = 20 * 535_060_992 / 819e9
    tr = Trace({DEV: [Event(NATIVE, 1.0, least / 2),
                      Event(NATIVE, 2.0, least / 2),
                      Event(OTHER_KERNEL, 4.0, 1.0),
                      Event(FUSION, 6.0, 1.0)]}, {},
               [Event("bench.window", 0.0, 10.0)])
    assert r.read(_cell(), tr, {"steps": 1}) == pytest.approx(100.0)
    assert r.read(_cell(), tr, {"steps": 0}) is None
    bare = Trace({DEV: [Event(FUSION, 6.0, 1.0)]}, {},
                 [Event("bench.window", 0.0, 10.0)])
    assert r.read(_cell(), bare, {"steps": 1}) is None


def test_the_new_cells_metric_lists():
    """The V2-Lite cell reads the superstep's phases, the device's idle
    share and its own readers (its kernel roofline among them), and no
    reader of the dense decoder's shapes; the one-agent qwen2 cell reads
    what it read before."""
    b = bench_tiny.bench()
    lists = {m["name"]: m.get("workloads", []) for m in
             b["per_layer"] + b["end_to_end"]}
    lite = sorted(n for n, w in lists.items() if LITE in w)
    assert lite == sorted([
        "train_tokens_per_s", "device_idle_pct.train", "superstep_grad_ms",
        "superstep_update_ms", "superstep_other_ms",
        "superstep_dispatch_host_ms", "model_attn_ms", "moe_dispatch_ms",
        "moe_experts_ms", "train_step_mfu_pct.mla_moe",
        "prox_update_kernel_roofline.mla_moe"])
    a1 = sorted(n for n, w in lists.items() if "qwen2-0.5b.apibcd-a1" in w)
    assert a1 == sorted([
        "train_tokens_per_s", "train_step_mfu_pct", "prox_update_roofline",
        "device_idle_pct.train", "superstep_grad_ms", "superstep_update_ms",
        "superstep_other_ms", "superstep_dispatch_host_ms",
        "prox_update_kernel_roofline"])
    chips = {w["name"]: w["chips"] for w in b["workloads"]}
    assert chips == {"qwen2-0.5b.apibcd-a1": 1, LITE: 1}
