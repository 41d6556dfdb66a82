"""The per-layer metric readers on a synthetic trace and counters."""
import types

import pytest

import bench_tiny
import run as harness
from devtrace import Event, Trace

KERNEL = ('%step_fn.14 = (f32[8,1024]{1,0:T(8,128)}, f32[8,1024]{1,0:T(8,'
          '128)}) custom-call(f32[8,1024]{1,0:T(8,128)} %r.1, f32[8,1024]'
          '{1,0:T(8,128)} %r.2, f32[8,1024]{1,0:T(8,128)} %r.3), '
          'custom_call_target="tpu_custom_call", operand_layout_constraints'
          '={f32[8,1024]{1,0}}')


def _reader(name):
    return harness.load_module(bench_tiny.HERE / "metrics" / f"{name}.py",
                               f"reader_{name}")


def _cell(name, chips=1):
    cell = harness.Cell(bench_tiny.ROOT, name, 1, [],
                        bench=bench_tiny.full_bench())
    cell.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")] * chips
    cell.chips = chips
    return cell


def _trace(ops, devices=1):
    ops = dict(ops)
    return Trace({f"/device:TPU:{i}": ops.get(i, []) for i in
                  range(devices)}, {}, [Event("bench.window", 0.0, 10.0)])


def test_prox_update_roofline_reads_the_kernel_only():
    cell = _cell("qwen2-0.5b.apibcd-a1")
    least = 20 * 494_032_768 / 819e9
    tr = _trace({0: [Event(KERNEL, 1.0, 2 * least),
                     Event("%fusion.1 = f32[8]{0} fusion()", 4.0, 1.0)]})
    r = _reader("prox_update_roofline")
    assert r.read(cell, tr, {"steps": 1}) == pytest.approx(50.0)
    assert r.read(cell, _trace({0: []}), {"steps": 1}) is None
    assert not r.is_kernel(KERNEL.replace("tpu_custom_call", "other"))


def test_idle_and_mfu_readers():
    cell = _cell("qwen2-0.5b.apibcd-a1", chips=2)
    tr = _trace({0: [Event("%fusion.2 = f32[2] fusion()", 2.0, 7.5)],
                 1: [Event("%fusion.2 = f32[2] fusion()", 1.0, 7.0),
                     Event("%fusion.3 = f32[2] fusion()", 8.5, 1.5)]},
                devices=2)
    # busy 7.5 s and 8.5 s of a 10 s window: 25% and 15% idle
    assert _reader("device_idle_pct.train").read(cell, tr, {}) == \
        pytest.approx(20.0)
    mfu = _reader("train_step_mfu_pct").read(cell, tr, {"steps": 100})
    import yardstick
    flops = yardstick.train_step_flops(cell.model, 1, 4, 128) * 100
    assert mfu == pytest.approx(100 * flops / (10.0 * 2 * 197e12))
    assert _reader("train_step_mfu_pct").read(cell, tr, {"steps": 0}) \
        is None


def test_serving_readers():
    cell = _cell("internlm2-1.8b.serve-chat")
    measured = {"stats": {"admit_host_s": 0.3, "topup_host_s": 0.1,
                          "decode_steps": 200, "mixed_steps": 50},
                "completed": [(100, 10), (20, 5)], "window_s": 10.0}
    assert _reader("serve_sched_host_ms_per_step").read(
        cell, None, measured) == pytest.approx(2.0)
    tr = Trace({"/device:TPU:0": []},
               {"/device:TPU:0": [Event("jit_a", 1.0, 0.02),
                                  Event("jit_b", 2.0, 0.04),
                                  Event("jit_c", 11.0, 0.04)]},
               [Event("bench.window", 0.0, 10.0)])
    assert _reader("serve_step_device_ms").read(cell, tr, measured) == \
        pytest.approx(30.0)
    import yardstick
    flops = sum(yardstick.serve_request_flops(cell.model, p, o)
                for p, o in measured["completed"])
    assert _reader("serve_step_mfu_pct").read(cell, tr, measured) == \
        pytest.approx(100 * flops / (10.0 * 197e12))
    assert _reader("serve_step_mfu_pct").read(
        cell, tr, dict(measured, completed=[])) is None
