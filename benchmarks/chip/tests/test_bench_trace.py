"""The reduction from a profiler trace to busy time, per-operation time,
collective time and idle gaps named by what the host was doing."""
from pathlib import Path

import pytest

import bench_tiny  # noqa: F401
import devtrace as dt
from devtrace import Event, Trace

DATA = Path(__file__).resolve().parent / "data"


def _trace():
    dev = "/device:TPU:0"
    ops = [Event("fusion.1", 1.0, 1.0),          # 1-2
           Event("prox_update", 1.5, 1.0),       # overlaps: 1-2.5
           Event("collective-permute-done", 4.0, 0.5),
           Event("fusion.1", 6.0, 2.0)]          # runs past the window
    spans = [Event("bench.window", 0.5, 6.5),     # 0.5-7
             Event("bench.step", 2.6, 1.0),
             Event("bench.batch_upload", 4.6, 1.0)]
    return Trace({dev: ops}, {dev: [Event("jit_step", 1.0, 3.5)]},
                 spans), dev


def test_union_and_busy_time_inside_the_window():
    tr, dev = _trace()
    assert dt.union(tr.ops[dev]) == [(1.0, 2.5), (4.0, 4.5), (6.0, 8.0)]
    assert tr.window == (0.5, 7.0) and tr.window_s == 6.5
    assert dt.busy_s(tr, dev) == pytest.approx(1.5 + 0.5 + 1.0)
    assert dt.mean_busy_s(tr) == pytest.approx(3.0)


def test_per_operation_and_collective_seconds():
    tr, dev = _trace()
    assert dt.op_seconds(tr, dev) == pytest.approx(
        {"fusion.1": 2.0, "prox_update": 1.0,
         "collective-permute-done": 0.5})
    assert dt.matching_seconds(tr, dev, lambda n: "collective" in n) == \
        pytest.approx(0.5)
    assert dt.matching_seconds(tr, dev, lambda n: True) == \
        pytest.approx(3.0)
    assert len(dt.module_runs(tr, dev)) == 1


def test_idle_gaps_are_named_by_the_host_span_over_them():
    tr, dev = _trace()
    gaps = dt.idle_gaps(tr, dev)
    assert gaps == [("bench.step", pytest.approx(1.5)),
                    ("bench.batch_upload", pytest.approx(1.5)),
                    ("host.none", pytest.approx(0.5))]
    b = dt.breakdown(tr)
    assert b["device_ops"][0] == ["fusion.1", 2.0]
    assert len(b["idle_gaps"]) == 3


def test_a_trace_without_a_window_span_is_refused():
    tr, _ = _trace()
    tr.spans = [s for s in tr.spans if s.name != "bench.window"]
    with pytest.raises(ValueError):
        tr.window


def test_a_trace_recorded_on_the_chip():
    """tools/record_trace.py on a TPU v5e: three launches of one jitted
    program, each followed by 20 ms in which the host sleeps."""
    tr = dt.load(DATA)
    assert tr.devices() == ["/device:TPU:0"]
    assert 0.06 < tr.window_s < 0.2
    dev = tr.devices()[0]
    busy = dt.busy_s(tr, dev)
    assert 0 < busy < 0.01 * tr.window_s
    runs = dt.module_runs(tr, dev)
    assert 2 <= len(runs) <= 3
    assert all(r.name.startswith("jit_") for r in runs)
    gaps = dt.idle_gaps(tr, dev)
    assert [name for name, _ in gaps[:3]] == ["bench.batch_upload"] * 3
    assert all(0.015 < sec < 0.03 for _, sec in gaps[:3])
    assert sum(sec for _, sec in gaps) == pytest.approx(tr.window_s - busy)
    b = dt.breakdown(tr)
    assert b["device_ops"][0][0] == "fusion fusion"
    assert len(b["idle_gaps"]) <= 10


def test_short_names_of_hlo_ops():
    assert dt.short_name(
        "%fusion.3 = (f32[2]{0}, bf16[4]{0}) fusion(f32[2]{0} %p), "
        "kind=kLoop") == "fusion.3 fusion"
    assert dt.short_name(
        '%step_fn.14 = (f32[8,1024]{1,0}, f32[8,1024]{1,0}) custom-call('
        'f32[8,1024]{1,0} %a), custom_call_target="tpu_custom_call"') == \
        "step_fn.14 custom-call tpu_custom_call"
    assert dt.short_name("jit_step(123)") == "jit_step(123)"
