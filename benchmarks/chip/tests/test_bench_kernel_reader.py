"""`prox_update_kernel_roofline` finds the update kernels by their name,
in the [rows, 1024] tiles of the older program and in each leaf's own
layout, and nothing else."""
import types

import pytest

import bench_tiny
import devtrace as dt
import run as harness
from devtrace import Event, Trace

TILED = ('%prox_update.14 = (f32[8,1024]{1,0:T(8,128)}, f32[8,1024]{1,0:'
         'T(8,128)}) custom-call(f32[8,1024]{1,0:T(8,128)} %r.1, f32[8,1024]'
         '{1,0:T(8,128)} %r.2, f32[8,1024]{1,0:T(8,128)} %r.3), '
         'custom_call_target="tpu_custom_call", operand_layout_constraints'
         '={f32[8,1024]{1,0}}')
NATIVE = ('%prox_update.26 = (f32[21504,4864]{1,0:T(8,128)}, f32[21504,4864]'
          '{1,0:T(8,128)}) custom-call(%bitcast.91, %bitcast.92, %bitcast.93),'
          ' custom_call_target="tpu_custom_call", operand_layout_constraints'
          '={f32[21504,4864]{1,0}}')
OTHER_KERNEL = NATIVE.replace("%prox_update.26", "%flash_attention.3")
FUSION = '%fusion.1 = f32[21504,4864]{1,0} fusion(%p.1), kind=kLoop'


def _reader(name):
    return harness.load_module(bench_tiny.HERE / "metrics" / f"{name}.py",
                               f"reader_{name}")


def _cell():
    cell = harness.Cell(bench_tiny.ROOT, "qwen2-0.5b.apibcd-a1", 1, [],
                        bench=bench_tiny.full_bench())
    cell.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    cell.chips = 1
    return cell


def _trace(events):
    return Trace({"/device:TPU:0": events}, {},
                 [Event("bench.window", 0.0, 10.0)])


@pytest.mark.parametrize("kernel", [TILED, NATIVE])
def test_reads_the_kernel_in_either_layout(kernel):
    least = 20 * 494_032_768 / 819e9
    tr = _trace([Event(kernel, 1.0, least), Event(kernel, 2.0, least),
                 Event(OTHER_KERNEL, 4.0, 1.0), Event(FUSION, 6.0, 1.0)])
    r = _reader("prox_update_kernel_roofline")
    assert r.read(_cell(), tr, {"steps": 1}) == pytest.approx(50.0)
    assert r.read(_cell(), tr, {"steps": 0}) is None


@pytest.mark.parametrize("name", [
    OTHER_KERNEL, FUSION, NATIVE.replace("tpu_custom_call", "other"),
    NATIVE.replace("%prox_update.26", "%prox_update_grad.2"),
    "prox_update"])
def test_reads_nothing_else(name):
    r = _reader("prox_update_kernel_roofline")
    assert not r.is_kernel(name)
    assert r.read(_cell(), _trace([Event(name, 1.0, 1.0)]),
                  {"steps": 1}) is None


def test_on_a_trace_recorded_on_the_chip_it_reads_the_tiled_kernels():
    """On a chip trace of the tiled program (tools/record_scoped_trace.py,
    TPU v5e) the reader by name and the reader by tile shape time the
    same kernels."""
    import gzip

    from jax.profiler import ProfileData
    raw = gzip.decompress((bench_tiny.HERE / "tests" / "scoped"
                           / "scoped.xplane.pb.gz").read_bytes())
    tr = dt.from_profile(ProfileData.from_serialized_xspace(raw))
    (dev,) = tr.devices()
    by_name = _reader("prox_update_kernel_roofline").is_kernel
    by_shape = _reader("prox_update_roofline").is_kernel
    sec = dt.matching_seconds(tr, dev, by_name)
    assert sec > 0
    assert sec == dt.matching_seconds(tr, dev, by_shape)
    assert sec == dt.matching_seconds(
        tr, dev, lambda n: by_name(n) or by_shape(n))
