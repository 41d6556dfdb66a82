"""The reduction of a traced training run to the superstep's phases
(`phases.py`) and the readers `superstep_*` built on it."""
import types
from pathlib import Path

import pytest

import bench_tiny
import devtrace as dt
import phases
import run as harness
from devtrace import Event, Trace

DATA = Path(__file__).resolve().parent / "data"
# beside data/, not in it: devtrace.load(data) reads the newest trace there
SCOPED = Path(__file__).resolve().parent / "scoped"
DEV = "/device:TPU:0"

# the shape of a compiled step's optimised HLO: a fusion whose metadata
# was dropped (its root's scope counts), a multi-output fusion with a
# tuple root, a while loop and its body, a kernel, and a copy the
# compiler put in under no scope
HLO = """HloModule jit_step_fn, is_scheduled=true

%fused_computation (p0: f32[8]) -> f32[8] {
  %p0 = f32[8]{0} parameter(0)
  ROOT %add.1 = f32[8]{0} add(%p0, %p0), metadata={op_name="jit(step_fn)/apibcd.token/add"}
}

%fused_computation.2 (p0: f32[8]) -> (f32[8], f32[8]) {
  %p0 = f32[8]{0} parameter(0)
  %mul.2 = f32[8]{0} multiply(%p0, %p0), metadata={op_name="jit(step_fn)/apibcd.accumulate/mul"}
  ROOT %tuple.2 = (f32[8]{0}, f32[8]{0}) tuple(%mul.2, %mul.2)
}

%body (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  ROOT %fusion.7 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(step_fn)/apibcd.grad/vmap(transpose(jvp(model.blocks)))/while/body/dot_general"}
}

ENTRY %main.9 (x: f32[8]) -> f32[8] {
  %x = f32[8]{0} parameter(0), metadata={op_name="x"}
  %fusion.1 = f32[8]{0} fusion(%x), kind=kLoop, calls=%fused_computation
  %fusion.2 = (f32[8]{0}, f32[8]{0}) fusion(%x), kind=kLoop, calls=%fused_computation.2
  %while.3 = f32[8]{0} while(%x), body=%body, metadata={op_name="jit(step_fn)/apibcd.grad/vmap(transpose(jvp(model.blocks)))/while"}
  %prox_update.4 = (f32[8,1024]{1,0}, f32[8,1024]{1,0}) custom-call(%x), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/apibcd.prox/prox_update/pallas_call"}
  %copy.5 = f32[8]{0} copy(%x)
  ROOT %rev.6 = f32[8]{0} reverse(%x), dimensions={0}, metadata={op_name="jit(step_fn)/apibcd.exchange/rev"}
}
"""


def _load(path):
    from jax.profiler import ProfileData
    return dt.from_profile(ProfileData.from_file(str(path)))


def _op(name, start, dur):
    return Event(f"%{name} = f32[8]{{0}} {name.split('.')[0]}(%x)",
                 start, dur)


def _trace():
    """One step run (1.0-9.0) in a 0-10 s window, and one op of another
    program after it."""
    ops = [_op("while.3", 1.0, 3.0),          # body 1.5-3.5: self 1.0
           _op("fusion.7", 1.5, 2.0),
           _op("fusion.2", 4.0, 0.5),
           _op("prox_update.4", 4.5, 1.0),
           _op("fusion.1", 5.5, 0.5),
           _op("copy.5", 6.0, 0.25),
           _op("rev.6", 6.25, 0.25),
           _op("fusion.99", 9.5, 0.25)]       # another program
    modules = [Event("jit_step_fn(42)", 1.0, 8.0),
               Event("jit_other(7)", 9.4, 0.5)]
    spans = [Event("bench.window", 0.0, 10.0),
             Event("bench.step", 0.2, 0.7)]
    return Trace({DEV: ops}, {DEV: modules}, spans)


PROGRAM_SPANS = [Event("apibcd.step", 0.3, 0.6),
                 Event("apibcd.step", 12.0, 0.5)]    # after the window


def _reduce(tr=None):
    module, names = phases.op_names(HLO)
    return phases.reduce(tr or _trace(), module, names, PROGRAM_SPANS)


def test_a_fusion_counts_under_its_roots_scope():
    module, names = phases.op_names(HLO)
    assert module == "jit_step_fn"
    assert names["fusion.1"] == "jit(step_fn)/apibcd.token/add"
    # a tuple root: the last instruction there with an op_name
    assert names["fusion.2"] == "jit(step_fn)/apibcd.accumulate/mul"
    assert "copy.5" not in names
    assert phases.scope(names["fusion.7"]) == ("grad", "blocks",
                                               "backward")
    assert phases.scope(names["prox_update.4"]) == ("prox", None, "")
    assert phases.scope(
        "jit(step_fn)/apibcd.grad/vmap(transpose(jvp(model.blocks)))/"
        "while/body/closed_call/checkpoint/rematted_computation/dot") == \
        ("grad", "blocks", "recompute")
    assert phases.scope("jit(step_fn)/apibcd.grad/vmap(jvp(model.head))"
                        "/dot") == ("grad", "head", "forward")
    assert phases.scope(None) == (None, None, "")


def test_a_while_counts_by_self_time_only():
    tr = _trace()
    own = {phases.instruction(e.name): s
           for e, s in phases.self_seconds(tr.ops[DEV], *tr.window)}
    assert own["while.3"] == pytest.approx(1.0)
    assert own["fusion.7"] == pytest.approx(2.0)
    assert own["prox_update.4"] == pytest.approx(1.0)


def test_partial_overlaps_and_the_window_edge_count_once():
    evs = [Event("a", 0.0, 2.0), Event("b", 1.0, 2.0),   # b outlives a
           Event("c", 4.0, 2.0)]                          # cut at 5.0
    own = dict((e.name, s) for e, s in phases.self_seconds(evs, 0.5, 5.0))
    assert own == pytest.approx({"a": 0.5, "b": 2.0, "c": 1.0})


def test_ops_are_attributed_to_the_step_by_time_containment():
    ph = _reduce()
    sec = ph.seconds[DEV]
    assert ph.steps == {DEV: 1}
    assert sec[phases.OUTSIDE] == pytest.approx(0.25)
    assert sec["grad"] == pytest.approx(3.0)
    assert sec["accumulate"] == pytest.approx(0.5)
    assert sec["prox"] == pytest.approx(1.0)
    assert sec["token"] == pytest.approx(0.5)
    assert sec["exchange"] == pytest.approx(0.25)
    assert ph.scoped


def test_other_is_the_steps_ops_under_no_scope():
    ph = _reduce()
    assert ph.seconds[DEV][phases.OTHER] == pytest.approx(0.25)
    assert ph.per_step_ms((phases.OTHER,)) == pytest.approx(250.0)
    assert ph.ops[(phases.OTHER, "copy.5 copy")] == pytest.approx(0.25)
    assert ph.ops[("prox", "prox_update.4 prox_update")] == \
        pytest.approx(1.0)
    assert ph.ops[(phases.OUTSIDE, "fusion.99 fusion")] == \
        pytest.approx(0.25)


def test_scopes_and_other_sum_to_the_busy_time():
    tr = _trace()
    ph = _reduce(tr)
    assert sum(ph.seconds[DEV].values()) == pytest.approx(
        dt.busy_s(tr, DEV))
    grad, update, other, exchange = (
        ph.per_step_ms(k) for k in (phases.GRAD, phases.UPDATE,
                                    (phases.OTHER,), phases.EXCHANGE))
    assert grad + update + other + exchange == pytest.approx(
        1e3 * (dt.busy_s(tr, DEV) - 0.25))


def test_an_idle_gap_is_named_by_the_program_span_in_a_bench_step():
    tr = _trace()
    # gaps 6.5-9.5, 0-1.0 (midpoint 0.5, under bench.step and
    # apibcd.step), 9.75-10
    gaps = phases.idle_gaps(tr, DEV, PROGRAM_SPANS)
    assert [n for n, _ in gaps] == ["host.none", "apibcd.step", "host.none"]
    assert [s for _, s in gaps] == pytest.approx([3.0, 1.0, 0.25])
    # without the program's spans it is the driver's
    assert [n for n, _ in dt.idle_gaps(tr, DEV)][1] == "bench.step"


def test_the_host_step_spans_inside_the_window():
    ph = _reduce()
    assert ph.host_steps == [pytest.approx(0.6)]
    assert ph.host_step_ms() == pytest.approx(600.0)


def test_a_program_without_scopes_reads_nothing():
    hlo = HLO.replace("apibcd.", "")
    module, names = phases.op_names(hlo)
    ph = phases.reduce(_trace(), module, names, [])
    assert not ph.scoped
    assert ph.per_step_ms(phases.GRAD) is None
    assert ph.host_step_ms() is None
    # another program's module name: no step ran
    ph = phases.reduce(_trace(), "jit_else", phases.op_names(HLO)[1], [])
    assert ph.steps == {DEV: 0}
    assert ph.per_step_ms(phases.UPDATE) is None


# ---------------------------------------------------------------------------
# the readers
# ---------------------------------------------------------------------------


READERS = ("superstep_grad_ms", "superstep_update_ms", "superstep_other_ms",
           "superstep_dispatch_host_ms")


def _reader(name):
    return harness.load_module(bench_tiny.HERE / "metrics" / f"{name}.py",
                               f"reader_{name}")


def _cell():
    cell = harness.Cell(bench_tiny.ROOT, "qwen2-0.5b.apibcd-a1", 1, [],
                        bench=bench_tiny.bench())
    cell.devices = [types.SimpleNamespace(device_kind="TPU v5 lite")]
    return cell


@pytest.fixture
def no_io(monkeypatch):
    """phases.of without the chip: the HLO and the program's spans as
    the test gives them."""
    given = {"hlo": HLO, "spans": PROGRAM_SPANS}
    monkeypatch.setattr(phases, "step_hlo", lambda cell: given["hlo"])
    monkeypatch.setattr(phases, "traced_profile", lambda: None)
    monkeypatch.setattr(phases, "program_spans",
                        lambda profile: given["spans"])
    return given


def test_the_four_readers(no_io, capsys):
    tr = _trace()
    got = {n: _reader(n).read(_cell(), tr, {"steps": 1}) for n in READERS}
    assert got == pytest.approx({"superstep_grad_ms": 3000.0,
                                 "superstep_update_ms": 2000.0,
                                 "superstep_other_ms": 250.0,
                                 "superstep_dispatch_host_ms": 600.0})
    # the table went to stderr once, for the first reader
    err = capsys.readouterr().err
    assert err.count("phases /device:TPU:0: 1 steps") == 1
    assert "apibcd.grad" in err and "grad model.blocks" in err
    assert "longest idle gaps, ms: host.none 3000.0000, apibcd.step" in err
    assert "1000.0000  apibcd.prox prox_update.4 prox_update" in err


@pytest.mark.parametrize("name", READERS)
def test_readers_read_none_where_nothing_ran(no_io, name):
    r = _reader(name)
    no_io["hlo"] = HLO.replace("apibcd.", "")      # a program unscoped
    assert r.read(_cell(), _trace(), {"steps": 1}) is None
    no_io["hlo"] = HLO
    empty = Trace({DEV: []}, {DEV: []}, [Event("bench.window", 0.0, 10.0)])
    assert r.read(_cell(), empty, {"steps": 0}) is None


def test_a_reduction_that_fails_reads_none(no_io, monkeypatch, capsys):
    def refuse(cell):
        raise RuntimeError("no compile here")
    monkeypatch.setattr(phases, "step_hlo", refuse)
    assert _reader("superstep_grad_ms").read(_cell(), _trace(), {}) is None
    assert "no compile here" in capsys.readouterr().err


def test_the_step_and_its_spans_as_the_chip_run_takes_them(tmp_path):
    """On the CPU at the tiny size: the step's HLO from an abstract
    Superstep names the phases of the update (with one walk the sum
    over walks is no operation, and with one agent the ring hop), and
    the program's host spans are read back from a profiler trace of two
    steps."""
    import json

    import jax

    root, data = bench_tiny.tiny_root(tmp_path)
    cell = harness.Cell(root, "qwen2-0.5b.apibcd-a1", 5, jax.devices(),
                        bench=json.loads((root / "BENCHMARK.json")
                                         .read_text()), data=data)
    module, names = phases.op_names(phases.step_hlo(cell))
    assert module == "jit_step_fn"
    found = {phases.scope(n)[0] for n in names.values()}
    assert set(phases.GRAD + phases.UPDATE) - {"zsum"} <= found

    from repro.launch.train import Superstep
    run = Superstep(cell.arch(), jax.devices()[:1], agents=1, walks=1,
                    batch_per_agent=2, seq=8)
    jax.block_until_ready(run.step(0))
    jax.profiler.start_trace(str(tmp_path / "trace"))
    for i in (1, 2):
        jax.block_until_ready(run.step(i))
    jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    spans = phases.program_spans(ProfileData.from_file(
        str(dt.newest_xplane(tmp_path / "trace"))))
    assert [s.name for s in spans].count("apibcd.step") == 2
    assert [s.name for s in spans].count("apibcd.batch_upload") == 2


def test_the_accepted_readers_read_the_same_after_the_phases(no_io):
    """On the trace recorded on the chip (tests/data/small.xplane.pb):
    `train_step_mfu_pct`, `prox_update_roofline` and
    `device_idle_pct.train` read the same before and after the phase
    reduction has run on the same trace."""
    tr = _load(DATA / "small.xplane.pb")
    cell, measured = _cell(), {"steps": 3}
    names = ("train_step_mfu_pct", "prox_update_roofline",
             "device_idle_pct.train")
    before = {n: _reader(n).read(cell, tr, measured) for n in names}
    for n in READERS:
        _reader(n).read(cell, tr, measured)
    assert hasattr(tr, "_phases")
    after = {n: _reader(n).read(cell, tr, measured) for n in names}
    assert after == before
    assert before["device_idle_pct.train"] > 90


def _scoped():
    """tools/record_scoped_trace.py on a TPU v5e: three steps of a
    2-layer Superstep (two agents, two walks) with the program's scopes
    and spans, and the op_names of its compiled step."""
    import gzip
    import json

    from jax.profiler import ProfileData
    raw = gzip.decompress((SCOPED / "scoped.xplane.pb.gz").read_bytes())
    profile = ProfileData.from_serialized_xspace(raw)
    ops = json.loads((SCOPED / "scoped.ops.json").read_text())
    return dt.from_profile(profile), ops, phases.program_spans(profile)


def test_a_scoped_trace_recorded_on_the_chip():
    tr, ops, spans = _scoped()
    (dev,) = tr.devices()
    ph = phases.reduce(tr, ops["module"], ops["op_names"], spans)
    assert ph.steps == {dev: 3}
    # every phase did device work, and the model's scopes are there
    assert set(ph.seconds[dev]) >= set(phases.PHASES)
    models = {m for (p, m, _) in ph.detail[dev] if p == "grad"}
    assert {"embed", "blocks", "head"} <= models
    directions = {d for (p, _, d) in ph.detail[dev] if p == "grad"}
    assert {"forward", "backward", "recompute"} <= directions
    # the scopes, other and the rest sum to the busy time
    assert sum(ph.seconds[dev].values()) == pytest.approx(
        dt.busy_s(tr, dev), rel=1e-9)
    keys = (phases.GRAD, phases.UPDATE, (phases.OTHER,), phases.EXCHANGE)
    total = sum(ph.per_step_ms(k) for k in keys)
    outside = 1e3 * ph.seconds[dev].get(phases.OUTSIDE, 0.0) / 3
    assert total + outside == pytest.approx(
        1e3 * dt.busy_s(tr, dev) / 3, rel=1e-9)
    # the kernel under apibcd.prox; the host's three dispatches
    kernels = [(k, n) for k, n in ph.ops if "tpu_custom_call" in n]
    assert kernels and all(k == "prox" and n.startswith("prox_update.")
                           for k, n in kernels)
    assert len(ph.host_steps) == 3 and ph.host_step_ms() > 0
    # the same gaps as the driver's naming; those under a dispatch are
    # named by the program's span, not by bench.step around it
    named = list(zip(phases.idle_gaps(tr, dev, spans), dt.idle_gaps(tr, dev)))
    assert all(a[1] == b[1] for a, b in named)
    dispatch = [b[0] for a, b in named if a[0] == "apibcd.step"]
    assert dispatch and set(dispatch) == {"bench.step"}
