"""Record the scoped trace that tests/test_bench_phases.py reads.

    python3 benchmarks/chip/tools/record_scoped_trace.py \
        benchmarks/chip/tests/scoped

Three steps of a 2-layer `repro.launch.train.Superstep` (qwen2-0.5b's
layer kinds at the tiny widths of tests/bench_tiny.py; two agents and
two walks on one chip, so the sum over walks and the ring hop do device
work) inside a "bench.window" span, each step driven as the training
driver drives it ("bench.batch_upload", "bench.step", "bench.wait") and
carrying the program's own scopes and spans.  Writes, into the
directory given, scoped.xplane.pb.gz (the trace, gzipped) and
scoped.ops.json: the step's HLO module name and the op_name of every
instruction that ran in the trace, from the compiled step's optimised
HLO.
"""
import gzip
import json
import sys
import tempfile
from pathlib import Path

import jax

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE.parents[1] / "src"))
sys.path.insert(0, str(HERE))
sys.path.insert(0, str(HERE / "tests"))

import devtrace  # noqa: E402
import phases  # noqa: E402
from bench_tiny import TINY_MODEL  # noqa: E402

STEPS = 3


def main():
    from jax.profiler import ProfileData

    from repro.configs.base import ArchConfig
    from repro.launch.train import Superstep

    out = Path(sys.argv[1])
    out.mkdir(parents=True, exist_ok=True)
    model = json.loads((HERE / "configs" / "qwen2-0.5b.json").read_text())
    arch = ArchConfig(**dict(model["model"], **TINY_MODEL))
    run = Superstep(arch, jax.devices()[:1], agents=2, walks=2,
                    batch_per_agent=2, seq=16)
    jax.block_until_ready(run.step(0))
    module, names = phases.op_names(
        run.lower(run.abstract_batch()).compile().as_text())
    # no HLO protos and no Python calls in the file: the reduction reads
    # neither (on a TPU most of the file is the ~700 device ops a step)
    opts = jax.profiler.ProfileOptions()
    opts.enable_hlo_proto = False
    opts.python_tracer_level = 0
    with tempfile.TemporaryDirectory(dir=out) as d:
        jax.profiler.start_trace(d, profiler_options=opts)
        with jax.profiler.TraceAnnotation("bench.window"):
            for i in range(1, STEPS + 1):
                with jax.profiler.TraceAnnotation("bench.batch_upload"):
                    batch = run.next_batch()
                with jax.profiler.TraceAnnotation("bench.step"):
                    metrics = run.step(i, batch)
                with jax.profiler.TraceAnnotation("bench.wait"):
                    jax.block_until_ready(metrics)
        jax.profiler.stop_trace()
        raw = devtrace.newest_xplane(d).read_bytes()
    ran = {phases.instruction(e.name)
           for plane in ProfileData.from_serialized_xspace(raw).planes
           if devtrace._is_device_plane(plane.name)
           for line in plane.lines for e in line.events}
    trace = out / "scoped.xplane.pb.gz"
    trace.write_bytes(gzip.compress(raw, 9))
    ops = out / "scoped.ops.json"
    ops.write_text(json.dumps(
        {"module": module,
         "op_names": {k: v for k, v in sorted(names.items()) if k in ran}},
        indent=0))
    print(f"{len(raw)} bytes of trace;", trace, trace.stat().st_size, ops,
          ops.stat().st_size)


if __name__ == "__main__":
    main()
