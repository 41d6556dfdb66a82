"""Record the small trace that tests/test_bench_trace.py reads.

    python3 benchmarks/chip/tools/record_trace.py <out.xplane.pb>

Three launches of one jitted program inside a "bench.window" span, each
launch in a "bench.step" span and followed by a 20 ms "bench.batch_upload"
span in which the host sleeps, so the device is idle there.
"""
import shutil
import sys
import tempfile
import time
from pathlib import Path

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import devtrace  # noqa: E402

LAUNCHES = 3
SLEEP_S = 0.02


def main():
    out = Path(sys.argv[1])
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum(axis=0))
    x = jnp.ones((1024, 1024), jnp.bfloat16)
    jax.block_until_ready(f(x))
    with tempfile.TemporaryDirectory(dir=out.parent) as d:
        jax.profiler.start_trace(d)
        with jax.profiler.TraceAnnotation("bench.window"):
            for _ in range(LAUNCHES):
                with jax.profiler.TraceAnnotation("bench.step"):
                    jax.block_until_ready(f(x))
                with jax.profiler.TraceAnnotation("bench.batch_upload"):
                    time.sleep(SLEEP_S)
        jax.profiler.stop_trace()
        shutil.copy(devtrace.newest_xplane(d), out)
    print(out, out.stat().st_size)


if __name__ == "__main__":
    main()
