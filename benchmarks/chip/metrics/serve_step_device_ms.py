"""Device milliseconds per launched program (the engine's jitted
mixed, decode and prefill steps) in the traced window, from the
trace's program executions on the chip."""
from devtrace import module_runs


def read(cell, trace, measured):
    runs = [r for d in trace.devices() for r in module_runs(trace, d)]
    if not runs:
        return None
    return 1e3 * sum(r.dur for r in runs) / len(runs)
