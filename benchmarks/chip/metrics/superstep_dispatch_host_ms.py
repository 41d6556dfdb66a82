"""Host ms per step spent dispatching the superstep: the mean duration
of the program's "apibcd.step" spans (`Superstep.step`) that start
inside the traced window (`phases.py`)."""
import phases


def read(cell, trace, measured):
    ph = phases.of(cell, trace)
    return ph.host_step_ms() if ph else None
