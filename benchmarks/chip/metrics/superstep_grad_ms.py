"""Device ms per step under the step's `apibcd.grad` scope (every
agent's forward and backward pass and the loss means), self time, the
mean over the chips (`phases.py`)."""
import phases


def read(cell, trace, measured):
    ph = phases.of(cell, trace)
    return ph.per_step_ms(phases.GRAD) if ph else None
