"""Device ms per step of the step program's operations under no
`apibcd.*` scope (copies and layout changes the compiler inserts), self
time, the mean over the chips (`phases.py`)."""
import phases


def read(cell, trace, measured):
    ph = phases.of(cell, trace)
    return ph.per_step_ms((phases.OTHER,)) if ph else None
