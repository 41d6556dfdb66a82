"""Device ms per step under the step's state-update scopes
(`apibcd.accumulate`, `.zsum`, `.prox`, `.select`, `.token`: eq. 15,
12b and 12c and the between-visit accumulation), self time, the mean
over the chips (`phases.py`)."""
import phases


def read(cell, trace, measured):
    ph = phases.of(cell, trace)
    return ph.per_step_ms(phases.UPDATE) if ph else None
