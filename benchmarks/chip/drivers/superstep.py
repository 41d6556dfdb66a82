"""Drive the API-BCD superstep for any model, checked against the model
reference the configuration names.

setup, window and release are `drivers/train.py`'s, by import; so is the
comparison.  The check follows the checked steps with
`references/superstep.py`, which takes the model reference from the
configuration's `reference` key, where `train` is tied to the dense
decoder.  Where the step's metrics count the expert assignments dropped
over capacity ("moe_dropped"), they are logged for each checked step and
for the window's last step.
"""
from __future__ import annotations

import contextlib

import jax

from drivers import train


@contextlib.contextmanager
def _dropped(log, what, every):
    """Log "moe_dropped" of the steps `repro.launch.train.Superstep`
    takes inside: of each (`every`), or of the last alone, so that the
    window reads nothing back while it runs."""
    from repro.launch.train import Superstep

    step, seen = Superstep.step, []

    def counted(self, *args, **kw):
        metrics = step(self, *args, **kw)
        if "moe_dropped" in metrics:
            seen[len(seen) if every else 0:] = [metrics["moe_dropped"]]
        return metrics

    Superstep.step = counted
    try:
        yield
    finally:
        Superstep.step = step
    if seen:
        log(f"superstep: moe_dropped {what} "
            f"{[int(v) for v in jax.device_get(seen)]}")


def setup(cell, log=print):
    with _dropped(log, "checked steps", every=True):
        return train.setup(cell, log=log)


def window(cell, state, seconds, log=print):
    with _dropped(log, "last window step", every=False):
        return train.window(cell, state, seconds, log=log)


release = train.release


def check(cell, kept, log=print, precision="f32", faults=()):
    from references import superstep

    t = cell.traffic
    ref = superstep.run(cell.config["reference"], cell.model, t,
                        jax.random.PRNGKey(cell.model_seed),
                        kept["batches"], cell.devices[:cell.chips],
                        precision=precision, faults=faults)
    grads, change, tokens = train._program_readings(cell, kept)
    return train.compare_training(cell, kept["losses"], grads, change,
                                  tokens, ref, log)
