"""The least time of the eq. 15/12b update's own work (read x, g, zsum;
write x_new and the token credit; float32, 20 bytes per parameter of
every agent a chip holds), over the device time of the prox_update
kernels in the trace, per step; the mean over the chips.

The kernels are found by name, whatever the shapes of their operands:
the Pallas calls (`tpu_custom_call`) that the program names
"prox_update", which the compiled step holds as instructions
`prox_update.N`, one per parameter leaf."""
import re

from devtrace import matching_seconds
from yardstick import peaks, prox_update_bytes

NAME = re.compile(r"%prox_update(\.\d+)? = ")
TARGET = 'custom_call_target="tpu_custom_call"'


def is_kernel(name):
    return (NAME.match(name) is not None and " custom-call(" in name
            and TARGET in name)


def read(cell, trace, measured):
    steps = measured.get("steps")
    if not steps:
        return None
    per_chip = cell.traffic["agents"] / cell.chips
    least = (prox_update_bytes(cell.model) * per_chip
             / peaks(cell.devices[0].device_kind)["hbm_bytes_per_s"])
    shares = []
    for d in trace.devices():
        sec = matching_seconds(trace, d, is_kernel)
        if sec > 0:
            shares.append(100.0 * least * steps / sec)
    return sum(shares) / len(shares) if shares else None
