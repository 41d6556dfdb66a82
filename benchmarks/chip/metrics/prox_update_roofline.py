"""The least time of the eq. 15/12b update's own work (read x, g, zsum;
write x_new and the token credit; float32, 20 bytes per parameter of
every agent a chip holds), over the device time of the prox_update
kernel in the trace, per step; the mean over the chips.

The kernel is the Pallas call (`tpu_custom_call`) that takes three
float32 [rows, 1024] tiles and returns two: x_new and the credit."""
import re

from devtrace import matching_seconds
from yardstick import peaks, prox_update_bytes

TILE = re.compile(r"f32\[(\d+),1024\]")
TARGET = 'custom_call_target="tpu_custom_call"'


def is_kernel(name):
    if TARGET not in name or " custom-call(" not in name:
        return False
    result, operands = name.split(" custom-call(", 1)
    outs = TILE.findall(result)
    ins = TILE.findall(operands.split(TARGET, 1)[0])
    return len(outs) == 2 and len(ins) == 3 and len(set(outs + ins)) == 1


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
