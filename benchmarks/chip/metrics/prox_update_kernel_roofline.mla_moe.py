"""`prox_update_kernel_roofline` for a model of latent attention and
routed experts: the least time of the update's own work (20 bytes per
parameter of every agent a chip holds, the parameters counted from the
configuration's shapes by `yardstick_mla_moe.all_params`), over the
device time of the kernels named "prox_update", per step; the mean over
the chips."""
from devtrace import matching_seconds
from metrics.prox_update_kernel_roofline import is_kernel
from yardstick import peaks
from yardstick_mla_moe import prox_update_bytes


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
