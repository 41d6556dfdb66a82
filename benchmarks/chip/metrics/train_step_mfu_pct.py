"""Model FLOPs of the supersteps in the traced window (forward and
backward from shapes, no recomputation), over the traced window's
length times the chips' bf16 peak."""
from yardstick import peaks, train_step_flops


def read(cell, trace, measured):
    if not measured.get("steps"):
        return None
    t = cell.traffic
    flops = train_step_flops(cell.model, t["agents"], t["batch_per_agent"],
                             t["seq"]) * measured["steps"]
    peak = peaks(cell.devices[0].device_kind)["bf16_flops"]
    return 100.0 * flops / (trace.window_s * cell.chips * peak)
