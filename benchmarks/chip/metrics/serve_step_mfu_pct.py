"""Model FLOPs of the requests completed inside the window (prompt
forward, one decode position per further output token, attention over
the context, from shapes) over the window's length times the chip's
bf16 peak."""
from yardstick import peaks, serve_request_flops


def read(cell, trace, measured):
    done = measured.get("completed")
    if not done:
        return None
    flops = sum(serve_request_flops(cell.model, p, o) for p, o in done)
    peak = peaks(cell.devices[0].device_kind)["bf16_flops"]
    return 100.0 * flops / (measured["window_s"] * cell.chips * peak)
