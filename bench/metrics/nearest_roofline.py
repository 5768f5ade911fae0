"""Share of the nearest-search roofline: the least time the chip could take
for each call (``bench/roofline.py``) over the device-busy time inside the
call's ``bench.call`` span, in percent."""
from bench.roofline import nearest_least_seconds


def read(ctx):
    if ctx.trace is None:
        return None
    cfg = ctx.config
    least = busy = 0.0
    for call, span in ctx.call_spans():
        least += nearest_least_seconds(call.rows, cfg["points"], cfg["dim"],
                                       cfg["k"], ctx.peak)
        busy += ctx.trace.busy_in(span.start, span.end)
    return 100.0 * least / busy if busy else None
