"""Share of the nearest calls' device time spent in the distance kernel's
``pallas_call``, in percent.  The kernel is the operation that the TPU's
trace names ``%distance_pallas.<n> = ... custom-call(...)``: XLA names
the custom call after the program's function ``distance_pallas``."""

KERNEL = "%distance_pallas"


def read(ctx):
    if ctx.trace is None:
        return None
    kernel = busy = 0.0
    for _, span in ctx.call_spans():
        busy += ctx.trace.busy_in(span.start, span.end)
        kernel += sum(s for name, s in
                      ctx.trace.op_seconds(span.start, span.end).items()
                      if name.startswith(KERNEL))
    return 100.0 * kernel / busy if kernel and busy else None
