"""Host time the engine holds the caller per frame call: the mean length
of the program's ``engine.trace`` spans in the window, in milliseconds
(planning, the block's slice, pad and enqueue, assembly).  Blocks run
asynchronously, so the span ends once the result is assembled, not when
the device is done."""
from bench.scopes import mean_span_ms


def read(ctx):
    return mean_span_ms(ctx, "engine.trace")
