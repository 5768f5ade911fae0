"""Host time the engine holds the caller per query set: the mean length of
the program's ``engine.nearest`` spans in the window, in milliseconds
(planning, one slice-pad-enqueue per block, assembly).  Once the runtime
holds as many programs in flight as it takes, each further enqueue waits
for a block to finish on the device, and the span holds that wait too."""
from bench.scopes import mean_span_ms


def read(ctx):
    return mean_span_ms(ctx, "engine.nearest")
