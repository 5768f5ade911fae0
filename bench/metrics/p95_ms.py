"""95th percentile of every request's latency, from when it was due to
when its answer was ready, on the host clock.  A request that was never
answered counts with the time it was waited for, a bound below its
latency."""
import numpy as np


def read(ctx):
    w = ctx.window
    lat = [(d if d is not None else w.waited_until) - (w.start + r.due)
           for r, d in zip(w.requests, w.done)]
    return 1e3 * float(np.percentile(lat, 95))
