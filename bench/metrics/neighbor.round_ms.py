"""Device time per neighbour-search round: device-busy time inside the
calls' ``bench.call`` spans over the rounds that the calls' result records
report (``NearestResult.rounds`` on the tree route).  A program whose
records carry no rounds gives no reading."""


def read(ctx):
    if ctx.trace is None:
        return None
    busy = rounds = 0.0
    for call, span in ctx.call_spans():
        n = getattr(call.result, "rounds", None)
        if n is None:
            return None
        busy += ctx.trace.busy_in(span.start, span.end)
        rounds += int(n)
    return 1e3 * busy / rounds if rounds else None
