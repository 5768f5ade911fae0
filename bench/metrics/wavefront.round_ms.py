"""Device time per wavefront round: device-busy time inside the calls'
``bench.call`` spans over the rounds that the calls' result records
report."""


def read(ctx):
    if ctx.trace is None:
        return None
    busy = rounds = 0.0
    for call, span in ctx.call_spans():
        busy += ctx.trace.busy_in(span.start, span.end)
        rounds += int(call.result.rounds)
    return 1e3 * busy / rounds if rounds else None
