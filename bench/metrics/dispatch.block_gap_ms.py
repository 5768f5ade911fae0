"""Mean time the device sits idle between two consecutive blocks of one
chunked call.  A block is one run of the call's main program: of the
programs that run inside the calls' ``bench.call`` spans (the trace's
``XLA Modules`` line), the one with the most device time."""
from collections import defaultdict


def read(ctx):
    if ctx.trace is None:
        return None
    runs = []  # per call, its program runs
    total = defaultdict(float)
    for _, span in ctx.call_spans():
        inside = [m for d in ctx.trace.devices
                  for m in ctx.trace.trace.modules.get(d, ())
                  if m.start >= span.start and m.end <= span.end]
        for m in inside:
            total[m.name] += m.end - m.start
        runs.append(inside)
    if not total:
        return None
    main = max(total, key=total.get)
    gaps = []
    for inside in runs:
        blocks = sorted((m for m in inside if m.name == main),
                        key=lambda m: m.start)
        for a, b in zip(blocks, blocks[1:]):
            gaps.append((b.start - a.end) / 1e9
                        - ctx.trace.busy_in(a.end, b.start))
    return 1e3 * sum(gaps) / len(gaps) if gaps else None
