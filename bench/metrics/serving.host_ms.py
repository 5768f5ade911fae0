"""The server's host work per batch: the mean, over the batches whose
``serving.split`` span opened in the window, of the time in the program's
``serving.assemble`` and ``serving.split`` spans (building the padded
batch and sending it to the device; bringing the answer back and
slicing it per request), in milliseconds."""
from bench.scopes import program_spans


def read(ctx):
    w = ctx.window
    assemble = program_spans("serving.assemble", w.start, w.waited_until)
    split = program_spans("serving.split", w.start, w.waited_until)
    if not split:
        return None
    return 1e3 * sum(s.end - s.start for s in assemble + split) / len(split)
