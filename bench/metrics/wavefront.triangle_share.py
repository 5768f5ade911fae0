"""Share of the frame calls' device time in the wavefront's triangle
stage: the operations under the ``wavefront.triangle`` named scope (the
leaf lookup, triangle gathers, ray-triangle test and closest-hit select,
``core/wavefront.py``) over the device-busy time inside the calls'
``bench.call`` spans, in percent."""
from bench.scopes import call_share


def read(ctx):
    return call_share(ctx, "wavefront.triangle")
