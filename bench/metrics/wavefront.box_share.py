"""Share of the frame calls' device time in the wavefront's box stage: the
operations under the ``wavefront.box`` named scope (child-box gathers and
the ray-box test, ``core/wavefront.py``) over the device-busy time inside
the calls' ``bench.call`` spans, in percent."""
from bench.scopes import call_share


def read(ctx):
    return call_share(ctx, "wavefront.box")
