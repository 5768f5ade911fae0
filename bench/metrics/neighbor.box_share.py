"""Share of the nearest calls' device time in the neighbour search's box
stage: the operations under the ``neighbor.box`` named scope (child-box
gathers and the point-box test, ``core/neighbor.py``) over the device-busy
time inside the calls' ``bench.call`` spans, in percent.  A program
without the scope reads 0 there and gives no reading."""
from bench.scopes import call_share


def read(ctx):
    return call_share(ctx, "neighbor.box") or None
