"""Share of the nearest calls' device time in top-k: the operations under
the ``nearest.topk`` named scope (``select_topk`` and the result's
epilogue, ``core/session.py``) over the device-busy time inside the calls'
``bench.call`` spans, in percent."""
from bench.scopes import call_share


def read(ctx):
    return call_share(ctx, "nearest.topk")
