"""1 - union of operation intervals / traced window, on the fullest-loaded
device."""
from benchmark import trace_reduce as tr


def read(ctx):
    if ctx["trace"] is None:
        return None
    return tr.idle_share(ctx["trace"])
