"""Share of the traced window in which a collective runs and no compute
does, on the worst device."""
from benchmark import trace_reduce as tr


def read(ctx):
    if ctx["trace"] is None:
        return None
    share = tr.collective_exposed_share(ctx["trace"])
    if share is None and ctx["chips"] > 1:
        raise LookupError("no collective operation in a trace of "
                          f"{ctx['chips']} chips")
    return share
