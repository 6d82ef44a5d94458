"""Mean time a step of the window waited for its batch (the optimizer's
own ``data_time`` per step)."""


def read(ctx):
    waits = ctx["window"].get("data_wait_s") or []
    if not waits:
        return None
    return 1e3 * sum(waits) / len(waits)
