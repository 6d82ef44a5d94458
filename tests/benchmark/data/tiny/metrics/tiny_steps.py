"""A metric a later PR might add: steps completed in the window."""


def read(ctx):
    return ctx["window"]["steps"]
