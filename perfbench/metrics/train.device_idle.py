"""Share of the traced training window in which no operation ran on the
device."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else trace.idle_pct
