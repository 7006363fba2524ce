"""Share of the traced window in which no operation ran on the device,
in percent (``trace_reduce``)."""


def read(ctx):
    trace = ctx["trace"]
    return None if trace is None else 100.0 * trace["idle_share"]
