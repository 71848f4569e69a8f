"""Operator layer: executions of compiled programs on the device (events of
the trace's 'XLA Modules' line) per query of the traced window; a request
counts by the share of its wall that lies inside that window."""


def read(ctx):
    tr = ctx["trace"]
    if not tr or not tr["queries"]:
        return None
    return tr["launches"] / tr["queries"]
