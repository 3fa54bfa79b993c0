"""Collective time on the busiest device in which no other operation ran
there, per traced step. Nothing where the trace holds no collective."""


def read(ctx):
    trace = ctx["trace"]
    if trace is None or not ctx["traced_steps"] \
            or trace["collective_s"] <= 0:
        return None
    return trace["collective_exposed_s"] * 1e3 / ctx["traced_steps"]
