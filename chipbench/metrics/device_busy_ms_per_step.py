"""Union of the operation intervals on the busiest device in the traced
stretch, over the steps the handler counted in it."""


def read(ctx):
    if ctx["trace"] is None or not ctx["traced_steps"]:
        return None
    return ctx["trace"]["busiest_busy_s"] * 1e3 / ctx["traced_steps"]
