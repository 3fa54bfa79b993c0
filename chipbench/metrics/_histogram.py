"""Mean of a registry histogram's observations made inside the window."""


def mean_in_window(ctx, name):
    before = ctx["registry_open"].get(name, {"count": 0, "sum": 0.0})
    after = ctx["registry_close"].get(name)
    if after is None or after["count"] <= before["count"]:
        return None
    return (after["sum"] - before["sum"]) / (after["count"] - before["count"])
