"""Process start to window open: imports, device open, topology, weights,
pool, compile or cache hit, and the first three steps."""


def read(ctx):
    return ctx["setup_s"]
