"""Programs the process asked the compile cache for between the window's
opening and its close: `compile_cache.stats()["requests"]`, after less
before. Should read 0: every shape was warmed up in set-up."""


def read(ctx):
    return float(ctx["compiles_close"]["requests"]
                 - ctx["compiles_open"]["requests"])
