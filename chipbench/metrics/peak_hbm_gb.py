"""`memory_stats()` of the fullest device after the window, before the
reference runs: `peak_bytes_in_use + peak_bytes_reserved` (live buffers
and the programs' reserved temporaries are separate pools), in 1e9 bytes."""


def read(ctx):
    return ctx["peak_bytes"] / 1e9 if ctx["peak_bytes"] else None
