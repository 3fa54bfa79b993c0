"""Set-up's time tracing functions to jaxprs: the sum of
`paddle_tpu_compile_trace_ms` when the window opened, each JAX event's self
time, so an inner trace does not count twice. Of which: it lies inside the
five rows that add up to `setup_s` (the harness's own compiles inside
`setup_unattributed_s`) and is no row beside them."""

from chipbench.metrics import _setup


def read(ctx):
    return _setup.total_s(ctx, ("paddle_tpu_compile_trace_ms",))
