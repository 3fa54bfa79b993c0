"""Set-up's time in backend compiles (XLA's compile, or on a cache hit the
retrieval and load): the sum of `paddle_tpu_compile_backend_ms` when the
window opened, each JAX event's self time. Of which: it lies inside the five
rows that add up to `setup_s` (the harness's own compiles inside
`setup_unattributed_s`) and is no row beside them."""

from chipbench.metrics import _setup


def read(ctx):
    return _setup.total_s(ctx, ("paddle_tpu_compile_backend_ms",))
