"""What the process built before its first `train` call: the program's spans
`import` (the first use of each `paddle.<submodule>`), `init`,
`params_create`, `params_update` (the harness's one overwrite of every
leaf) and `trainer_prepare`, their histograms' sums when the window
opened."""

from chipbench.metrics import _setup


def read(ctx):
    return _setup.build_s(ctx)
