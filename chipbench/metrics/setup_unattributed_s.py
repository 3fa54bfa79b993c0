"""`setup_s` less the program's four rows (`setup_build_s`,
`setup_train_call_s`, `setup_sync_back_s`, `setup_steps_s`): the
interpreter and the imports, the device's opening where the harness does it,
and the harness's own work between the program's calls (the reference's
initial weights, the pool, two snapshots of the parameters)."""

from chipbench.metrics import _setup


def read(ctx):
    rows = [row(ctx) for row in _setup.ROWS]
    if any(value is None for value in rows):
        return None
    return ctx["setup_s"] - sum(rows)
