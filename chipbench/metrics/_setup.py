"""Set-up's readers: sums, in seconds, of what the program's always-on
histograms (ms) held when the window opened. Absolute, not close less open:
everything before the window's first stamp is set-up. A program without a
histogram (the parent commit of the PR that added the spans) gives None,
never 0."""

BUILD = ("paddle_tpu_setup_import_ms", "paddle_tpu_setup_init_ms",
         "paddle_tpu_setup_params_create_ms",
         "paddle_tpu_setup_params_update_ms",
         "paddle_tpu_setup_trainer_prepare_ms")
ENTER = "paddle_tpu_train_enter_ms"
EXIT = "paddle_tpu_train_exit_ms"
SYNC_BACK = "paddle_tpu_train_sync_back_ms"
STEPS = ("paddle_tpu_data_feed_stall_ms", "paddle_tpu_train_dispatch_ms",
         "paddle_tpu_train_readback_ms", "paddle_tpu_train_handler_ms")


def total_s(ctx, names):
    """Seconds in all of `names` at the window's opening, or None where
    the program has none of them; one that observed nothing yet (no user
    call of `update_from`) counts 0 beside the others."""
    held = [ctx["registry_open"][n]["sum"] for n in names
            if n in ctx["registry_open"]]
    return sum(held) / 1e3 if held else None


def build_s(ctx):
    return total_s(ctx, BUILD)


def train_call_s(ctx):
    """What the `train` calls spent outside their steps: their ways in
    and out, less the `sync_back`s that lie inside the ways out."""
    around, sync = total_s(ctx, (ENTER, EXIT)), total_s(ctx, (SYNC_BACK,))
    if around is None or sync is None:
        return None
    return around - sync


def sync_back_s(ctx):
    return total_s(ctx, (SYNC_BACK,))


def steps_s(ctx):
    """The step thread's four phases of every step finalized before the
    window."""
    return total_s(ctx, STEPS)


ROWS = (build_s, train_call_s, sync_back_s, steps_s)
