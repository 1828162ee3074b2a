"""Full searches a dispatch (the `full_moves` its `rollout.fold`
carries), mean over every dispatch of the window: the engine's key
deals them, and a chunk's time follows them (`wait_ms_per_full_move`).
None on a program whose fold counts none."""

from chipbench import window_spans


def read(ctx):
    return window_spans.full_moves(window_spans.window_periods(ctx))
