"""The widest gap between one dispatch's `rollout.wait` and what its
fast and full moves cost by the window's own line
(`window_spans.move_costs`): under a millisecond where a chunk's time is
its work; one stalled wait (the cell's slow mode) shows here, where
`period_host_ms_max.rollout` sees the host's stalls only."""

from chipbench import window_spans


def read(ctx):
    costs = window_spans.move_costs(window_spans.window_periods(ctx))
    return costs and costs["residual_ms"]
