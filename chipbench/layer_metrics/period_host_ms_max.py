"""The largest host-only part of one dispatch period of the window: the
period's length (one dispatch of the cell's program to the next) less
the spans in which the host is blocked on the device
(`window_spans.BLOCKING`). One stall of the host in one period shows
here and not in a mean. One reader for `period_host_ms_max.rollout` and
`period_host_ms_max.learner`."""

from chipbench import window_spans


def read(ctx):
    return window_spans.host_ms_max(window_spans.window_periods(ctx))
