"""Host milliseconds a dispatch blocked on the group's results (the
program's span `learner.wait`), mean over every dispatch of the window.
`group_device_ms` is the device's own time, over the traced ones."""

from chipbench import window_spans


def read(ctx):
    found = window_spans.window_periods(ctx)
    return window_spans.mean_self_ms(found, ("learner.wait",))
