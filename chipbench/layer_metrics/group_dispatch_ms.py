"""Host milliseconds a dispatch handing the group to the device (the
program's span `learner.dispatch`: stacking the samples' slot numbers
and weights, the call); mean over every dispatch of the window."""

from chipbench import window_spans


def read(ctx):
    found = window_spans.window_periods(ctx)
    return window_spans.mean_self_ms(found, ("learner.dispatch",))
