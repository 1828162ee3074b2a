"""Per cent of the window's dispatch periods in which the host was not
blocked on the device. It stands beside `device_idle_share.*`, which a
device trace of the first units gives, and is biased against it both
ways (`window_spans.host_unblocked_share` says how). One reader for
`host_unblocked_share.rollout` and `host_unblocked_share.learner`."""

from chipbench import window_spans


def read(ctx):
    return window_spans.host_unblocked_share(window_spans.window_periods(ctx))
