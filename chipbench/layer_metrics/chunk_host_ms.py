"""Host milliseconds a dispatch in the engine's own code around the
chunk: handing it to the device (`rollout.dispatch`) and folding the
harvest it fetched (`rollout.fold`); mean over the whole window."""

from chipbench import window_spans


def read(ctx):
    found = window_spans.window_periods(ctx)
    return window_spans.mean_self_ms(found, ("rollout.dispatch", "rollout.fold"))
