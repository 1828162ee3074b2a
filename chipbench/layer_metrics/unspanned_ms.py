"""Milliseconds of a dispatch period under no span of the program (the
driver's own code between the calls, a copy it dispatches), mean over
the window's periods from one dispatch of the cell's program to the
next. One reader for `unspanned_ms.rollout` and `unspanned_ms.learner`."""

from chipbench import window_spans


def read(ctx):
    return window_spans.mean_unspanned_ms(window_spans.window_periods(ctx))
