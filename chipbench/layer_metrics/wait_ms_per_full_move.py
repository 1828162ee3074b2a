"""Milliseconds of `rollout.wait` a full-search move of all lanes
costs: see `wait_ms_per_fast_move`."""

from chipbench import window_spans


def read(ctx):
    costs = window_spans.move_costs(window_spans.window_periods(ctx))
    return costs and costs["full_ms"]
