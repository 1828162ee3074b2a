"""Milliseconds of `rollout.wait` a fast-search move of all lanes
costs: with `wait_ms_per_full_move`, the least-squares line through the
window's dispatches (`window_spans.move_costs`). What a change to the
search or the net moves, whatever mix of moves the engine's key deals."""

from chipbench import window_spans


def read(ctx):
    costs = window_spans.move_costs(window_spans.window_periods(ctx))
    return costs and costs["fast_ms"]
