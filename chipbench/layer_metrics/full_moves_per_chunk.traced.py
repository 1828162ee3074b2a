"""Full searches a dispatch over the dispatches the device trace saw
(the window's first `trace_units` units): beside `full_moves_per_chunk`
it says how far `chunk_device_ms`, `host_gap_ms.rollout`,
`device_idle_share.rollout` and `breakdown` sample lighter or heavier
chunks than the window's mean."""

from chipbench import window_spans


def read(ctx):
    found = window_spans.window_periods(ctx)
    return window_spans.full_moves(found, traced_only=True)
