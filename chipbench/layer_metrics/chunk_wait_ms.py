"""Host milliseconds a dispatch blocked on the chunk (the program's
span `rollout.wait`: the fetch of the chunk's small harvest, which ends
when the device has run the chunk), mean over every dispatch of the
window, traced or not. `chunk_device_ms` is the same time as the device
saw it, over the traced dispatches only."""

from chipbench import window_spans


def read(ctx):
    found = window_spans.window_periods(ctx)
    return window_spans.mean_self_ms(found, ("rollout.wait",))
