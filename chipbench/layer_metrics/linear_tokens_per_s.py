"""Tokens the linear-attention layers' recurrence took a second of
window: the program's own count in each harvest
(`trace["linear_tokens"]`, tokens x linear layers of every evaluation),
summed over the window's dispatches. A program that sows no such
counter gives nothing to read."""


def read(ctx):
    tokens = ctx["counters"].get("linear_tokens")
    if tokens is None:
        return None
    return tokens / ctx["window_s"]
