"""Tokens the state-space layers' scan took a second of window: the
program's own count in each harvest (`trace["ssm_tokens"]`, tokens x
state-space layers of every evaluation), summed over the window's
dispatches. A program that sows no such counter gives nothing to read."""


def read(ctx):
    tokens = ctx["counters"].get("ssm_tokens")
    if tokens is None:
        return None
    return tokens / ctx["window_s"]
