"""Token-expert products the held experts computed a second of window:
the program's own count in each harvest (`trace["expert_tokens"]`, the
grouped product's group sizes), summed over the window's dispatches."""


def read(ctx):
    tokens = ctx["counters"].get("expert_tokens")
    if tokens is None:
        return None
    return sum(map(sum, tokens)) / ctx["window_s"]
