"""Device milliseconds of one dispatch of the cell's program (a rollout
chunk, a fused group): mean length of that program's executions in the
traced part of the window."""


def read(ctx):
    runs = ctx["trace"]["dispatch_ms"]
    return sum(runs) / len(runs) if runs else None
