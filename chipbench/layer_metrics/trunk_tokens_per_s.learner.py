"""Tokens x trunk layers the learner's forward passes took a second of
window (`Trainer.last_counters["trunk_tokens"]`: a step's rows x the
board's cells x the stack's layers, summed over the window)."""


def read(ctx):
    tokens = ctx["counters"].get("trunk_tokens")
    if tokens is None:
        return None
    return tokens / ctx["window_s"]
