"""Token-expert products the held experts computed a second of window,
in the forward passes of the learner's steps (the backward's and the
recomputed forward's are the same assignments again and are not
counted): the program's own count on each group's fetch
(`Trainer.last_counters["expert_tokens"]`, the grouped product's group
sizes), summed over the window by the driver. None where the driver
has no such counter (a learner cell without routers)."""


def read(ctx):
    tokens = ctx["counters"].get("expert_tokens")
    if tokens is None:
        return None
    return sum(map(sum, tokens)) / ctx["window_s"]
