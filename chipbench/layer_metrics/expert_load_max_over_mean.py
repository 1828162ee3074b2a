"""How uneven the held experts' load was over the window: the busiest
held expert's tokens over the mean of its layer's held experts, in the
worst sparse layer. The search decides which boards are evaluated, so
the token mix is its doing; 1 would be even."""


def read(ctx):
    tokens = ctx["counters"].get("expert_tokens")
    if not tokens or not all(sum(layer) for layer in tokens):
        return None
    return max(max(layer) * len(layer) / sum(layer) for layer in tokens)
