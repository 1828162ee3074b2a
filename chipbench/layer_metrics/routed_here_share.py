"""Share of all token-expert assignments the router made over the window
that fell on the experts held here, in percent: held / all experts (12.5
for 16 of 128) if routing were even."""


def read(ctx):
    tokens, routed = (ctx["counters"].get(k) for k in ("expert_tokens", "routed"))
    if tokens is None or not routed:
        return None
    return 100.0 * sum(map(sum, tokens)) / routed
