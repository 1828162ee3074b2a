"""The whole rollout's share of the chip's bf16 peak: (leaf + root)
evaluations x the FLOP of a forward pass over the window and the peak.
Tree work is overhead, not counted."""


def read(ctx):
    if not ctx["peak"]:
        return None
    evaluations = ctx["counters"]["simulations"] + ctx["work"]
    achieved = evaluations * ctx["counters"]["forward_flops"] / ctx["window_s"]
    return 100.0 * achieved / (ctx["peak"]["bf16_tflops"] * 1e12)
