"""The whole step's share of the chip's bf16 peak: learner steps of the
window x the FLOP a step needs (`chipbench/flops.py`) over the window's
seconds and the peak (`chipbench/peaks.json`)."""


def read(ctx):
    if not ctx["peak"]:
        return None
    achieved = ctx["work"] * ctx["counters"]["step_flops"] / ctx["window_s"]
    return 100.0 * achieved / (ctx["peak"]["bf16_tflops"] * 1e12)
