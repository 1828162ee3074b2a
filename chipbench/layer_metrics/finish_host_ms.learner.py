"""Host milliseconds per group inside `Trainer.train_steps_finish` after
the group's results have arrived (the program's span `learner.results`:
the per-step `float(...)` loop and the learning-rate schedule's calls).
The device is idle all through it; the wait for the device is the span
before it, `learner.wait`, and is not counted."""

from chipbench.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, ("learner.results",))
