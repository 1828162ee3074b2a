"""Host milliseconds per group in the replay layer: the K SumTree
samples and the K priority write-backs (harness spans, whole window)."""


def read(ctx):
    spans, mark = ctx["spans"], ctx["span_mark"]
    seconds = spans.seconds("sample", mark) + spans.seconds("priorities", mark)
    return 1e3 * seconds / ctx["units"]
