"""Host milliseconds per chunk in the ring's ingest after the row count
is known: the SumTree's `update_batch` over the new rows and the ring's
pointer bookkeeping (the program's span `replay.tree_update`)."""

from chipbench.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, ("replay.tree_update",))
