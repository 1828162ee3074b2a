"""Host milliseconds per chunk in the ring's ingest up to the row count:
the scatter's dispatch (`replay.ingest_dispatch`) and the blocking fetch
of the count it returns (`replay.ingest_wait`, which is the device
running the scatter)."""

from chipbench.program_spans import span_ms


def read(ctx):
    return span_ms(ctx, ("replay.ingest_dispatch", "replay.ingest_wait"))
