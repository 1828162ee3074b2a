"""Median idle gap of the device between two dispatches of the cell's
program, from the trace's line of program executions: what the host
does between them (sampling, fetch, priority write-back; the harvest's
fetch, the ingest's count fetch, the SumTree update), as the chip sees
it. Other programs (the ingest scatter) may run inside the gap; the gap
is between the cell's dispatches."""

from chipbench.trace import median


def read(ctx):
    gaps = ctx["trace"]["gap_ms"]
    return median(gaps) if gaps else None
