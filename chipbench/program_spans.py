"""The program's own spans, for the per-layer readers.

The program keeps them in its default `SpanTracer`
(`alphatriangle_tpu/telemetry/tracer.py`), on the clock the harness's
`Spans` use (`time.perf_counter_ns`). A reader sums the spans of some
names that began inside the window, per dispatch. A program from before
it drew spans of its own has no default tracer: nothing to read, None.
"""


def span_ms(ctx, names) -> "float | None":
    """Milliseconds per dispatch under the spans `names`, from the
    window's first harness span on; None where there is no such span."""
    try:
        from alphatriangle_tpu.telemetry.tracer import default_tracer
    except ImportError:
        return None
    harness = ctx["spans"].records
    if len(harness) <= ctx["span_mark"]:
        return None
    since = harness[ctx["span_mark"]][1]
    durations = [
        record[3]
        for record in default_tracer().records()
        if record[1] in names and record[2] >= since
    ]
    if not durations:
        return None
    return sum(durations) / 1e6 / ctx["units"]
