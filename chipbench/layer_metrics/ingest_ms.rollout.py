"""Host milliseconds per chunk around `ingest_payload`: the scatter's
dispatch, the fetch of its row count, the SumTree update."""


def read(ctx):
    return 1e3 * ctx["spans"].seconds("ingest", ctx["span_mark"]) / ctx["units"]
