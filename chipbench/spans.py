"""Host spans around the calls into each layer.

Kept in memory on the host's monotonic clock, and, while a device trace
is being taken, written into the profiler's trace as well
(`jax.profiler.TraceAnnotation`), so that `trace.py` can put an idle
gap of the device beside what the host was doing in it.
"""

import contextlib
import time

PREFIX = "chipbench:"


class Spans:
    def __init__(self):
        self.records: list[tuple[str, int, int]] = []  # name, start, end (ns)
        self.annotate = False

    @contextlib.contextmanager
    def span(self, name: str):
        annotation = contextlib.nullcontext()
        if self.annotate:
            import jax

            annotation = jax.profiler.TraceAnnotation(PREFIX + name)
        start = time.perf_counter_ns()
        try:
            with annotation:
                yield
        finally:
            self.records.append((name, start, time.perf_counter_ns()))

    def mark(self) -> int:
        """A position in the records: `since(mark)` is what came after."""
        return len(self.records)

    def seconds(self, name: str, since: int = 0) -> float:
        return sum(
            (end - start) / 1e9
            for n, start, end in self.records[since:]
            if n == name
        )
