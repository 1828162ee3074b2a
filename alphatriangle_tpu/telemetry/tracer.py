"""Host-side span tracer: begin/end spans -> Chrome/Perfetto trace.json.

`PhaseTimers` (profiling.py) answers "how much time did phase X take
over the whole run" — a lossy mean that cannot say *where* a specific
stall happened. The tracer keeps the individual spans: every rollout
chunk, sample, learner dispatch/finish, weight sync and checkpoint is
recorded with its begin/end and thread id, ring-buffered in memory
(O(1) append under a lock, no IO on the hot path) and exported as
Chrome trace events into the run dir.

Spans are stamped on the monotonic clock (`time.perf_counter_ns`); one
`wall - perf` offset taken at construction turns them into epoch time
at export, so `trace.json` and the cross-process merge
(`telemetry/merge.py`) still see wall-clock microseconds. Every span
also enters `jax.profiler.TraceAnnotation("at:" + name)`: a flag test
while no profiler session is open, and while one is open (`--profile`,
the benchmark's `--trace 1`) the span lands in the xplane's host planes
on the profiler's own clock, beside the device's `XLA Ops`.

One tracer per process is the default (`default_tracer()`): components
built without a `RunTelemetry` (the benchmark's drivers, tests) record
into it; `RunTelemetry` installs its own with `set_default_tracer`.

Load `trace.json` in chrome://tracing or https://ui.perfetto.dev, or
summarize it in-terminal with `alphatriangle-tpu trace <run>`.
"""

import itertools
import json
import logging
import os
import threading
import time
from collections import defaultdict, deque
from contextlib import contextmanager
from pathlib import Path

logger = logging.getLogger(__name__)

# A span record: (kind, name, begin_ns, duration_ns, thread_id,
# thread_name, args-or-None, id, parent_id). `kind` "X" (complete span)
# or "i" (instant event, duration 0) per the Chrome trace event format;
# begin_ns is on the monotonic clock; parent_id 0 = no span was open on
# the recording thread.
_COMPLETE = "X"
_INSTANT = "i"

# The program's spans in the profiler's trace carry this prefix, so a
# reader tells them from the profiler's own events and the harness's.
ANNOTATION_PREFIX = "at:"

_annotation = None


def _trace_annotation(name: str):
    """`jax.profiler.TraceAnnotation`, imported on first use: this
    module is read by CLI paths that never import JAX."""
    global _annotation
    if _annotation is None:
        from jax.profiler import TraceAnnotation

        _annotation = TraceAnnotation
    return _annotation(ANNOTATION_PREFIX + name)


class SpanTracer:
    """Thread-aware ring buffer of named spans on the monotonic clock.

    Ingestion is two clock reads plus one deque append under a lock —
    safe from any thread (rollout producers, the learner/consumer, the
    watchdog) and cheap enough to run always-on. The ring bounds memory:
    a multi-day run keeps the most recent `capacity` spans, which is
    exactly the window that matters when diagnosing where it stalled.
    """

    def __init__(self, capacity: int = 65536, enabled: bool = True) -> None:
        self.enabled = enabled
        self._lock = threading.Lock()
        self._spans: deque = deque(maxlen=max(1, capacity))
        self.recorded = 0  # total ever recorded (ring may have evicted)
        # The wall clock steps; spans are stamped on the monotonic one
        # and `export` adds this back.
        self.wall_offset_ns = time.time_ns() - time.perf_counter_ns()
        self._ids = itertools.count(1)  # next() is atomic under the GIL
        self._open = threading.local()  # per-thread stack of open span ids

    # --- ingestion (any thread, O(1)) ---------------------------------

    def _open_stack(self) -> list:
        stack = getattr(self._open, "stack", None)
        if stack is None:
            stack = self._open.stack = []
        return stack

    def _record(self, kind, name, t0, dur, args, span_id=None) -> None:
        """Append one record; its parent is the span open on this
        thread (a finished `span` has popped itself already)."""
        stack = self._open_stack()
        thread = threading.current_thread()
        with self._lock:
            self._spans.append(
                (kind, name, t0, dur, thread.ident, thread.name,
                 args or None, span_id or next(self._ids),
                 stack[-1] if stack else 0)
            )
            self.recorded += 1

    @contextmanager
    def span(self, name: str, **args):
        """Record one complete span around the with-body, as a child of
        the span open on this thread. Yields the span's args, so a count
        known only at the end (rows written) can be added inside."""
        if not self.enabled:
            yield args
            return
        stack = self._open_stack()
        span_id = next(self._ids)
        stack.append(span_id)
        t0 = time.perf_counter_ns()
        try:
            with _trace_annotation(name):
                yield args
        finally:
            dur = time.perf_counter_ns() - t0
            stack.pop()
            self._record(_COMPLETE, name, t0, dur, args, span_id)

    def complete(
        self, name: str, begin_ns: int, end_ns: int, **args
    ) -> None:
        """Record a complete span from explicit wall timestamps — for
        spans whose begin was captured earlier than the code that
        finishes them (e.g. a serve replica records the whole episode
        span at finish, begin captured at request arrival). Duration is
        clamped non-negative so a torn clock can't corrupt the trace."""
        if not self.enabled:
            return
        self._record(
            _COMPLETE,
            name,
            int(begin_ns) - self.wall_offset_ns,
            max(0, int(end_ns) - int(begin_ns)),
            args,
        )

    def instant(self, name: str, **args) -> None:
        """Record a zero-duration marker (e.g. a watchdog stall)."""
        if not self.enabled:
            return
        self._record(_INSTANT, name, time.perf_counter_ns(), 0, args)

    # --- export -------------------------------------------------------

    def records(self) -> list:
        """The buffered records, oldest first (the tuple's layout is at
        the top of this module); begin_ns on `time.perf_counter_ns`."""
        with self._lock:
            return list(self._spans)

    def export(self, path: Path) -> int:
        """Write the buffered spans as a Chrome trace; returns the event
        count. Atomic (tmp + rename) so a reader never sees a torn file;
        IO failures are logged, never raised (observability is not
        allowed to kill a run)."""
        spans = self.records()
        pid = os.getpid()
        events = []
        thread_names: dict[int, str] = {}
        for kind, name, t0_ns, dur_ns, tid, tname, args, sid, parent in spans:
            thread_names.setdefault(tid, tname)
            ev = {
                "name": name,
                "ph": kind,
                # Epoch microseconds (Chrome traces use microseconds).
                "ts": (t0_ns + self.wall_offset_ns) // 1000,
                "pid": pid,
                "tid": tid,
                "cat": "host",
                "id": sid,
                "parent": parent,
            }
            if kind == _COMPLETE:
                ev["dur"] = dur_ns // 1000
            else:
                ev["s"] = "g"  # global-scope instant
            if args:
                ev["args"] = args
            events.append(ev)
        meta = [
            {
                "name": "thread_name",
                "ph": "M",
                "pid": pid,
                "tid": tid,
                "args": {"name": tname},
            }
            for tid, tname in sorted(thread_names.items())
        ]
        payload = {
            "traceEvents": meta + events,
            "displayTimeUnit": "ms",
            "otherData": {"recorded": self.recorded, "exported": len(events)},
        }
        try:
            path = Path(path)
            path.parent.mkdir(parents=True, exist_ok=True)
            tmp = path.with_suffix(".json.tmp")
            tmp.write_text(json.dumps(payload))
            tmp.replace(path)
        except OSError:
            logger.exception("span trace export to %s failed", path)
            return 0
        if self.recorded > len(spans):
            logger.info(
                "span trace: ring kept the newest %d of %d spans.",
                len(spans),
                self.recorded,
            )
        return len(events)


# --- process-wide default ---------------------------------------------------

_default_tracer: SpanTracer | None = None
_default_lock = threading.Lock()


def default_tracer() -> SpanTracer:
    """The tracer the program's own spans go to: the one a
    `RunTelemetry` installed, else a process-wide ring made on first
    use (the benchmark's drivers and the tests build components with no
    `RunTelemetry`)."""
    global _default_tracer
    with _default_lock:
        if _default_tracer is None:
            _default_tracer = SpanTracer()
        return _default_tracer


def set_default_tracer(tracer: SpanTracer) -> SpanTracer:
    """Install `tracer` as the process-wide default; returns it."""
    global _default_tracer
    with _default_lock:
        _default_tracer = tracer
        return tracer


def summarize_trace_file(path: Path, top: int = 20) -> list[dict]:
    """Aggregate a `trace.json` (this tracer's or any Chrome trace) into
    per-name rows, busiest first. `self_ms` is a name's time less what
    its spans' children cover (an event's `parent` names its parent's
    `id`, as `export` writes them; events without them are their own
    time). Accepts both the object form ({"traceEvents": [...]}) and
    the bare-array form. Raises OSError / ValueError on unreadable
    input — the CLI maps that to exit 1."""
    data = json.loads(Path(path).read_text())
    events = data.get("traceEvents", []) if isinstance(data, dict) else data
    total_us: dict[str, float] = defaultdict(float)
    self_us: dict[str, float] = defaultdict(float)
    max_us: dict[str, float] = defaultdict(float)
    count: dict[str, int] = defaultdict(int)
    threads: dict[str, set] = defaultdict(set)
    spans = [
        ev for ev in events
        if isinstance(ev, dict) and ev.get("ph") == _COMPLETE
    ]
    name_of = {  # ids are one process's own
        (ev.get("pid"), ev["id"]): ev.get("name", "?")
        for ev in spans
        if "id" in ev
    }
    for ev in spans:
        name = ev.get("name", "?")
        dur = float(ev.get("dur", 0))
        total_us[name] += dur
        self_us[name] += dur
        parent = (ev.get("pid"), ev.get("parent"))
        if parent in name_of:  # the parent loses its child's time
            self_us[name_of[parent]] -= dur
        max_us[name] = max(max_us[name], dur)
        count[name] += 1
        threads[name].add(ev.get("tid"))
    rows = [
        {
            "name": name,
            "count": count[name],
            "total_ms": total_us[name] / 1e3,
            "self_ms": self_us[name] / 1e3,
            "mean_ms": total_us[name] / 1e3 / max(count[name], 1),
            "max_ms": max_us[name] / 1e3,
            "threads": len(threads[name]),
        }
        for name in total_us
    ]
    rows.sort(key=lambda r: r["total_ms"], reverse=True)
    return rows[:top]
