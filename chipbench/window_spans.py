"""The whole window, dispatch by dispatch, on the program's own clock.

The device trace covers the window's first `trace_units` units; the
program's `SpanTracer` (`alphatriangle_tpu/telemetry/tracer.py`) covers
every dispatch, traced or not. This module cuts the tracer's records
into dispatch periods. A period runs from the begin of one anchor span
(`rollout.dispatch` in a rollout cell, `learner.dispatch` in a learner
cell) to the begin of the next, so it holds the chunk's `rollout.*`
spans and the `replay.ingest_*` spans of the same chunk, or a group's
`learner.*` spans, its priorities and the next group's samples. A period
is a real dispatch, whatever the driver calls a unit.

For each period: its length; the self time of each span name (a span's
duration less what its children cover, by the records' `id` and
`parent`); the nanoseconds under no program span; the args of its spans.
The self times and the unspanned nanoseconds of a period are its length,
to the nanosecond. Two stretches of the clock fall in no period: the
hole in which `run_window` wrote the device trace out (it lies between
two units, where `ctx["traced_s"]` after the window's first harness span
falls: it is taken off the clock before anything is cut), and whatever
follows the end of the window's last harness span. What precedes the
first anchor (a learner's first samples) is in no period either.

Pure functions over the tuples `SpanTracer.records()` returns and the
harness's `Spans.records`; `window_periods` alone touches the program,
once a run: it keeps what it cut in the context it was handed. The self
time follows the rule `telemetry/tracer.py:summarize_trace_file` uses;
the benchmark keeps its own copy and imports no arithmetic of the
program's. Read by `layer_metrics/`: `chunk_wait_ms`, `chunk_host_ms`,
`full_moves_per_chunk`, `full_moves_per_chunk.traced`,
`wait_ms_per_fast_move`, `wait_ms_per_full_move`, `wait_residual_ms_max`
(the rollout's; the last five read the `full_moves` a `rollout.fold`
carries), `group_wait_ms`, `group_dispatch_ms` (the learner's), and one
reader for both anchors behind each of `unspanned_ms.*`,
`period_host_ms_max.*`, `host_unblocked_share.*`.
"""

import bisect

ROLLOUT_ANCHOR = "rollout.dispatch"
LEARNER_ANCHOR = "learner.dispatch"
# The spans in which the host does nothing but wait for the device.
BLOCKING = ("rollout.wait", "replay.ingest_wait", "learner.wait")

# A record of the tracer: (kind, name, begin_ns, duration_ns, thread id,
# thread name, args, id, parent id); kind "X" is a complete span.
KIND, NAME, BEGIN, DURATION, THREAD, THREAD_NAME, ARGS, ID, PARENT = range(9)


def pause_hole(harness: list, traced_s: "float | None"):
    """(begin, end) of the stretch between two units in which the trace
    was written out, from the harness's (name, start, end) records of
    the window: `traced_s` after the first record's start lies in it.
    None when nothing was traced or no unit followed the traced ones."""
    if traced_s is None or not harness:
        return None
    at = harness[0][1] + int(traced_s * 1e9)
    ended = [end for _, _, end in harness if end <= at]
    if not ended:
        return None
    begin = max(ended)
    later = [start for _, start, _ in harness if start >= begin]
    return (begin, min(later)) if later else None


def _off_the_clock(t: int, hole) -> int:
    """`t` on a clock that stands still inside the hole."""
    if hole is None or t <= hole[0]:
        return t
    return hole[0] if t < hole[1] else t - (hole[1] - hole[0])


def periods(records: list, anchor: str, end_ns: int, hole=None) -> list[dict]:
    """The dispatch periods of a window that ends at `end_ns`, from the
    first span named `anchor` on; spans of the anchors' thread only."""
    anchors = sorted(
        (
            r for r in records
            if r[KIND] == "X" and r[NAME] == anchor and r[BEGIN] < end_ns
        ),
        key=lambda r: r[BEGIN],
    )
    if not anchors:
        return []
    end = _off_the_clock(end_ns, hole)
    spans = []  # (begin, stop, name, id, parent, args), the hole taken out
    for r in records:
        if (
            r[KIND] == "X"
            and r[THREAD] == anchors[0][THREAD]
            and anchors[0][BEGIN] <= r[BEGIN] < end_ns
        ):
            spans.append(
                (
                    _off_the_clock(r[BEGIN], hole),
                    min(end, _off_the_clock(r[BEGIN] + r[DURATION], hole)),
                    r[NAME], r[ID], r[PARENT], r[ARGS],
                )
            )
    name_of = {s[3]: s[2] for s in spans}
    cuts = [_off_the_clock(a[BEGIN], hole) for a in anchors] + [end]
    out = [
        {
            "begin_ns": a[BEGIN],
            "length_ns": stop - start,
            "self_ns": {},
            "unspanned_ns": stop - start,
            "args": {},
        }
        for a, start, stop in zip(anchors, cuts, cuts[1:])
    ]
    for begin, finish, name, _, parent, span_args in spans:
        at = bisect.bisect_right(cuts, begin) - 1  # the period it begins in
        if span_args and name not in out[at]["args"]:
            out[at]["args"][name] = span_args
        while at < len(out) and cuts[at] < finish:
            period, self_ns = out[at], out[at]["self_ns"]
            inside = min(cuts[at + 1], finish) - max(cuts[at], begin)
            at += 1
            if inside <= 0:
                continue
            self_ns[name] = self_ns.get(name, 0) + inside
            if parent in name_of:  # the parent loses what its child covers
                up = name_of[parent]
                self_ns[up] = self_ns.get(up, 0) - inside
            else:
                period["unspanned_ns"] -= inside
    return out


def window_periods(ctx: dict) -> "list[dict] | None":
    """The periods of the window `ctx` describes, from the program's
    default tracer, cut at whichever anchor began in it (a cell runs one
    of the two programs); None where the program has no tracer, the
    window has no harness span or no anchor began in it. A period is
    `traced` where it began within `ctx["traced_s"]` of the window's
    start: the device trace saw it. Cut once a context: the readers
    share what the first of them found."""
    if "window_periods" not in ctx:
        ctx["window_periods"] = _cut(ctx)
    return ctx["window_periods"]


def _cut(ctx: dict) -> "list[dict] | None":
    try:
        from alphatriangle_tpu.telemetry.tracer import default_tracer
    except ImportError:
        return None
    harness = ctx["spans"].records[ctx["span_mark"]:]
    if not harness:
        return None
    since = harness[0][1]
    end = max(end for _, _, end in harness)
    records = [r for r in default_tracer().records() if r[BEGIN] >= since]
    traced_s = ctx.get("traced_s")
    hole = pause_hole(harness, traced_s)
    traced_end = since + int((traced_s or 0.0) * 1e9)
    for anchor in (ROLLOUT_ANCHOR, LEARNER_ANCHOR):
        found = periods(records, anchor, end, hole)
        if found:
            for period in found:
                period["traced"] = period["begin_ns"] < traced_end
            return found
    return None


def blocked_ns(period: dict) -> int:
    """Nanoseconds of the period the host spent waiting for the device."""
    return sum(period["self_ns"].get(name, 0) for name in BLOCKING)


def mean_self_ms(found: "list[dict] | None", names) -> "float | None":
    """Mean milliseconds a period under the spans `names`; None where
    there is no period or no such span in any."""
    if not found or not any(n in p["self_ns"] for p in found for n in names):
        return None
    total = sum(p["self_ns"].get(n, 0) for p in found for n in names)
    return total / 1e6 / len(found)


def mean_unspanned_ms(found: "list[dict] | None") -> "float | None":
    """Mean milliseconds a period under no span of the program."""
    if not found:
        return None
    return sum(p["unspanned_ns"] for p in found) / 1e6 / len(found)


def host_ms_max(found: "list[dict] | None") -> "float | None":
    """The largest host-only part of a period: its length less the
    spans in which the host is blocked on the device."""
    if not found:
        return None
    return max(p["length_ns"] - blocked_ns(p) for p in found) / 1e6


def host_unblocked_share(found: "list[dict] | None") -> "float | None":
    """Per cent of the periods' time in which the host was not blocked
    on the device (`BLOCKING`), over the whole window. It stands beside
    the trace's `device_idle_share.*` and is not that share: the device
    idles inside a blocking span while its results travel to the host
    (2.5 ms a gap in `flagship-rollout`, counted here as blocked), and
    runs inside `*.dispatch` from the moment the call is queued (1 to
    3.5 ms, counted here as the host's). Work moved between a dispatch
    and its wait moves this number and not the device's idle time."""
    if not found:
        return None
    length = sum(p["length_ns"] for p in found)
    return 100.0 * (1.0 - sum(blocked_ns(p) for p in found) / length)


def full_moves(found: "list[dict] | None", traced_only=False) -> "float | None":
    """Mean full searches a dispatch, from the `full_moves` its
    `rollout.fold` carries: over the window, or over the periods the
    device trace saw. None where a fold carries none, or none is meant."""
    meant = [p for p in found or [] if p["traced"] or not traced_only]
    counts = [p["args"].get("rollout.fold", {}).get("full_moves") for p in meant]
    if not counts or None in counts:
        return None
    return sum(counts) / len(counts)


def move_costs(found: "list[dict] | None") -> "dict | None":
    """A dispatch's `rollout.wait` as fast moves x `fast_ms` + full
    moves x `full_ms`, by least squares over the window's periods (a
    dispatch of `t` moves, `full_moves` of them full searches), and the
    widest gap any period leaves to that line, `residual_ms`: one
    stalled wait shows there. None where a fold carries no `full_moves`
    or the window has one kind of dispatch only."""
    rows = []
    for p in found or []:
        fold = p["args"].get("rollout.fold", {})
        if "full_moves" not in fold or "t" not in fold:
            return None
        full = fold["full_moves"]
        rows.append((fold["t"] - full, full, p["self_ns"].get("rollout.wait", 0) / 1e6))
    ff = sum(fast * fast for fast, _, _ in rows)
    fu = sum(fast * full for fast, full, _ in rows)
    uu = sum(full * full for _, full, _ in rows)
    det = ff * uu - fu * fu
    if not det:
        return None
    wf = sum(wait * fast for fast, _, wait in rows)
    wu = sum(wait * full for _, full, wait in rows)
    fast_ms, full_ms = (wf * uu - wu * fu) / det, (wu * ff - wf * fu) / det
    return {
        "fast_ms": fast_ms,
        "full_ms": full_ms,
        "residual_ms": max(
            abs(wait - fast * fast_ms - full * full_ms) for fast, full, wait in rows
        ),
    }
