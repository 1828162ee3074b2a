"""`chipbench/window_spans.py` and the readers over it, on hand-made
records: a window is cut into dispatch periods at the anchor spans, a
span's self time is its duration less its children's, the hole in which
the device trace was written out falls in no period, the last period
ends with the window, and each reader says nothing where the program
gives it nothing to read."""

import pytest

from alphatriangle_tpu.telemetry import (
    SpanTracer,
    default_tracer,
    set_default_tracer,
)
from chipbench import manifest, window_spans
from chipbench.spans import Spans

MS = 1_000_000
MAIN, OTHER_THREAD = 7, 8


def rec(
    name, begin_ms, end_ms, sid=0, parent=0, args=None, thread=MAIN, kind="X",
    more_ns=0,
):
    """One record as `SpanTracer.records()` returns it."""
    begin, end = int(begin_ms * MS), int(end_ms * MS) + more_ns
    return (kind, name, begin, end - begin, thread, "t", args, sid, parent)


# --- the pure functions -------------------------------------------------------


def test_periods_run_from_anchor_to_anchor_and_account_for_every_nanosecond():
    records = [
        rec("before", 0, 5, 1),  # began before the first anchor: in no period
        rec("rollout.dispatch", 10, 12, 2, args={"t": 16}),
        rec("rollout.wait", 12, 112, 3),
        rec("rollout.fold", 112, 115, 4, args={"full_moves": 9}),
        rec("replay.ingest_wait", 118, 121, 5, args={"rows": 3}),
        rec("rollout.dispatch", 125, 128, 6),
        rec("rollout.wait", 128, 200, 7),
        rec("mark", 150, 150, 8, kind="i"),  # an instant is no span
    ]
    first, second = window_spans.periods(records, "rollout.dispatch", 210 * MS)
    assert first["begin_ns"] == 10 * MS and first["length_ns"] == 115 * MS
    assert first["self_ns"] == {
        "rollout.dispatch": 2 * MS,
        "rollout.wait": 100 * MS,
        "rollout.fold": 3 * MS,
        "replay.ingest_wait": 3 * MS,
    }
    assert first["unspanned_ns"] == 7 * MS  # 115-118 and 121-125
    assert first["args"] == {
        "rollout.dispatch": {"t": 16},
        "rollout.fold": {"full_moves": 9},
        "replay.ingest_wait": {"rows": 3},
    }
    # The last period is cut at the window's end.
    assert second["length_ns"] == 85 * MS and second["unspanned_ns"] == 10 * MS
    for period in (first, second):
        assert (
            sum(period["self_ns"].values()) + period["unspanned_ns"]
            == period["length_ns"]
        )
    assert window_spans.blocked_ns(first) == 103 * MS


def test_self_time_is_duration_less_the_children_by_id_and_parent():
    records = [
        rec("learner.dispatch", 0, 40, 1, more_ns=1),
        rec("compile", 5, 25, 2, parent=1),
        rec("compile.lower", 6, 10, 3, parent=2),
        rec("compile.lower", 11, 13, 4, parent=2),
        rec("learner.wait", 45, 60, 5, parent=99),  # its parent began earlier
        rec("elsewhere", 0, 60, 6, thread=OTHER_THREAD),  # another thread's
    ]
    (period,) = window_spans.periods(records, "learner.dispatch", 70 * MS)
    assert period["self_ns"] == {
        "learner.dispatch": 20 * MS + 1,
        "compile": 14 * MS,
        "compile.lower": 6 * MS,
        "learner.wait": 15 * MS,
    }
    assert period["unspanned_ns"] == 70 * MS - 55 * MS - 1
    assert sum(period["self_ns"].values()) + period["unspanned_ns"] == 70 * MS


def test_a_span_across_an_anchor_is_shared_between_the_two_periods():
    """A phase span of a real run may hold several dispatches."""
    records = [
        rec("learner.dispatch", 10, 12, 1),
        rec("train", 13, 50, 2),
        rec("learner.dispatch", 30, 33, 3, parent=2),
    ]
    first, second = window_spans.periods(records, "learner.dispatch", 60 * MS)
    assert first["self_ns"] == {"learner.dispatch": 2 * MS, "train": 17 * MS}
    assert first["unspanned_ns"] == MS and first["args"] == {}
    assert second["self_ns"] == {"learner.dispatch": 3 * MS, "train": 17 * MS}
    assert second["unspanned_ns"] == 10 * MS and second["length_ns"] == 30 * MS


def test_the_hole_where_the_trace_was_written_out_is_in_no_period():
    harness = [
        ("rollout", 50 * MS, 160 * MS),
        ("ingest", 160 * MS, 170 * MS),
        ("rollout", 3172 * MS, 3290 * MS),
        ("ingest", 3290 * MS, 3300 * MS),
    ]
    # `traced_s` runs from `run_window`'s start, a little before the
    # first harness span, to the end of the traced unit.
    hole = window_spans.pause_hole(harness, 0.1205)
    assert hole == (170 * MS, 3172 * MS)
    records = [
        rec("rollout.dispatch", 51, 53, 1),
        rec("rollout.wait", 53, 153, 2),
        rec("rollout.dispatch", 3173, 3176, 3),
        rec("rollout.wait", 3176, 3280, 4),
    ]
    first, second = window_spans.periods(
        records, "rollout.dispatch", 3300 * MS, hole
    )
    assert first["length_ns"] == 120 * MS  # 51-170 and 3172-3173
    assert first["unspanned_ns"] == 18 * MS
    assert second["begin_ns"] == 3173 * MS and second["length_ns"] == 127 * MS
    # The periods' lengths are the window less the pause.
    assert first["length_ns"] + second["length_ns"] == (3300 - 51 - 3002) * MS
    # A span that straddles the hole loses what lies in it.
    (only,) = window_spans.periods(
        [rec("rollout.dispatch", 100, 3200, 1)], "rollout.dispatch", 3300 * MS, hole
    )
    assert only["self_ns"] == {"rollout.dispatch": 98 * MS}
    assert only["length_ns"] == 198 * MS


@pytest.mark.parametrize(
    "harness, traced_s, want",
    [
        ([], 1.0, None),
        ([("rollout", 0, 10 * MS)], None, None),  # nothing was traced
        ([("rollout", 0, 10 * MS)], 0.011, None),  # no unit followed
        ([("rollout", 5 * MS, 10 * MS)], 0.001, None),  # inside the first span
        (
            [("a", 0, 4 * MS), ("b", 4 * MS, 9 * MS), ("a", 40 * MS, 44 * MS)],
            0.0091,
            (9 * MS, 40 * MS),
        ),
    ],
    ids=["no-span", "untraced", "traced-to-the-end", "too-early", "between"],
)
def test_pause_hole(harness, traced_s, want):
    assert window_spans.pause_hole(harness, traced_s) == want


def test_no_anchor_no_period_and_spans_after_the_window_are_left_out():
    records = [rec("rollout.wait", 0, 5, 1), rec("learner.dispatch", 6, 7, 2)]
    assert window_spans.periods(records, "rollout.dispatch", 10 * MS) == []
    records = [
        rec("rollout.dispatch", 1, 2, 1),
        rec("rollout.dispatch", 12, 13, 2),  # the reference's, say
        rec("rollout.wait", 9, 14, 3),  # cut at the window's end
    ]
    (period,) = window_spans.periods(records, "rollout.dispatch", 10 * MS)
    assert period["length_ns"] == 9 * MS
    assert period["self_ns"] == {"rollout.dispatch": MS, "rollout.wait": MS}


def test_the_summaries_say_nothing_of_no_periods():
    for summary in (
        window_spans.mean_unspanned_ms,
        window_spans.host_ms_max,
        window_spans.host_unblocked_share,
        window_spans.full_moves,
        window_spans.move_costs,
    ):
        assert summary(None) is None and summary([]) is None
    assert window_spans.mean_self_ms(None, ("rollout.wait",)) is None


def dispatches(*rows):
    """Periods of (moves, full moves, `rollout.wait` ms, traced)."""
    return [
        {
            "traced": traced,
            "self_ns": {"rollout.wait": int(wait * MS)},
            "args": {"rollout.fold": {"t": t, "full_moves": full}},
        }
        for t, full, wait, traced in rows
    ]


@pytest.mark.parametrize(
    "rows, fast_ms, full_ms, residual_ms",
    [
        # Chunks of 16 moves on one line: 10 ms a fast move, 50 a full.
        ([(16, 2, 240, True), (16, 5, 360, False), (16, 3, 280, False)], 10, 50, 0),
        # One-move dispatches: the line is the two kinds' means.
        ([(1, 0, 18, True), (1, 1, 70, True), (1, 0, 22, True)], 20, 70, 2),
        # One stalled wait among five of 40 ms bends the line (10 ms a
        # fast move without it) and still stands 500 ms off it.
        (
            [(4, 0, 40, False)] * 5 + [(4, 4, 200, False)] * 5 + [(4, 0, 640, False)],
            35, 50, 500,
        ),
    ],
    ids=["chunks", "one-move-dispatches", "a-stall"],
)
def test_move_costs_are_the_line_through_the_dispatches(
    rows, fast_ms, full_ms, residual_ms
):
    assert window_spans.move_costs(dispatches(*rows)) == pytest.approx(
        {"fast_ms": fast_ms, "full_ms": full_ms, "residual_ms": residual_ms}
    )


def test_move_costs_need_both_kinds_of_dispatch_and_the_count():
    alike = dispatches((16, 3, 280, False), (16, 3, 281, False))
    assert window_spans.move_costs(alike) is None  # one kind: no line
    assert window_spans.full_moves(alike) == 3
    parents = dispatches((16, 2, 240, True), (16, 5, 360, False))
    del parents[1]["args"]["rollout.fold"]["full_moves"]
    assert window_spans.move_costs(parents) is None
    assert window_spans.full_moves(parents) is None


def test_full_moves_of_the_window_and_of_the_traced_dispatches():
    found = dispatches((16, 3, 0, True), (16, 2, 0, True), (16, 7, 0, False))
    assert window_spans.full_moves(found) == 4
    assert window_spans.full_moves(found, traced_only=True) == 2.5
    assert window_spans.full_moves(found[2:], traced_only=True) is None


# --- the readers, on hand-made contexts ---------------------------------------


@pytest.fixture
def tracer():
    before = default_tracer()
    fresh = set_default_tracer(SpanTracer())
    yield fresh
    set_default_tracer(before)


def put(tracer, name, begin_ms, end_ms, **args):
    """A finished span of the program at a chosen place on the clock."""
    wall = int(begin_ms * MS) + tracer.wall_offset_ns
    tracer.complete(name, wall, wall + int((end_ms - begin_ms) * MS), **args)


def rollout_ctx(tracer, counted=True):
    """Two chunks of 1 and 3 full moves, the first traced: 3,002 ms of
    trace writing between them. Periods of 120 and 127 ms."""
    spans = Spans()
    spans.records = [
        ("ingest", 10 * MS, 20 * MS),  # set-up's, before the mark
        ("rollout", 50 * MS, 160 * MS),
        ("ingest", 160 * MS, 170 * MS),
        ("rollout", 3172 * MS, 3290 * MS),
        ("ingest", 3290 * MS, 3300 * MS),
    ]
    put(tracer, "rollout.dispatch", 11, 12)  # the warm-up's
    put(tracer, "rollout.wait", 12, 19)
    chunks = [
        ((51, 53, 153, 156), (160, 161, 165, 169), 1),
        ((3173, 3176, 3280, 3284), (3290, 3291, 3297, 3299), 3),
    ]
    for (d, w, f, done), (i, iw, tu, end), full in chunks:
        put(tracer, "rollout.dispatch", d, w, t=16, lanes=512)
        put(tracer, "rollout.wait", w, f, t=16, lanes=512)
        fold = {"full_moves": full} if counted else {}
        put(tracer, "rollout.fold", f, done, t=16, lanes=512, **fold)
        put(tracer, "replay.ingest_dispatch", i, iw)
        put(tracer, "replay.ingest_wait", iw, tu, rows=7)
        put(tracer, "replay.tree_update", tu, end, rows=7)
    put(tracer, "rollout.dispatch", 3305, 3306)  # after the window
    return {"spans": spans, "span_mark": 1, "units": 2, "traced_s": 0.1205}


def learner_ctx(tracer):
    """Two groups of two steps, untraced. Periods of 110 and 102 ms; the
    first group's samples come before the first anchor."""
    spans = Spans()
    spans.records = [
        ("sample", 50 * MS, 54 * MS),
        ("dispatch", 54 * MS, 57 * MS),
        ("fetch", 57 * MS, 157 * MS),
        ("priorities", 157 * MS, 160 * MS),
        ("sample", 160 * MS, 164 * MS),
        ("dispatch", 164 * MS, 168 * MS),
        ("fetch", 168 * MS, 262 * MS),
        ("priorities", 263 * MS, 266 * MS),
    ]
    for at, (d, w, r, done), (p, q, end) in [
        (50, (54, 57, 150, 156), (157, 158.5, 160)),
        (160, (164, 168, 258, 262), (263, 265, 266)),
    ]:
        put(tracer, "replay.sample", at, at + 2)
        put(tracer, "replay.sample", at + 2, at + 4)
        put(tracer, "learner.dispatch", d, w, k=2)
        put(tracer, "learner.wait", w, r, k=2)
        put(tracer, "learner.results", r, done, k=2)
        put(tracer, "replay.priorities", p, q)
        put(tracer, "replay.priorities", q, end)
    return {"spans": spans, "span_mark": 0, "units": 2, "traced_s": None}


CONTEXTS = {"rollout": rollout_ctx, "learner": learner_ctx}
# 100 = 15 fast + 1 full and 104 = 13 fast + 3 full: 6.125 and 8.125 ms.
READERS = [
    ("chunk_wait_ms", "rollout", (100 + 104) / 2),
    ("chunk_host_ms", "rollout", (2 + 3 + 3 + 4) / 2),
    ("full_moves_per_chunk", "rollout", 2.0),
    ("full_moves_per_chunk.traced", "rollout", 1.0),
    ("wait_ms_per_fast_move", "rollout", 6.125),
    ("wait_ms_per_full_move", "rollout", 8.125),
    ("wait_residual_ms_max", "rollout", 0.0),
    ("group_wait_ms", "learner", (93 + 90) / 2),
    ("group_dispatch_ms", "learner", (3 + 4) / 2),
    ("unspanned_ms.rollout", "rollout", (6 + 7) / 2),
    ("unspanned_ms.learner", "learner", 1.0),
    ("period_host_ms_max.rollout", "rollout", 127 - 104 - 6),
    ("period_host_ms_max.learner", "learner", 110 - 93),
    ("host_unblocked_share.rollout", "rollout", 100 * (1 - 214 / 247)),
    ("host_unblocked_share.learner", "learner", 100 * (1 - 183 / 212)),
]
# One reader serves both programs behind these: the manifest's
# `workloads` keep each name to its cells.
SHARED = ("unspanned_ms", "period_host_ms_max", "host_unblocked_share")


@pytest.mark.parametrize("metric, side, value", READERS)
class TestReaders:
    def test_reads_every_period_of_the_window(self, tracer, metric, side, value):
        ctx = CONTEXTS[side](tracer)
        assert manifest.layer_reader(metric)(ctx) == pytest.approx(value, abs=1e-9)

    def test_on_a_window_of_the_other_program(self, tracer, metric, side, value):
        """A reader of one program's spans finds none of them there; a
        shared reader reads the anchor that began in the window."""
        other = "learner" if side == "rollout" else "rollout"
        read = manifest.layer_reader(metric)(CONTEXTS[other](tracer))
        if metric.rsplit(".", 1)[0] in SHARED:
            twin = f"{metric.rsplit('.', 1)[0]}.{other}"
            assert read == pytest.approx(
                next(v for name, _, v in READERS if name == twin)
            )
        else:
            assert read is None

    def test_in_the_manifest_as_a_program_span(self, metric, side, value):
        bench = manifest.benchmark()
        entry = next(m for m in bench["per_layer"] if m["name"] == metric)
        assert entry["source"] == "program_span" and len(entry["unit"]) <= 16
        rate = "selfplay_moves_per_s" if side == "rollout" else "learner_steps_per_s"
        assert entry["moves"] == rate
        reports = next(
            m["workloads"] for m in bench["end_to_end"] if m["name"] == rate
        )
        assert entry["workloads"] == reports
        for cell in entry["workloads"]:
            assert metric in [
                m["name"] for m in manifest.metrics_of(cell, trace=True)
            ]


def test_the_readers_are_all_of_this_modules_and_cut_the_window_once(
    tracer, monkeypatch
):
    """`READERS` is every entry from `chunk_wait_ms` on, and a
    context's periods are cut by the first reader alone."""
    names = [m["name"] for m in manifest.benchmark()["per_layer"]]
    assert names[names.index("chunk_wait_ms"):] == [m for m, _, _ in READERS]
    ctx, cuts = rollout_ctx(tracer), []
    cut = window_spans.periods
    monkeypatch.setattr(
        window_spans, "periods", lambda *a, **k: cuts.append(a[1]) or cut(*a, **k)
    )
    for metric, side, _ in READERS:
        if side == "rollout":
            manifest.layer_reader(metric)(ctx)
    assert cuts == [window_spans.ROLLOUT_ANCHOR]


def test_the_periods_of_a_context_close_their_own_account(tracer):
    found = window_spans.window_periods(rollout_ctx(tracer))
    assert [p["length_ns"] for p in found] == [120 * MS, 127 * MS]
    assert [p["args"]["rollout.fold"]["full_moves"] for p in found] == [1, 3]
    assert [p["traced"] for p in found] == [True, False]
    for period in found:
        assert (
            sum(period["self_ns"].values()) + period["unspanned_ns"]
            == period["length_ns"]
        )
    # The window (first anchor to the last harness span's end) less the
    # profiler's pause.
    assert sum(p["length_ns"] for p in found) == (3300 - 51 - 3002) * MS


def test_a_unit_of_several_dispatches_is_several_periods(tracer):
    """A trunk cell's unit is a playout-cap period of several one-move
    dispatches: the readers divide by the dispatches, not the units."""
    ctx = rollout_ctx(tracer)
    ctx["units"], ctx["traced_s"] = 1, None
    ctx["spans"].records[3] = ("rollout", 170 * MS, 3290 * MS)  # no hole
    found = window_spans.window_periods(ctx)
    assert len(found) == 2 and not any(p["traced"] for p in found)
    assert manifest.layer_reader("chunk_wait_ms")(ctx) == pytest.approx(102.0)
    # Traced to its end, as the trunk cells' windows are: every period.
    ctx = {**ctx, "traced_s": 3.3}
    del ctx["window_periods"]
    assert all(p["traced"] for p in window_spans.window_periods(ctx))
    assert manifest.layer_reader("full_moves_per_chunk.traced")(ctx) == 2.0


def test_a_program_whose_fold_counts_no_full_moves(tracer):
    """The parent commit's `rollout.fold` carries `t` and `lanes` alone."""
    ctx = rollout_ctx(tracer, counted=False)
    for metric, _, _ in READERS[2:7]:
        assert manifest.layer_reader(metric)(ctx) is None
    assert manifest.layer_reader("chunk_wait_ms")(ctx) == pytest.approx(102.0)


def test_none_when_the_window_has_no_harness_span(tracer):
    put(tracer, "rollout.dispatch", 50, 51)
    empty = {"spans": Spans(), "span_mark": 0, "units": 1, "traced_s": None}
    assert window_spans.window_periods(empty) is None


def test_none_against_a_program_without_a_default_tracer(tracer, monkeypatch):
    import alphatriangle_tpu.telemetry.tracer as tracer_module

    ctx = rollout_ctx(tracer)
    monkeypatch.delattr(tracer_module, "default_tracer")
    for metric, side, _ in READERS:
        if side == "rollout":
            assert manifest.layer_reader(metric)(ctx) is None
