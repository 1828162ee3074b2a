"""The readers of the program's own spans (`chipbench/program_spans.py`
and the three `layer_metrics/` files that use it), on hand-made
contexts: only spans that began inside the window count, the sum is
divided by the window's dispatches, and no span means no number."""

import pytest

from alphatriangle_tpu.telemetry import (
    SpanTracer,
    default_tracer,
    set_default_tracer,
)
from chipbench import manifest, program_spans
from chipbench.spans import Spans

MS = 1_000_000
WINDOW_START = 50 * MS  # on the perf_counter_ns clock both sides use


@pytest.fixture
def tracer():
    before = default_tracer()
    fresh = set_default_tracer(SpanTracer())
    yield fresh
    set_default_tracer(before)


def put(tracer, name, start_ns, duration_ns):
    """A finished span of the program at a chosen place on the clock."""
    wall = start_ns + tracer.wall_offset_ns
    tracer.complete(name, wall, wall + duration_ns)


def ctx(units=2):
    spans = Spans()
    spans.records = [
        ("ingest", 10 * MS, 20 * MS),  # set-up's, before the mark
        ("rollout", WINDOW_START, 90 * MS),
    ]
    return {"spans": spans, "span_mark": 1, "units": units}


CASES = [
    ("finish_host_ms.learner", ["learner.results"], "learner.wait"),
    (
        "ingest_wait_ms.rollout",
        ["replay.ingest_dispatch", "replay.ingest_wait"],
        "replay.tree_update",
    ),
    ("ingest_tree_ms.rollout", ["replay.tree_update"], "replay.ingest_wait"),
]


@pytest.mark.parametrize("metric, names, other", CASES)
class TestReaders:
    def test_sums_the_window_spans_per_dispatch(
        self, tracer, metric, names, other
    ):
        for name in names:
            put(tracer, name, WINDOW_START - 30 * MS, 7 * MS)  # warm-up's
            put(tracer, name, WINDOW_START, 3 * MS)
            put(tracer, name, WINDOW_START + 40 * MS, 5 * MS)
        put(tracer, other, WINDOW_START + 10 * MS, 100 * MS)  # not its span
        value = manifest.layer_reader(metric)(ctx(units=2))
        assert value == pytest.approx(len(names) * (3 + 5) / 2)

    def test_none_without_a_span(self, tracer, metric, names, other):
        put(tracer, other, WINDOW_START, 5 * MS)
        for name in names:
            put(tracer, name, WINDOW_START - 30 * MS, 7 * MS)
        assert manifest.layer_reader(metric)(ctx()) is None

    def test_in_the_manifest_as_a_program_span(self, metric, names, other):
        entry = next(
            m for m in manifest.benchmark()["per_layer"] if m["name"] == metric
        )
        assert entry["source"] == "program_span" and entry["unit"] == "ms"
        cell = entry["workloads"][0]
        assert metric in [
            m["name"] for m in manifest.metrics_of(cell, trace=True)
        ]


def test_none_when_the_window_has_no_harness_span(tracer):
    put(tracer, "learner.results", WINDOW_START, MS)
    empty = {"spans": Spans(), "span_mark": 0, "units": 1}
    assert program_spans.span_ms(empty, ("learner.results",)) is None


def test_none_against_a_program_without_a_default_tracer(monkeypatch):
    """The parent commit's `telemetry/tracer.py` has no such function:
    the reader must say nothing, not raise."""
    import alphatriangle_tpu.telemetry.tracer as tracer_module

    monkeypatch.delattr(tracer_module, "default_tracer")
    assert program_spans.span_ms(ctx(), ("learner.results",)) is None
