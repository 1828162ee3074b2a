"""`chipbench/trace.py` on hand-made events: the numbers below are
worked by hand from the intervals."""

import pytest

from chipbench import trace

US = 1000  # ns


def test_busy_is_the_union_of_the_intervals():
    ops = [
        ("while.1", 0, 100 * US),  # contains the next two
        ("fusion.1", 10 * US, 20 * US),
        ("fusion.2", 50 * US, 30 * US),
        ("copy.3", 90 * US, 30 * US),  # runs 20 us past the while
        ("fusion.4", 200 * US, 50 * US),
    ]
    assert trace.busy_ns(ops) == (120 + 50) * US
    assert trace.busy_ns([]) == 0


def test_dispatches_gaps_and_median():
    modules = [
        ("jit_other(1)", 5 * US, 1 * US),
        ("jit__train_steps_from_impl(7)", 100 * US, 300 * US),
        ("jit__train_steps_from_impl(7)", 450 * US, 310 * US),
        ("jit__train_steps_from_impl(7)", 800 * US, 290 * US),
    ]
    assert trace.main_program(modules) == "jit__train_steps_from_impl(7)"
    runs = trace.dispatches(modules, "jit__train_steps_from_impl(7)")
    assert [d for _, _, d in runs] == [300 * US, 310 * US, 290 * US]
    assert trace.gaps_ns(runs) == [(400 * US, 50 * US), (760 * US, 40 * US)]
    assert trace.median([50, 40]) == 45.0
    assert trace.median([3, 1, 2]) == 2.0


def test_gaps_go_to_the_host_span_that_covered_them():
    gaps = [(400 * US, 50 * US), (760 * US, 40 * US)]
    host = [
        ("fetch", 390 * US, 15 * US),  # 5 us inside the first gap
        ("priorities", 405 * US, 20 * US),
        ("sample", 425 * US, 20 * US),
        ("sample", 770 * US, 40 * US),  # 30 us inside the second
    ]
    assert trace.attribute_gaps(gaps, host) == {
        "fetch": 5 * US,
        "priorities": 20 * US,
        "sample": (20 + 30) * US,
        "other": (5 + 10) * US,
    }


def test_top_operations_leave_containers_out():
    ops = [
        ("while.1", 0, 900),
        ("fusion.2", 0, 300),
        ("fusion.2", 400, 200),
        ("convolution.5", 700, 100),
    ]
    assert trace.top_operations(ops, n=2) == [
        ["fusion.2", 500 / 1e9],
        ["convolution.5", 100 / 1e9],
    ]


def test_summarize_averages_over_the_planes_that_ran_operations():
    xplane = {
        "devices": {
            "/device:TPU:0": {
                trace.OPS_LINE: [("fusion.1", 0, 600 * US), ("fusion.1", 800 * US, 200 * US)],
                trace.MODULES_LINE: [
                    ("jit_step(1)", 0, 600 * US),
                    ("jit_step(1)", 800 * US, 200 * US),
                ],
            },
            "/device:TPU:0 runtime": {"Steps": []},
        },
        "host": [("sample", 600 * US, 200 * US)],
    }
    out = trace.summarize(xplane, window_s=0.002)
    assert out["program"] == "jit_step(1)"
    assert out["busy_s"] == pytest.approx(800e-6)
    assert out["dispatch_ms"] == [0.6, 0.2]
    assert out["gap_ms"] == [0.2]
    assert out["breakdown"]["idle_gaps"] == [["sample", 200e-6]]
    with pytest.raises(RuntimeError):
        trace.summarize({"devices": {"/device:X": {"Steps": []}}, "host": []}, 1.0)
