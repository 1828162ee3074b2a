"""Roofline attribution plane (telemetry/roofline.py, `cli roofline`;
docs/OBSERVABILITY.md "Roofline & gap attribution").

The reader-side tests here are JAX-free and fast: peak-bandwidth
resolution, machine balance, cost-record extraction, the roofline join
against flight rows, gap forensics over synthetic flight timelines,
and the CLI/legacy degradation contract (pre-roofline run dirs must
render with ZERO new fields — the same tolerance bar as the beacon and
device-stats suites). The compile-cache capture leg (real
`cost_analysis()` on compiled programs, sidecar round-trips, torn-file
recovery) needs JAX and lives at the bottom. Real-run integration is
`make roofline-smoke`, not here, to keep tier-1 fast.
"""

import json

import pytest

from alphatriangle_tpu.cli import main as cli_main
from alphatriangle_tpu.telemetry.flight import FLIGHT_FILENAME
from alphatriangle_tpu.telemetry.ledger import MetricsLedger, read_ledger
from alphatriangle_tpu.telemetry.perf import (
    COMPARE_METRICS,
    LOWER_IS_BETTER,
    UtilizationMeter,
    summarize_utilization,
)
from alphatriangle_tpu.telemetry.roofline import (
    COST_PRECAPTURE_ENV,
    GAP_CATEGORIES,
    PEAK_HBM_GBPS_ENV,
    attribute_gaps,
    cost_precapture_enabled,
    cost_flops_by_family,
    load_trace_spans,
    machine_balance_flops_per_byte,
    peak_hbm_gbps_info,
    program_cost_record,
    roofline_rows,
    summarize_roofline,
)

from tests.test_ledger import FakeClock, synthetic_run


class FakeCompiled:
    """Stands in for jax.stages.Compiled: cost_analysis only."""

    def __init__(self, stats):
        self._stats = stats

    def cost_analysis(self):
        if isinstance(self._stats, Exception):
            raise self._stats
        return self._stats


def _cost(program, flops, bytes_accessed, transcendentals=0.0):
    return {
        "kind": "cost",
        "category": "program",
        "component": f"program/{program}",
        "program": program,
        "key": "k",
        "backend": "cpu",
        "origin": "compile",
        "flops": flops,
        "bytes_accessed": bytes_accessed,
        "transcendentals": transcendentals,
        "time": 100.0,
    }


def _intent(seq, t_mono, program="megastep/t4_k2", **kw):
    return {
        "kind": "flight", "phase": "intent", "seq": seq,
        "program": program, "family": "megastep",
        "t_mono": float(t_mono), "time": kw.pop("time", 100.0 + t_mono),
        **kw,
    }


def _seal(seq, t_mono, program="megastep/t4_k2", wall_s=1.0, **kw):
    return {
        "kind": "flight", "phase": "seal", "seq": seq,
        "program": program, "family": "megastep", "ok": True,
        "wall_s": wall_s, "t_mono": float(t_mono),
        "time": kw.pop("time", 100.0 + t_mono), **kw,
    }


class TestPeakHbm:
    def test_table_lookup(self, monkeypatch):
        monkeypatch.delenv(PEAK_HBM_GBPS_ENV, raising=False)
        assert peak_hbm_gbps_info("TPU v4") == (1228.0, "table")
        assert peak_hbm_gbps_info("TPU v5e") == (819.0, "table")
        assert peak_hbm_gbps_info("TPU v5p") == (2765.0, "table")

    @pytest.mark.parametrize(
        "kind", ["TPU v5litepod-8", "TPU v4 megacore", "Quantum Q1"]
    )
    def test_unlisted_accelerator_raises_no_prefix_guess(
        self, monkeypatch, kind
    ):
        monkeypatch.delenv(PEAK_HBM_GBPS_ENV, raising=False)
        with pytest.raises(ValueError, match="no peak listed"):
            peak_hbm_gbps_info(kind)

    def test_cpu_is_explicit_unknown(self, monkeypatch):
        monkeypatch.delenv(PEAK_HBM_GBPS_ENV, raising=False)
        assert peak_hbm_gbps_info("cpu") == (None, "unknown")
        assert peak_hbm_gbps_info("") == (None, "unknown")

    def test_env_override_wins_with_provenance(self, monkeypatch):
        monkeypatch.setenv(PEAK_HBM_GBPS_ENV, "42.5")
        assert peak_hbm_gbps_info("TPU v4") == (42.5, "env")
        assert peak_hbm_gbps_info("cpu") == (42.5, "env")

    def test_bad_env_values_ignored(self, monkeypatch):
        monkeypatch.setenv(PEAK_HBM_GBPS_ENV, "not-a-number")
        assert peak_hbm_gbps_info("TPU v4") == (1228.0, "table")
        monkeypatch.setenv(PEAK_HBM_GBPS_ENV, "-3")
        assert peak_hbm_gbps_info("TPU v4") == (1228.0, "table")


class TestCostPrecaptureKnob:
    def test_default_on_suite_off(self, monkeypatch):
        # conftest turns it off for the whole suite (and subprocess
        # children); the default everywhere else is on.
        assert not cost_precapture_enabled()
        monkeypatch.delenv(COST_PRECAPTURE_ENV, raising=False)
        assert cost_precapture_enabled()
        monkeypatch.setenv(COST_PRECAPTURE_ENV, "0")
        assert not cost_precapture_enabled()
        monkeypatch.setenv(COST_PRECAPTURE_ENV, "1")
        assert cost_precapture_enabled()


class TestMachineBalance:
    def test_v4_balance(self):
        # 275 TFLOP/s over 1228 GB/s ~= 224 FLOPs/byte.
        balance = machine_balance_flops_per_byte(275.0, 1228.0)
        assert balance == pytest.approx(275e12 / 1228e9)

    def test_unknown_peaks_yield_none(self):
        assert machine_balance_flops_per_byte(None, 1228.0) is None
        assert machine_balance_flops_per_byte(275.0, None) is None
        assert machine_balance_flops_per_byte(0.0, 1228.0) is None


class TestProgramCostRecord:
    def test_dict_shape(self):
        rec = program_cost_record(
            "megastep/t4_k2",
            FakeCompiled(
                {"flops": 1e9, "bytes accessed": 2e6, "transcendentals": 7.0}
            ),
            backend="cpu",
            key="abc",
        )
        assert rec["kind"] == "cost"
        assert rec["program"] == "megastep/t4_k2"
        assert rec["component"] == "program/megastep/t4_k2"
        assert rec["flops"] == 1e9
        assert rec["bytes_accessed"] == 2e6
        assert rec["transcendentals"] == 7.0
        assert rec["origin"] == "compile"

    def test_legacy_list_of_dicts_shape(self):
        rec = program_cost_record(
            "p", FakeCompiled([{"flops": 5.0, "bytes accessed": 2.0}])
        )
        assert rec["flops"] == 5.0
        assert rec["bytes_accessed"] == 2.0

    def test_degrades_to_none(self):
        assert program_cost_record("p", object()) is None
        assert program_cost_record("p", FakeCompiled(RuntimeError())) is None
        assert program_cost_record("p", FakeCompiled({})) is None
        assert program_cost_record("p", FakeCompiled("bogus")) is None


class TestCostFlopsByFamily:
    def test_hottest_program_per_family_wins(self):
        records = [
            _cost("megastep/t4_k2", 1e9, 1e6),
            _cost("megastep/t8_k2", 4e9, 1e6),
            _cost("self_play_chunk/t4", 2e8, 1e6),
            _cost("learner_step/b8", 0.0, 1e6),  # non-positive: skipped
        ]
        fams = cost_flops_by_family(records)
        assert fams["megastep"] == 4e9
        assert fams["rollout"] == 2e8
        assert "learner" not in fams

    def test_non_cost_rows_skipped(self):
        assert cost_flops_by_family([{"kind": "util"}, "torn", None]) == {}


class TestRooflineRows:
    def _flight_row(self, program, p50=0.5, total=5.0, count=10):
        return {
            "program": program, "family": "megastep", "count": count,
            "errors": 0, "wall_s_p50": p50, "wall_s_p95": p50,
            "wall_s_total": total,
        }

    def test_compute_bound_join(self):
        # balance = 1e12 / 1e9 = 1000 FLOPs/byte; intensity 2000 is
        # compute-bound, ceiling = peak FLOP/s.
        [row] = roofline_rows(
            [_cost("megastep/t4_k2", 2e9, 1e6)],
            [self._flight_row("megastep/t4_k2", p50=0.5)],
            peak_tflops=1.0,
            peak_hbm_gbps=1.0,
        )
        assert row["intensity"] == pytest.approx(2000.0)
        assert row["bound"] == "compute"
        assert row["achieved_tflops"] == pytest.approx(2e9 / 0.5 / 1e12)
        assert row["roofline_tflops"] == pytest.approx(1.0)
        assert row["roofline_fraction"] == pytest.approx(0.004)

    def test_memory_bound_ceiling_is_bandwidth(self):
        # intensity 0.5 < balance 1000: ceiling = 0.5 * 1 GB/s = 5e8.
        [row] = roofline_rows(
            [_cost("megastep/t4_k2", 5e5, 1e6)],
            [self._flight_row("megastep/t4_k2", p50=0.001)],
            peak_tflops=1.0,
            peak_hbm_gbps=1.0,
        )
        assert row["bound"] == "memory"
        assert row["roofline_tflops"] == pytest.approx(5e8 / 1e12)
        assert row["roofline_fraction"] == pytest.approx(
            (5e5 / 0.001) / 5e8
        )

    def test_missing_cost_record_degrades_to_na_row(self):
        # A legacy run's flight ring without cost sidecars still rows.
        [row] = roofline_rows(
            [], [self._flight_row("serve/b4")], peak_tflops=1.0,
            peak_hbm_gbps=1.0,
        )
        assert row["program"] == "serve/b4"
        assert row["flops"] is None
        assert row["intensity"] is None
        assert row["bound"] is None
        assert row["roofline_fraction"] is None

    def test_unknown_peaks_classify_nothing(self):
        [row] = roofline_rows(
            [_cost("megastep/t4_k2", 2e9, 1e6)],
            [self._flight_row("megastep/t4_k2")],
        )
        assert row["intensity"] == pytest.approx(2000.0)
        assert row["bound"] is None
        assert row["roofline_fraction"] is None


class TestAttributeGaps:
    def test_too_few_records_is_none(self):
        assert attribute_gaps([]) is None
        assert attribute_gaps([_intent(1, 0.0)]) is None
        assert attribute_gaps([{"kind": "flight"}, {"no": "stamp"}]) is None

    def test_dispatch_and_gap_cover_the_timeline(self):
        records = [
            _intent(1, 0.0), _seal(1, 1.0),
            _intent(2, 2.0), _seal(2, 3.0),
        ]
        a = attribute_gaps(records)
        assert a["wall_s"] == pytest.approx(3.0)
        assert a["dispatch_s"] == pytest.approx(2.0)
        assert a["gap_s"] == pytest.approx(1.0)
        assert a["chip_idle_fraction"] == pytest.approx(1.0 / 3.0)
        assert a["attributed_fraction"] == pytest.approx(1.0)
        assert a["dispatches"] == 2
        assert a["unsealed"] == 0
        # No spans: the whole gap lands in "other", nothing dropped.
        assert a["gaps"]["other"] == pytest.approx(1.0)
        assert set(a["gaps"]) == set(GAP_CATEGORIES)

    def test_span_overlap_attributes_gap_categories(self):
        # mono->wall offset is exactly +100 in the helpers; the gap is
        # mono [1, 2] == wall [101, 102]. A 0.6s fetch span inside it
        # claims 0.6, the residual 0.4 lands in "other".
        records = [
            _intent(1, 0.0), _seal(1, 1.0),
            _intent(2, 2.0), _seal(2, 3.0),
        ]
        spans = [("fetch", 101.2, 101.8)]
        a = attribute_gaps(records, spans=spans)
        assert a["gaps"]["fetch"] == pytest.approx(0.6)
        assert a["gaps"]["other"] == pytest.approx(0.4)
        assert a["attributed_fraction"] == pytest.approx(1.0)

    def test_overclaimed_gap_scales_proportionally(self):
        # Two overlapping span categories claim 1.5s of a 1.0s gap:
        # both scale by 2/3, "other" gets nothing, total stays 1.0.
        records = [
            _intent(1, 0.0), _seal(1, 1.0),
            _intent(2, 2.0), _seal(2, 3.0),
        ]
        spans = [("fetch", 101.0, 102.0), ("ingest", 101.5, 102.0)]
        a = attribute_gaps(records, spans=spans)
        assert a["gaps"]["fetch"] == pytest.approx(1.0 * (1.0 / 1.5))
        assert a["gaps"]["ingest"] == pytest.approx(0.5 * (1.0 / 1.5))
        assert a["gaps"]["other"] == pytest.approx(0.0)
        assert sum(a["gaps"].values()) == pytest.approx(a["gap_s"])

    def test_unsealed_intent_counted_not_attributed(self):
        records = [
            _intent(1, 0.0), _seal(1, 1.0),
            _intent(2, 2.0),  # died in flight
        ]
        a = attribute_gaps(records)
        assert a["unsealed"] == 1
        assert a["dispatches"] == 1

    def test_overlapping_dispatches_merge(self):
        # Pipelined programs (overlapped loop): two in-flight intervals
        # overlapping [0,2] and [1,3] are 3s busy, not 4.
        records = [
            _intent(1, 0.0), _intent(2, 1.0),
            _seal(1, 2.0), _seal(2, 3.0),
        ]
        a = attribute_gaps(records)
        assert a["dispatch_s"] == pytest.approx(3.0)
        assert a["gap_s"] == pytest.approx(0.0)
        assert a["chip_idle_fraction"] == pytest.approx(0.0)


class TestLoadTraceSpans:
    def test_reads_categorized_complete_events(self, tmp_path):
        trace = tmp_path / "trace.json"
        trace.write_text(json.dumps({
            "traceEvents": [
                {"ph": "X", "name": "fetch_results", "ts": 1_000_000,
                 "dur": 500_000},
                {"ph": "X", "name": "checkpoint", "ts": 2_000_000,
                 "dur": 100_000},
                {"ph": "X", "name": "mystery_phase", "ts": 0, "dur": 1},
                {"ph": "B", "name": "fetch", "ts": 0},
                {"ph": "X", "name": "fold", "ts": 5, "dur": 0},
            ]
        }))
        spans = load_trace_spans(trace)
        assert spans == [
            ("fetch", 1.0, 1.5),
            ("checkpoint", 2.0, 2.1),
        ]

    def test_missing_or_corrupt_trace_degrades_to_empty(self, tmp_path):
        assert load_trace_spans(tmp_path / "ghost.json") == []
        bad = tmp_path / "trace.json"
        bad.write_text("{torn")
        assert load_trace_spans(bad) == []


class TestSummarizeRoofline:
    def test_none_when_no_evidence(self):
        assert summarize_roofline([], []) is None

    def test_full_summary_schema(self, monkeypatch):
        monkeypatch.setenv(PEAK_HBM_GBPS_ENV, "1.0")
        records = [
            _intent(1, 0.0), _seal(1, 1.0),
            _intent(2, 2.0), _seal(2, 3.0),
        ]
        s = summarize_roofline(
            [_cost("megastep/t4_k2", 2e9, 1e6)],
            records,
            device_kind="cpu",
            peak_tflops=1.0,
        )
        assert s["schema"] == "alphatriangle.roofline.v1"
        assert s["peak_hbm_gbps"] == 1.0
        assert s["peak_hbm_source"] == "env"
        assert s["machine_balance_flops_per_byte"] == pytest.approx(1000.0)
        [row] = s["programs"]
        assert row["bound"] == "compute"
        assert s["attribution"]["chip_idle_fraction"] == pytest.approx(
            1.0 / 3.0
        )

    def test_flight_only_run_still_attributes(self, monkeypatch):
        # Cost records absent (legacy sidecars lost): gap forensics
        # still works, rows degrade instead of vanishing.
        monkeypatch.delenv(PEAK_HBM_GBPS_ENV, raising=False)
        records = [_intent(1, 0.0), _seal(1, 1.0), _intent(2, 2.0),
                   _seal(2, 3.0)]
        s = summarize_roofline([], records, device_kind="cpu")
        assert s is not None
        assert s["attribution"]["dispatches"] == 2
        [row] = s["programs"]
        assert row["flops"] is None


class TestChipIdleGauge:
    """UtilizationMeter.tick's live counterpart of attribute_gaps."""

    def _meter(self, clock):
        return UtilizationMeter(
            forward_flops=1_000_000,
            train_step_flops=50_000_000,
            device_kind="cpu",
            buffer_capacity=1000,
            clock=clock,
        )

    def test_idle_fraction_from_consecutive_counters(self):
        clock = FakeClock()
        meter = self._meter(clock)
        assert meter.tick(step=0, dispatch_wall_s=0.0) is None
        clock.advance(2.0)
        rec = meter.tick(step=10, dispatch_wall_s=1.5)
        assert rec["chip_idle_fraction"] == pytest.approx(0.25)

    def test_legacy_wiring_emits_no_field(self):
        clock = FakeClock()
        meter = self._meter(clock)
        meter.tick(step=0)
        clock.advance(2.0)
        rec = meter.tick(step=10)
        assert "chip_idle_fraction" not in rec

    def test_counter_appearing_mid_run_waits_one_tick(self):
        # Flight recorder attached late: the first tick that carries
        # the counter has no baseline, so no delta is invented.
        clock = FakeClock()
        meter = self._meter(clock)
        meter.tick(step=0)
        clock.advance(2.0)
        rec = meter.tick(step=10, dispatch_wall_s=1.0)
        assert "chip_idle_fraction" not in rec
        clock.advance(2.0)
        rec = meter.tick(step=20, dispatch_wall_s=2.0)
        assert rec["chip_idle_fraction"] == pytest.approx(0.5)

    def test_clamped_to_unit_interval(self):
        # Pipelined dispatch can exceed the window (overlap) — clamp,
        # never a negative idle fraction.
        clock = FakeClock()
        meter = self._meter(clock)
        meter.tick(step=0, dispatch_wall_s=0.0)
        clock.advance(1.0)
        rec = meter.tick(step=10, dispatch_wall_s=5.0)
        assert rec["chip_idle_fraction"] == 0.0

    def test_summary_folds_mean_and_max(self):
        clock = FakeClock()
        meter = self._meter(clock)
        records = []
        walls = [0.0, 1.0, 1.5, 3.5]
        for i, w in enumerate(walls):
            rec = meter.tick(step=i * 10, dispatch_wall_s=w)
            if rec is not None:
                records.append(rec)
            clock.advance(2.0)
        s = summarize_utilization(records)
        # idle fractions: 0.5, 0.75, 0.0
        assert s["chip_idle_fraction"] == pytest.approx(
            (0.5 + 0.75 + 0.0) / 3
        )
        assert s["chip_idle_fraction_max"] == pytest.approx(0.75)

    def test_compare_gates_idle_lower_is_better(self):
        assert "chip_idle_fraction" in COMPARE_METRICS
        assert "chip_idle_fraction" in LOWER_IS_BETTER


class TestLegacyRooflineTolerance:
    """Run dirs from BEFORE the roofline plane existed (no
    `kind:"cost"` records, no dispatch-wall counter on util ticks)
    must keep reading exactly as they always did: no roofline_* keys
    invented, no idle line printed, compare still clean — even though
    such runs may well carry a flight.jsonl."""

    def test_perf_json_has_no_roofline_fields(self, tmp_path, capsys):
        run = synthetic_run(tmp_path)
        rc = cli_main(["perf", str(run), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        assert not [k for k in summary if k.startswith("roofline_")]
        assert "chip_idle_fraction" not in summary

    def test_perf_with_flight_but_no_cost_stays_legacy(
        self, tmp_path, capsys
    ):
        # PR-18-era run: flight ring present, zero cost records. The
        # perf fold is gated on cost records, so even the attribution
        # (computable from flight alone) must NOT appear.
        run = synthetic_run(tmp_path)
        lines = [
            _intent(1, 0.0), _seal(1, 1.0),
            _intent(2, 2.0), _seal(2, 3.0),
        ]
        (run / FLIGHT_FILENAME).write_text(
            "".join(json.dumps(r) + "\n" for r in lines)
        )
        rc = cli_main(["perf", str(run), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        assert not [k for k in summary if k.startswith("roofline_")]
        for row in summary.get("programs") or []:
            assert "intensity" not in row
            assert "bound" not in row
        capsys.readouterr()
        rc = cli_main(["perf", str(run)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "roofline" not in out
        assert "intensity" not in out

    def test_cli_roofline_exits_2_on_legacy_run(self, tmp_path, capsys):
        run = synthetic_run(tmp_path)
        rc = cli_main(["roofline", str(run)])
        err = capsys.readouterr().err
        assert rc == 2
        assert "no cost records or flight timeline" in err

    def test_cli_roofline_renders_cost_run(self, tmp_path, capsys):
        run = synthetic_run(tmp_path)
        led = MetricsLedger(run / "metrics.jsonl")
        led.append(_cost("megastep/t4_k2", 2e9, 1e6))
        lines = [
            _intent(1, 0.0), _seal(1, 1.0),
            _intent(2, 2.0), _seal(2, 3.0),
        ]
        (run / FLIGHT_FILENAME).write_text(
            "".join(json.dumps(r) + "\n" for r in lines)
        )
        rc = cli_main(["roofline", str(run), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["schema"] == "alphatriangle.roofline.v1"
        assert summary["attribution"]["dispatches"] == 2
        [row] = summary["programs"]
        assert row["flops"] == 2e9
        capsys.readouterr()
        assert cli_main(["roofline", str(run)]) == 0
        out = capsys.readouterr().out
        assert "megastep/t4_k2" in out
        assert "idle" in out

    def test_torn_cost_ledger_line_skipped(self, tmp_path, capsys):
        run = synthetic_run(tmp_path)
        led = MetricsLedger(run / "metrics.jsonl")
        led.append(_cost("megastep/t4_k2", 2e9, 1e6))
        with (run / "metrics.jsonl").open("a") as fh:
            fh.write('{"kind": "cost", "program": "torn')  # SIGKILL
        recs = read_ledger(run / "metrics.jsonl", kinds={"cost"})
        assert len(recs) == 1
        rc = cli_main(["perf", str(run), "--json"])
        assert rc == 0

    def test_compare_legacy_vs_roofline_reference_clean(
        self, tmp_path, capsys
    ):
        """A reference regenerated WITH the new fields must not regress
        a legacy run: chip_idle_fraction is gated on both sides
        carrying it, roofline_* keys are not in COMPARE_METRICS."""
        run = synthetic_run(tmp_path)
        rc = cli_main(["perf", str(run), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        ref = dict(
            summary,
            chip_idle_fraction=0.05,
            roofline_attributed_fraction=0.99,
            roofline_chip_idle_fraction=0.04,
        )
        ref_path = tmp_path / "ref_roofline.json"
        ref_path.write_text(json.dumps(ref))
        assert cli_main(["compare", str(run), str(ref_path)]) == 0

    def test_watch_renders_no_idle_line_on_legacy(self):
        from alphatriangle_tpu.stats.watch import idle_line

        assert idle_line({}) is None
        assert idle_line({"mfu": 0.5, "steps_per_sec": 1.0}) is None

    def test_watch_idle_line_flags_host_bound(self):
        from alphatriangle_tpu.stats.watch import idle_line

        line = idle_line({"chip_idle_fraction": 0.12})
        assert "12.0%" in line
        assert "HOST-BOUND" not in line
        assert "HOST-BOUND?" in idle_line({"chip_idle_fraction": 0.61})


class TestAutotuneCostAnchor:
    def test_cost_anchored_efficiency(self):
        from alphatriangle_tpu.autotune.model import (
            cost_anchored_efficiency,
        )

        # 2e9 FLOPs over 0.5s on a 1-TFLOP peak: 0.4% efficiency.
        eff = cost_anchored_efficiency(
            {"megastep": 2e9}, {"megastep": 0.5}, 1.0
        )
        assert eff == pytest.approx(2e9 / 0.5 / 1e12)

    def test_anchor_requires_both_sides(self):
        from alphatriangle_tpu.autotune.model import (
            cost_anchored_efficiency,
        )

        assert cost_anchored_efficiency({}, {"megastep": 0.5}, 1.0) is None
        assert cost_anchored_efficiency({"megastep": 2e9}, {}, 1.0) is None
        assert (
            cost_anchored_efficiency({"megastep": 2e9}, {"megastep": 0.5},
                                     None)
            is None
        )

    def test_implausible_ratio_rejected(self):
        from alphatriangle_tpu.autotune.model import (
            cost_anchored_efficiency,
        )

        # Above-peak implied efficiency means clock skew or a torn
        # record — never anchor on it.
        assert (
            cost_anchored_efficiency({"megastep": 2e12}, {"megastep": 0.5},
                                     1.0)
            is None
        )

    def test_calibration_round_trips_cost_flops(self):
        from alphatriangle_tpu.autotune.model import Calibration

        cal = Calibration(cost_flops={"megastep": 2e9})
        assert cal.as_dict()["cost_flops"] == {"megastep": 2e9}

    def test_merge_calibrations_means_cost_flops(self):
        from alphatriangle_tpu.autotune.model import (
            Calibration,
            merge_calibrations,
        )

        a = Calibration(cost_flops={"megastep": 2e9, "serve": 1e6})
        b = Calibration(cost_flops={"megastep": 4e9})
        merged = merge_calibrations([a, b])
        assert merged.cost_flops["megastep"] == pytest.approx(3e9)
        assert merged.cost_flops["serve"] == pytest.approx(1e6)


class TestCostSidecarCapture:
    """The JAX-dependent writer leg: real cost_analysis() capture on
    compiled programs, `.cost.json` sidecar round-trips, and torn-file
    recovery (the same degradation bar as the `.mem.json` tests)."""

    def test_capture_on_compile_and_sidecar_on_hit(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from alphatriangle_tpu.compile_cache import reset_compile_cache

        cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
        try:
            fn = cache.wrap("costtest", jax.jit(lambda x: x @ x + 1.0))
            fn(jnp.ones((16, 16), jnp.float32))
            [rec] = cache.cost_summary()
            assert rec["program"] == "costtest"
            assert rec["origin"] == "compile"
            assert rec["flops"] and rec["flops"] > 0
            sidecars = list((tmp_path / "aot").glob("*.cost.json"))
            assert len(sidecars) == 1
            assert json.loads(sidecars[0].read_text())["kind"] == "cost"

            # Fresh cache object, same dir: the AOT hit re-attributes
            # from the persisted sidecar without re-analyzing.
            cache2 = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            fn2 = cache2.wrap("costtest", jax.jit(lambda x: x @ x + 1.0))
            fn2(jnp.ones((16, 16), jnp.float32))
            assert cache2.hits == 1
            [rec2] = cache2.cost_summary()
            assert rec2["origin"] == "sidecar"
            assert rec2["flops"] == rec["flops"]
        finally:
            reset_compile_cache()

    def test_torn_sidecar_recaptured_on_hit(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from alphatriangle_tpu.compile_cache import reset_compile_cache

        cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
        try:
            fn = cache.wrap("torntest", jax.jit(lambda x: x @ x))
            fn(jnp.ones((8, 8), jnp.float32))
            [sidecar] = list((tmp_path / "aot").glob("*.cost.json"))
            sidecar.write_text('{"kind": "cost", "torn')  # SIGKILL mid-write

            cache2 = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            fn2 = cache2.wrap("torntest", jax.jit(lambda x: x @ x))
            fn2(jnp.ones((8, 8), jnp.float32))
            assert cache2.hits == 1
            [rec] = cache2.cost_summary()
            # Degraded to a fresh analysis of the reloaded executable,
            # never an exception.
            assert rec["origin"] == "compile"
            assert rec["flops"] and rec["flops"] > 0
        finally:
            reset_compile_cache()

    def test_legacy_artifact_without_sidecar_recaptured(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from alphatriangle_tpu.compile_cache import reset_compile_cache

        cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
        try:
            fn = cache.wrap("legacytest", jax.jit(lambda x: x + 1.0))
            fn(jnp.ones(8, jnp.float32))
            for sidecar in (tmp_path / "aot").glob("*.cost.json"):
                sidecar.unlink()

            cache2 = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            fn2 = cache2.wrap("legacytest", jax.jit(lambda x: x + 1.0))
            fn2(jnp.ones(8, jnp.float32))
            assert cache2.hits == 1
            assert len(cache2.cost_summary()) == 1
        finally:
            reset_compile_cache()

    def test_analyze_captures_cost_for_cpu_bypassed_program(self, tmp_path):
        import jax
        import jax.numpy as jnp

        from alphatriangle_tpu.compile_cache import reset_compile_cache

        cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
        try:
            fn = cache.wrap("bypassed", jax.jit(lambda x: x @ x),
                            cpu_aot=False)
            assert not fn.aot_active
            assert fn.analyze(jnp.ones((8, 8), jnp.float32)) is not None
            [rec] = cache.cost_summary()
            assert rec["program"] == "bypassed"
            # The cost sidecar persists even though the executable
            # never touches the artifact path (analyze's persist flag
            # guards only the .mem.json side).
            assert list((tmp_path / "aot").glob("*.cost.json"))
            assert list((tmp_path / "aot").glob("*.jaxexe")) == []
        finally:
            reset_compile_cache()
