"""Metrics ledger + utilization accounting + perf/compare CLI tests:
append/rotate round-trips, crash-mid-write (torn line) recovery, meter
math under a frozen clock, golden `cli perf`/`cli compare` outputs on
synthetic runs with threshold exit codes, Prometheus export, and the
`cli watch` utilization line fed from the ledger tail."""

import json

import pytest

from alphatriangle_tpu.cli import main as cli_main
from alphatriangle_tpu.telemetry.ledger import (
    MetricsLedger,
    ledger_paths,
    read_ledger,
    resolve_ledger_path,
    tick_record,
    write_prometheus_textfile,
)
from alphatriangle_tpu.telemetry.perf import (
    SUMMARY_SCHEMA,
    UtilizationMeter,
    compare_summaries,
    load_comparable,
    summarize_utilization,
)


class FakeClock:
    def __init__(self, t: float = 0.0) -> None:
        self.t = t

    def __call__(self) -> float:
        return self.t

    def advance(self, dt: float) -> None:
        self.t += dt


def make_meter(clock, peak_env=None, monkeypatch=None, **kw):
    if peak_env is not None:
        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", str(peak_env))
    defaults = dict(
        forward_flops=1_000_000,
        train_step_flops=50_000_000,
        device_kind="cpu",
        buffer_capacity=1000,
        clock=clock,
    )
    defaults.update(kw)
    return UtilizationMeter(**defaults)


def synthetic_run(tmp_path, name="run_a", scale=1.0, ticks=6):
    """A run dir holding a metrics.jsonl of synthetic util records."""
    run_dir = tmp_path / name
    clock = FakeClock()
    meter = UtilizationMeter(
        forward_flops=1_000_000,
        train_step_flops=50_000_000,
        device_kind="TPU v4",
        buffer_capacity=1000,
        clock=clock,
    )
    ledger = MetricsLedger(run_dir / "metrics.jsonl")
    for i in range(ticks):
        rec = meter.tick(
            step=int(i * 10 * scale),
            episodes=int(i * 5 * scale),
            experiences=int(i * 100 * scale),
            simulations=int(i * 5000 * scale),
            buffer_size=min(1000, i * 100),
            transfer_h2d_s=i * 0.01,
            transfer_d2h_s=i * 0.02,
            compile_hits=3,
            compile_misses=1,
        )
        clock.advance(2.0)
        if rec is not None:
            ledger.append(rec)
    return run_dir


class TestLedger:
    def test_append_read_roundtrip(self, tmp_path):
        led = MetricsLedger(tmp_path / "metrics.jsonl")
        for i in range(5):
            assert led.append(tick_record(i, {"Loss/total_loss": 0.5 + i}))
        recs = read_ledger(tmp_path / "metrics.jsonl")
        assert [r["step"] for r in recs] == list(range(5))
        assert all(r["kind"] == "tick" for r in recs)
        # Kind filter.
        assert read_ledger(tmp_path / "metrics.jsonl", kinds={"util"}) == []

    def test_rotation_keeps_recent_generations(self, tmp_path):
        path = tmp_path / "metrics.jsonl"
        led = MetricsLedger(path, max_bytes=400, keep=2)
        for i in range(50):
            led.append({"kind": "tick", "step": i, "means": {"m": i}})
        paths = ledger_paths(path)
        assert path in paths
        assert path.with_name("metrics.jsonl.1") in paths
        # Bounded: never more than keep rotations + live file.
        assert len(paths) <= 3
        assert not path.with_name("metrics.jsonl.3").exists()
        recs = read_ledger(path)
        # Reads span rotations in order; the newest record is last.
        steps = [r["step"] for r in recs]
        assert steps == sorted(steps)
        assert steps[-1] == 49

    def test_torn_last_line_recovery(self, tmp_path):
        """Crash mid-write: the torn tail is skipped, later appends and
        reads keep working."""
        path = tmp_path / "metrics.jsonl"
        led = MetricsLedger(path)
        led.append({"kind": "tick", "step": 1, "means": {"m": 1.0}})
        with path.open("a") as f:
            f.write('{"kind": "tick", "step": 2, "mea')  # torn: no newline
        # Reader skips the torn line.
        assert [r["step"] for r in read_ledger(path)] == [1]
        # A restarted process (fresh ledger over the same file) detects
        # the torn tail and terminates it before its first append — its
        # record must not glue onto the scar and vanish with it.
        led2 = MetricsLedger(path)
        led2.append({"kind": "tick", "step": 3, "means": {"m": 3.0}})
        steps = [r["step"] for r in read_ledger(path)]
        assert steps == [1, 3]

    def test_junk_bytes_never_raise(self, tmp_path):
        path = tmp_path / "metrics.jsonl"
        path.write_bytes(b"\xff\xfe garbage\n[1,2]\n" + b'{"kind":"tick","step":7,"means":{}}\n')
        assert [r["step"] for r in read_ledger(path)] == [7]

    def test_resolve_ledger_path(self, tmp_path):
        run = synthetic_run(tmp_path)
        assert resolve_ledger_path(run) == run / "metrics.jsonl"
        assert resolve_ledger_path(run / "metrics.jsonl") is not None
        assert resolve_ledger_path(tmp_path / "nope") is None


class TestUtilizationMeter:
    def test_first_tick_baselines_then_derives(self, monkeypatch):
        clock = FakeClock()
        meter = make_meter(clock, peak_env=2.0, monkeypatch=monkeypatch)
        assert meter.tick(step=0) is None  # baseline
        clock.advance(2.0)
        rec = meter.tick(
            step=10,
            episodes=5,
            experiences=100,
            simulations=5000,
            buffer_size=100,
            transfer_h2d_s=0.01,
            transfer_d2h_s=0.02,
            compile_hits=3,
            compile_misses=1,
        )
        assert rec["kind"] == "util"
        assert rec["learner_steps_per_sec"] == pytest.approx(5.0)
        assert rec["step_time_ms"] == pytest.approx(200.0)
        assert rec["moves_per_sec"] == pytest.approx(50.0)
        assert rec["games_per_hour"] == pytest.approx(9000.0)
        assert rec["sims_per_sec"] == pytest.approx(2500.0)
        # FLOPs: 5 steps/s * 50e6 + (2500 + 50) evals/s * 1e6.
        expected_tflops = (5 * 50e6 + 2550 * 1e6) / 1e12
        assert rec["tflops_per_sec"] == pytest.approx(
            expected_tflops, rel=1e-3
        )
        assert rec["mfu"] == pytest.approx(expected_tflops / 2.0, rel=1e-3)
        assert rec["peak_source"] == "env"
        assert rec["buffer_fill"] == pytest.approx(0.1)
        assert rec["transfer_h2d_ms"] == pytest.approx(10.0)
        assert rec["transfer_d2h_ms"] == pytest.approx(20.0)
        assert rec["compile_cache_hit_rate"] == pytest.approx(0.75)

    def test_cpu_peak_yields_null_mfu_with_marker(self, monkeypatch):
        monkeypatch.delenv("ALPHATRIANGLE_PEAK_TFLOPS", raising=False)
        clock = FakeClock()
        meter = make_meter(clock, device_kind="cpu")
        assert meter.peak_tflops is None
        assert meter.peak_source == "unknown"
        meter.tick(step=0)
        clock.advance(1.0)
        rec = meter.tick(step=5, experiences=10)
        assert rec["mfu"] is None
        assert rec["peak_bf16_tflops"] is None
        assert rec["peak_source"] == "unknown"

    def test_unlisted_accelerator_refuses_a_meter(self, monkeypatch):
        monkeypatch.delenv("ALPHATRIANGLE_PEAK_TFLOPS", raising=False)
        with pytest.raises(ValueError, match="no peak listed"):
            make_meter(FakeClock(), device_kind="NPU weird9000")

    def test_known_chip_uses_table(self, monkeypatch):
        monkeypatch.delenv("ALPHATRIANGLE_PEAK_TFLOPS", raising=False)
        meter = make_meter(FakeClock(), device_kind="TPU v4")
        assert meter.peak_tflops == 275.0
        assert meter.peak_source == "table"

    def test_zero_width_tick_skipped(self, monkeypatch):
        clock = FakeClock()
        meter = make_meter(clock)
        meter.tick(step=0)
        assert meter.tick(step=1) is None  # same clock instant


class TestSummarize:
    def test_summary_fields(self, tmp_path):
        run = synthetic_run(tmp_path)
        recs = read_ledger(run / "metrics.jsonl", kinds={"util"})
        s = summarize_utilization(recs)
        assert s["schema"] == SUMMARY_SCHEMA
        assert s["ticks"] == len(recs)
        assert s["learner_steps_per_sec"] == pytest.approx(5.0)
        assert s["games_per_hour"] == pytest.approx(9000.0)
        assert s["step_time_ms_p50"] == pytest.approx(200.0)
        assert s["step_time_ms_p95"] == pytest.approx(200.0)
        assert s["mfu"] is not None
        assert s["throughput_trend"] == pytest.approx(0.0)
        assert s["device_kind"] == "TPU v4"

    def test_window_limits_records(self, tmp_path):
        run = synthetic_run(tmp_path, ticks=10)
        recs = read_ledger(run / "metrics.jsonl", kinds={"util"})
        s = summarize_utilization(recs, window=3)
        assert s["ticks"] == 3
        assert s["ticks_total"] == len(recs)

    def test_no_records_is_none(self):
        assert summarize_utilization([]) is None
        assert summarize_utilization([{"kind": "tick", "step": 1}]) is None


class TestCompare:
    def test_parity_and_regression(self, tmp_path):
        a = synthetic_run(tmp_path, "run_a")
        sa, _ = load_comparable(str(a))
        rows, reg = compare_summaries(sa, sa, threshold=0.1)
        assert reg == []
        assert all(r[4] in ("ok", "n/a") for r in rows)
        # 20% slower candidate vs baseline: regression.
        slower = dict(sa, games_per_hour=sa["games_per_hour"] * 0.8)
        rows, reg = compare_summaries(slower, sa, threshold=0.1)
        assert "games_per_hour" in reg

    def test_load_comparable_json_must_be_a_perf_summary(self, tmp_path):
        """A snapshot is a `cli perf --json` summary; any other JSON
        object (a retired bench line among them) is refused with a
        reason, never half-read."""
        path = tmp_path / "other.json"
        path.write_text(
            json.dumps(
                {"metric": "self_play_games_per_hour", "value": 12000.0}
            )
        )
        s, reason = load_comparable(str(path))
        assert s is None and "not a perf summary" in reason
        path.write_text(
            json.dumps({"schema": SUMMARY_SCHEMA, "games_per_hour": 7.0})
        )
        s, label = load_comparable(str(path))
        assert s["games_per_hour"] == 7.0 and s["source"] == str(path)

    def test_load_comparable_missing(self, tmp_path):
        s, reason = load_comparable(str(tmp_path / "ghost"))
        assert s is None and "ghost" in reason


class TestCliPerf:
    def test_golden_summary_on_synthetic_run(self, tmp_path, capsys):
        run = synthetic_run(tmp_path)
        rc = cli_main(["perf", str(run)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "steps 10" in out and "TPU v4" in out
        assert "step p50 200.0ms" in out and "p95 200.0ms" in out
        assert "9,000.0 games/h" in out
        assert "MFU" in out and "trend" in out
        assert "[table]" in out  # peak source surfaced

    def test_json_summary_feeds_compare(self, tmp_path, capsys):
        run = synthetic_run(tmp_path)
        rc = cli_main(["perf", str(run), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        assert summary["schema"] == SUMMARY_SCHEMA
        ref = tmp_path / "ref.json"
        ref.write_text(json.dumps(summary))
        assert cli_main(["compare", str(run), str(ref)]) == 0

    def test_missing_ledger_exits_2(self, tmp_path, capsys):
        empty = tmp_path / "empty_run"
        empty.mkdir()
        assert cli_main(["perf", str(empty)]) == 2

    def test_tick_only_ledger_exits_2(self, tmp_path, capsys):
        run = tmp_path / "tickrun"
        MetricsLedger(run / "metrics.jsonl").append(
            tick_record(1, {"m": 1.0})
        )
        assert cli_main(["perf", str(run)]) == 2


class TestCliCompare:
    def test_parity_exit_0(self, tmp_path, capsys):
        a = synthetic_run(tmp_path, "run_a")
        b = synthetic_run(tmp_path, "run_b")
        rc = cli_main(["compare", str(a), str(b)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "parity" in out

    def test_injected_20pct_regression_exits_1(self, tmp_path, capsys):
        a = synthetic_run(tmp_path, "run_a", scale=0.8)  # 20% slower
        b = synthetic_run(tmp_path, "run_b", scale=1.0)
        rc = cli_main(["compare", str(a), str(b)])
        out = capsys.readouterr().out
        assert rc == 1
        assert "REGRESSION" in out

    def test_threshold_is_respected(self, tmp_path):
        a = synthetic_run(tmp_path, "run_a", scale=0.8)
        b = synthetic_run(tmp_path, "run_b", scale=1.0)
        assert cli_main(["compare", str(a), str(b), "--threshold", "0.3"]) == 0

    def test_unreadable_side_exits_2(self, tmp_path, capsys):
        a = synthetic_run(tmp_path, "run_a")
        assert cli_main(["compare", str(a), str(tmp_path / "ghost")]) == 2

    def test_json_report(self, tmp_path, capsys):
        a = synthetic_run(tmp_path, "run_a", scale=0.5)
        b = synthetic_run(tmp_path, "run_b")
        rc = cli_main(["compare", str(a), str(b), "--json"])
        report = json.loads(capsys.readouterr().out)
        assert rc == 1
        assert "games_per_hour" in report["regressions"]
        assert any(r["status"] == "regression" for r in report["rows"])


class TestPrometheus:
    def test_textfile_gauges(self, tmp_path):
        rec = {
            "kind": "util",
            "step": 42,
            "mfu": 0.125,
            "games_per_hour": 9000.0,
            "learner_steps_per_sec": 5.0,
            "device_kind": "TPU v4",  # non-numeric: skipped
            "step_time_ms": None,  # missing: skipped
        }
        path = tmp_path / "metrics.prom"
        assert write_prometheus_textfile(path, rec, run_name="r1")
        text = path.read_text()
        assert 'alphatriangle_mfu{run="r1"} 0.125' in text
        assert 'alphatriangle_step{run="r1"} 42' in text
        assert "# TYPE alphatriangle_games_per_hour gauge" in text
        assert "device_kind" not in text
        assert "step_time_ms" not in text
        assert not path.with_suffix(".prom.tmp").exists()


class TestWatchUtilization:
    def test_tail_and_render_util_line(self, tmp_path):
        from alphatriangle_tpu.stats.watch import (
            WatchState,
            render_frame,
            tail_ledger_utils,
        )

        run = synthetic_run(tmp_path)
        state = WatchState()
        offset = tail_ledger_utils(run / "metrics.jsonl", state, 0)
        assert offset > 0
        assert state.util["kind"] == "util"
        frame = render_frame(state, "run_a")
        assert "utilization" in frame
        assert "TFLOP/s" in frame and "MFU" in frame

    def test_torn_ledger_tail_survives(self, tmp_path):
        from alphatriangle_tpu.stats.watch import WatchState, tail_ledger_utils

        path = tmp_path / "metrics.jsonl"
        path.write_text('{"kind": "util", "step": 3, "mfu": 0.5}\n{"kind": "ut')
        state = WatchState()
        offset = tail_ledger_utils(path, state, 0)
        assert state.util["step"] == 3
        # Torn tail not consumed; completing it folds on the next tail.
        with path.open("a") as f:
            f.write('il", "step": 4, "mfu": 0.6}\n')
        tail_ledger_utils(path, state, offset)
        assert state.util["step"] == 4

    def test_no_util_no_line(self):
        from alphatriangle_tpu.stats.watch import WatchState, render_frame

        frame = render_frame(WatchState(), "r")
        assert "utilization" not in frame


class TestRunTelemetryLedger:
    def test_on_util_tick_appends_and_updates_health(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")
        from alphatriangle_tpu.telemetry import RunTelemetry, TelemetryConfig

        clock = FakeClock()
        meter = make_meter(clock)
        tel = RunTelemetry(
            TelemetryConfig(WATCHDOG_ENABLED=False),
            run_dir=tmp_path,
            run_name="r",
            clock=clock,
            perf=meter,
        )
        assert tel.on_util_tick(0, compile_hits=0, compile_misses=0) is None
        clock.advance(2.0)
        rec = tel.on_util_tick(
            10, experiences=100, compile_hits=1, compile_misses=1
        )
        assert rec is not None
        utils = read_ledger(tmp_path / "metrics.jsonl", kinds={"util"})
        assert len(utils) == 1 and utils[0]["step"] == 10
        tel.close(10)
        health = json.loads((tmp_path / "health.json").read_text())
        assert health["device_kind"] == "cpu"
        assert health["peak_bf16_tflops"] == 1.0
        assert health["utilization"]["step"] == 10

    def test_record_metrics_sink(self, tmp_path):
        from alphatriangle_tpu.telemetry import RunTelemetry, TelemetryConfig

        tel = RunTelemetry(
            TelemetryConfig(WATCHDOG_ENABLED=False), run_dir=tmp_path
        )
        tel.record_metrics(5, {"Loss/total_loss": 0.3})
        ticks = read_ledger(tmp_path / "metrics.jsonl", kinds={"tick"})
        assert ticks[0]["step"] == 5
        assert ticks[0]["means"]["Loss/total_loss"] == 0.3
        tel.close()

    def test_disabled_writes_nothing(self, tmp_path):
        from alphatriangle_tpu.telemetry import RunTelemetry, TelemetryConfig

        tel = RunTelemetry(
            TelemetryConfig(ENABLED=False), run_dir=tmp_path, perf=make_meter(FakeClock())
        )
        tel.record_metrics(1, {"m": 1.0})
        assert tel.on_util_tick(1) is None
        assert not (tmp_path / "metrics.jsonl").exists()

    def test_prometheus_opt_in(self, tmp_path, monkeypatch):
        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")
        from alphatriangle_tpu.telemetry import RunTelemetry, TelemetryConfig

        clock = FakeClock()
        tel = RunTelemetry(
            TelemetryConfig(WATCHDOG_ENABLED=False, PROMETHEUS_TEXTFILE=True),
            run_dir=tmp_path,
            run_name="promrun",
            clock=clock,
            perf=make_meter(clock),
        )
        tel.on_util_tick(0, compile_hits=0, compile_misses=0)
        clock.advance(1.0)
        tel.on_util_tick(5, experiences=10, compile_hits=0, compile_misses=0)
        text = (tmp_path / "metrics.prom").read_text()
        assert 'alphatriangle_step{run="promrun"} 5' in text
        tel.close()


class TestFlopsPeakOverride:
    def test_env_override_wins(self, monkeypatch):
        from alphatriangle_tpu.utils.flops import (
            mfu,
            peak_bf16_tflops,
            peak_bf16_tflops_info,
        )

        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "2.5")
        assert peak_bf16_tflops_info("TPU v4") == (2.5, "env")
        assert peak_bf16_tflops("whatever") == 2.5
        assert mfu(2.5e12, "cpu") == pytest.approx(1.0)

    def test_invalid_override_ignored(self, monkeypatch):
        from alphatriangle_tpu.utils.flops import peak_bf16_tflops_info

        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "not-a-number")
        assert peak_bf16_tflops_info("TPU v4") == (275.0, "table")
        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "-3")
        assert peak_bf16_tflops_info("cpu") == (None, "unknown")


class TestLegacyDeviceStatsTolerance:
    """Runs recorded BEFORE the device-telemetry plane existed (no
    `kind:"device_stats"` records, no stat-pack gauges on the util
    ticks) must keep reading exactly as they always did: no ds_* keys
    invented, no search-health line printed, compare still clean."""

    def test_perf_json_has_no_ds_fields(self, tmp_path, capsys):
        run = synthetic_run(tmp_path)
        rc = cli_main(["perf", str(run), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        assert not [k for k in summary if k.startswith("ds_")]
        assert "root_visit_entropy" not in summary
        assert "tree_occupancy" not in summary

    def test_perf_text_has_no_search_health_line(self, tmp_path, capsys):
        run = synthetic_run(tmp_path)
        rc = cli_main(["perf", str(run)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "entropy" not in out
        assert "ingest/per" not in out

    def test_summarize_device_stats_none_on_legacy(self, tmp_path):
        from alphatriangle_tpu.telemetry.device_stats import (
            summarize_device_stats,
        )

        run = synthetic_run(tmp_path)
        recs = read_ledger(run / "metrics.jsonl", kinds={"device_stats"})
        assert recs == []
        assert summarize_device_stats(recs) is None

    def test_compare_legacy_run_vs_ds_reference_clean(self, tmp_path, capsys):
        """A reference regenerated WITH ds_* fields must not regress a
        legacy run: ds_* keys are not in COMPARE_METRICS, so the rows
        stay absent unless --metrics names them explicitly."""
        run = synthetic_run(tmp_path)
        rc = cli_main(["perf", str(run), "--json"])
        assert rc == 0
        summary = json.loads(capsys.readouterr().out)
        ref = dict(
            summary,
            ds_records=12,
            ds_root_entropy=1.2,
            ds_tree_occupancy=0.4,
            root_visit_entropy=1.2,
        )
        ref_path = tmp_path / "ref_ds.json"
        ref_path.write_text(json.dumps(ref))
        assert cli_main(["compare", str(run), str(ref_path)]) == 0

    def test_watch_renders_no_devstats_line_on_legacy(self):
        from alphatriangle_tpu.stats.watch import device_stats_line

        assert device_stats_line({}) is None
        assert device_stats_line({"mfu": 0.5, "steps_per_sec": 1.0}) is None
