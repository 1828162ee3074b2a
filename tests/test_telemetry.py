"""Telemetry subsystem tests: span tracer, health/watchdog, anomaly
detector, the health/trace CLI surfaces, and a CPU smoke run proving a
tiny training session emits a loadable trace.json + advancing
health.json heartbeat (docs/OBSERVABILITY.md acceptance bar)."""

import json
import threading
import time

import numpy as np
import pytest

from alphatriangle_tpu import cli
from alphatriangle_tpu.config import PersistenceConfig, TelemetryConfig
from alphatriangle_tpu.stats.collector import StatsCollector
from alphatriangle_tpu.telemetry import (
    AnomalyDetector,
    HealthMonitor,
    RunTelemetry,
    SpanTracer,
    Watchdog,
    health_verdict,
    read_health,
    summarize_trace_file,
)


class FakeClock:
    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


class TestSpanTracer:
    def test_spans_export_chrome_trace(self, tmp_path):
        tr = SpanTracer()
        with tr.span("rollout", chunk=3):
            time.sleep(0.002)
        with tr.span("train"):
            pass
        tr.instant("stall_marker")
        n = tr.export(tmp_path / "trace.json")
        assert n == 3
        data = json.loads((tmp_path / "trace.json").read_text())
        events = data["traceEvents"]
        spans = [e for e in events if e.get("ph") == "X"]
        assert len(spans) == 2
        for ev in spans:
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(ev)
        # Real duration in microseconds.
        rollout = next(e for e in spans if e["name"] == "rollout")
        assert rollout["dur"] >= 2000
        assert rollout["args"] == {"chunk": 3}
        assert any(e["ph"] == "i" for e in events)
        # Thread metadata names the recording thread.
        meta = [e for e in events if e["ph"] == "M"]
        assert meta and meta[0]["args"]["name"]

    def test_ring_bounds_memory(self, tmp_path):
        tr = SpanTracer(capacity=8)
        for i in range(20):
            with tr.span(f"s{i}"):
                pass
        assert tr.recorded == 20
        assert tr.export(tmp_path / "t.json") == 8
        names = [
            e["name"]
            for e in json.loads((tmp_path / "t.json").read_text())[
                "traceEvents"
            ]
            if e["ph"] == "X"
        ]
        assert names == [f"s{i}" for i in range(12, 20)]

    def test_threads_recorded_separately(self, tmp_path):
        tr = SpanTracer()

        def work():
            with tr.span("worker_phase"):
                pass

        t = threading.Thread(target=work, name="producer-0")
        t.start()
        t.join()
        with tr.span("main_phase"):
            pass
        tr.export(tmp_path / "t.json")
        events = json.loads((tmp_path / "t.json").read_text())["traceEvents"]
        tids = {e["tid"] for e in events if e["ph"] == "X"}
        assert len(tids) == 2
        meta_names = {
            e["args"]["name"] for e in events if e["ph"] == "M"
        }
        assert "producer-0" in meta_names

    def test_disabled_records_nothing(self, tmp_path):
        tr = SpanTracer(enabled=False)
        with tr.span("x"):
            pass
        tr.instant("y")
        assert tr.recorded == 0
        assert tr.export(tmp_path / "t.json") == 0

    def test_file_summary_counts_and_takes_children_off_self_time(
        self, tmp_path
    ):
        tr = SpanTracer()
        for _ in range(3):
            with tr.span("rollout"):
                with tr.span("rollout.wait"):
                    time.sleep(0.002)
        tr.export(tmp_path / "t.json")
        rows = {r["name"]: r for r in summarize_trace_file(tmp_path / "t.json")}
        assert rows["rollout"]["count"] == rows["rollout.wait"]["count"] == 3
        wait = rows["rollout.wait"]
        assert wait["self_ms"] == wait["total_ms"] >= 6.0
        assert rows["rollout"]["self_ms"] == pytest.approx(
            rows["rollout"]["total_ms"] - wait["total_ms"]
        )
        # A trace without ids (another tool's) is all self time.
        bare = [{"name": "a", "ph": "X", "ts": 0, "dur": 1500, "tid": 1}]
        (tmp_path / "bare.json").write_text(json.dumps(bare))
        (row,) = summarize_trace_file(tmp_path / "bare.json")
        assert row["self_ms"] == row["total_ms"] == 1.5


class TestHealthMonitor:
    def test_heartbeat_roundtrip(self, tmp_path):
        clock = FakeClock(100.0)
        h = HealthMonitor(
            tmp_path / "health.json", deadline_s=60, run_name="r",
            clock=clock,
        )
        h.note_rollout(experiences=32, episodes=2)
        clock.t = 105.0
        h.note_learner_step(7)
        h.note_buffer(500)
        clock.t = 110.0
        h.write()
        payload = read_health(tmp_path / "health.json")
        assert payload["run"] == "r"
        assert payload["learner_step"] == 7
        assert payload["learner_age_s"] == pytest.approx(5.0)
        assert payload["rollout_age_s"] == pytest.approx(10.0)
        assert payload["buffer_size"] == 500
        assert payload["episodes_played"] == 2
        assert payload["experiences_added"] == 32
        assert not payload["stalled"]
        assert payload["watchdog_deadline_s"] == 60

    def test_last_progress_tracks_newest_beat(self):
        clock = FakeClock(10.0)
        h = HealthMonitor("unused", clock=clock)
        assert h.last_progress() == 10.0  # start counts as progress
        clock.t = 20.0
        h.note_rollout()
        clock.t = 30.0
        h.note_learner_step(1)
        assert h.last_progress() == 30.0

    def test_verdict(self):
        base = {"time": 1000.0, "watchdog_deadline_s": 100.0}
        ok, age, _ = health_verdict(base, now=1050.0)
        assert ok and age == pytest.approx(50.0)
        ok, age, reason = health_verdict(base, now=1200.0)
        assert not ok and "no heartbeat" in reason
        ok, _, reason = health_verdict(
            {**base, "stalled": True}, now=1050.0
        )
        assert not ok and "stall" in reason
        # Explicit deadline override wins.
        ok, _, _ = health_verdict(base, now=1050.0, deadline_s=10.0)
        assert not ok

    def test_read_health_missing_or_torn(self, tmp_path):
        assert read_health(tmp_path / "nope.json") is None
        (tmp_path / "torn.json").write_text('{"time": 1')
        assert read_health(tmp_path / "torn.json") is None


class TestWatchdog:
    def test_stall_fires_once_then_recovers_and_rearms(self, tmp_path):
        clock = FakeClock(0.0)
        h = HealthMonitor(
            tmp_path / "health.json", deadline_s=10.0, clock=clock
        )
        calls: list[float] = []
        wd = Watchdog(
            h, deadline_s=10.0, on_stall=calls.append, clock=clock
        )
        assert not wd.check()
        # Frozen progress past the deadline: fires exactly once.
        clock.t = 11.0
        assert wd.check()
        assert wd.check()  # still stalled, no second fire
        assert len(calls) == 1 and calls[0] == pytest.approx(11.0)
        h.write()
        assert read_health(h.path)["stalled"] is True
        # Progress resumes: recovers cleanly...
        h.note_learner_step(1)
        assert not wd.check()
        h.write()
        payload = read_health(h.path)
        assert payload["stalled"] is False
        assert payload["stall_count"] == 1
        # ...and a second stall re-arms the dump.
        clock.t = 30.0
        assert wd.check()
        assert len(calls) == 2 and wd.stall_count == 2

    def test_on_stall_failure_does_not_kill_watchdog(self, tmp_path):
        clock = FakeClock(0.0)
        h = HealthMonitor(
            tmp_path / "health.json", deadline_s=5.0, clock=clock
        )

        def boom(age):
            raise RuntimeError("hook failed")

        wd = Watchdog(h, deadline_s=5.0, on_stall=boom, clock=clock)
        clock.t = 6.0
        assert wd.check()  # must not raise
        assert wd.stall_count == 1

    def test_thread_start_stop(self, tmp_path):
        h = HealthMonitor(tmp_path / "health.json", deadline_s=1000.0)
        wd = Watchdog(h, deadline_s=1000.0, poll_s=0.01)
        wd.start()
        assert any(
            t.name == "telemetry-watchdog" for t in threading.enumerate()
        )
        wd.stop()
        assert not any(
            t.name == "telemetry-watchdog" and t.is_alive()
            for t in threading.enumerate()
        )


class TestRunTelemetryStall:
    def test_stall_dumps_stacks_metric_and_trace(self, tmp_path):
        clock = FakeClock(0.0)
        pc = PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME="s")
        stats = StatsCollector(pc, use_tensorboard=False)
        cfg = TelemetryConfig(WATCHDOG_DEADLINE_S=10.0)
        t = RunTelemetry(
            cfg, run_dir=tmp_path, stats=stats, run_name="s", clock=clock
        )
        with t.tracer.span("rollout"):
            pass
        t.on_learner_step(3, {"Loss/total_loss": 1.0})
        clock.t = 20.0
        assert t.watchdog.check()
        # Exactly one stack dump, containing this (main) thread.
        stacks = (tmp_path / "stall_stacks.txt").read_text()
        assert stacks.count("=== stall at") == 1
        assert "MainThread" in stacks or "Current thread" in stacks
        # Health/stall metric (value = stall age) queued for the tick.
        means = stats.process_and_log(3)
        assert means["Health/stall"] == pytest.approx(20.0)
        # Span buffer flushed (stall marker included).
        data = json.loads((tmp_path / "trace.json").read_text())
        names = [e["name"] for e in data["traceEvents"]]
        assert "rollout" in names and "watchdog_stall" in names
        assert read_health(tmp_path / "health.json")["stalled"] is True
        # Recovery clears the flag; a second frozen window fires again.
        t.on_learner_step(4, {})
        assert not t.watchdog.check()
        clock.t = 40.0
        assert t.watchdog.check()
        assert (tmp_path / "stall_stacks.txt").read_text().count(
            "=== stall at"
        ) == 2
        t.close(4)
        stats.close()

    def test_disabled_is_inert(self, tmp_path):
        t = RunTelemetry(
            TelemetryConfig(ENABLED=False), run_dir=tmp_path
        )
        assert t.watchdog is None
        with t.tracer.span("x"):
            pass
        t.on_rollout(1, 1)
        assert t.on_learner_step(1, {"Loss/total_loss": float("nan")}) == []
        t.on_tick(1, 0)
        t.start()
        t.close(1)
        assert not (tmp_path / "health.json").exists()
        assert not (tmp_path / "trace.json").exists()


class TestAnomalyDetector:
    def test_quiet_on_noisy_stationary_series(self):
        det = AnomalyDetector(z_threshold=6.0, warmup=20)
        rng = np.random.default_rng(0)
        fired = []
        for step, v in enumerate(2.0 + 0.1 * rng.standard_normal(500)):
            fired += det.observe("Loss/total_loss", float(v), step)
        assert fired == []

    def test_spike_fires_at_the_right_step(self):
        det = AnomalyDetector(z_threshold=6.0, warmup=20)
        rng = np.random.default_rng(1)
        series = 2.0 + 0.1 * rng.standard_normal(300)
        series[150] = 10.0  # injected loss spike
        fired = []
        for step, v in enumerate(series):
            fired += det.observe("Loss/total_loss", float(v), step)
        assert [a.step for a in fired] == [150]
        a = fired[0]
        assert a.kind == "spike" and a.zscore > 6.0
        assert a.window  # recent context travels with the anomaly
        assert "sigma" in a.describe()

    def test_grad_norm_explosion(self):
        det = AnomalyDetector(z_threshold=6.0, warmup=20)
        fired = []
        for step in range(100):
            v = 0.5 if step != 80 else 500.0
            fired += det.observe("Loss/Grad_Norm", v, step)
        assert [a.step for a in fired] == [80]

    def test_nonfinite_fires_and_does_not_poison_baseline(self):
        det = AnomalyDetector(z_threshold=6.0, warmup=10)
        fired = []
        for step in range(30):
            v = float("nan") if step == 20 else 1.0
            fired += det.observe("Loss/total_loss", v, step)
        kinds = [(a.kind, a.step) for a in fired]
        assert kinds == [("nonfinite", 20)]  # no trailing spike

    def test_entropy_collapse_latches_and_rearms(self):
        det = AnomalyDetector(warmup=5, entropy_floor=0.01)
        fired = []
        series = [1.0] * 10 + [0.001] * 5 + [1.0] * 5 + [0.0] * 3
        for step, v in enumerate(series):
            fired += det.observe("Loss/Entropy", v, step)
        collapses = [a for a in fired if a.kind == "collapse"]
        # Once per excursion: steps 10 and 20.
        assert [a.step for a in collapses] == [10, 20]

    def test_constant_series_never_spikes(self):
        det = AnomalyDetector(z_threshold=6.0, warmup=5)
        fired = []
        for step in range(200):
            fired += det.observe("LearningRate", 3e-4, step)
        assert fired == []


def _build_components(tmp_path, cfgs, run_name, telemetry_config=None, **kw):
    from alphatriangle_tpu.training import setup_training_components
    from tests.test_training_loop import make_train_cfg

    env_cfg, model_cfg, mcts_cfg = cfgs
    tc = make_train_cfg(run_name, str(tmp_path), **kw)
    pc = PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME=run_name)
    return setup_training_components(
        train_config=tc,
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        persistence_config=pc,
        telemetry_config=telemetry_config,
        use_tensorboard=False,
    )


class TestTrainingSmoke:
    """Acceptance bar: a tiny CPU training session with telemetry on
    emits a Chrome-loadable trace.json and an advancing heartbeat, and
    the health CLI gates on it."""

    def test_cpu_run_emits_trace_and_heartbeat(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
    ):
        from alphatriangle_tpu.training import LoopStatus, TrainingLoop

        c = _build_components(
            tmp_path,
            (tiny_env_config, tiny_model_config, tiny_mcts_config),
            run_name="telemetry_smoke",
            MAX_TRAINING_STEPS=4,
        )
        assert c.telemetry is not None and c.telemetry.enabled
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        run_dir = c.persistence_config.get_run_base_dir()

        # Heartbeat: learner step advanced from 0 to the horizon.
        payload = read_health(run_dir / "health.json")
        assert payload is not None
        assert payload["learner_step"] == 4
        assert payload["buffer_size"] > 0
        assert payload["episodes_played"] >= 0
        assert payload["stalled"] is False

        # Chrome trace: the loop phases appear as complete events with
        # ph/ts/tid/dur fields.
        data = json.loads((run_dir / "trace.json").read_text())
        events = data["traceEvents"]
        spans = [e for e in events if e.get("ph") == "X"]
        assert spans
        for ev in spans:
            assert {"name", "ph", "ts", "dur", "pid", "tid"} <= set(ev)
        names = {e["name"] for e in spans}
        assert {"rollout", "sample", "train", "checkpoint"} <= names
        assert "weight_sync" in names

        # The watchdog thread shut down with the loop.
        assert not any(
            t.name == "telemetry-watchdog" and t.is_alive()
            for t in threading.enumerate()
        )

        # CLI verdicts: live now, stale once the heartbeat ages out.
        rc = cli.main(
            ["health", "telemetry_smoke", "--root-dir", str(tmp_path)]
        )
        assert rc == 0
        stale = dict(payload, time=payload["time"] - 10_000)
        (run_dir / "health.json").write_text(json.dumps(stale))
        rc = cli.main(
            ["health", "telemetry_smoke", "--root-dir", str(tmp_path)]
        )
        assert rc == 1
        # Span-trace summary renders from the same run.
        rc = cli.main(
            ["trace", "telemetry_smoke", "--root-dir", str(tmp_path)]
        )
        assert rc == 0

        c.stats.close()
        c.checkpoints.close()

    def test_telemetry_opt_out(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
    ):
        from alphatriangle_tpu.training import LoopStatus, TrainingLoop

        c = _build_components(
            tmp_path,
            (tiny_env_config, tiny_model_config, tiny_mcts_config),
            run_name="no_telemetry",
            MAX_TRAINING_STEPS=2,
            telemetry_config=TelemetryConfig(ENABLED=False),
        )
        loop = TrainingLoop(c)
        assert loop.run() == LoopStatus.COMPLETED
        run_dir = c.persistence_config.get_run_base_dir()
        assert not (run_dir / "health.json").exists()
        assert not (run_dir / "trace.json").exists()
        c.stats.close()
        c.checkpoints.close()
