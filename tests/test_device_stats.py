"""Device telemetry plane unit tests (telemetry/device_stats.py):
enable-state + cache signatures, the crash-safe beacon channel (armed
emit -> beacons.jsonl rows -> JAX-free readers), the host-side folds
feeding `kind:"device_stats"` ledger records, RunTelemetry wiring, the
dispatch watchdog's near-deadline warning (the in-process beacon armer),
anomaly latches on search health, and the supervisor's
`TELEMETRY__BEACONS` respawn directive end to end (policy -> runner)."""

import json

import pytest

from alphatriangle_tpu.telemetry.device_stats import (
    BEACONS_FILENAME,
    arm_beacons,
    attach_beacon_run_dir,
    beacon_every,
    beacon_signature,
    beacons_armed,
    describe_beacon,
    device_stats_enabled,
    device_stats_record,
    device_stats_signature,
    disarm_beacons,
    emit_beacon,
    fold_search_stats,
    last_beacon,
    merge_search_folds,
    note_dispatch,
    read_beacons,
    reset_device_stats_state,
    rollout_chunk_stats,
    set_device_stats,
    summarize_device_stats,
)


@pytest.fixture(autouse=True)
def _fresh_state(monkeypatch):
    """Every test starts from import-time defaults with the env arming
    knobs cleared, and leaves no armed state behind for the suite."""
    for var in (
        "ALPHATRIANGLE_DEVICE_STATS",
        "ALPHATRIANGLE_BEACONS",
        "ALPHATRIANGLE_BEACON_EVERY",
    ):
        monkeypatch.delenv(var, raising=False)
    reset_device_stats_state()
    yield
    reset_device_stats_state()


class TestEnableState:
    def test_defaults_off(self):
        assert device_stats_enabled() is False
        assert beacons_armed() is False
        assert device_stats_signature() == ""
        assert beacon_signature() == ""

    def test_set_device_stats_and_signature(self):
        set_device_stats(True)
        assert device_stats_enabled() is True
        assert device_stats_signature() == "|devstats1"

    def test_env_override_wins_over_setter(self, monkeypatch):
        set_device_stats(True)
        monkeypatch.setenv("ALPHATRIANGLE_DEVICE_STATS", "0")
        assert device_stats_enabled() is False
        monkeypatch.setenv("ALPHATRIANGLE_DEVICE_STATS", "1")
        set_device_stats(False)
        assert device_stats_enabled() is True

    def test_env_arms_beacons(self, monkeypatch):
        monkeypatch.setenv("ALPHATRIANGLE_BEACONS", "1")
        monkeypatch.setenv("ALPHATRIANGLE_BEACON_EVERY", "3")
        reset_device_stats_state()
        assert beacons_armed() is True
        assert beacon_every() == 3
        assert beacon_signature() == "|beacons3"

    def test_arm_and_disarm(self):
        arm_beacons(every=5)
        assert beacons_armed() is True
        assert beacon_every() == 5
        disarm_beacons()
        assert beacons_armed() is False
        assert beacon_signature() == ""

    def test_bad_beacon_every_env_falls_back(self, monkeypatch):
        monkeypatch.setenv("ALPHATRIANGLE_BEACON_EVERY", "banana")
        reset_device_stats_state()
        assert beacon_every() == 8  # DEFAULT_BEACON_EVERY


class TestBeaconChannel:
    def test_unarmed_emit_is_pure_noop(self, tmp_path):
        attach_beacon_run_dir(tmp_path)
        emit_beacon("search_wave", 3)
        assert not (tmp_path / BEACONS_FILENAME).exists()

    def test_armed_emit_writes_subsampled_rows(self, tmp_path):
        arm_beacons()
        attach_beacon_run_dir(tmp_path)
        note_dispatch("megastep/t4_k2")
        for k in range(7):
            emit_beacon("search_wave", k, every=3)
        rows = read_beacons(tmp_path / BEACONS_FILENAME)
        assert [r["index"] for r in rows] == [0, 3, 6]
        assert all(r["phase"] == "search_wave" for r in rows)
        assert all(r["program"] == "megastep/t4_k2" for r in rows)

    def test_emit_inside_jit(self, tmp_path):
        """The traced form: `jax.debug.callback` rows land after the
        dispatch completes (async callbacks drained by block_until_ready)."""
        jax = pytest.importorskip("jax")
        import jax.numpy as jnp

        arm_beacons()
        attach_beacon_run_dir(tmp_path)
        note_dispatch("test/jit")

        @jax.jit
        def f(x):
            emit_beacon("learner_step", jnp.int32(4))
            return x * 2

        f(jnp.ones(3)).block_until_ready()
        jax.effects_barrier()
        rows = read_beacons(tmp_path / BEACONS_FILENAME)
        assert rows and rows[-1]["phase"] == "learner_step"
        assert rows[-1]["index"] == 4

    def test_last_beacon_reads_run_dir(self, tmp_path):
        arm_beacons()
        attach_beacon_run_dir(tmp_path)
        emit_beacon("ring_scatter", 2)
        emit_beacon("learner_step", 9)
        newest = last_beacon(tmp_path)
        assert newest["phase"] == "learner_step"
        assert newest["index"] == 9
        assert "phase=learner_step" in describe_beacon(newest)

    def test_describe_beacon_legacy(self):
        assert describe_beacon(None) is None
        assert describe_beacon("junk") is None


class TestFolds:
    def test_fold_search_stats_reduces_stacked(self):
        np = pytest.importorskip("numpy")

        stats = {
            "root_entropy": np.array([1.0, 3.0]),
            "occupancy": np.array([0.25, 0.75]),
            "value_abs_max": np.array([0.5, 2.0]),
            "depth_hist": np.array([[1.0, 0.0], [2.0, 4.0]]),
        }
        fold = fold_search_stats(stats)
        assert fold["root_entropy"] == pytest.approx(2.0)
        assert fold["occupancy"] == pytest.approx(0.5)
        assert fold["value_abs_max"] == pytest.approx(2.0)  # max, not mean
        assert fold["depth_hist"] == [3.0, 4.0]

    def test_fold_empty_is_none(self):
        assert fold_search_stats(None) is None
        assert fold_search_stats({}) is None

    def test_merge_search_folds(self):
        merged = merge_search_folds(
            [
                {"root_entropy": 1.0, "value_abs_max": 0.5,
                 "depth_hist": [1.0, 1.0]},
                None,
                {"root_entropy": 3.0, "value_abs_max": 2.5,
                 "depth_hist": [2.0, 0.0, 4.0]},
            ]
        )
        assert merged["root_entropy"] == pytest.approx(2.0)
        assert merged["value_abs_max"] == pytest.approx(2.5)
        assert merged["depth_hist"] == [3.0, 1.0, 4.0]
        assert merge_search_folds([]) is None

    def test_rollout_chunk_stats(self):
        np = pytest.importorskip("numpy")

        endings = np.array([[0, 1, 0], [0, 0, 1]])  # (T, B)
        rewards = np.array([[0.1, -0.4, 0.0], [2.0, 0.0, 0.3]])
        leg = rollout_chunk_stats(endings, rewards)
        assert leg["terminations_per_step"] == [1, 1]
        assert leg["reward_min"] == pytest.approx(-0.4)
        assert leg["reward_max"] == pytest.approx(2.0)

    def test_record_and_summary_roundtrip(self):
        rec = device_stats_record(
            7,
            program="megastep/t4_k2",
            search={"root_entropy": 1.5, "occupancy": 0.4,
                    "value_abs_max": 0.9},
            learner={"grad_norm_max": 3.0},
            now=123.0,
        )
        assert rec["kind"] == "device_stats"
        assert rec["step"] == 7 and rec["program"] == "megastep/t4_k2"
        summary = summarize_device_stats([rec, rec])
        assert summary["ds_records"] == 2
        assert summary["ds_root_entropy"] == pytest.approx(1.5)
        assert summary["ds_tree_occupancy"] == pytest.approx(0.4)
        assert summary["ds_grad_norm_max"] == pytest.approx(3.0)
        assert summary["ds_reuse_frac"] is None  # leg absent, not invented

    def test_record_all_empty_is_none(self):
        assert device_stats_record(3) is None
        assert device_stats_record(3, search=None, per={}) is None


class TestRunTelemetryWiring:
    def test_record_device_stats_ledgers_and_detects(self, tmp_path, caplog):
        from alphatriangle_tpu.telemetry import RunTelemetry, TelemetryConfig
        from alphatriangle_tpu.telemetry.ledger import read_ledger

        tel = RunTelemetry(
            TelemetryConfig(WATCHDOG_ENABLED=False), run_dir=tmp_path
        )
        with caplog.at_level("WARNING", logger="alphatriangle_tpu.telemetry"):
            rec = tel.record_device_stats(
                4,
                program="megastep/t4_k2",
                search={"root_entropy": 0.0, "occupancy": 1.0,
                        "value_abs_max": 0.5},
            )
        assert rec is not None
        rows = read_ledger(tmp_path / "metrics.jsonl", kinds={"device_stats"})
        assert len(rows) == 1 and rows[0]["step"] == 4
        # entropy collapse + occupancy saturation escalated as anomalies
        text = caplog.text
        assert "collapse" in text and "saturation" in text
        tel.close()

    def test_disabled_record_is_none(self, tmp_path):
        from alphatriangle_tpu.telemetry import RunTelemetry, TelemetryConfig

        tel = RunTelemetry(
            TelemetryConfig(ENABLED=False), run_dir=tmp_path
        )
        assert tel.record_device_stats(1, search={"root_entropy": 1.0}) is None
        assert not (tmp_path / "metrics.jsonl").exists()

    def test_ctor_attaches_beacon_run_dir(self, tmp_path):
        from alphatriangle_tpu.telemetry import RunTelemetry, TelemetryConfig

        tel = RunTelemetry(
            TelemetryConfig(WATCHDOG_ENABLED=False), run_dir=tmp_path
        )
        arm_beacons()
        emit_beacon("search_wave", 0)
        assert last_beacon(tmp_path)["phase"] == "search_wave"
        tel.close()


class TestWatchdogWarning:
    def _pair(self, tmp_path, **kw):
        from alphatriangle_tpu.telemetry.flight import (
            FLIGHT_FILENAME,
            DispatchWatchdog,
            FlightRecorder,
        )

        clock = {"t": 0.0}
        wd = DispatchWatchdog(
            tmp_path, exit_on_wedge=False, clock=lambda: clock["t"], **kw
        )
        rec = FlightRecorder(
            tmp_path / FLIGHT_FILENAME, watchdog=wd,
            min_deadline_s=5.0, first_deadline_s=10.0,
        )
        return clock, wd, rec

    def test_warn_fires_once_before_wedge(self, tmp_path):
        warned = []
        clock, wd, rec = self._pair(
            tmp_path, warn_fraction=0.5, on_warn=warned.append
        )
        rec.begin("learner", "learner_step")
        clock["t"] += 4.0  # 40% of the 10s first deadline: quiet
        assert wd.check() is None
        assert not warned
        clock["t"] += 2.0  # 60%: past the warn fraction, under deadline
        assert wd.check() is None
        assert len(warned) == 1 and warned[0]["program"] == "learner_step"
        clock["t"] += 1.0
        assert wd.check() is None  # warn latched per dispatch
        assert len(warned) == 1
        assert wd.warn_count == 1
        clock["t"] += 5.0  # past the deadline: the wedge still fires
        assert wd.check() is not None

    def test_no_warn_without_fraction(self, tmp_path):
        clock, wd, rec = self._pair(tmp_path)
        rec.begin("learner", "learner_step")
        clock["t"] += 9.0
        assert wd.check() is None
        assert wd.warn_count == 0

    def test_warn_hook_error_never_raises(self, tmp_path):
        def boom(info):
            raise RuntimeError("hook exploded")

        clock, wd, rec = self._pair(
            tmp_path, warn_fraction=0.5, on_warn=boom
        )
        rec.begin("learner", "learner_step")
        clock["t"] += 6.0
        assert wd.check() is None
        assert wd.warn_count == 1

    def test_telemetry_warn_arms_beacons(self, tmp_path):
        from alphatriangle_tpu.telemetry import RunTelemetry, TelemetryConfig

        tel = RunTelemetry(
            TelemetryConfig(
                WATCHDOG_ENABLED=False, BEACON_EVERY_N_WAVES=2
            ),
            run_dir=tmp_path,
        )
        assert beacons_armed() is False
        tel._on_dispatch_warn(
            {"program": "megastep/t4_k2", "elapsed_s": 3.0,
             "deadline_s": 5.0, "family": "megastep", "seq": 1}
        )
        assert beacons_armed() is True
        assert beacon_every() == 2
        tel.close()


class TestAnomalySearchHealth:
    def test_collapse_and_saturation_latch_once(self):
        from alphatriangle_tpu.telemetry.anomaly import AnomalyDetector

        det = AnomalyDetector()
        first = det.observe_search(
            {"root_entropy": 0.0, "occupancy": 1.0, "value_abs_max": 1.0}, 5
        )
        assert {a.kind for a in first} == {"collapse", "saturation"}
        again = det.observe_search(
            {"root_entropy": 0.0, "occupancy": 1.0}, 6
        )
        assert again == []

    def test_healthy_leg_is_quiet(self):
        from alphatriangle_tpu.telemetry.anomaly import AnomalyDetector

        det = AnomalyDetector()
        for step in range(12):
            assert (
                det.observe_search(
                    {"root_entropy": 1.4, "occupancy": 0.3,
                     "value_abs_max": 0.9},
                    step,
                )
                == []
            )

    def test_value_explosion_screened(self):
        from alphatriangle_tpu.telemetry.anomaly import AnomalyDetector

        det = AnomalyDetector(warmup=4, z_threshold=4.0)
        for step in range(30):
            det.observe_search(
                {"value_abs_max": 1.0 + 0.01 * (step % 3)}, step
            )
        hits = det.observe_search({"value_abs_max": 500.0}, 30)
        assert any(a.kind == "spike" for a in hits)


class TestSupervisorDirective:
    def test_policy_arms_beacons_on_wedge(self):
        from alphatriangle_tpu.supervise import RecoveryPolicy

        policy = RecoveryPolicy(backoff_base_s=0.1)
        action = policy.decide(
            verdict="dispatch-hung", exit_code=113, family="megastep"
        )
        assert action.kind == "restart"
        assert action.overrides.get("TELEMETRY__BEACONS") is True
        assert "beacons" in action.reason
        # Second wedge keeps the override without re-announcing it.
        again = policy.decide(
            verdict="dispatch-hung", exit_code=113, family="megastep",
            progress_step=4,
        )
        assert again.overrides.get("TELEMETRY__BEACONS") is True
        assert "arming progress beacons" not in again.reason

    def test_clean_crash_does_not_arm(self):
        from alphatriangle_tpu.supervise import RecoveryPolicy

        policy = RecoveryPolicy(backoff_base_s=0.1)
        action = policy.decide(verdict="crashed", exit_code=1)
        assert "TELEMETRY__BEACONS" not in (action.overrides or {})

    def test_runner_pops_directive_and_arms(self, monkeypatch):
        from alphatriangle_tpu.config import TrainConfig
        from alphatriangle_tpu.training.runner import (
            SUPERVISE_OVERRIDES_ENV,
            _apply_supervise_overrides,
        )

        tc = TrainConfig(RUN_NAME="directive_probe")
        monkeypatch.setenv(
            SUPERVISE_OVERRIDES_ENV,
            json.dumps({"TELEMETRY__BEACONS": True}),
        )
        out = _apply_supervise_overrides(tc)
        # The reserved key is NOT a TrainConfig field: it must be popped
        # (no validation error) and the config returned unchanged.
        assert out.RUN_NAME == "directive_probe"
        assert beacons_armed() is True

    def test_runner_mixes_directive_with_real_overrides(self, monkeypatch):
        from alphatriangle_tpu.config import TrainConfig
        from alphatriangle_tpu.training.runner import (
            SUPERVISE_OVERRIDES_ENV,
            _apply_supervise_overrides,
        )

        tc = TrainConfig(RUN_NAME="directive_mix", FUSED_LEARNER_STEPS=4)
        monkeypatch.setenv(
            SUPERVISE_OVERRIDES_ENV,
            json.dumps(
                {"TELEMETRY__BEACONS": True, "FUSED_LEARNER_STEPS": 1}
            ),
        )
        out = _apply_supervise_overrides(tc)
        assert out.FUSED_LEARNER_STEPS == 1
        assert beacons_armed() is True
