"""Stats collector + Orbax persistence tests (trieye-equivalent surface;
the resume bar: kill a run mid-training, rerun, resume)."""

import numpy as np
import pytest

from alphatriangle_tpu.config import PersistenceConfig, TrainConfig
from alphatriangle_tpu.nn.network import NeuralNetwork
from alphatriangle_tpu.rl import ExperienceBuffer, Trainer
from alphatriangle_tpu.stats import (
    CheckpointManager,
    RawMetricEvent,
    StatsCollector,
)


class TestCollector:
    def test_aggregates_means_per_tick(self, tmp_path):
        col = StatsCollector(log_dir=tmp_path / "tb")
        col.log_scalar("Loss/Total", 4.0, step=1)
        col.log_scalar("Loss/Total", 2.0, step=1)
        col.log_event(RawMetricEvent(name="score", value=7.0, global_step=1))
        means = col.process_and_log(1)
        assert means["Loss/Total"] == pytest.approx(3.0)
        assert means["score"] == pytest.approx(7.0)
        # Window cleared after the tick.
        assert col.process_and_log(2) == {}
        assert col.get_series("Loss/Total") == [(1, 3.0)]
        assert col.latest("score") == 7.0
        col.close()

    def test_nonfinite_dropped_counted_not_silent(self, tmp_path, caplog):
        import logging

        col = StatsCollector(log_dir=tmp_path / "tb")
        with caplog.at_level(logging.WARNING):
            col.log_scalar("x", float("nan"))
            col.log_scalar("x", float("inf"))
            col.log_scalar("y", float("nan"))
        # Dropped from aggregation, but surfaced: cumulative count as a
        # scalar on each tick, per-name counts introspectable, and one
        # warning per metric name (not one per value, not silence).
        means = col.process_and_log(0)
        assert means == {"Stats/nonfinite_dropped": 3.0}
        assert col.nonfinite_dropped() == {"x": 2, "y": 1}
        warnings = [
            r for r in caplog.records if "Non-finite" in r.getMessage()
        ]
        assert len(warnings) == 2  # once for x, once for y
        # Counter is cumulative and keeps appearing on later ticks.
        col.log_scalar("z", 1.0, step=1)
        means = col.process_and_log(1)
        assert means["Stats/nonfinite_dropped"] == 3.0
        assert means["z"] == 1.0
        col.close()

    def test_no_drops_no_counter_metric(self, tmp_path):
        col = StatsCollector(log_dir=tmp_path / "tb")
        col.log_scalar("x", 1.0)
        assert "Stats/nonfinite_dropped" not in col.process_and_log(0)
        col.close()

    def test_close_flushes_pending_events(self, tmp_path):
        """Trailing sub-interval metrics must not be silently lost at
        shutdown: close() runs a final process_and_log at the newest
        step seen, and the tick sink receives it."""
        col = StatsCollector(log_dir=tmp_path / "tb")
        sink_calls = []
        col.set_tick_sink(lambda step, means: sink_calls.append((step, means)))
        col.log_scalar("m", 1.0, step=3)
        col.log_scalar("late", 9.0, step=7)  # never ticked
        col.close()
        assert col.latest("late") == 9.0
        assert col.get_series("late") == [(7, 9.0)]
        assert sink_calls and sink_calls[-1][0] == 7
        assert sink_calls[-1][1]["late"] == 9.0
        # Idempotent: a second close neither flushes nor raises.
        n = len(sink_calls)
        col.close()
        assert len(sink_calls) == n

    def test_tick_sink_receives_every_tick_and_never_raises(self, tmp_path):
        col = StatsCollector(log_dir=tmp_path / "tb")

        def bad_sink(step, means):
            raise RuntimeError("sink down")

        col.set_tick_sink(bad_sink)
        col.log_scalar("m", 1.0, step=1)
        # A failing sink must not break the tick.
        assert col.process_and_log(1)["m"] == 1.0
        col.close()

    def test_atexit_registration_cleared_on_close(self, tmp_path):
        import atexit

        col = StatsCollector(log_dir=tmp_path / "tb")
        col.close()
        # Unregistered: atexit must not re-run close on a closed
        # collector at interpreter exit (would resurrect the writer).
        atexit.unregister(col._atexit_cb)  # no-op if already done

    def test_tensorboard_files_written(self, tmp_path):
        col = StatsCollector(log_dir=tmp_path / "tb")
        col.log_scalar("m", 1.0, 0)
        col.process_and_log(0)
        col.close()
        assert list((tmp_path / "tb").glob("events.out.tfevents.*"))

    def test_history_bounded(self, tmp_path):
        col = StatsCollector(log_dir=tmp_path / "tb", history_limit=4)
        for step in range(10):
            col.log_scalar("m", float(step), step)
            col.process_and_log(step)
        series = col.get_series("m")
        assert len(series) == 4
        assert series == [(6, 6.0), (7, 7.0), (8, 8.0), (9, 9.0)]
        col.close()

    def test_log_params_writes_text(self, tmp_path, tiny_env_config):
        col = StatsCollector(log_dir=tmp_path / "tb")
        col.log_params({"env": tiny_env_config, "plain": {"k": 1}})
        col.close()
        files = list((tmp_path / "tb").glob("events.out.tfevents.*"))
        assert files and files[0].stat().st_size > 0

    def test_mlflow_mirroring_when_available(self, tmp_path, monkeypatch):
        """With a tracking URI configured and mlflow importable, metrics
        and params mirror to it (absent mlflow degrades to TB-only)."""
        import sys
        import types

        calls = {"metrics": [], "params": [], "runs": 0, "ended": 0}
        fake = types.ModuleType("mlflow")
        fake.set_tracking_uri = lambda uri: calls.setdefault("uri", uri)
        fake.start_run = lambda run_name=None: (
            calls.__setitem__("runs", calls["runs"] + 1) or object()
        )
        fake.log_metrics = lambda m, step=None: calls["metrics"].append(
            (m, step)
        )
        fake.log_params = lambda p: calls["params"].append(p)
        fake.end_run = lambda: calls.__setitem__(
            "ended", calls["ended"] + 1
        )
        monkeypatch.setitem(sys.modules, "mlflow", fake)

        cfg = PersistenceConfig(
            ROOT_DATA_DIR=str(tmp_path),
            RUN_NAME="ml_run",
            MLFLOW_TRACKING_URI="file:///tmp/mlruns",
        )
        col = StatsCollector(cfg)
        assert calls["runs"] == 1 and calls["uri"] == "file:///tmp/mlruns"
        col.log_scalar("Loss/Total", 2.0, step=3)
        col.process_and_log(3)
        assert calls["metrics"] == [({"Loss.Total": 2.0}, 3)]
        col.log_params({"train": {"BATCH_SIZE": 8}})
        assert calls["params"] == [{"train.BATCH_SIZE": "8"}]
        col.close()
        assert calls["ended"] == 1


def per_cfg(tmp_path, run="run_a") -> PersistenceConfig:
    return PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME=run)


class TestCheckpointManager:
    def test_train_state_roundtrip(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        from tests.test_trainer import make_batch

        trainer.train_step(make_batch())
        mgr = CheckpointManager(per_cfg(tmp_path))
        counters = {"episodes_played": 5, "total_simulations": 99}
        mgr.save(1, trainer.state, counters=counters)
        mgr.wait_until_finished()

        # Fresh process-equivalent: new net/trainer, restore by template.
        net2 = NeuralNetwork(tiny_model_config, tiny_env_config, seed=123)
        trainer2 = Trainer(net2, tiny_train_config)
        loaded = mgr.restore(trainer2.state)
        assert loaded.global_step == 1
        assert loaded.counters["episodes_played"] == 5
        trainer2.set_state(loaded.train_state)
        import jax

        for a, b in zip(
            jax.tree_util.tree_leaves(trainer.state.params),
            jax.tree_util.tree_leaves(trainer2.state.params),
            strict=True,
        ):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
        assert int(trainer2.state.step) == 1

    def test_checkpoint_retention_prunes_oldest(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        cfg = per_cfg(tmp_path).model_copy(
            update={"KEEP_LAST_CHECKPOINTS": 2, "KEEP_LAST_BUFFERS": 1}
        )
        mgr = CheckpointManager(cfg)
        for step in (1, 2, 3, 4):
            mgr.save(step, trainer.state)
        mgr.wait_until_finished()
        kept = sorted(
            p.name
            for p in cfg.get_checkpoint_dir().iterdir()
            if p.is_dir()
        )
        assert kept == ["step_00000003", "step_00000004"]
        # Meta files pruned alongside their checkpoint dirs.
        metas = sorted(
            p.name for p in cfg.get_checkpoint_dir().glob("*.meta.json")
        )
        assert metas == ["step_00000003.meta.json", "step_00000004.meta.json"]
        # Restore still lands on the newest survivor.
        assert mgr.latest_step() == 4

        tc = TrainConfig(
            BATCH_SIZE=4, BUFFER_CAPACITY=64, MIN_BUFFER_SIZE_TO_TRAIN=8,
            MAX_TRAINING_STEPS=10, RUN_NAME="t",
        )
        from tests.test_buffer import make_dense

        buf = ExperienceBuffer(tc)
        buf.add_dense(*make_dense(4))
        for step in (1, 2, 3):
            mgr.save_buffer(step, buf)
        spills = sorted(p.name for p in cfg.get_buffer_dir().iterdir())
        assert spills == ["buffer_00000003.npz"]

    def test_retention_zero_keeps_everything(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        cfg = per_cfg(tmp_path).model_copy(
            update={"KEEP_LAST_CHECKPOINTS": 0}
        )
        mgr = CheckpointManager(cfg)
        for step in (1, 2, 3):
            mgr.save(step, trainer.state)
        mgr.wait_until_finished()
        dirs = [p for p in cfg.get_checkpoint_dir().iterdir() if p.is_dir()]
        assert len(dirs) == 3

    def test_restore_empty_run(self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        mgr = CheckpointManager(per_cfg(tmp_path))
        loaded = mgr.restore(trainer.state)
        assert loaded.train_state is None
        assert loaded.global_step == 0

    def test_buffer_spill_roundtrip(self, tmp_path):
        tc = TrainConfig(
            BATCH_SIZE=4, BUFFER_CAPACITY=64, MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=True, PER_BETA_ANNEAL_STEPS=10, MAX_TRAINING_STEPS=10,
            RUN_NAME="t",
        )
        from tests.test_buffer import make_dense

        buf = ExperienceBuffer(tc)
        buf.add_dense(*make_dense(20, value=2.5))
        buf.update_priorities(np.arange(20), np.linspace(0.5, 3.0, 20))
        mgr = CheckpointManager(per_cfg(tmp_path))
        mgr.save_buffer(7, buf)

        buf2 = ExperienceBuffer(tc)
        assert mgr.restore_buffer(buf2)
        assert len(buf2) == 20
        np.testing.assert_array_equal(
            buf2._storage["value_target"][:20],
            buf._storage["value_target"][:20],
        )
        np.testing.assert_allclose(
            buf2.tree.tree[buf2.tree._cap2 : buf2.tree._cap2 + 20],
            buf.tree.tree[buf.tree._cap2 : buf.tree._cap2 + 20],
        )

    def test_restore_explicit_path(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        mgr = CheckpointManager(per_cfg(tmp_path))
        mgr.save(5, trainer.state, counters={"episodes_played": 2})
        mgr.wait_until_finished()
        path = per_cfg(tmp_path).get_checkpoint_dir() / "step_00000005"

        net2 = NeuralNetwork(tiny_model_config, tiny_env_config, seed=9)
        trainer2 = Trainer(net2, tiny_train_config)
        mgr2 = CheckpointManager(per_cfg(tmp_path, "other_run"))
        loaded = mgr2.restore_path(path, trainer2.state)
        assert loaded.global_step == 5
        assert loaded.counters["episodes_played"] == 2
        with pytest.raises(FileNotFoundError):
            mgr2.restore_path(tmp_path / "nope", trainer2.state)

    def test_restore_buffer_explicit_path(self, tmp_path):
        tc = TrainConfig(
            BATCH_SIZE=4, BUFFER_CAPACITY=64, MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=False, MAX_TRAINING_STEPS=10, RUN_NAME="t",
        )
        from tests.test_buffer import make_dense

        buf = ExperienceBuffer(tc)
        buf.add_dense(*make_dense(10))
        mgr = CheckpointManager(per_cfg(tmp_path))
        spill = mgr.save_buffer(3, buf)
        buf2 = ExperienceBuffer(tc)
        assert CheckpointManager.restore_buffer_path(buf2, spill)
        assert len(buf2) == 10
        with pytest.raises(FileNotFoundError):
            CheckpointManager.restore_buffer_path(buf2, tmp_path / "nope.npz")

    def test_latest_step_and_multiple_saves(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        mgr = CheckpointManager(per_cfg(tmp_path))
        mgr.save(3, trainer.state)
        mgr.save(12, trainer.state)
        mgr.wait_until_finished()
        assert mgr.latest_step() == 12

    def test_find_latest_run(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        mgr_a = CheckpointManager(per_cfg(tmp_path, "run_a"))
        mgr_a.save(1, trainer.state)
        mgr_a.wait_until_finished()
        import time

        time.sleep(0.05)
        mgr_b = CheckpointManager(per_cfg(tmp_path, "run_b"))
        mgr_b.save(2, trainer.state)
        mgr_b.wait_until_finished()
        # run_c has dirs but no checkpoints -> ignored.
        CheckpointManager(per_cfg(tmp_path, "run_c"))
        assert CheckpointManager.find_latest_run(per_cfg(tmp_path)) == "run_b"

    def test_save_configs(self, tmp_path, tiny_env_config):
        mgr = CheckpointManager(per_cfg(tmp_path))
        mgr.save_configs({"env": tiny_env_config, "note": "x"})
        import json

        data = json.loads(
            (per_cfg(tmp_path).get_run_base_dir() / "configs.json").read_text()
        )
        assert data["env"]["ROWS"] == 3
        assert data["note"] == "x"


class TestCheckpointIntegrity:
    """Crash-integrity contract (docs/ROBUSTNESS.md): commit markers
    certify fully-landed Orbax trees; restore never trusts a torn one."""

    def _trainer(self, tiny_model_config, tiny_env_config, tiny_train_config):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        return Trainer(net, tiny_train_config)

    def test_commit_marker_lands_without_explicit_wait(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        """The background flusher commits a save as soon as the async
        write finishes — `cli supervise` reads the markers at death
        time, so they must not wait for the NEXT save to settle them."""
        import time

        trainer = self._trainer(
            tiny_model_config, tiny_env_config, tiny_train_config
        )
        cfg = per_cfg(tmp_path)
        mgr = CheckpointManager(cfg)
        mgr.save(1, trainer.state)
        marker = cfg.get_checkpoint_dir() / "step_00000001.commit"
        deadline = time.monotonic() + 30.0
        while not marker.exists() and time.monotonic() < deadline:
            time.sleep(0.02)
        assert marker.exists(), "commit marker never flushed in background"
        mgr.close()

    def test_restore_skips_step_without_commit_marker(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        """A SIGKILL mid-save leaves a step dir with no marker: restore
        must fall back to the previous committed step, not crash and
        not trust the torn tree."""
        import json

        trainer = self._trainer(
            tiny_model_config, tiny_env_config, tiny_train_config
        )
        cfg = per_cfg(tmp_path)
        mgr = CheckpointManager(cfg)
        mgr.save(1, trainer.state)
        mgr.save(2, trainer.state)
        mgr.wait_until_finished()
        # Forge the torn artifact: a half-written step-3 tree + meta,
        # killed before its commit marker.
        ckpts = cfg.get_checkpoint_dir()
        torn = ckpts / "step_00000003"
        torn.mkdir()
        (torn / "partial_array").write_bytes(b"\x00\x01garbage")
        (ckpts / "step_00000003.meta.json").write_text(
            json.dumps({"global_step": 3})
        )
        assert mgr.valid_steps() == [1, 2]
        assert mgr.latest_step() == 2
        loaded = mgr.restore(trainer.state)
        assert loaded.global_step == 2
        mgr.close()

    def test_restore_skips_unparseable_meta(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        trainer = self._trainer(
            tiny_model_config, tiny_env_config, tiny_train_config
        )
        cfg = per_cfg(tmp_path)
        mgr = CheckpointManager(cfg)
        mgr.save(1, trainer.state)
        mgr.save(2, trainer.state)
        mgr.wait_until_finished()
        (cfg.get_checkpoint_dir() / "step_00000002.meta.json").write_text(
            "{torn mid-write"
        )
        assert mgr.valid_steps() == [1]
        loaded = mgr.restore(trainer.state)
        assert loaded.global_step == 1
        mgr.close()

    def test_restore_falls_back_when_committed_tree_unreadable(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        """Belt and braces: even a MARKED step whose tree turns out
        unreadable (disk fault) costs one cadence, not the run. An
        explicitly requested step still raises."""
        import shutil

        trainer = self._trainer(
            tiny_model_config, tiny_env_config, tiny_train_config
        )
        cfg = per_cfg(tmp_path)
        mgr = CheckpointManager(cfg)
        mgr.save(1, trainer.state)
        mgr.save(2, trainer.state)
        mgr.wait_until_finished()
        step2 = cfg.get_checkpoint_dir() / "step_00000002"
        shutil.rmtree(step2)
        step2.mkdir()  # marker present, tree gutted
        loaded = mgr.restore(trainer.state)
        assert loaded.global_step == 1
        with pytest.raises(Exception):
            mgr.restore(trainer.state, step=2)
        mgr.close()

    def test_restore_buffer_falls_back_past_torn_spill(self, tmp_path):
        from tests.test_buffer import make_dense

        tc = TrainConfig(
            BATCH_SIZE=4, BUFFER_CAPACITY=64, MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=False, MAX_TRAINING_STEPS=10, RUN_NAME="t",
        )
        buf = ExperienceBuffer(tc)
        buf.add_dense(*make_dense(10))
        cfg = per_cfg(tmp_path)
        mgr = CheckpointManager(cfg)
        mgr.save_buffer(3, buf)
        # A newer spill torn by a kill mid-write (pre-atomic artifact).
        (cfg.get_buffer_dir() / "buffer_00000009.npz").write_bytes(
            b"PK\x03\x04 torn"
        )
        buf2 = ExperienceBuffer(tc)
        assert mgr.restore_buffer(buf2)
        assert len(buf2) == 10

    def test_find_latest_run_ignores_torn_only_runs(
        self, tmp_path, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        import time

        trainer = self._trainer(
            tiny_model_config, tiny_env_config, tiny_train_config
        )
        mgr_a = CheckpointManager(per_cfg(tmp_path, "run_good"))
        mgr_a.save(1, trainer.state)
        mgr_a.close()
        time.sleep(0.05)
        # run_torn is NEWER but its only step dir has no commit marker
        # (its single marker names a step whose dir is gone).
        cfg_t = per_cfg(tmp_path, "run_torn")
        cfg_t.create_run_dirs()
        ckpts = cfg_t.get_checkpoint_dir()
        (ckpts / "step_00000002").mkdir()
        (ckpts / "step_00000001.commit").write_text('{"global_step": 1}')
        assert (
            CheckpointManager.find_latest_run(per_cfg(tmp_path)) == "run_good"
        )


class _MlflowStub:
    """In-memory mlflow facade: records every mirror call the collector
    makes, so the MLflow channel is pinned even on images where mlflow
    itself cannot be installed (reference treats MLflow as the primary
    tracker, its `training/logging_utils.py:13-35`)."""

    def __init__(self):
        self.tracking_uri = None
        self.run_name = None
        self.metrics: list[tuple[dict, int]] = []
        self.params: dict = {}
        self.ended = False

    def set_tracking_uri(self, uri):
        self.tracking_uri = uri

    def start_run(self, run_name=None):
        self.run_name = run_name
        return object()

    def log_metrics(self, metrics, step=None):
        self.metrics.append((dict(metrics), step))

    def log_params(self, params):
        self.params.update(params)

    def end_run(self):
        self.ended = True


class TestMlflowMirror:
    def _collector(self, tmp_path, monkeypatch, stub):
        import alphatriangle_tpu.stats.collector as collector_mod

        monkeypatch.setattr(
            collector_mod, "_import_mlflow", lambda: stub
        )
        pc = PersistenceConfig(
            ROOT_DATA_DIR=str(tmp_path),
            RUN_NAME="ml_run",
            MLFLOW_TRACKING_URI=f"file://{tmp_path}/mlruns",
        )
        return StatsCollector(pc, use_tensorboard=False)

    def test_metrics_and_params_mirrored(self, tmp_path, monkeypatch):
        stub = _MlflowStub()
        stats = self._collector(tmp_path, monkeypatch, stub)
        assert stub.tracking_uri == f"file://{tmp_path}/mlruns"
        assert stub.run_name == "ml_run"

        stats.log_scalar("Loss/total_loss", 1.5, step=3)
        stats.log_scalar("Loss/total_loss", 2.5, step=3)
        stats.process_and_log(3)
        # Mean of the tick, MLflow-legal metric name ('/' -> '.').
        assert stub.metrics == [({"Loss.total_loss": 2.0}, 3)]

        stats.log_params({"train": TrainConfig(RUN_NAME="ml_run")})
        assert stub.params["train.RUN_NAME"] == "ml_run"
        assert "train.BATCH_SIZE" in stub.params

        stats.close()
        assert stub.ended

    def test_mirror_failure_never_fatal(self, tmp_path, monkeypatch):
        stub = _MlflowStub()

        def boom(metrics, step=None):
            raise RuntimeError("tracking server down")

        stub.log_metrics = boom
        stats = self._collector(tmp_path, monkeypatch, stub)
        stats.log_scalar("Loss/x", 1.0, step=1)
        means = stats.process_and_log(1)  # must not raise
        assert means == {"Loss/x": 1.0}
        stats.close()

    @pytest.mark.skipif(
        __import__("importlib").util.find_spec("mlflow") is None,
        reason="mlflow not installed in this image",
    )
    def test_real_mlflow_file_store(self, tmp_path):
        """End-to-end against a real file-backed mlflow store (runs
        automatically wherever mlflow is importable, e.g. CI with the
        dev extra installed)."""
        pc = PersistenceConfig(
            ROOT_DATA_DIR=str(tmp_path),
            RUN_NAME="ml_real",
            MLFLOW_TRACKING_URI=f"file://{tmp_path}/mlruns",
        )
        stats = StatsCollector(pc, use_tensorboard=False)
        stats.log_scalar("Loss/total_loss", 1.0, step=1)
        stats.process_and_log(1)
        stats.log_params({"train": TrainConfig(RUN_NAME="ml_real")})
        stats.close()
        assert (tmp_path / "mlruns").exists()
