"""Loop/runner integration tests (reference
`tests/training/test_loop_integration.py:328-428` — but with REAL
components instead of mocks: a tiny-config
end-to-end run on CPU, then kill + resume)."""

import numpy as np
import pytest

from alphatriangle_tpu.config import (
    PersistenceConfig,
    TrainConfig,
    expected_other_features_dim,
)
from alphatriangle_tpu.config.env_config import EnvConfig
from alphatriangle_tpu.config.mcts_config import AlphaTriangleMCTSConfig
from alphatriangle_tpu.config.model_config import ModelConfig
from alphatriangle_tpu.training import (
    LoopStatus,
    TrainingLoop,
    run_training,
    setup_training_components,
)


@pytest.fixture(scope="module")
def tiny_world_configs(tiny_env_config, tiny_model_config, tiny_mcts_config):
    return tiny_env_config, tiny_model_config, tiny_mcts_config


def make_train_cfg(run_name: str, root: str, **kw) -> TrainConfig:
    base = dict(
        RUN_NAME=run_name,
        AUTO_RESUME_LATEST=False,
        MAX_TRAINING_STEPS=8,
        SELF_PLAY_BATCH_SIZE=4,
        ROLLOUT_CHUNK_MOVES=4,
        BATCH_SIZE=8,
        BUFFER_CAPACITY=2000,
        MIN_BUFFER_SIZE_TO_TRAIN=16,
        USE_PER=True,
        PER_BETA_ANNEAL_STEPS=8,
        N_STEP_RETURNS=2,
        WORKER_UPDATE_FREQ_STEPS=2,
        CHECKPOINT_SAVE_FREQ_STEPS=4,
        MAX_EPISODE_MOVES=30,
        RANDOM_SEED=5,
    )
    base.update(kw)
    return TrainConfig(**base)


def build(tmp_path, cfgs, run_name="loop_run", **kw):
    env_cfg, model_cfg, mcts_cfg = cfgs
    tc = make_train_cfg(run_name, str(tmp_path), **kw)
    pc = PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME=run_name)
    return setup_training_components(
        train_config=tc,
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        persistence_config=pc,
        use_tensorboard=False,
    )


class TestLoop:
    def test_end_to_end_tiny_run(self, tmp_path, tiny_world_configs, monkeypatch):
        # Peak override: CPU has no table entry, so without it the
        # utilization records would carry mfu null (acceptance bar:
        # a smoke run produces a non-null MFU via the override).
        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")
        c = build(tmp_path, tiny_world_configs)
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 8
        assert loop.episodes_played > 0
        # Weight sync cadence honored (every 2 steps -> 4 updates).
        assert loop.weight_updates == 4
        assert c.net.weights_version == 4
        # Metrics flowed through the collector.
        assert c.stats.latest("Loss/total_loss") is not None
        assert c.stats.latest("Buffer/Size") > 0
        # The stats value is a per-tick mean and an iteration can cover
        # several learner steps; the anneal endpoint itself must be exact.
        assert c.stats.latest("PER/Beta") == pytest.approx(1.0, abs=0.1)
        assert c.buffer.beta(loop.global_step) == pytest.approx(1.0)
        # Checkpoints: cadence (step 4) + final (step 8).
        assert c.checkpoints.latest_step() == 8
        steps = sorted(
            int(p.name.split("_")[1])
            for p in c.persistence_config.get_checkpoint_dir().iterdir()
            if p.is_dir()
        )
        assert 4 in steps and 8 in steps
        # Metrics ledger (docs/OBSERVABILITY.md "Ledger"): the run dir
        # holds a parseable metrics.jsonl whose tick records advance
        # and whose utilization records carry a non-null MFU.
        import json

        ledger = c.persistence_config.get_run_base_dir() / "metrics.jsonl"
        assert ledger.exists()
        records = [
            json.loads(line) for line in ledger.read_text().splitlines()
        ]
        ticks = [r for r in records if r["kind"] == "tick"]
        utils = [r for r in records if r["kind"] == "util"]
        assert ticks and utils
        tick_steps = [r["step"] for r in ticks]
        assert tick_steps == sorted(tick_steps)
        assert tick_steps[-1] > tick_steps[0]  # the ledger advanced
        assert any("Loss/total_loss" in r["means"] for r in ticks)
        for r in utils:
            assert r["mfu"] is not None
            assert r["peak_source"] == "env"
            assert r["learner_steps_per_sec"] >= 0
        assert utils[-1]["step"] == 8
        # Health heartbeat records the device identity + utilization.
        health = json.loads(
            (
                c.persistence_config.get_run_base_dir() / "health.json"
            ).read_text()
        )
        assert health["device_kind"] == "cpu"
        assert health["peak_bf16_tflops"] == 1.0
        assert health["utilization"] is not None
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_fused_learner_steps_run(self, tmp_path, tiny_world_configs):
        """FUSED_LEARNER_STEPS>1 completes the same run; cadences use
        crossing checks because steps advance by the group size."""
        c = build(
            tmp_path, tiny_world_configs, run_name="fused_run",
            FUSED_LEARNER_STEPS=3,
        )
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 8
        # Weight sync: one sync per group that crosses a freq-2
        # multiple (group boundaries depend on harvest sizes, so the
        # count is bounded, not exact: 8 steps in groups of <=3 means
        # at least ceil(8/3)=3 boundary checks, at most the per-step 4).
        assert 2 <= loop.weight_updates <= 4
        assert c.net.weights_version == loop.weight_updates
        # Checkpoint crossing (freq 4) + final save at 8.
        steps = sorted(
            int(p.name.split("_")[1])
            for p in c.persistence_config.get_checkpoint_dir().iterdir()
            if p.is_dir()
        )
        assert steps[-1] == 8
        assert any(4 <= s <= 8 for s in steps)
        assert c.stats.latest("Loss/total_loss") is not None
        c.stats.close()
        c.checkpoints.close()

    def test_sp_mesh_end_to_end(self, tmp_path, tiny_world_configs):
        """setup wires ring attention automatically when the mesh has a
        real sp axis; the whole loop (self-play search included) runs
        sequence-sharded on (dp=4, sp=2)."""
        from alphatriangle_tpu.config import MeshConfig

        env_cfg, model_cfg, mcts_cfg = tiny_world_configs
        tc = make_train_cfg("sp_run", str(tmp_path), MAX_TRAINING_STEPS=2)
        pc = PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME="sp_run")
        c = setup_training_components(
            train_config=tc,
            env_config=env_cfg,
            model_config=model_cfg,
            mcts_config=mcts_cfg,
            mesh_config=MeshConfig(DP_SIZE=4, SP_SIZE=2),
            persistence_config=pc,
            use_tensorboard=False,
        )
        assert c.net.model.attention_fn is not None
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 2
        assert loop.episodes_played >= 0
        c.stats.close()
        c.checkpoints.close()

    def test_stop_event(self, tmp_path, tiny_world_configs):
        c = build(
            tmp_path, tiny_world_configs, run_name="stop_run",
            MAX_TRAINING_STEPS=1000, BUFFER_CAPACITY=200_000,
            MIN_BUFFER_SIZE_TO_TRAIN=100_000,
        )
        loop = TrainingLoop(c)
        loop.stop_event.set()
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 0
        c.stats.close()
        c.checkpoints.close()


class TestAsyncLoop:
    def test_async_end_to_end(self, tmp_path, tiny_world_configs):
        """Overlapped mode reaches MAX_TRAINING_STEPS with the same
        cadence guarantees as the synchronous loop."""
        c = build(
            tmp_path, tiny_world_configs, run_name="async_run",
            ASYNC_ROLLOUTS=True, REPLAY_RATIO=1.0,
        )
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 8
        # Weight sync cadence pinned in async mode too (every 2 -> 4).
        assert loop.weight_updates == 4
        assert c.net.weights_version == 4
        assert c.stats.latest("Loss/total_loss") is not None
        # Async gauges exported.
        assert c.stats.latest("System/Rollout_Queue_Depth") is not None
        # Checkpoints: cadence (step 4) + final (step 8).
        assert c.checkpoints.latest_step() == 8
        # Producer thread shut down cleanly.
        import threading

        assert not any(
            t.name == "self-play-producer" and t.is_alive()
            for t in threading.enumerate()
        )
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_multi_stream_producers(self, tmp_path, tiny_world_configs):
        """NUM_SELF_PLAY_WORKERS=2 runs two independent rollout
        streams into the shared queue (the reference's worker fan-out,
        worker_manager.py:39-75, as producer threads)."""
        c = build(
            tmp_path, tiny_world_configs, run_name="multi_stream",
            ASYNC_ROLLOUTS=True, NUM_SELF_PLAY_WORKERS=2,
            MAX_TRAINING_STEPS=4,
        )
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 4
        assert loop.experiences_added > 0
        assert len(c.buffer) > 0
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_all_features_compose(self, tmp_path, tiny_world_configs):
        """Cross-feature integration: Gumbel root search + playout cap
        randomization + fused learner groups + overlapped multi-stream
        + PER, all in one run. Guards against pairwise-tested features
        breaking in combination."""
        env_cfg, model_cfg, mcts_cfg = tiny_world_configs
        pcr_gumbel_cfg = type(mcts_cfg)(
            **{
                **mcts_cfg.model_dump(),
                "root_selection": "gumbel",
                "gumbel_m": 4,
                "fast_simulations": 2,
                "full_search_prob": 0.5,
            }
        )
        c = build(
            tmp_path,
            (env_cfg, model_cfg, pcr_gumbel_cfg),
            run_name="combo_run",
            ASYNC_ROLLOUTS=True,
            NUM_SELF_PLAY_WORKERS=2,
            FUSED_LEARNER_STEPS=2,
            MAX_TRAINING_STEPS=4,
        )
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 4
        assert loop.experiences_added > 0
        # PCR default drops fast rows: everything in the buffer is
        # policy-trainable.
        sample = c.buffer.sample(4, current_train_step=4)
        assert sample is not None
        assert np.all(sample["batch"]["policy_weight"] == 1.0)
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_replay_ratio_gate(self, tmp_path, tiny_world_configs):
        """The learner never consumes more than REPLAY_RATIO allows."""
        ratio = 0.5
        c = build(
            tmp_path, tiny_world_configs, run_name="ratio_run",
            ASYNC_ROLLOUTS=True, REPLAY_RATIO=ratio,
        )
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        consumed = loop._steps_this_run * c.train_config.BATCH_SIZE
        assert consumed <= loop.experiences_added * ratio + 1e-9
        assert loop.experiences_added > 0
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_pipeline_disabled_still_completes(
        self, tmp_path, tiny_world_configs
    ):
        """PIPELINE_LEARNER=False restores the strictly serial
        dispatch-then-fetch path."""
        c = build(
            tmp_path, tiny_world_configs, run_name="serial_async",
            ASYNC_ROLLOUTS=True, PIPELINE_LEARNER=False,
            MAX_TRAINING_STEPS=4,
        )
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 4
        assert not loop._inflight
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_pipelined_fused_groups(self, tmp_path, tiny_world_configs):
        """Pipelined pump + fused groups: steps, cadences and the final
        checkpoint all land; nothing is left inflight."""
        c = build(
            tmp_path, tiny_world_configs, run_name="pipelined_run",
            ASYNC_ROLLOUTS=True, FUSED_LEARNER_STEPS=2,
            MAX_TRAINING_STEPS=8,
        )
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 8
        assert not loop._inflight
        assert c.checkpoints.latest_step() == 8
        assert c.stats.latest("Loss/total_loss") is not None
        c.stats.close()
        c.checkpoints.close()

    def test_async_chunk_autotune(self, tmp_path, tiny_world_configs):
        """One clean chunk measurement sizes async dispatches to the
        ASYNC_CHUNK_SECONDS budget (shared across streams)."""
        c = build(
            tmp_path, tiny_world_configs, run_name="tune_run",
            ASYNC_ROLLOUTS=True, ASYNC_CHUNK_SECONDS=2.0,
        )
        loop = TrainingLoop(c)
        # Not warmed (compile chunk): no tuning.
        loop._maybe_tune_chunk(4, dt=4.0, warmed=False)
        assert loop._tuned_chunk_moves is None
        assert loop._producer_chunk_moves() == 4
        # 4 moves took 4s -> 1s/move -> 2 moves fit the 2s target.
        loop._maybe_tune_chunk(4, dt=4.0, warmed=True)
        assert loop._tuned_chunk_moves == 2
        assert loop._producer_chunk_moves() == 2
        # First accurate measurement wins; later ones don't retune.
        loop._maybe_tune_chunk(2, dt=0.1, warmed=True)
        assert loop._tuned_chunk_moves == 2
        c.stats.close()
        c.checkpoints.close()

    def test_worker_clamp(self, monkeypatch):
        """Stream counts clamp to cores-2 and the per-device budget
        (reference clamps actors to cores-2, setup.py:106-151)."""
        import os as os_mod

        from alphatriangle_tpu.training.setup import (
            clamp_self_play_workers,
        )

        monkeypatch.setattr(os_mod, "cpu_count", lambda: 4)
        assert clamp_self_play_workers(1) == 1
        assert clamp_self_play_workers(2) == 2
        assert clamp_self_play_workers(8) == 2  # cores-2 wins (cpu backend)
        monkeypatch.setattr(os_mod, "cpu_count", lambda: 64)
        import jax as jax_mod

        cap = 4 * jax_mod.local_device_count()
        assert clamp_self_play_workers(10_000) == min(62, cap)
        # Accelerator host: producer threads are dispatch-bound, so a
        # 1-core TPU VM frontend still gets the full per-device budget.
        monkeypatch.setattr(os_mod, "cpu_count", lambda: 1)
        assert clamp_self_play_workers(8) == 1  # cpu backend: host-bound
        monkeypatch.setattr(jax_mod, "default_backend", lambda: "tpu")
        assert clamp_self_play_workers(8) == 8
        assert clamp_self_play_workers(10_000) == cap

    def test_producer_error_surfaces(
        self, tmp_path, tiny_world_configs, monkeypatch
    ):
        """A PERSISTENT producer crash fails the run (after bounded
        respawns) instead of silently starving the learner — the fault
        is patched at class level so respawned engines crash too."""
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine

        c = build(
            tmp_path, tiny_world_configs, run_name="crash_run",
            ASYNC_ROLLOUTS=True,
            # No pre-start auto-tune chunk: it runs play_moves on the
            # consumer thread, outside producer supervision, and the
            # class-level fault would fail the run before any respawn.
            ASYNC_CHUNK_SECONDS=None,
            PRODUCER_MAX_RESTARTS=1,
            PRODUCER_RESTART_BACKOFF_S=0.01,
        )

        def boom(self, num_moves):
            raise RuntimeError("producer crashed")

        monkeypatch.setattr(SelfPlayEngine, "play_moves", boom)
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.ERROR
        # The stream was respawned the configured number of times
        # before the run gave up.
        assert loop.producer_restarts == 1
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_producer_respawn_recovers(
        self, tmp_path, tiny_world_configs, monkeypatch
    ):
        """A TRANSIENT producer crash is healed by supervision: the
        stream respawns (fresh engine, shared compiled programs) and
        the run completes (improves on reference
        `worker_manager.py:153-159`, which only removes dead actors)."""
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine

        c = build(
            tmp_path, tiny_world_configs, run_name="respawn_run",
            ASYNC_ROLLOUTS=True,
            ASYNC_CHUNK_SECONDS=None,  # as in test_producer_error_surfaces
            PRODUCER_MAX_RESTARTS=3,
            PRODUCER_RESTART_BACKOFF_S=0.01,
        )

        real = SelfPlayEngine.play_moves
        fails = {"left": 2}

        def flaky(self, num_moves):
            if fails["left"] > 0:
                fails["left"] -= 1
                raise RuntimeError("transient device fault")
            return real(self, num_moves)

        monkeypatch.setattr(SelfPlayEngine, "play_moves", flaky)
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.producer_restarts == 2
        assert loop.global_step == 8
        c.stats.close()
        c.checkpoints.close()


class TestRunnerResume:
    @pytest.mark.slow
    def test_run_training_and_resume(self, tmp_path, tiny_world_configs):
        """The resume bar: run, 'kill', rerun -> resumes from latest."""
        env_cfg, model_cfg, mcts_cfg = tiny_world_configs
        pc = PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME="resume_run")
        tc = make_train_cfg("resume_run", str(tmp_path), MAX_TRAINING_STEPS=4)
        rc = run_training(
            train_config=tc,
            env_config=env_cfg,
            model_config=model_cfg,
            mcts_config=mcts_cfg,
            persistence_config=pc,
            use_tensorboard=False,
            log_level="WARNING",
        )
        assert rc == 0

        # Second session, auto-resume on, longer horizon: must continue
        # from step 4, not restart.
        tc2 = make_train_cfg(
            "fresh_name", str(tmp_path),
            MAX_TRAINING_STEPS=6, AUTO_RESUME_LATEST=True,
        )
        pc2 = PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME="fresh_name")
        rc = run_training(
            train_config=tc2,
            env_config=env_cfg,
            model_config=model_cfg,
            mcts_config=mcts_cfg,
            persistence_config=pc2,
            use_tensorboard=False,
            log_level="WARNING",
        )
        assert rc == 0
        # The resumed run continued in the original run dir.
        from alphatriangle_tpu.stats import CheckpointManager

        mgr = CheckpointManager(
            PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME="resume_run")
        )
        assert mgr.latest_step() == 6
        # Counters persisted across sessions.
        import json

        meta = json.loads(
            (
                mgr.config.get_checkpoint_dir() / "step_00000006.meta.json"
            ).read_text()
        )
        assert meta["episodes_played"] > 0
