"""Device-resident replay ring (rl/device_buffer.py).

Covers: host/device ingest parity (content, order, slots, ring wrap),
on-device row validation, PER bookkeeping via ingest counts,
sample+gather training equivalence against the host path, snapshot
round trips in both directions, and the training loop running end to
end in device-replay mode (sync + overlapped).
"""

import numpy as np
import pytest

from alphatriangle_tpu.rl.buffer import ExperienceBuffer
from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer


GRID_SHAPE = (1, 3, 4)
OTHER_DIM = 5
ACTION_DIM = 12


def _cfg(tiny_train_config, **updates):
    return tiny_train_config.model_copy(update=updates)


def _dev_buffer(cfg, seed=0):
    return DeviceReplayBuffer(
        cfg,
        grid_shape=GRID_SHAPE,
        other_dim=OTHER_DIM,
        action_dim=ACTION_DIM,
        seed=seed,
    )


def _rows(n, rng, value=None):
    """n valid experience rows (grids in {-1,0,1}, normalized policy)."""
    grid = rng.integers(-1, 2, size=(n, *GRID_SHAPE)).astype(np.float32)
    other = rng.random((n, OTHER_DIM), dtype=np.float32)
    policy = rng.random((n, ACTION_DIM), dtype=np.float32) + 0.01
    policy /= policy.sum(axis=1, keepdims=True)
    val = (
        np.full(n, value, np.float32)
        if value is not None
        else rng.normal(size=n).astype(np.float32)
    )
    pw = (rng.random(n) > 0.3).astype(np.float32)
    return grid, other, policy, val, pw


class TestIngestParity:
    def test_add_dense_matches_host_buffer(self, tiny_train_config):
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=32, USE_PER=True,
                   PER_BETA_ANNEAL_STEPS=100)
        rng = np.random.default_rng(1)
        host = ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        dev = _dev_buffer(cfg)
        for n in (5, 11, 7):
            rows = _rows(n, rng)
            s_host = host.add_dense(*rows[:4], policy_weight=rows[4])
            s_dev = dev.add_dense(*rows[:4], policy_weight=rows[4])
            np.testing.assert_array_equal(s_host, s_dev)
        assert len(host) == len(dev)
        hs, ds = host.get_state(), dev.get_state()
        assert hs["pos"] == ds["pos"] and hs["size"] == ds["size"]
        for k in hs["storage"]:
            np.testing.assert_array_equal(
                hs["storage"][k], ds["storage"][k], err_msg=k
            )
        np.testing.assert_allclose(hs["priorities"], ds["priorities"])

    def test_ring_wraparound(self, tiny_train_config):
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=8, USE_PER=False)
        rng = np.random.default_rng(2)
        host = ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        dev = _dev_buffer(cfg)
        for n in (6, 5, 4):  # wraps twice
            rows = _rows(n, rng)
            host.add_dense(*rows[:4], policy_weight=rows[4])
            dev.add_dense(*rows[:4], policy_weight=rows[4])
        assert len(dev) == 8 and dev._pos == host._pos
        hs, ds = host.get_state(), dev.get_state()
        for k in hs["storage"]:
            np.testing.assert_array_equal(
                hs["storage"][k], ds["storage"][k], err_msg=k
            )

    def test_single_ingest_larger_than_capacity(self, tiny_train_config):
        """One add of 20 rows into an 8-slot ring keeps the newest 8 in
        the same slots the host ring's last-write-wins produces."""
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=8, USE_PER=False)
        rng = np.random.default_rng(7)
        host = ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        dev = _dev_buffer(cfg)
        rows = _rows(20, rng)
        host.add_dense(*rows[:4], policy_weight=rows[4])
        dev.add_dense(*rows[:4], policy_weight=rows[4])
        assert len(dev) == 8 and dev._pos == host._pos == 20 % 8
        hs, ds = host.get_state(), dev.get_state()
        for k in hs["storage"]:
            np.testing.assert_array_equal(
                hs["storage"][k], ds["storage"][k], err_msg=k
            )

    def test_invalid_rows_dropped(self, tiny_train_config):
        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=16, USE_PER=False)
        rng = np.random.default_rng(3)
        dev = _dev_buffer(cfg)
        grid, other, policy, val, pw = _rows(6, rng)
        grid[1, 0, 0, 0] = np.nan  # non-finite feature
        policy[3] *= 3.0  # not a distribution
        val[4] = np.inf  # non-finite return
        slots = dev.add_dense(grid, other, policy, val, policy_weight=pw)
        assert len(dev) == 3 and len(slots) == 3
        state = dev.get_state()
        keep = [0, 2, 5]
        np.testing.assert_array_equal(
            state["storage"]["grid"], grid[keep].astype(np.int8)
        )
        np.testing.assert_allclose(
            state["storage"]["value_target"], val[keep]
        )

    def test_sample_returns_indices_only(self, tiny_train_config):
        cfg = _cfg(
            tiny_train_config,
            BUFFER_CAPACITY=32,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=True,
            PER_BETA_ANNEAL_STEPS=10,
        )
        rng = np.random.default_rng(4)
        dev = _dev_buffer(cfg)
        assert dev.sample(4, current_train_step=0) is None  # not ready
        rows = _rows(12, rng)
        dev.add_dense(*rows[:4], policy_weight=rows[4])
        s = dev.sample(4, current_train_step=0)
        assert s is not None and "batch" not in s
        assert s["indices"].shape == (4,) and (s["indices"] < 12).all()
        assert s["weights"].shape == (4,) and (s["weights"] <= 1.0).all()
        # PER priority updates shift sampling mass (inherited machinery).
        dev.update_priorities(np.array([0]), np.array([100.0]))
        hits = sum(
            0 in dev.sample(4, current_train_step=1)["indices"]
            for _ in range(50)
        )
        assert hits > 25


class TestTrainEquivalence:
    def test_train_steps_from_matches_host_path(
        self, tiny_env_config, tiny_model_config, tiny_train_config
    ):
        """K fused device-gathered steps == K fused host-staged steps
        on the same rows (identical final params + per-step outputs)."""
        import jax

        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.trainer import Trainer

        cfg = _cfg(
            tiny_train_config,
            BUFFER_CAPACITY=64,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=False,
            FUSED_LEARNER_STEPS=3,
        )
        rng = np.random.default_rng(5)
        grid_shape = (
            tiny_model_config.GRID_INPUT_CHANNELS,
            tiny_env_config.ROWS,
            tiny_env_config.COLS,
        )
        other_dim = tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM
        action_dim = tiny_env_config.action_dim
        dev = DeviceReplayBuffer(
            cfg,
            grid_shape=grid_shape,
            other_dim=other_dim,
            action_dim=action_dim,
        )
        n = 32
        grid = rng.integers(-1, 2, size=(n, *grid_shape)).astype(np.float32)
        other = rng.random((n, other_dim), dtype=np.float32)
        policy = rng.random((n, action_dim), dtype=np.float32) + 0.01
        policy /= policy.sum(axis=1, keepdims=True)
        val = rng.normal(size=n).astype(np.float32)
        pw = (rng.random(n) > 0.5).astype(np.float32)
        dev.add_dense(grid, other, policy, val, policy_weight=pw)

        samples = [dev.sample(cfg.BATCH_SIZE) for _ in range(3)]
        host_batches = []
        for s in samples:
            i = s["indices"]
            host_batches.append(
                {
                    "grid": grid[i].astype(np.int8).astype(np.float32),
                    "other_features": other[i],
                    "policy_target": policy[i],
                    "value_target": val[i],
                    "policy_weight": pw[i],
                    "weights": s["weights"],
                }
            )

        net_a = NeuralNetwork(tiny_model_config, tiny_env_config, seed=7)
        net_b = NeuralNetwork(tiny_model_config, tiny_env_config, seed=7)
        tr_a = Trainer(net_a, cfg)
        tr_b = Trainer(net_b, cfg)
        outs_host = tr_a.train_steps(host_batches)
        outs_dev = tr_b.train_steps_from(dev, samples)
        assert len(outs_host) == len(outs_dev) == 3
        for (m_h, td_h), (m_d, td_d) in zip(outs_host, outs_dev):
            for key in m_h:
                np.testing.assert_allclose(
                    m_h[key], m_d[key], rtol=1e-5, err_msg=key
                )
            np.testing.assert_allclose(td_h, td_d, rtol=1e-5)
        pa = jax.device_get(tr_a.state.params)
        pb = jax.device_get(tr_b.state.params)
        jax.tree_util.tree_map(
            lambda x, y: np.testing.assert_allclose(x, y, rtol=1e-5), pa, pb
        )
        assert tr_a.global_step == tr_b.global_step == 3

    def test_pipelined_begin_finish(
        self, tiny_env_config, tiny_model_config, tiny_train_config
    ):
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.trainer import Trainer

        cfg = _cfg(
            tiny_train_config,
            BUFFER_CAPACITY=64,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            USE_PER=False,
        )
        rng = np.random.default_rng(6)
        grid_shape = (
            tiny_model_config.GRID_INPUT_CHANNELS,
            tiny_env_config.ROWS,
            tiny_env_config.COLS,
        )
        dev = DeviceReplayBuffer(
            cfg,
            grid_shape=grid_shape,
            other_dim=tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM,
            action_dim=tiny_env_config.action_dim,
        )
        n = 16
        grid = rng.integers(-1, 2, size=(n, *grid_shape)).astype(np.float32)
        other = rng.random(
            (n, tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM),
            dtype=np.float32,
        )
        policy = rng.random((n, tiny_env_config.action_dim), dtype=np.float32)
        policy /= policy.sum(axis=1, keepdims=True)
        dev.add_dense(grid, other, policy, np.zeros(n, np.float32))
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=8)
        tr = Trainer(net, cfg)
        # Two groups in flight (K=2 then K=1), fetched oldest-first.
        h1 = tr.train_steps_from_begin(dev, [dev.sample(4), dev.sample(4)])
        h2 = tr.train_steps_from_begin(dev, [dev.sample(4)])
        assert tr.train_steps_from_begin(dev, []) is None
        outs1 = tr.train_steps_finish(h1)
        outs2 = tr.train_steps_finish(h2)
        assert len(outs1) == 2 and len(outs2) == 1
        assert outs1[0][1].shape == (4,)  # per-step TD rows
        assert tr.global_step == 3
        want = [float(tr.schedule(i)) for i in range(1, 6)]
        lrs = [m["learning_rate"] for m, _ in outs1 + outs2]
        assert lrs == pytest.approx(want[:3], rel=1e-6)
        # A from-ring group labels its steps without the optax schedule
        # (tests/test_trainer.py holds the other learner paths to that).
        tr.schedule = None
        outs3 = tr.train_steps_from(dev, [dev.sample(4), dev.sample(4)])
        lrs = [m["learning_rate"] for m, _ in outs3]
        assert lrs == pytest.approx(want[3:], rel=1e-6)


class TestSelfPlayIntegration:
    def test_play_chunk_device_matches_host_harvest(
        self,
        tiny_env_config,
        tiny_model_config,
        tiny_train_config,
        tiny_mcts_config,
    ):
        """Same seed, two engines: the device payload ingested into the
        ring equals the host harvest's rows, and stats agree."""
        from alphatriangle_tpu.env.engine import TriangleEnv
        from alphatriangle_tpu.features.core import get_feature_extractor
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine

        cfg = _cfg(tiny_train_config, BUFFER_CAPACITY=512, USE_PER=False)
        env = TriangleEnv(tiny_env_config)
        extractor = get_feature_extractor(env, tiny_model_config)
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=3)
        mk = lambda: SelfPlayEngine(  # noqa: E731
            env, extractor, net, tiny_mcts_config, cfg, seed=11
        )
        host_eng, dev_eng = mk(), mk()
        result = host_eng.play_moves(8)
        dev = DeviceReplayBuffer(
            cfg,
            grid_shape=(
                tiny_model_config.GRID_INPUT_CHANNELS,
                tiny_env_config.ROWS,
                tiny_env_config.COLS,
            ),
            other_dim=extractor.other_dim,
            action_dim=tiny_env_config.action_dim,
        )
        stats, payload = dev_eng.play_moves_device(8)
        added = dev.ingest_payload(payload)
        assert added == result.num_experiences == len(dev)
        assert stats.num_episodes == result.num_episodes
        assert stats.episode_scores == result.episode_scores
        assert stats.total_simulations == result.total_simulations
        assert stats.num_experiences == 0  # stats-only harvest
        state = dev.get_state()
        np.testing.assert_array_equal(
            state["storage"]["grid"], result.grid.astype(np.int8)
        )
        np.testing.assert_allclose(
            state["storage"]["policy_target"], result.policy_target,
            rtol=1e-6,
        )
        np.testing.assert_allclose(
            state["storage"]["value_target"], result.value_target, rtol=1e-6
        )
        np.testing.assert_array_equal(
            state["storage"]["policy_weight"], result.policy_weight
        )


class TestSnapshotRoundTrip:
    @pytest.mark.parametrize("direction", ["dev_to_host", "host_to_dev"])
    def test_round_trip(self, tiny_train_config, direction):
        cfg = _cfg(
            tiny_train_config,
            BUFFER_CAPACITY=16,
            USE_PER=True,
            PER_BETA_ANNEAL_STEPS=50,
        )
        rng = np.random.default_rng(9)
        src: ExperienceBuffer = (
            _dev_buffer(cfg) if direction == "dev_to_host"
            else ExperienceBuffer(cfg, action_dim=ACTION_DIM)
        )
        rows = _rows(20, rng)  # wraps the 16-slot ring
        src.add_dense(*rows[:4], policy_weight=rows[4])
        src.update_priorities(np.arange(4), np.array([1.0, 2.0, 3.0, 4.0]))
        snap = src.get_state()
        dst: ExperienceBuffer = (
            ExperienceBuffer(cfg, action_dim=ACTION_DIM)
            if direction == "dev_to_host"
            else _dev_buffer(cfg)
        )
        dst.set_state(snap)
        assert len(dst) == len(src) == 16
        a, b = src.get_state(), dst.get_state()
        # set_state re-orders slots chronologically; compare as sets of
        # rows via lexicographic sort on the value column.
        oa, ob = np.argsort(a["storage"]["value_target"]), np.argsort(
            b["storage"]["value_target"]
        )
        for k in a["storage"]:
            np.testing.assert_allclose(
                a["storage"][k][oa].astype(np.float32),
                b["storage"][k][ob].astype(np.float32),
                err_msg=k,
            )
        s = dst.sample(4, current_train_step=0)
        assert s is not None


class TestLoopIntegration:
    @pytest.mark.parametrize("async_mode", [False, True])
    def test_training_loop_device_replay(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_train_config,
        tiny_mcts_config,
        async_mode,
    ):
        from alphatriangle_tpu.config import MeshConfig, PersistenceConfig
        from alphatriangle_tpu.training.loop import LoopStatus, TrainingLoop
        from alphatriangle_tpu.training.setup import setup_training_components

        cfg = _cfg(
            tiny_train_config,
            DEVICE_REPLAY="on",
            ASYNC_ROLLOUTS=async_mode,
            ASYNC_CHUNK_SECONDS=None,
            FUSED_LEARNER_STEPS=2,
            MAX_TRAINING_STEPS=6,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            BUFFER_CAPACITY=256,
            CHECKPOINT_SAVE_FREQ_STEPS=4,
            RUN_NAME=f"pytest_devreplay_{async_mode}",
        )
        comps = setup_training_components(
            train_config=cfg,
            env_config=tiny_env_config,
            model_config=tiny_model_config,
            mcts_config=tiny_mcts_config,
            # The device ring lives on ONE chip; pin a 1-device mesh
            # (the test harness exposes 8 virtual CPU devices).
            mesh_config=MeshConfig(DP_SIZE=1),
            persistence_config=PersistenceConfig(
                ROOT_DATA_DIR=str(tmp_path), RUN_NAME=cfg.RUN_NAME
            ),
            use_tensorboard=False,
        )
        assert getattr(comps.buffer, "is_device", False)
        loop = TrainingLoop(comps)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 6
        assert loop.experiences_added > 0
        ckpts = list(tmp_path.rglob("step_*"))
        assert ckpts, "no checkpoint written"

    def test_training_loop_trains_a_routed_trunk_in_blocks(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_train_config,
        tiny_mcts_config,
    ):
        """The loop's device-replay learner with a routed latent stack
        as the trunk: the same entry points, ring and sampler; a step in
        blocks of 4 boards under recomputation, the routers' counters
        off the group's fetch, the biases moved by the rule."""
        from alphatriangle_tpu.config import (
            MeshConfig, PersistenceConfig, TrunkConfig,
        )
        from alphatriangle_tpu.training.loop import LoopStatus, TrainingLoop
        from alphatriangle_tpu.training.setup import setup_training_components

        trunk = TrunkConfig(
            hidden_size=32, num_attention_heads=2, num_key_value_heads=2,
            intermediate_size=48, moe_intermediate_size=16, num_experts=4,
            num_experts_per_tok=2, layer_types=["latent_attention"] * 2,
            mlp_layer_types=["dense", "sparse"], experts_held=(0, 2),
            kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, norm_position="pre", rope_layers="latent",
            router_bias=True, latent_gate=False, learner_block_boards=4,
            router_bias_rate=0.001,
        )
        model = tiny_model_config.model_copy(update={"TRUNK": trunk, "REMAT": True})
        cfg = _cfg(
            tiny_train_config,
            DEVICE_REPLAY="on",
            FUSED_LEARNER_STEPS=2,
            BATCH_SIZE=8,
            MAX_TRAINING_STEPS=4,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            BUFFER_CAPACITY=256,
            CHECKPOINT_SAVE_FREQ_STEPS=4,
            RUN_NAME="pytest_devreplay_trunk",
        )
        comps = setup_training_components(
            train_config=cfg,
            env_config=tiny_env_config,
            model_config=model,
            mcts_config=tiny_mcts_config,
            mesh_config=MeshConfig(DP_SIZE=1),
            persistence_config=PersistenceConfig(
                ROOT_DATA_DIR=str(tmp_path), RUN_NAME=cfg.RUN_NAME
            ),
            use_tensorboard=False,
        )
        before = np.asarray(comps.net.variables["params"]["DecoderTrunk_0"]["l1_router_bias"])
        loop = TrainingLoop(comps)
        assert loop.run() == LoopStatus.COMPLETED and loop.global_step == 4
        counted = comps.trainer.last_counters
        assert counted["expert_loads"].shape == (2, 1, 4)
        tokens = 8 * tiny_env_config.ROWS * tiny_env_config.COLS
        assert counted["routed"] == 2 * tokens * 2
        after = np.asarray(
            comps.trainer.state.params["DecoderTrunk_0"]["l1_router_bias"]
        )
        moved = np.abs(after - before)
        assert moved.max() <= 4 * 0.001 + 1e-7 and moved.max() > 0
