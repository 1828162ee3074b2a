"""Learner tests (reference matrix: `tests/rl/test_trainer.py:135-270`)
plus the multi-device dp-sharding correctness story:
an 8-virtual-device train step keeps replicas bit-identical and matches
the single-device result."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.config import MeshConfig, TrainConfig
from alphatriangle_tpu.nn.network import NeuralNetwork
from alphatriangle_tpu.rl.trainer import (
    Trainer,
    make_host_lr_schedule,
    make_lr_schedule,
    make_optimizer,
    project_to_support,
)

B, A = 8, 12


@pytest.fixture(scope="module")
def network(tiny_model_config, tiny_env_config):
    return NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)


def make_batch(n=B, seed=0, weights=None):
    rng = np.random.default_rng(seed)
    policy = rng.random((n, A)).astype(np.float32)
    policy /= policy.sum(axis=1, keepdims=True)
    return {
        "grid": rng.integers(-1, 2, size=(n, 1, 3, 4)).astype(np.float32),
        "other_features": rng.random((n, 14), dtype=np.float32),
        "policy_target": policy,
        "value_target": rng.uniform(-5, 5, n).astype(np.float32),
        "weights": (
            np.ones(n, dtype=np.float32) if weights is None else weights
        ),
    }


T_MAX, STEP_SIZE = 100_000, 10_000
SCHEDULE_CASES = {
    "cosine_t_max": dict(
        LR_SCHEDULER_TYPE="CosineAnnealingLR", LR_SCHEDULER_T_MAX=T_MAX
    ),
    # T_MAX left out: TrainConfig derives it from the horizon.
    "cosine_horizon": dict(
        LR_SCHEDULER_TYPE="CosineAnnealingLR", MAX_TRAINING_STEPS=T_MAX
    ),
    "step_lr": dict(
        LR_SCHEDULER_TYPE="StepLR",
        LR_SCHEDULER_STEP_SIZE=STEP_SIZE,
        LR_SCHEDULER_GAMMA=0.9,
    ),
    "constant": dict(LR_SCHEDULER_TYPE=None),
}
SCHEDULE_STEPS = [
    0, 1, 2, STEP_SIZE - 1, STEP_SIZE, T_MAX - 1, T_MAX, T_MAX + 1, 10 * T_MAX
]


def raising_schedule(step):
    raise AssertionError(f"optax schedule called on a host path (step {step})")


class TestSchedules:
    @pytest.mark.parametrize("step", SCHEDULE_STEPS)
    @pytest.mark.parametrize("case", SCHEDULE_CASES)
    def test_host_twin_matches_optax(self, case, step):
        """The numpy twin that labels finished steps equals the optax
        schedule the optimizer compiles, scalar and vectorised."""
        cfg = TrainConfig(RUN_NAME="t", **SCHEDULE_CASES[case])
        want = float(make_lr_schedule(cfg)(step))
        host = make_host_lr_schedule(cfg)
        scalar = host(step)
        assert scalar.dtype == np.float32 and scalar.shape == ()
        assert float(scalar) == pytest.approx(want, rel=1e-6)
        around = host(np.array([step + 3, step, 7]))
        assert around.dtype == np.float32 and around.shape == (3,)
        assert float(around[1]) == pytest.approx(want, rel=1e-6)
        assert float(around[2]) == float(host(7))

    def test_cosine_endpoints(self):
        cfg = TrainConfig(
            MAX_TRAINING_STEPS=1000,
            LR_SCHEDULER_TYPE="CosineAnnealingLR",
            LEARNING_RATE=1e-3,
            LR_SCHEDULER_ETA_MIN=1e-6,
            RUN_NAME="t",
        )
        sched = make_lr_schedule(cfg)
        assert float(sched(0)) == pytest.approx(1e-3)
        assert float(sched(1000)) == pytest.approx(1e-6, rel=1e-3)

    def test_step_lr_staircase(self):
        cfg = TrainConfig(
            LR_SCHEDULER_TYPE="StepLR",
            LR_SCHEDULER_STEP_SIZE=10,
            LR_SCHEDULER_GAMMA=0.5,
            LEARNING_RATE=1e-3,
            RUN_NAME="t",
        )
        sched = make_lr_schedule(cfg)
        assert float(sched(9)) == pytest.approx(1e-3)
        assert float(sched(10)) == pytest.approx(5e-4)
        assert float(sched(25)) == pytest.approx(2.5e-4)

    def test_optimizer_types(self):
        for opt_type in ["Adam", "AdamW", "SGD"]:
            cfg = TrainConfig(OPTIMIZER_TYPE=opt_type, RUN_NAME="t")
            opt = make_optimizer(cfg)
            params = {"w": jnp.ones(3)}
            state = opt.init(params)
            grads = {"w": jnp.ones(3)}
            updates, _ = opt.update(grads, state, params)
            assert jnp.all(jnp.isfinite(updates["w"]))


class TestProjection:
    def test_exact_atom_is_one_hot(self):
        # support [-10, 10], 51 atoms => atom spacing 0.4; -10 is atom 0.
        out = project_to_support(jnp.array([-10.0, 10.0, 0.0]), 51, -10, 10)
        np.testing.assert_allclose(out.sum(axis=1), 1.0, rtol=1e-6)
        assert out[0, 0] == 1.0
        assert out[1, 50] == 1.0
        assert out[2, 25] == 1.0

    def test_between_atoms_two_hot(self):
        # 11 atoms on [-1, 1] => spacing 0.2; 0.15 sits 3/4 between atoms 5,6.
        out = project_to_support(jnp.array([0.15]), 11, -1, 1)
        assert out[0, 5] == pytest.approx(0.25, abs=1e-5)
        assert out[0, 6] == pytest.approx(0.75, abs=1e-5)
        assert out[0].sum() == pytest.approx(1.0)

    def test_out_of_range_clipped(self):
        out = project_to_support(jnp.array([-100.0, 100.0]), 11, -1, 1)
        assert out[0, 0] == 1.0
        assert out[1, 10] == 1.0


class TestTrainStep:
    def test_params_change_and_metrics(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        before = jax.tree_util.tree_map(np.asarray, trainer.state.params)
        out = trainer.train_step(make_batch())
        assert out is not None
        metrics, td = out
        assert td.shape == (B,)
        assert np.all(np.isfinite(td)) and np.all(td >= 0)
        for key in ["total_loss", "policy_loss", "value_loss", "entropy"]:
            assert np.isfinite(metrics[key])
        after = trainer.state.params
        changed = jax.tree_util.tree_map(
            lambda a, b: not np.allclose(a, np.asarray(b)), before, after
        )
        assert any(jax.tree_util.tree_leaves(changed))
        assert trainer.global_step == 1

    def test_empty_batch_returns_none(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        assert trainer.train_step(make_batch(0)) is None

    def test_zero_weights_leave_only_entropy_grads(
        self, network, tiny_train_config
    ):
        """IS weights gate the policy/value losses but NOT the entropy
        regularizer, which the reference keeps as an unweighted mean
        (`trainer.py:253-256`)."""
        trainer = Trainer(network, tiny_train_config)
        # Step off the freshly-initialized params first: at init the
        # policy is exactly uniform (entropy = ln(A), its maximum), a
        # stationary point where the entropy gradient is mathematically
        # ZERO — the zero-weight assertion below needs a non-degenerate
        # policy to have anything to regularize.
        assert trainer.train_step(make_batch()) is not None
        out = trainer.train_step(
            make_batch(weights=np.zeros(B, dtype=np.float32))
        )
        assert out is not None
        metrics = out[0]
        # Weighted terms vanish...
        assert metrics["policy_loss"] == pytest.approx(0.0, abs=1e-12)
        assert metrics["value_loss"] == pytest.approx(0.0, abs=1e-12)
        # ...but the entropy bonus still produces a gradient.
        ent_w = tiny_train_config.ENTROPY_BONUS_WEIGHT
        assert metrics["total_loss"] == pytest.approx(
            -ent_w * metrics["entropy"], abs=1e-9
        )
        if ent_w > 0:
            assert metrics["grad_norm"] > 0.0

    def test_lr_follows_schedule(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        lr0 = trainer.get_current_lr()
        for _ in range(3):
            trainer.train_step(make_batch())
        assert trainer.get_current_lr() < lr0  # cosine decays

    def test_sync_to_network_bumps_version(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        v0 = network.weights_version
        trainer.train_step(make_batch())
        assert trainer.sync_to_network() == v0 + 1
        # The wrapper now evaluates with the trained params.
        np.testing.assert_array_equal(
            np.asarray(jax.tree_util.tree_leaves(network.params)[0]),
            np.asarray(jax.tree_util.tree_leaves(trainer.state.params)[0]),
        )


class TestPolicyWeightMask:
    def test_zero_policy_weight_rows_drop_policy_loss(
        self, network, tiny_train_config
    ):
        """Rows with policy_weight 0 (fast PCR searches) contribute no
        policy CE or entropy; the value head still trains on them."""
        trainer = Trainer(network, tiny_train_config)
        batch = make_batch()
        batch["policy_weight"] = np.zeros(B, dtype=np.float32)
        out = trainer.train_step(batch)
        assert out is not None
        metrics = out[0]
        assert metrics["policy_loss"] == pytest.approx(0.0, abs=1e-12)
        assert metrics["entropy"] == pytest.approx(0.0, abs=1e-12)
        assert metrics["value_loss"] > 0.0

    def test_mixed_weights_match_subset(self, tiny_model_config, tiny_env_config, tiny_train_config):
        """policy_loss with half the rows masked equals the IS-weighted
        mean over all rows with masked rows as zeros."""
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        batch = make_batch()
        pw = np.zeros(B, dtype=np.float32)
        pw[: B // 2] = 1.0
        batch["policy_weight"] = pw
        metrics, _ = trainer.train_step(batch)

        net2 = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer2 = Trainer(net2, tiny_train_config)
        full_metrics, _ = trainer2.train_step(make_batch())
        # Same data, same params: the masked run's policy loss must be
        # strictly less than the unmasked run's (half the rows zeroed).
        assert 0.0 < metrics["policy_loss"] < full_metrics["policy_loss"]

    def test_absent_key_defaults_to_ones(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        out = trainer.train_step(make_batch())  # no policy_weight key
        assert out is not None and out[0]["policy_loss"] > 0.0


class TestFusedSteps:
    """`train_steps` (FUSED_LEARNER_STEPS) must be a pure dispatch
    optimization: K fused steps == K sequential steps."""

    def test_fused_matches_sequential(
        self, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        batches = [make_batch(seed=i) for i in range(3)]
        net_a = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        net_b = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        tr_seq = Trainer(net_a, tiny_train_config)
        tr_fused = Trainer(net_b, tiny_train_config)

        seq = [tr_seq.train_step(b) for b in batches]
        fused = tr_fused.train_steps(batches)

        assert len(fused) == 3
        assert tr_fused.global_step == 3
        for (m_s, td_s), (m_f, td_f) in zip(seq, fused):
            np.testing.assert_allclose(td_s, td_f, rtol=1e-5, atol=1e-6)
            for key in m_s:
                assert m_s[key] == pytest.approx(
                    m_f[key], rel=1e-4, abs=1e-6
                ), key
        p_seq = jax.tree_util.tree_leaves(tr_seq.state.params)
        p_fused = jax.tree_util.tree_leaves(tr_fused.state.params)
        for a, b in zip(p_seq, p_fused):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )

    def test_single_batch_delegates(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        out = trainer.train_steps([make_batch()])
        assert len(out) == 1
        assert trainer.global_step == 1

    def test_empty_list(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        assert trainer.train_steps([]) == []
        assert trainer.global_step == 0

    def test_host_step_mirrors_device_step(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        trainer.train_step(make_batch())
        trainer.train_steps([make_batch(seed=1), make_batch(seed=2)])
        assert trainer.global_step == 3
        assert int(trainer.state.step) == 3


class TestTensorParallel:
    """Real mdl-axis tensor parallelism: transformer params shard
    Megatron-style over the mesh's mdl axis, results match the
    replicated learner, and the eval wrapper receives whole tensors."""

    def _tx_config(self, tiny_model_config):
        return tiny_model_config.model_copy(
            update={
                "USE_TRANSFORMER": True,
                "TRANSFORMER_LAYERS": 1,
                "TRANSFORMER_DIM": 8,
                "TRANSFORMER_HEADS": 2,
                "TRANSFORMER_FC_DIM": 16,
            }
        )

    def test_tp_matches_replicated(
        self, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        from jax.sharding import PartitionSpec as P

        from alphatriangle_tpu.config import MeshConfig

        mc = self._tx_config(tiny_model_config)
        batch = make_batch(16, seed=3)

        net_rep = NeuralNetwork(mc, tiny_env_config, seed=0)
        tr_rep = Trainer(
            net_rep,
            tiny_train_config,
            mesh=MeshConfig(DP_SIZE=8).build_mesh(),
        )
        net_tp = NeuralNetwork(mc, tiny_env_config, seed=0)
        tr_tp = Trainer(
            net_tp,
            tiny_train_config,
            mesh=MeshConfig(DP_SIZE=4, MDL_SIZE=2).build_mesh(),
        )
        assert tr_tp.tp_size == 2

        # Transformer QKV kernels sharded on heads; MLP Dense_0 on
        # columns; everything else replicated.
        def spec_of(substr):
            flat = jax.tree_util.tree_flatten_with_path(
                tr_tp.state.params
            )[0]
            for path, leaf in flat:
                name = "/".join(str(k.key) for k in path)
                if substr in name:
                    return name, leaf.sharding.spec
            raise AssertionError(f"no param matching {substr}")

        _, qspec = spec_of("query/kernel")
        assert qspec == P(None, "mdl", None)
        _, d0spec = spec_of("TransformerEncoderLayer_0/Dense_0/kernel")
        assert d0spec == P(None, "mdl")
        # The top-level shared-FC Dense_0 is NOT a transformer MLP and
        # stays replicated.
        flat = jax.tree_util.tree_flatten_with_path(tr_tp.state.params)[0]
        for path, leaf in flat:
            name = "/".join(str(k.key) for k in path)
            if name == "Dense_0/kernel":
                assert leaf.sharding.spec == P()
        _, convspec = spec_of("ConvBlock_0/Conv_0/kernel")
        assert convspec == P()

        out_rep = tr_rep.train_step(dict(batch))
        out_tp = tr_tp.train_step(dict(batch))
        m_rep, td_rep = out_rep
        m_tp, td_tp = out_tp
        np.testing.assert_allclose(td_rep, td_tp, rtol=1e-4, atol=1e-5)
        for key in m_rep:
            assert m_rep[key] == pytest.approx(
                m_tp[key], rel=1e-3, abs=1e-5
            ), key

        # Weight sync gathers shards: the eval wrapper gets whole,
        # single-device tensors and still evaluates.
        tr_tp.sync_to_network()
        leaves = jax.tree_util.tree_leaves(net_tp.variables["params"])
        assert all(
            len(leaf.sharding.device_set) == 1 for leaf in leaves
        )
        policy, value = net_tp.evaluate_features(
            np.asarray(batch["grid"]), np.asarray(batch["other_features"])
        )
        assert np.all(np.isfinite(np.asarray(policy)))

    def test_indivisible_widths_fall_back_to_replication(
        self, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        """Widths that don't divide the mdl axis replicate (never
        crash, never shard unevenly)."""
        from jax.sharding import PartitionSpec as P

        from alphatriangle_tpu.config import MeshConfig

        mc = self._tx_config(tiny_model_config).model_copy(
            update={"TRANSFORMER_HEADS": 1}  # 1 head % mdl=2 != 0
        )
        net = NeuralNetwork(mc, tiny_env_config, seed=0)
        tr = Trainer(
            net,
            tiny_train_config,
            mesh=MeshConfig(DP_SIZE=4, MDL_SIZE=2).build_mesh(),
        )
        flat = jax.tree_util.tree_flatten_with_path(tr.state.params)[0]
        for path, leaf in flat:
            name = "/".join(str(k.key) for k in path)
            if "query/kernel" in name:
                assert leaf.sharding.spec == P()
        assert tr.train_step(make_batch(16)) is not None


class TestPipelinedSteps:
    """`train_steps_begin`/`train_steps_finish`: the overlapped loop's
    double-buffered dispatch path must be bit-equivalent to serial
    `train_step` calls, even with two groups in flight."""

    def test_two_inflight_groups_match_sequential(
        self, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        batches = [make_batch(seed=i) for i in range(4)]
        net_a = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        net_b = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        tr_seq = Trainer(net_a, tiny_train_config)
        tr_pipe = Trainer(net_b, tiny_train_config)

        seq = [tr_seq.train_step(b) for b in batches]
        # Dispatch BOTH groups before fetching either (pipeline depth 2).
        h1 = tr_pipe.train_steps_begin(batches[:2])
        h2 = tr_pipe.train_steps_begin(batches[2:])
        piped = tr_pipe.train_steps_finish(h1) + tr_pipe.train_steps_finish(
            h2
        )

        assert tr_pipe.global_step == 4
        assert int(tr_pipe.state.step) == 4
        for (m_s, td_s), (m_p, td_p) in zip(seq, piped):
            np.testing.assert_allclose(td_s, td_p, rtol=1e-5, atol=1e-6)
            for key in m_s:
                assert m_s[key] == pytest.approx(
                    m_p[key], rel=1e-4, abs=1e-6
                ), key
        for a, b in zip(
            jax.tree_util.tree_leaves(tr_seq.state.params),
            jax.tree_util.tree_leaves(tr_pipe.state.params),
        ):
            np.testing.assert_allclose(
                np.asarray(a), np.asarray(b), rtol=1e-5, atol=1e-7
            )

    def test_single_batch_group(self, network, tiny_train_config):
        """A 1-batch group rides the per-step program but still follows
        the begin/finish contract."""
        trainer = Trainer(network, tiny_train_config)
        handle = trainer.train_steps_begin([make_batch()])
        assert handle is not None and handle["k"] == 1
        assert trainer.global_step == 1  # dispatch advances the clock
        outs = trainer.train_steps_finish(handle)
        assert len(outs) == 1
        metrics, td = outs[0]
        assert np.isfinite(metrics["total_loss"])
        assert td.shape == (B,)

    def test_begin_empty_returns_none(self, network, tiny_train_config):
        trainer = Trainer(network, tiny_train_config)
        assert trainer.train_steps_begin([]) is None
        assert trainer.global_step == 0

    def test_lr_labels_per_step(self, network, tiny_train_config):
        """Per-step learning rates in a fetched group match the
        schedule at each step's own index, not the group end."""
        trainer = Trainer(network, tiny_train_config)
        h = trainer.train_steps_begin([make_batch(seed=i) for i in range(3)])
        outs = trainer.train_steps_finish(h)
        for i, (m, _) in enumerate(outs):
            assert m["learning_rate"] == pytest.approx(
                float(trainer.schedule(i + 1))
            )

    def test_lr_labels_never_call_optax(self, network, tiny_train_config):
        """No learner path evaluates the optax schedule on the host
        (op-by-op device programs in the gap between two groups): the
        labels come from the numpy twin alone."""
        trainer = Trainer(network, tiny_train_config)
        want = [float(trainer.schedule(i)) for i in range(1, 7)]
        trainer.schedule = raising_schedule
        got = [trainer.train_step(make_batch())[0]["learning_rate"]]
        assert trainer.get_current_lr() == got[0]
        outs = trainer.train_steps([make_batch(seed=i) for i in range(3)])
        outs += trainer.train_steps([make_batch(seed=9)])  # unstacked K=1
        got += [m["learning_rate"] for m, _ in outs]
        assert trainer.global_step == 5
        assert got == pytest.approx(want[:5], rel=1e-6)


class TestBatchNormPath:
    def test_batch_stats_updated(self, tiny_model_config, tiny_env_config):
        bn_cfg = tiny_model_config.model_copy(update={"NORM_TYPE": "batch"})
        net = NeuralNetwork(bn_cfg, tiny_env_config, seed=0)
        cfg = TrainConfig(
            BATCH_SIZE=4, BUFFER_CAPACITY=100, MIN_BUFFER_SIZE_TO_TRAIN=10,
            USE_PER=False, MAX_TRAINING_STEPS=10, RUN_NAME="bn",
        )
        trainer = Trainer(net, cfg)
        assert trainer.state.batch_stats
        before = jax.tree_util.tree_map(np.asarray, trainer.state.batch_stats)
        trainer.train_step(make_batch())
        changed = jax.tree_util.tree_map(
            lambda a, b: not np.allclose(a, np.asarray(b)),
            before,
            trainer.state.batch_stats,
        )
        assert any(jax.tree_util.tree_leaves(changed))


class TestMultiDevice:
    """The multi-device criteria: dp-sharded batch, params change,
    replicas stay bit-identical, and the result matches single-device."""

    def test_8dev_step_matches_single_device(
        self, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        assert len(jax.devices()) == 8
        mesh = MeshConfig(DP_SIZE=8, MDL_SIZE=1).build_mesh()
        batch = make_batch(16, seed=7)

        net1 = NeuralNetwork(tiny_model_config, tiny_env_config, seed=3)
        t_single = Trainer(net1, tiny_train_config)
        t_single.train_step(batch)
        single_params = jax.tree_util.tree_map(
            np.asarray, t_single.state.params
        )

        net8 = NeuralNetwork(tiny_model_config, tiny_env_config, seed=3)
        t_mesh = Trainer(net8, tiny_train_config, mesh=mesh)
        out = t_mesh.train_step(batch)
        assert out is not None

        # Replicas bit-identical across all 8 devices (the grad
        # all-reduce actually ran and agreed).
        leaf = jax.tree_util.tree_leaves(t_mesh.state.params)[0]
        shards = [np.asarray(s.data) for s in leaf.addressable_shards]
        assert len(shards) == 8
        for s in shards[1:]:
            np.testing.assert_array_equal(shards[0], s)

        # Multi-device result matches the single-device step.
        mesh_params = jax.tree_util.tree_map(np.asarray, t_mesh.state.params)
        flat_s = jax.tree_util.tree_leaves(single_params)
        flat_m = jax.tree_util.tree_leaves(mesh_params)
        for a, b in zip(flat_s, flat_m, strict=True):
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-5)

    def test_custom_axis_names(
        self, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        mesh = MeshConfig(DP_SIZE=8, DP_AXIS="data", MDL_AXIS="model").build_mesh()
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config, mesh=mesh)
        assert trainer.dp_size == 8
        out = trainer.train_step(make_batch(16))
        assert out is not None
        with pytest.raises(ValueError, match="not divisible"):
            trainer.train_step(make_batch(12))

    def test_set_state_does_not_alias_caller(
        self, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        trainer.train_step(make_batch())
        saved = trainer.state
        trainer.set_state(saved)
        trainer.train_step(make_batch(seed=1))
        # The caller's snapshot must survive the donated step.
        assert all(
            np.isfinite(np.asarray(leaf)).all()
            for leaf in jax.tree_util.tree_leaves(saved.params)
        )

    def test_indivisible_batch_raises(
        self, tiny_model_config, tiny_env_config, tiny_train_config
    ):
        mesh = MeshConfig(DP_SIZE=8, MDL_SIZE=1).build_mesh()
        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config, mesh=mesh)
        with pytest.raises(ValueError, match="not divisible"):
            trainer.train_step(make_batch(6))


class TestRoutedTrunk:
    """A decoder stack with routers under the learner: what the trainer
    holds by itself. The comparison with the plain reference lives in
    tests/chipbench/test_chipbench_glm.py."""

    @staticmethod
    def _shapes(name, settings_of):
        from chipbench import manifest

        from alphatriangle_tpu.config import TrunkConfig
        from alphatriangle_tpu.nn.model import AlphaTriangleNet

        cfg = manifest.load_json(manifest.HERE / "configs" / f"{name}.json")
        configs = manifest.program_configs(cfg)
        model = configs["model"].model_copy(
            update={"TRUNK": TrunkConfig(**settings_of(cfg))}
        )
        env = configs["env"]
        module = AlphaTriangleNet(model, env.action_dim)
        return jax.eval_shape(
            lambda k: module.init(
                k, jnp.zeros((1, model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS)),
                jnp.zeros((1, model.OTHER_NN_INPUT_FEATURES_DIM)), train=False,
            ),
            jax.random.PRNGKey(0),
        )["params"]

    def test_refused_or_built_by_the_bytes_of_the_training_state(self, monkeypatch):
        """Under one v5e chip's 16 GB the two rollout trunks are still
        refused and `glm-flash-ep8` (579M parameters in float32, 9.27 GB
        of state) passes; under 8 GB it is refused too."""
        from chipbench import reference_exaone_moe, reference_glm_moe
        from chipbench import reference_ling_hybrid

        from alphatriangle_tpu.rl.trainer import refuse_untrainable
        from alphatriangle_tpu.telemetry.memory import BYTES_LIMIT_ENV

        monkeypatch.setenv(BYTES_LIMIT_ENV, str(16 * 2**30))
        for name, module in (
            ("k-exaone-ep8", reference_exaone_moe),
            ("ling-flash-ep4", reference_ling_hybrid),
        ):
            with pytest.raises(ValueError, match="B of training state"):
                refuse_untrainable(self._shapes(name, module.trunk_settings))
        glm = self._shapes("glm-flash-ep8", reference_glm_moe.trunk_settings)
        count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(glm))
        assert count == 579_147_239
        refuse_untrainable(glm)
        monkeypatch.setenv(BYTES_LIMIT_ENV, str(8 * 2**30))
        with pytest.raises(ValueError, match=f"{16 * count:,} B of training state"):
            refuse_untrainable(glm)

    @pytest.fixture()
    def routed(self, tiny_model_config, tiny_env_config, tiny_train_config):
        from alphatriangle_tpu.config import TrunkConfig

        trunk = TrunkConfig(
            hidden_size=32, num_attention_heads=2, num_key_value_heads=2,
            intermediate_size=48, moe_intermediate_size=16, num_experts=4,
            num_experts_per_tok=2, layer_types=["latent_attention"] * 2,
            mlp_layer_types=["dense", "sparse"], experts_held=(0, 2),
            kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, norm_position="pre", rope_layers="latent",
            router_bias=True, latent_gate=False, learner_block_boards=2,
            router_bias_rate=0.01,
        )
        # Heads of 16: at the fixture's 8 a GroupNorm group is one
        # channel, the head's ReLU is dead and no gradient reaches the trunk.
        model = tiny_model_config.model_copy(
            update={
                "TRUNK": trunk, "REMAT": True, "FC_DIMS_SHARED": [16],
                "POLICY_HEAD_DIMS": [16], "VALUE_HEAD_DIMS": [16],
            }
        )
        train = tiny_train_config.model_copy(update={"WEIGHT_DECAY": 0.1})
        return Trainer(NeuralNetwork(model, tiny_env_config, seed=1), train)

    def test_a_selection_bias_is_no_parameter_of_the_optimizers(self, routed):
        trainer = routed
        params = trainer.state.params
        biases = [
            path
            for path, _ in jax.tree_util.tree_leaves_with_path(params)
            if "router_bias" in jax.tree_util.keystr(path)
        ]
        assert len(biases) == 1
        # No moment: the optimizer's state has a leaf less, per moment.
        leaves = len(jax.tree_util.tree_leaves(params))
        adam = [
            s for s in jax.tree_util.tree_leaves(
                trainer.state.opt_state, is_leaf=lambda x: hasattr(x, "mu")
            ) if hasattr(s, "mu")
        ][0]
        assert len(jax.tree_util.tree_leaves(adam.mu)) == leaves - 1
        assert len(jax.tree_util.tree_leaves(adam.nu)) == leaves - 1
        # Not in the clipped norm, no decay: a gradient of any size on
        # the bias changes no other leaf's update, and passes through.
        ones = jax.tree_util.tree_map(jnp.ones_like, params)
        huge = jax.tree_util.tree_map_with_path(
            lambda path, x: x * (1e6 if "router_bias" in jax.tree_util.keystr(path) else 1),
            ones,
        )
        plain_updates, _ = trainer.optimizer.update(ones, trainer.state.opt_state, params)
        huge_updates, _ = trainer.optimizer.update(huge, trainer.state.opt_state, params)
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(plain_updates),
            jax.tree_util.tree_leaves(huge_updates),
        ):
            if "router_bias" in jax.tree_util.keystr(path):
                assert float(b[0]) == 1e6
            else:
                np.testing.assert_array_equal(a, b)

    def test_a_step_moves_the_bias_by_the_rule_and_reports_the_loads(
        self, routed, tiny_env_config
    ):
        trainer = routed
        n = 8
        rng = np.random.default_rng(0)
        batch = trainer._zero_batch(n)
        batch["grid"] = rng.integers(-1, 2, batch["grid"].shape).astype(np.float32)
        batch["other_features"] = rng.random(batch["other_features"].shape).astype(
            np.float32
        )
        before = np.asarray(trainer.state.params["DecoderTrunk_0"]["l1_router_bias"])
        grads = jax.grad(
            lambda p: trainer._loss_fn(p, {}, jax.random.PRNGKey(0), batch)[0]
        )(trainer.state.params)
        assert not np.asarray(grads["DecoderTrunk_0"]["l1_router_bias"]).any()
        assert np.asarray(grads["DecoderTrunk_0"]["l1_w_router"]).any()
        metrics, td = trainer.train_step(dict(batch))
        assert set(metrics) == {
            "total_loss", "policy_loss", "value_loss", "entropy", "grad_norm",
            "update_norm", "learning_rate",
        }
        assert td.shape == (n,) and np.isfinite(td).all()
        counted = trainer.last_counters
        loads = counted["expert_loads"]
        assert loads.shape == (1, 1, 4) and counted["expert_tokens"].shape == (1, 1, 2)
        tokens = n * tiny_env_config.ROWS * tiny_env_config.COLS
        assert counted["routed"] == int(loads.sum()) == tokens * 2
        assert counted["trunk_tokens"] == tokens * 2
        np.testing.assert_array_equal(counted["expert_tokens"][0, 0], loads[0, 0, :2])
        after = np.asarray(trainer.state.params["DecoderTrunk_0"]["l1_router_bias"])
        load = loads[0, 0].astype(np.float32)
        np.testing.assert_array_equal(
            after, before + np.float32(0.01) * np.sign(load.mean() - load)
        )
        # The fused group from a list of batches reports per step.
        trainer.train_steps([dict(batch), dict(batch)])
        assert trainer.last_counters["expert_loads"].shape == (2, 1, 4)
        assert trainer.last_counters["trunk_tokens"] == 2 * tokens * 2


def test_the_flagships_learner_programs_are_the_parents_text(monkeypatch):
    """A net without a decoder stack takes no block, counts no load and
    moves no bias: its per-step and its fused-from-ring programs lower
    to the text they had on commit 7fee68d (where both digests were
    taken with this test's code), at `flagship-p3`'s widths, batch 8, a
    ring of 512 rows. Since PR 37 the from-ring program gathers through
    `read_rows`; with the plain gather in its place the text is still
    that commit's, so the read is all that changed."""
    import hashlib

    from chipbench import manifest

    from alphatriangle_tpu.rl import trainer as trainer_module
    from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer

    cfg = manifest.load_json(manifest.HERE / "configs" / "flagship-p3.json")
    configs = manifest.program_configs(cfg)
    env, model = configs["env"], configs["model"]
    train = configs["train"].model_copy(
        update={
            "BUFFER_CAPACITY": 512, "MIN_BUFFER_SIZE_TO_TRAIN": 512, "BATCH_SIZE": 8
        }
    )
    trainer = Trainer(NeuralNetwork(model, env, seed=1), train)
    buffer = DeviceReplayBuffer(
        train, (model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS),
        model.OTHER_NN_INPUT_FEATURES_DIM, env.action_dim, seed=0,
    )

    def from_ring():
        return (
            jax.jit(trainer._train_steps_from_impl)
            .lower(
                trainer.state, buffer.storage,
                np.zeros((2, 8), np.int32), np.ones((2, 8), np.float32),
            )
            .as_text()
        )

    texts = [
        from_ring(),
        jax.jit(trainer._train_step_impl)
        .lower(trainer.state, trainer._zero_batch(8))
        .as_text(),
    ]
    monkeypatch.setattr(
        trainer_module,
        "read_rows",
        lambda storage, idx: {name: v[idx] for name, v in storage.items()},
    )
    texts.append(from_ring())
    assert [hashlib.sha256(t.encode()).hexdigest() for t in texts] == [
        "61e1be3729f32876b32d073dca766922a48141b637f7046d905ec65ac3f417de",
        "64c7e94ee9a7bffcad4947b646a58f9270b88b83c770c754190a50584d0744b1",
        "2cff0f97b4f77481535d8c8bc012071663883d8596fbc8a1ba81f60d250f20ff",
    ]
