"""Fused-megastep tests (rl/megastep.py; `TrainConfig.FUSED_MEGASTEP`).

The acceptance bars from the megastep issue:
- one host dispatch per steady-state iteration (counter-asserted);
- params actually update across megasteps (the donation/reload
  regression guard from the compile-cache work, extended to the
  megastep program family);
- the counters contract (global_step, episodes, buffer fill) matches
  the sync loop's, PER priorities reconcile between the device array
  and the host SumTree mirror, and the loss decreases (learning
  sanity) at the sync mode's step count;
- checkpoints/resume and telemetry (health, ledger) keep working;
- the megastep program lands in the compile cache with a `.mem.json`
  sidecar and `cli warm`/`cli fit` cover it.
"""

import json

import jax
import numpy as np
import pytest

from alphatriangle_tpu.compile_cache import (
    get_compile_cache,
    reset_compile_cache,
)
from alphatriangle_tpu.config import (
    MeshConfig,
    PersistenceConfig,
    TrainConfig,
)
from alphatriangle_tpu.training import (
    LoopStatus,
    TrainingLoop,
    run_training,
    setup_training_components,
)


@pytest.fixture(scope="module")
def tiny_world_configs(tiny_env_config, tiny_model_config, tiny_mcts_config):
    return tiny_env_config, tiny_model_config, tiny_mcts_config


@pytest.fixture(scope="module", autouse=True)
def _collect_module_garbage():
    """Free cycle-held device arrays (components <-> loop references)
    when this module finishes: test_memory's live-array accounting runs
    next alphabetically and must not see our dead engines/rings."""
    yield
    import gc

    gc.collect()


def make_cfg(run_name: str, **kw) -> TrainConfig:
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
        FUSED_MEGASTEP=True,
        DEVICE_REPLAY="on",
        FUSED_LEARNER_STEPS=2,
    )
    base.update(kw)
    return TrainConfig(**base)


def build(tmp_path, cfgs, run_name="mega_run", mcts_kw=None, **kw):
    env_cfg, model_cfg, mcts_cfg = cfgs
    if mcts_kw:
        mcts_cfg = mcts_cfg.model_copy(update=mcts_kw)
    tc = make_cfg(run_name, **kw)
    pc = PersistenceConfig(ROOT_DATA_DIR=str(tmp_path), RUN_NAME=run_name)
    return setup_training_components(
        train_config=tc,
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        # The megastep (like the single-device ring it drives) lives on
        # ONE chip; the harness exposes 8 virtual CPU devices.
        mesh_config=MeshConfig(DP_SIZE=1),
        persistence_config=pc,
        use_tensorboard=False,
    )


def direct_runner(cfgs, train_cfg):
    """A `MegastepRunner` over components built directly (no loop, no
    run directory); its ring is empty: `fill_ring` makes it sampleable."""
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl import MegastepRunner, SelfPlayEngine, Trainer
    from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer

    env_cfg, model_cfg, mcts_cfg = cfgs
    env = TriangleEnv(env_cfg)
    extractor = get_feature_extractor(env, model_cfg)
    net = NeuralNetwork(model_cfg, env_cfg, seed=0)
    engine = SelfPlayEngine(env, extractor, net, mcts_cfg, train_cfg, seed=0)
    trainer = Trainer(net, train_cfg)
    buffer = DeviceReplayBuffer(
        train_cfg,
        grid_shape=(model_cfg.GRID_INPUT_CHANNELS, env_cfg.ROWS, env_cfg.COLS),
        other_dim=extractor.other_dim,
        action_dim=env_cfg.action_dim,
    )
    return MegastepRunner(engine, trainer, buffer, train_cfg)


def fill_ring(buffer, n: int) -> None:
    """`n` synthetic rows with fixed targets (a stationary distribution)."""
    rng = np.random.default_rng(0)
    grid, other, policy = (
        (n, *buffer.storage[key].shape[1:])
        for key in ("grid", "other_features", "policy_target")
    )
    policy = rng.random(policy).astype(np.float32)
    policy /= policy.sum(axis=1, keepdims=True)
    buffer.add_dense(
        rng.integers(-1, 2, size=grid).astype(np.float32),
        rng.random(other).astype(np.float32),
        policy,
        rng.uniform(-2, 2, n).astype(np.float32),
    )


def _priorities_sides(c):
    """(device priority array, host SumTree mirror leaves) for the
    first `size` ring slots."""
    runner = c.megastep
    tree = c.buffer.tree
    size = len(c.buffer)
    dev = np.asarray(runner._priorities)[:size]
    host = tree.tree[np.arange(size) + tree._cap2]
    return dev, host


class TestConfigValidation:
    def test_megastep_excludes_async(self):
        with pytest.raises(ValueError, match="mutually exclusive"):
            make_cfg("bad", ASYNC_ROLLOUTS=True)

    def test_megastep_needs_device_replay(self):
        with pytest.raises(ValueError, match="device-resident replay"):
            make_cfg("bad", DEVICE_REPLAY="off")

    def test_setup_rejects_non_divisible_dp_mesh(
        self, tmp_path, tiny_world_configs
    ):
        # dp-sharded megastep meshes are accepted now, but only when
        # the ring / batch / lane geometry divides evenly: dp=8 with
        # SELF_PLAY_BATCH_SIZE=4 leaves the rollout lanes unshardable.
        env_cfg, model_cfg, mcts_cfg = tiny_world_configs
        with pytest.raises(Exception, match="divisible by dp"):
            setup_training_components(
                train_config=make_cfg("multi_mesh"),
                env_config=env_cfg,
                model_config=model_cfg,
                mcts_config=mcts_cfg,
                mesh_config=MeshConfig(DP_SIZE=8),
                persistence_config=PersistenceConfig(
                    ROOT_DATA_DIR=str(tmp_path), RUN_NAME="multi_mesh"
                ),
                use_tensorboard=False,
            )


class TestMegastepLoop:
    def test_end_to_end_one_dispatch_per_iteration(
        self, tmp_path, tiny_world_configs, monkeypatch
    ):
        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")
        # 2-move chunks keep the fused program's scan short (tier-1
        # compile budget); the loop semantics are chunk-length-free.
        # The backup's Pallas lowering is on (interpret mode on CPU), so
        # the one-dispatch contract is asserted with a kernel inside the
        # fused program (ops/mcts_backup.py, docs/KERNELS.md).
        c = build(
            tmp_path,
            tiny_world_configs,
            ROLLOUT_CHUNK_MOVES=2,
            mcts_kw={"backup_update": "pallas"},
        )
        params0 = jax.device_get(c.trainer.state.params)
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        # Counters contract, same as the sync loop's.
        assert loop.global_step == 8
        assert loop.episodes_played > 0
        assert len(c.buffer) > 0
        assert loop.experiences_added > 0

        # THE acceptance bar: steady state makes exactly ONE device
        # dispatch per iteration — the megastep program itself. The
        # trainer never dispatched on its own; engine/ring dispatches
        # happened only as warmup pairs (rollout + ingest).
        runner = c.megastep
        assert loop.megastep_iterations > 0
        assert runner.dispatch_count == loop.megastep_iterations
        assert c.trainer.dispatch_count == 0
        assert c.self_play.dispatch_count == c.buffer.dispatch_count

        # Donation/reload regression guard extended to the megastep:
        # params must actually change across megasteps.
        params1 = jax.device_get(c.trainer.state.params)
        leaves0 = jax.tree_util.tree_leaves(params0)
        leaves1 = jax.tree_util.tree_leaves(params1)
        assert any(
            not np.allclose(a, b) for a, b in zip(leaves0, leaves1)
        ), "megastep did not update params (donation regression)"

        # PER reconciliation: the device priority array and the host
        # SumTree mirror agree row for row (float32 vs float64 only).
        dev, host = _priorities_sides(c)
        assert dev.size > 0
        np.testing.assert_allclose(dev, host, rtol=1e-4, atol=1e-6)

        # Weight sync cadence (K=2 crossing freq=2 every megastep).
        assert loop.weight_updates == 4
        # Checkpoints: cadence (step 4) + final (step 8).
        assert c.checkpoints.latest_step() == 8

        # Telemetry keeps working: ledger util records carry the
        # dispatches-per-iteration gauge, converged to 1.0 in steady
        # state; health heartbeat exists.
        run_dir = c.persistence_config.get_run_base_dir()
        records = [
            json.loads(line)
            for line in (run_dir / "metrics.jsonl").read_text().splitlines()
        ]
        utils = [r for r in records if r.get("kind") == "util"]
        assert utils
        dpi = [
            r["dispatches_per_iteration"]
            for r in utils
            if isinstance(
                r.get("dispatches_per_iteration"), (int, float)
            )
        ]
        assert dpi, "no dispatches_per_iteration in util records"
        assert dpi[-1] == pytest.approx(1.0)
        assert (run_dir / "health.json").exists()
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_one_dispatch_holds_with_tree_reuse(
        self, tmp_path, tiny_world_configs, monkeypatch
    ):
        """Subtree reuse rides INSIDE the fused program: with
        tree_reuse on, steady state is still exactly one device
        dispatch per iteration (the promotion never becomes its own
        dispatch) and the loop's reused-visit counter proves the
        carried trees were actually consumed. Marked slow (a second
        full megastep compile); the in-program reuse accumulation is
        tier-1-covered at engine level and the dispatch accounting by
        the fresh-root one-dispatch test above."""
        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")
        c = build(
            tmp_path,
            tiny_world_configs,
            run_name="mega_reuse",
            MAX_TRAINING_STEPS=4,
            ROLLOUT_CHUNK_MOVES=2,
            mcts_kw={"tree_reuse": True},
        )
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        runner = c.megastep
        assert loop.megastep_iterations > 0
        assert runner.dispatch_count == loop.megastep_iterations
        assert c.trainer.dispatch_count == 0
        assert loop.total_reused_visits > 0
        c.stats.close()
        c.checkpoints.close()

    @pytest.mark.slow
    def test_counters_contract_matches_sync(
        self, tmp_path, tiny_world_configs
    ):
        """Same seeds, same step budget: megastep and sync modes both
        complete the run with the same counters contract (global_step,
        episodes played, buffer fill). Marked slow (two full component
        builds + loop runs); the megastep side of the contract is
        tier-1-asserted by the end-to-end test above against the same
        numbers the sync-mode tier-1 test pins."""
        steps = 8
        c_sync = build(
            tmp_path,
            tiny_world_configs,
            run_name="contract_sync",
            FUSED_MEGASTEP=False,
            LEARNER_STEPS_PER_ROLLOUT=2,
            MAX_TRAINING_STEPS=steps,
            PER_BETA_ANNEAL_STEPS=steps,
        )
        loop_sync = TrainingLoop(c_sync)
        assert loop_sync.run() == LoopStatus.COMPLETED
        c_sync.stats.close()
        c_sync.checkpoints.close()

        c_mega = build(
            tmp_path,
            tiny_world_configs,
            run_name="contract_mega",
            MAX_TRAINING_STEPS=steps,
            PER_BETA_ANNEAL_STEPS=steps,
        )
        loop_mega = TrainingLoop(c_mega)
        assert loop_mega.run() == LoopStatus.COMPLETED
        c_mega.stats.close()
        c_mega.checkpoints.close()

        # Same counters contract at identical seeds/budget.
        assert loop_mega.global_step == loop_sync.global_step == steps
        assert loop_mega.episodes_played > 0
        assert loop_sync.episodes_played > 0
        assert len(c_mega.buffer) > 0 and len(c_sync.buffer) > 0
        # PER beta annealed on the same learner-step clock.
        assert c_mega.buffer.beta(steps) == c_sync.buffer.beta(steps)

    @pytest.mark.slow
    def test_learning_sanity_loss_decreases(self, tiny_world_configs):
        """The megastep's learner actually learns: against a FIXED ring
        of synthetic targets (stationary distribution — the live loop's
        loss is a moving-target signal in every mode), repeated
        megasteps must drive the loss down. Marked slow — the tier-1
        end-to-end test already pins that params update; this adds the
        loss-decrease bar on stationary data."""
        tc = make_cfg(
            "learning_probe",
            MAX_TRAINING_STEPS=100,
            ROLLOUT_CHUNK_MOVES=2,
            BATCH_SIZE=16,
            LEARNING_RATE=3e-3,
        )
        runner = direct_runner(tiny_world_configs, tc)
        fill_ring(runner.buffer, 512)  # dominates the live rollout rows
        losses = []
        for _ in range(12):
            outs, _added = runner.run_megastep(2, 2)
            losses.extend(m["total_loss"] for m, _td in outs)
        early = float(np.mean(losses[:4]))
        late = float(np.mean(losses[-4:]))
        assert late < early, (
            f"megastep loss did not decrease ({early:.4f} -> {late:.4f})"
        )

    def test_lr_labels_never_call_optax(self, tiny_world_configs):
        """A megastep group labels its steps from the numpy twin of the
        schedule: nothing is dispatched op by op after the one fetch."""
        tc = make_cfg("lr_probe", ROLLOUT_CHUNK_MOVES=2)
        runner = direct_runner(tiny_world_configs, tc)
        fill_ring(runner.buffer, 64)
        trainer = runner.trainer
        want = [float(trainer.schedule(i)) for i in (1, 2)]
        trainer.schedule = None  # a call on the host path would raise
        outs, _added = runner.run_megastep(2, 2)
        assert trainer.global_step == 2
        lrs = [m["learning_rate"] for m, _td in outs]
        assert lrs == pytest.approx(want, rel=1e-6)

    def test_the_dispatch_span_says_how_the_ring_is_read(
        self, tiny_world_configs
    ):
        """`megastep.dispatch` around the one jitted call: the chunk's
        moves, the group's steps, and `ring_read`, by the rule the
        program's own `read_rows` goes by; the ring it names is the one
        that went in (the call donates it)."""
        from alphatriangle_tpu.rl.device_buffer import ring_read
        from alphatriangle_tpu.telemetry import (
            SpanTracer,
            default_tracer,
            set_default_tracer,
        )

        tc = make_cfg("span_probe", ROLLOUT_CHUNK_MOVES=2)
        runner = direct_runner(tiny_world_configs, tc)
        fill_ring(runner.buffer, 64)
        want = ring_read(runner.buffer.storage)
        before = default_tracer()
        tracer = set_default_tracer(SpanTracer())
        try:
            runner.run_megastep(2, 2)
        finally:
            set_default_tracer(before)
        spans = [r for r in tracer.records() if r[1] == "megastep.dispatch"]
        assert [r[6] for r in spans] == [{"t": 2, "k": 2, "ring_read": want}]
        assert sorted(want["in_place"] + want["as_is"]) == sorted(
            runner.buffer.storage
        )

    @pytest.mark.slow
    def test_run_training_and_resume(self, tmp_path, tiny_world_configs):
        """Checkpoint + resume work in megastep mode (run, 'kill',
        rerun with a longer horizon -> continues from the saved step).
        Marked slow (two full run_training sessions); the sync-mode
        resume contract is tier-1-covered in test_training_loop and the
        megastep checkpoint cadence in the end-to-end test above."""
        env_cfg, model_cfg, mcts_cfg = tiny_world_configs
        pc = PersistenceConfig(
            ROOT_DATA_DIR=str(tmp_path), RUN_NAME="mega_resume"
        )
        tc = make_cfg(
            "mega_resume", MAX_TRAINING_STEPS=4, CHECKPOINT_SAVE_FREQ_STEPS=2
        )
        rc = run_training(
            train_config=tc,
            env_config=env_cfg,
            model_config=model_cfg,
            mcts_config=mcts_cfg,
            mesh_config=MeshConfig(DP_SIZE=1),
            persistence_config=pc,
            use_tensorboard=False,
            log_level="WARNING",
        )
        assert rc == 0
        tc2 = make_cfg(
            "mega_resume", MAX_TRAINING_STEPS=8, CHECKPOINT_SAVE_FREQ_STEPS=2
        )
        rc = run_training(
            train_config=tc2,
            env_config=env_cfg,
            model_config=model_cfg,
            mcts_config=mcts_cfg,
            mesh_config=MeshConfig(DP_SIZE=1),
            persistence_config=pc,
            use_tensorboard=False,
            log_level="WARNING",
        )
        assert rc == 0
        from alphatriangle_tpu.stats import CheckpointManager

        mgr = CheckpointManager(pc)
        assert mgr.latest_step() == 8


class TestMegastepCompileCache:
    @pytest.mark.slow
    def test_analyze_registers_record_and_sidecar(
        self, tmp_path, tiny_world_configs
    ):
        """The megastep program lands in the compile cache's memory
        registry with a `.mem.json` sidecar — on CPU too, where the
        executable itself is cpu_aot-bypassed. Marked slow (a real
        megastep compile); the fit/warm WIRING stays tier-1 below."""
        train_cfg = make_cfg("cache_probe", MAX_TRAINING_STEPS=2)
        try:
            cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            runner = direct_runner(tiny_world_configs, train_cfg)
            rec = runner.analyze_megastep(2, 1)
            assert rec is not None
            assert rec["program"] == "megastep/t2_k1"
            assert any(
                r.get("program") == "megastep/t2_k1"
                for r in cache.memory_summary()
            )
            sidecars = list((tmp_path / "aot").glob("megastep*.mem.json"))
            assert len(sidecars) == 1
            assert (
                json.loads(sidecars[0].read_text())["program"]
                == "megastep/t2_k1"
            )
        finally:
            reset_compile_cache()

    def test_cli_warm_and_fit_cover_megastep(
        self, tmp_path, tiny_world_configs, monkeypatch
    ):
        """`cli warm` lists the megastep program (skipped-cpu on the
        CPU backend, like the learner family it embeds) and `cli fit`'s
        estimator includes it in its analysis targets. The analyze
        implementations are stubbed here (their real compile/record
        path is covered by the sidecar test above) — this test pins the
        WIRING, inside the tier-1 compile budget."""
        from alphatriangle_tpu.rl.megastep import MegastepRunner
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine
        from alphatriangle_tpu.rl.trainer import Trainer
        from alphatriangle_tpu.telemetry.memory import estimate_fit
        from alphatriangle_tpu.warm import warm_programs

        def stub_record(program):
            return {
                "kind": "memory",
                "category": "program",
                "component": f"program/{program}",
                "program": program,
                "bytes": {"argument": 64, "output": 8, "temp": 8,
                          "generated_code": 0},
                "total": 80,
                "transient": 16,
            }

        monkeypatch.setattr(
            SelfPlayEngine,
            "analyze_chunk",
            lambda self, n=None: stub_record("self_play_chunk/t4"),
        )
        monkeypatch.setattr(
            Trainer,
            "analyze_step",
            lambda self, b=None: stub_record("learner_step/b8"),
        )
        monkeypatch.setattr(
            Trainer,
            "analyze_steps",
            lambda self, k, b=None: stub_record("learner_fused/k2"),
        )
        monkeypatch.setattr(
            MegastepRunner,
            "analyze_megastep",
            lambda self, t=None, k=None: stub_record("megastep/t4_k2"),
        )

        env_cfg, model_cfg, mcts_cfg = tiny_world_configs
        train_cfg = make_cfg("warm_fit_probe", MAX_TRAINING_STEPS=2)
        # A FUSED_MEGASTEP bundle: the megastep is the learner's side.
        bundle = {
            "env": env_cfg,
            "model": model_cfg,
            "mcts": mcts_cfg,
            "train": train_cfg,
            "mesh": MeshConfig(DP_SIZE=1),
        }
        try:
            reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            report = warm_programs(
                bundle, jobs=1, programs={"megastep", "learner"}
            )
            rows = {r["program"]: r["status"] for r in report["programs"]}
            assert rows == {"megastep/t4_k2": "skipped-cpu"}

            fit = estimate_fit(
                env_cfg,
                model_cfg,
                mcts_cfg,
                train_cfg,
                fused_k=2,
                megastep=True,
            )
            programs = {
                str(r.get("program", ""))
                for r in fit["records"]
                if r.get("category") == "program"
            }
            assert "megastep/t4_k2" in programs
            # The pre-megastep targets are still analyzed too.
            assert any(p.startswith("self_play_chunk") for p in programs)
        finally:
            reset_compile_cache()


# sha256 of the lowered text of (the rollout chunk, the megastep) at a
# preset's widths, 8 lanes, a ring of 512 rows, batches of 8, the
# preset's own chunk length and K. Taken on commit 9e5aaf1, where the
# descent's gather, the PER draw and the subtree promotion could still
# be lowered by config-chosen Pallas kernels, with this module's code:
# the one lowering left of each is that commit's default, byte for byte.
PROGRAM_DIGESTS = {
    3: (
        "eb137f563e20a12762c56b58fac605fdeb75507fdf00b1b3934fc929519258e3",
        "87c734920308428c78fa60cc6076c7f9791be30e9799c946ee23b48af7fab8af",
    ),
    4: (
        "6572dce4558e6ae58a7367882ccc607de80ddcadc05e50049aae74677670c95d",
        "6c7cc5cbe0782379b927bb4efd03737b12ccf75fb152b6a6195d413f9dd93cf5",
    ),
}


@pytest.fixture(scope="module")
def preset_runner():
    """`direct_runner` at a preset's widths (preset 3 is `flagship-p3`),
    one per preset, built on first use. The engines are built with the
    device telemetry a fresh process has (no stat-packs, no beacons):
    an engine reads it when it is made, and the loops above set it."""
    from alphatriangle_tpu.config.presets import baseline_preset
    from alphatriangle_tpu.telemetry.device_stats import reset_device_stats_state

    reset_device_stats_state()
    runners = {}

    def get(preset: int):
        if preset not in runners:
            cfgs = baseline_preset(preset)
            train = TrainConfig(
                **{
                    **cfgs["train"].model_dump(),
                    "AUTO_RESUME_LATEST": False,
                    "SELF_PLAY_BATCH_SIZE": 8,
                    "BUFFER_CAPACITY": 512,
                    "MIN_BUFFER_SIZE_TO_TRAIN": 512,
                    "BATCH_SIZE": 8,
                }
            )
            runners[preset] = direct_runner(
                (cfgs["env"], cfgs["model"], cfgs["mcts"]), train
            )
        return runners[preset]

    yield get
    reset_device_stats_state()


def _digest(text: str) -> str:
    import hashlib

    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize("preset", sorted(PROGRAM_DIGESTS))
def test_the_rollout_chunk_is_the_parents_text(preset, preset_runner):
    import functools

    import jax.numpy as jnp

    engine = preset_runner(preset).engine
    text = (
        jax.jit(functools.partial(engine._chunk, engine.config.ROLLOUT_CHUNK_MOVES))
        .lower(
            engine._inference_variables(engine.net.variables, 0),
            engine._carry,
            jnp.int32(0),
        )
        .as_text()
    )
    assert _digest(text) == PROGRAM_DIGESTS[preset][0]


@pytest.mark.parametrize("preset", sorted(PROGRAM_DIGESTS))
def test_the_megastep_is_the_parents_text(preset, preset_runner):
    """The fused program at its defaults, PER on: the stratified draw
    is inside it."""
    import functools

    runner = preset_runner(preset)
    assert runner.use_per
    t, k = runner.config.ROLLOUT_CHUNK_MOVES, runner.config.FUSED_LEARNER_STEPS
    text = (
        jax.jit(functools.partial(runner._impl, t, k))
        .lower(*runner._dispatch_args(t, k))
        .as_text()
    )
    assert _digest(text) == PROGRAM_DIGESTS[preset][1]
