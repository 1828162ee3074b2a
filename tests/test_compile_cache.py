"""Compile-latency subsystem tests (compile_cache.py, warm.py, cli warm).

Cold-vs-warm is asserted via the cache's hit/miss counters and the
on-disk executable files — never wall clock (CI machines make timing
assertions flaky). The cross-PROCESS reuse property is exercised
in-process by resetting the process-global cache between engines: a
fresh `CompileCache` has no in-memory executables, so a hit can only
come from deserializing the serialized artifact, exactly what a new
process would do.
"""

import json
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.compile_cache import (
    CompileCache,
    config_digest,
    get_compile_cache,
    reset_compile_cache,
)


@pytest.fixture(autouse=True)
def no_xla_persistent_cache():
    """Keep the XLA persistent cache off for this module whatever an
    earlier test did. This mirrors the real CPU environment —
    `enable_persistent_compilation_cache` skips CPU — and matters for
    correctness here: an executable that compile() loads FROM the
    persistent cache serializes to a truncated payload on XLA:CPU, so
    with it on, fresh AOT artifacts could never be published (the
    validation round trip in `_serialize` rejects them).

    jax LATCHES cache-used at the first compile of the process, so in
    a full-suite run (where earlier test files already compiled through
    the cache) flipping the config alone does nothing: the latch must
    be reset too (`compilation_cache.reset_cache`)."""
    from jax._src import compilation_cache as _cc

    jax.config.update("jax_enable_compilation_cache", False)
    _cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    _cc.reset_cache()  # re-latch with the cache enabled for later tests


@pytest.fixture()
def fresh_cache(tmp_path):
    """Point the process-global cache at an empty tmp dir; restore the
    default afterwards so other tests keep their shared cache."""
    cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
    yield cache
    reset_compile_cache()


def _double(x):
    return x * 2.0


class TestCachedProgram:
    def test_roundtrip_serialize_deserialize_cpu(self, fresh_cache):
        """An executable serialized by one cache instance is
        deserialized (hit) by a fresh instance — the cross-process
        path — and computes the same answer."""
        x = jnp.arange(6.0).reshape(2, 3)
        prog = fresh_cache.wrap("t/double", jax.jit(_double))
        cold = np.asarray(prog(x))
        assert fresh_cache.misses == 1 and fresh_cache.hits == 0
        files = list(fresh_cache.cache_dir.glob("*.jaxexe"))
        assert len(files) == 1  # serialized artifact on disk

        second = CompileCache(cache_dir=str(fresh_cache.cache_dir))
        prog2 = second.wrap("t/double", jax.jit(_double))
        warm = np.asarray(prog2(x))
        assert second.hits == 1 and second.misses == 0
        np.testing.assert_array_equal(cold, warm)

    def test_one_device_program_reloads_for_one_device(self, fresh_cache):
        """The `execution_devices` regression. jax 0.9's
        `deserialize_and_load` loads for EVERY device of the backend
        unless told otherwise, and a one-device program reloaded on this
        8-device host then refused its first call: "Expected args to
        execute_sharded_on_local_devices to have 8 shards, got: [1]".
        A four-chip host does the same to every one-chip program."""
        import pickle

        assert len(jax.devices()) == 8
        x = jnp.arange(6.0).reshape(2, 3)
        fresh_cache.wrap("t/double", jax.jit(_double))(x)
        (artifact,) = fresh_cache.cache_dir.glob("*.jaxexe")
        with artifact.open("rb") as fh:
            assert pickle.load(fh)["device_ids"] == [jax.devices()[0].id]

        second = CompileCache(cache_dir=str(fresh_cache.cache_dir))
        out = second.wrap("t/double", jax.jit(_double))(x)  # executes
        assert second.stats()["hits"] == 1
        assert second.stats()["deserialize_errors"] == 0
        assert out.devices() == {jax.devices()[0]}
        np.testing.assert_array_equal(np.asarray(out), np.asarray(x) * 2.0)

    def test_mesh_program_reloads_on_its_own_mesh(self, fresh_cache):
        """A dp=2 program on devices 2 and 3 — not the default device —
        reloads for exactly that pair, executes there, and keys apart
        from the same program on another pair."""
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        def on(devices):
            mesh = Mesh(np.array(devices), ("dp",))
            return jax.device_put(
                jnp.arange(8.0), NamedSharding(mesh, P("dp"))
            )

        x = on(jax.devices()[2:4])
        cold = fresh_cache.wrap("t/mesh", jax.jit(_double))(x)
        second = CompileCache(cache_dir=str(fresh_cache.cache_dir))
        prog = second.wrap("t/mesh", jax.jit(_double))
        warm = prog(x)
        assert (second.hits, second.misses) == (1, 0)
        assert warm.devices() == set(jax.devices()[2:4])
        np.testing.assert_array_equal(np.asarray(cold), np.asarray(warm))
        # Same shapes and spec on devices 4 and 5: another executable.
        other = prog(on(jax.devices()[4:6]))
        assert (second.hits, second.misses) == (1, 1)
        assert other.devices() == set(jax.devices()[4:6])

    def test_warm_populates_without_executing(self, fresh_cache):
        calls = []

        def spy(x):
            calls.append(1)
            return x + 1

        prog = fresh_cache.wrap("t/spy", jax.jit(spy))
        x = jnp.ones((3,))
        assert prog.warm(x) is True  # compiles + serializes
        assert fresh_cache.misses == 1
        # warm() traced (to lower) but never executed on real data;
        # the later call reuses the in-memory executable (no new event).
        out = np.asarray(prog(x))
        np.testing.assert_array_equal(out, np.full(3, 2.0))
        assert fresh_cache.misses == 1 and fresh_cache.hits == 0

    def test_shape_mismatch_is_fresh_compile_not_hit(self, fresh_cache):
        prog = fresh_cache.wrap("t/double", jax.jit(_double))
        prog(jnp.ones((2, 3)))
        second = CompileCache(cache_dir=str(fresh_cache.cache_dir))
        prog2 = second.wrap("t/double", jax.jit(_double))
        # Different shape -> different signature -> miss, new artifact.
        prog2(jnp.ones((4, 5)))
        assert second.hits == 0 and second.misses == 1
        assert len(list(second.cache_dir.glob("*.jaxexe"))) == 2
        # Same shape again -> hit against the first artifact.
        prog3 = CompileCache(cache_dir=str(fresh_cache.cache_dir)).wrap(
            "t/double", jax.jit(_double)
        )
        prog3(jnp.ones((2, 3)))

    def test_config_extra_splits_the_key(self, fresh_cache):
        x = jnp.ones((2, 2))
        fresh_cache.wrap("t/double", jax.jit(_double), extra="cfgA")(x)
        second = CompileCache(cache_dir=str(fresh_cache.cache_dir))
        second.wrap("t/double", jax.jit(_double), extra="cfgB")(x)
        # Same avals, different config digest: must NOT reuse.
        assert second.hits == 0 and second.misses == 1

    def test_corrupt_artifact_degrades_to_recompile(self, fresh_cache):
        x = jnp.ones((2, 2))
        fresh_cache.wrap("t/double", jax.jit(_double))(x)
        (artifact,) = fresh_cache.cache_dir.glob("*.jaxexe")
        artifact.write_bytes(b"not a pickle")
        second = CompileCache(cache_dir=str(fresh_cache.cache_dir))
        out = second.wrap("t/double", jax.jit(_double))(x)
        np.testing.assert_array_equal(np.asarray(out), np.full((2, 2), 2.0))
        assert second.deserialize_errors == 1
        assert second.misses == 1 and second.hits == 0

    def test_disabled_cache_delegates_to_jit(self, tmp_path):
        cache = CompileCache(cache_dir=str(tmp_path), enabled=False)
        prog = cache.wrap("t/double", jax.jit(_double))
        out = prog(jnp.ones((2, 2)))
        np.testing.assert_array_equal(np.asarray(out), np.full((2, 2), 2.0))
        assert cache.hits == cache.misses == 0
        assert not list(tmp_path.glob("*.jaxexe"))

    def test_donated_args_work_through_the_aot_path(self, fresh_cache):
        def bump(state, dx):
            return state + dx

        prog = fresh_cache.wrap(
            "t/donate", jax.jit(bump, donate_argnums=(0,))
        )
        state = jnp.zeros((4,))
        for i in range(3):  # state threads through donated calls
            state = prog(state, jnp.ones((4,)))
        np.testing.assert_array_equal(np.asarray(state), np.full(4, 3.0))

    def test_compile_spans_reach_the_tracer(self, fresh_cache):
        from alphatriangle_tpu.telemetry import SpanTracer

        tracer = SpanTracer(enabled=True)
        fresh_cache.set_tracer(tracer)
        fresh_cache.wrap("t/double", jax.jit(_double))(jnp.ones((2,)))
        names = {s[1] for s in tracer.records()}
        assert "compile/t/double" in names

    def test_config_digest_ignores_run_name(self, tiny_train_config):
        a = config_digest(tiny_train_config)
        b = config_digest(
            tiny_train_config.model_copy(update={"RUN_NAME": "other"})
        )
        c = config_digest(
            tiny_train_config.model_copy(update={"GAMMA": 0.5})
        )
        assert a == b
        assert a != c


class TestEngineAndTrainerReuse:
    """The acceptance property: the rollout-chunk and learner programs
    are genuinely reused across cache instances (counter-proven)."""

    def _engine(self, env_cfg, model_cfg, mcts_cfg, train_cfg, seed=0):
        from alphatriangle_tpu.env.engine import TriangleEnv
        from alphatriangle_tpu.features.core import get_feature_extractor
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl import SelfPlayEngine

        env = TriangleEnv(env_cfg)
        extractor = get_feature_extractor(env, model_cfg)
        net = NeuralNetwork(model_cfg, env_cfg, seed=seed)
        return SelfPlayEngine(
            env, extractor, net, mcts_cfg, train_cfg, seed=seed
        )

    def test_rollout_chunk_cold_then_warm(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
        tiny_train_config,
    ):
        cache_dir = str(tmp_path / "aot")
        try:
            cold = reset_compile_cache(cache_dir=cache_dir)
            e1 = self._engine(
                tiny_env_config,
                tiny_model_config,
                tiny_mcts_config,
                tiny_train_config,
            )
            e1.play_chunk(2)
            assert cold.misses >= 1 and cold.hits == 0

            warm = reset_compile_cache(cache_dir=cache_dir)
            e2 = self._engine(
                tiny_env_config,
                tiny_model_config,
                tiny_mcts_config,
                tiny_train_config,
            )
            e2.play_chunk(2)  # same shapes -> deserialized executable
            assert warm.hits == 1 and warm.misses == 0
            r = e2.harvest()
            assert r.num_episodes >= 0  # the reused program really ran
        finally:
            reset_compile_cache()

    def test_warm_chunk_then_play_needs_no_more_compiles(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
        tiny_train_config,
    ):
        try:
            cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            engine = self._engine(
                tiny_env_config,
                tiny_model_config,
                tiny_mcts_config,
                tiny_train_config,
            )
            assert engine.warm_chunk(2) is True
            events_after_warm = len(cache.events)
            engine.play_chunk(2)
            # Dispatch found the warmed executable: no new compile event.
            assert len(cache.events) == events_after_warm
        finally:
            reset_compile_cache()

    def test_trainer_step_cold_then_warm(
        self, tmp_path, tiny_env_config, tiny_model_config, tiny_train_config
    ):
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl import Trainer

        b = tiny_train_config.BATCH_SIZE
        rng = np.random.default_rng(0)
        batch = {
            "grid": rng.random(
                (b, 1, tiny_env_config.ROWS, tiny_env_config.COLS)
            ).astype(np.float32),
            "other_features": rng.random(
                (b, tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM)
            ).astype(np.float32),
            "policy_target": np.full(
                (b, tiny_env_config.action_dim),
                1.0 / tiny_env_config.action_dim,
                np.float32,
            ),
            "value_target": np.zeros(b, np.float32),
            "weights": np.ones(b, np.float32),
        }
        cache_dir = str(tmp_path / "aot")
        try:
            # Learner programs NEVER ride the AOT artifact path on the
            # CPU backend (trainer wraps with cpu_aot=False): an XLA:CPU
            # deserialized learner executable runs without error but
            # returns the donated train state UNCHANGED — params stop
            # updating silently. This test is the regression lock: no
            # artifacts, no hits, and the second trainer still LEARNS.
            cold = reset_compile_cache(cache_dir=cache_dir)
            net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
            t1 = Trainer(net, tiny_train_config)
            assert t1.aot_enabled is False  # CPU bypass active
            out1 = t1.train_step(dict(batch))
            assert out1 is not None
            assert cold.misses == 0 and cold.hits == 0
            assert not list(Path(cache_dir).glob("learner_step-*.jaxexe"))

            warm = reset_compile_cache(cache_dir=cache_dir)
            net2 = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
            t2 = Trainer(net2, tiny_train_config)
            before = jax.tree_util.tree_map(
                np.asarray, t2.state.params
            )
            out2 = t2.train_step(dict(batch))
            assert out2 is not None
            assert warm.hits == 0 and warm.misses == 0
            # Same seed, same batch, fresh compile: same loss...
            assert out1[0]["total_loss"] == pytest.approx(
                out2[0]["total_loss"], rel=1e-5
            )
            # ...and the step genuinely updated the params (the exact
            # thing a reloaded CPU executable silently failed to do).
            changed = jax.tree_util.tree_map(
                lambda a, b: not np.allclose(a, np.asarray(b)),
                before,
                t2.state.params,
            )
            assert any(jax.tree_util.tree_leaves(changed))
        finally:
            reset_compile_cache()

    def test_fused_steps_warm_covers_dispatch(
        self, tmp_path, tiny_env_config, tiny_model_config, tiny_train_config
    ):
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl import Trainer

        try:
            cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
            trainer = Trainer(net, tiny_train_config)
            # CPU backend: learner warming reports not-AOT (cpu_aot
            # bypass — reloads corrupt donated state) and records no
            # cache events; the fused path still runs correctly via
            # the plain jitted program.
            assert trainer.warm_steps(3) is False
            assert len(cache.events) == 0
            b = tiny_train_config.BATCH_SIZE
            batch = trainer._zero_batch(b)
            results = trainer.train_steps([dict(batch)] * 3)
            assert len(results) == 3
            assert len(cache.events) == 0  # bypass never touches cache
        finally:
            reset_compile_cache()


def _tiny_bundle(env_cfg, model_cfg, mcts_cfg, train_cfg, **train_updates):
    """What `cli.resolve_preset` returns, at test size."""
    return {
        "env": env_cfg,
        "model": model_cfg,
        "mcts": mcts_cfg,
        "train": train_cfg.model_copy(update=train_updates),
        "description": "tiny",
    }


class TestPresetTargets:
    """`cli warm` / `fit` / `tune` take their shapes from the bundle
    `cli train --preset` runs: one resolver, no second statement."""

    @pytest.mark.parametrize("n", [1, 2, 3, 4, 5])
    def test_target_is_the_training_preset(self, n):
        from alphatriangle_tpu.cli import resolve_preset
        from alphatriangle_tpu.config import baseline_preset

        got, want = resolve_preset(str(n)), baseline_preset(n)
        assert set(got) == set(want)
        for key in ("env", "model", "mcts", "train", "mesh"):
            assert got[key] == want[key], key
        train, mcts, model = got["train"], got["mcts"], got["model"]
        lanes = {1: 16, 2: 128, 3: 512, 4: 512, 5: 1024}[n]
        assert train.SELF_PLAY_BATCH_SIZE == lanes
        assert train.FUSED_LEARNER_STEPS == (1 if n == 1 else 16)
        # The run's own ring and horizon, not a measurement's.
        defaults = type(train)()
        assert train.BUFFER_CAPACITY == defaults.BUFFER_CAPACITY
        assert train.BATCH_SIZE == defaults.BATCH_SIZE
        assert train.ROLLOUT_CHUNK_MOVES == defaults.ROLLOUT_CHUNK_MOVES
        assert train.MAX_TRAINING_STEPS == defaults.MAX_TRAINING_STEPS
        assert mcts.root_selection == ("gumbel" if n == 3 else "puct")
        assert mcts.fast_simulations == (16 if n == 3 else None)
        assert model.COMPUTE_DTYPE == ("float32" if n == 1 else "bfloat16")

    @pytest.mark.parametrize("command", ["warm", "fit", "tune"])
    @pytest.mark.parametrize("target", ["auto", "smoke", "cpu"])
    def test_retired_targets_are_refused(self, command, target):
        from alphatriangle_tpu import cli

        with pytest.raises(SystemExit) as exc:
            cli.main([command, target])
        assert "1..5" in str(exc.value) and "BASELINE" in str(exc.value)

    def test_tuned_artifact_carries_its_serve_ladder(self):
        from alphatriangle_tpu.autotune.artifact import serve_ladder

        assert serve_ladder({"train": None}) is None
        tuned = {"kernels": {"serve_buckets": "16,32"}}
        assert serve_ladder({"tuned": tuned}) == "16,32"
        assert serve_ladder({"tuned": {"kernels": {"serve_buckets": ""}}}) is None


class TestWarmCLI:
    def test_cli_warm_tiny_bundle(
        self,
        tmp_path,
        monkeypatch,
        capsys,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
        tiny_train_config,
    ):
        """`cli warm` end to end on a tiny bundle: compiles the rollout
        chunk, the serve rung and the learner programs, serializes
        them, prints a JSON report, and a second invocation is all
        hits."""
        from alphatriangle_tpu import cli

        bundle = _tiny_bundle(
            tiny_env_config,
            tiny_model_config,
            tiny_mcts_config,
            tiny_train_config,
            FUSED_LEARNER_STEPS=2,
        )
        monkeypatch.setattr(cli, "resolve_preset", lambda target: bundle)
        try:
            reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            rc = cli.main(["warm", "1", "--jobs", "2"])
            out = capsys.readouterr().out
            report = json.loads(out.strip().splitlines()[-1])
            assert rc == 0
            # CPU backend: the rollout chunk and the serve rung
            # AOT-warm; the learner programs are deliberately skipped
            # (cpu_aot bypass — reloaded learner executables corrupt
            # donated state).
            statuses = {r["program"]: r["status"] for r in report["programs"]}
            assert statuses == {
                "self_play_chunk/t4": "aot",
                "learner_fused/k2": "skipped-cpu",
                "learner_step/b4": "skipped-cpu",
                "serve/b4": "aot",
            }
            assert report["stats"]["misses"] == 2

            reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            rc2 = cli.main(["warm", "1", "--jobs", "2"])
            report2 = json.loads(
                capsys.readouterr().out.strip().splitlines()[-1]
            )
            assert rc2 == 0
            assert report2["stats"]["hits"] == 2
            assert report2["stats"]["misses"] == 0
        finally:
            reset_compile_cache()

    def test_cli_warm_program_filter(
        self,
        tmp_path,
        monkeypatch,
        capsys,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
        tiny_train_config,
    ):
        from alphatriangle_tpu import cli

        bundle = _tiny_bundle(
            tiny_env_config,
            tiny_model_config,
            tiny_mcts_config,
            tiny_train_config,
        )
        monkeypatch.setattr(cli, "resolve_preset", lambda target: bundle)
        try:
            reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            rc = cli.main(
                ["warm", "1", "--programs", "self_play", "--jobs", "1"]
            )
            report = json.loads(
                capsys.readouterr().out.strip().splitlines()[-1]
            )
            assert rc == 0
            assert [r["program"] for r in report["programs"]] == [
                "self_play_chunk/t4"
            ]

            # Filtering down to CPU-skipped learner programs leaves
            # nothing warmable: reported, and exit 1 ("nothing warm").
            reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            rc2 = cli.main(
                ["warm", "1", "--programs", "learner_step", "--jobs", "1"]
            )
            report2 = json.loads(
                capsys.readouterr().out.strip().splitlines()[-1]
            )
            assert rc2 == 1
            assert [r["status"] for r in report2["programs"]] == [
                "skipped-cpu"
            ]
        finally:
            reset_compile_cache()

    def test_warm_then_setup_hits(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
        tiny_train_config,
    ):
        """The proof that `cli warm` is aimed at the run: warm a
        bundle, then build the training components from the same
        configs over the same cache directory in a "new process" (a
        fresh CompileCache holds no executables in memory). The run's
        first rollout dispatch reloads what the warm serialized: one
        hit, no miss."""
        from alphatriangle_tpu.config import PersistenceConfig
        from alphatriangle_tpu.training import setup_training_components
        from alphatriangle_tpu.warm import warm_programs

        bundle = _tiny_bundle(
            tiny_env_config,
            tiny_model_config,
            tiny_mcts_config,
            tiny_train_config,
            RUN_NAME="warmed",
        )
        cache_dir = str(tmp_path / "aot")
        try:
            reset_compile_cache(cache_dir=cache_dir)
            report = warm_programs(bundle, jobs=1, programs={"self_play"})
            assert [
                (r["program"], r["status"]) for r in report["programs"]
            ] == [("self_play_chunk/t4", "aot")]

            run = reset_compile_cache(cache_dir=cache_dir)
            c = setup_training_components(
                train_config=bundle["train"].model_copy(
                    update={"RUN_NAME": "the_run"}
                ),
                env_config=bundle["env"],
                model_config=bundle["model"],
                mcts_config=bundle["mcts"],
                persistence_config=PersistenceConfig(
                    ROOT_DATA_DIR=str(tmp_path), RUN_NAME="the_run"
                ),
                use_tensorboard=False,
            )
            try:
                c.self_play.play_chunk()
                chunk_events = [
                    e["event"]
                    for e in run.events
                    if e["program"].startswith("self_play_chunk")
                ]
                assert chunk_events == ["hit"]
                assert run.misses == 0
            finally:
                c.telemetry.close()
                c.stats.close()
                c.checkpoints.close()
        finally:
            reset_compile_cache()


class TestGlobalCache:
    def test_global_accessor_is_a_singleton(self):
        try:
            a = reset_compile_cache()
            assert get_compile_cache() is a
        finally:
            reset_compile_cache()
