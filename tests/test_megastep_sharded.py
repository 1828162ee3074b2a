"""dp-sharded fused megastep (rl/megastep.py `megastep/dp<D>_t<T>_k<K>`).

PR 8 lifts the single-device gate: the whole Anakin program (rollout +
ring ingest + K learner steps) runs dp-sharded over the mesh — each
shard scatters its harvest into its ring shard, samples its stratum of
the PER batch device-locally, and the embedded learner's gradient
all-reduce keeps params bit-identical on every shard.

Fast tier: setup wiring + host-side reconciliation + the per-shard
sampling kernel (no megastep compile). Slow tier: the dp=2 in-process
end-to-end loop, and the 8-way `--xla_force_host_platform_device_count`
subprocess dryrun (tests/megastep_dp_driver.py) that also covers resume
from a single-device-mode checkpoint.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from alphatriangle_tpu.config import (
    MeshConfig,
    PersistenceConfig,
    TrainConfig,
)
from alphatriangle_tpu.rl.sharded_device_buffer import (
    ShardedDeviceReplayBuffer,
)
from alphatriangle_tpu.training import (
    LoopStatus,
    TrainingLoop,
    setup_training_components,
)

REPO_ROOT = Path(__file__).resolve().parent.parent

DP = 2


def make_cfg(run_name: str, **kw) -> TrainConfig:
    base = dict(
        RUN_NAME=run_name,
        AUTO_RESUME_LATEST=False,
        MAX_TRAINING_STEPS=8,
        SELF_PLAY_BATCH_SIZE=4,
        ROLLOUT_CHUNK_MOVES=2,
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


def build(tmp_path, cfgs, run_name="mega_dp", dp=DP, **kw):
    env_cfg, model_cfg, mcts_cfg = cfgs
    return setup_training_components(
        train_config=make_cfg(run_name, **kw),
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        mesh_config=MeshConfig(DP_SIZE=dp),
        persistence_config=PersistenceConfig(
            ROOT_DATA_DIR=str(tmp_path), RUN_NAME=run_name
        ),
        use_tensorboard=False,
    )


@pytest.fixture(scope="module")
def tiny_world_configs(tiny_env_config, tiny_model_config, tiny_mcts_config):
    return tiny_env_config, tiny_model_config, tiny_mcts_config


@pytest.fixture(scope="module")
def shared_components(tmp_path_factory, tiny_world_configs):
    """One dp=2 component build shared by the fast read-mostly tests —
    setup_training_components is the dominant cost here (several
    seconds), and the tier-1 870s budget is razor-thin. Tests that
    mutate buffer state call _reset_buffer first."""
    c = build(
        tmp_path_factory.mktemp("mega_dp_shared"),
        tiny_world_configs,
        run_name="shared",
    )
    yield c
    c.stats.close()
    c.checkpoints.close()


def _reset_buffer(buf) -> None:
    """Zero the host mirrors (trees/cursors/sizes) between tests; the
    device storage contents are irrelevant to the host-side asserts."""
    from alphatriangle_tpu.utils.sumtree import SumTree

    if buf.trees is not None:
        buf.trees = [SumTree(buf.cap_local) for _ in range(buf.dp)]
    buf._cursors[:] = 0
    buf._sizes[:] = 0
    buf._size = 0


class TestShardedWiring:
    def test_setup_builds_sharded_megastep(self, shared_components):
        c = shared_components
        buf = c.buffer
        assert isinstance(buf, ShardedDeviceReplayBuffer)
        assert c.megastep is not None and c.megastep.sharded
        assert c.megastep.dp == DP
        # Per-shard ring geometry: the global capacity splits into
        # dp local rings, each with its own trash row.
        assert buf.cap_local == buf.capacity // DP
        assert buf.stride == buf.cap_local + 1
        # All three participants share one mesh, dp-only.
        assert c.trainer.mesh is buf.mesh
        assert c.self_play.mesh is buf.mesh

    def test_warmup_gate_requires_every_shard(self, shared_components):
        # _megastep_ready: the in-program gather samples each shard's
        # stratum locally, so warmup must run until EVERY shard holds a
        # full per-shard batch — a global row count is not enough.
        c = shared_components
        _reset_buffer(c.buffer)
        loop = TrainingLoop(c)
        need = c.train_config.MIN_BUFFER_SIZE_TO_TRAIN
        assert not loop._megastep_ready(need)
        rows = _rows(need * DP, c)
        c.buffer.add_dense(**rows)
        assert loop._megastep_ready(need)
        # Starve one shard below b_local by rebuilding lopsided.
        c.buffer._sizes[0] = 0
        assert not loop._megastep_ready(need)


def _rows(n, c, seed=0):
    env = c.self_play.env
    rng = np.random.default_rng(seed)
    adim = env.action_dim
    policy = rng.random((n, adim)).astype(np.float32)
    policy /= policy.sum(axis=1, keepdims=True)
    grid_shape = jax.device_get(c.buffer.storage["grid"]).shape[1:]
    other_dim = jax.device_get(c.buffer.storage["other_features"]).shape[1]
    return {
        "grid": rng.integers(-1, 2, size=(n, *grid_shape)).astype(
            np.float32
        ),
        "other_features": rng.random((n, other_dim)).astype(np.float32),
        "policy_target": policy,
        "value_target": rng.uniform(-3, 3, n).astype(np.float32),
    }


class TestHostReconciliation:
    def test_reconcile_ingest_advances_mirrors(self, shared_components):
        buf = shared_components.buffer
        _reset_buffer(buf)
        counts = np.array([3, 5], dtype=np.int64)
        total, slots = buf.reconcile_ingest(counts, max_priority=2.5)
        assert total == 8
        assert len(buf) == 8
        np.testing.assert_array_equal(buf._sizes, counts)
        np.testing.assert_array_equal(
            buf._cursors, counts % buf.cap_local
        )
        # Slots are globally encoded, shard-major.
        np.testing.assert_array_equal(
            slots // buf.stride, np.repeat([0, 1], [3, 5])
        )
        # Every ingested row carries the sampling watermark the
        # device program used — device and host trees agree.
        for k, tree in enumerate(buf.trees):
            sz = int(counts[k])
            leaves = tree.tree[np.arange(sz) + tree._cap2]
            np.testing.assert_allclose(leaves, 2.5)
        assert buf.max_priority == pytest.approx(2.5)

    def test_reconcile_wraps_per_shard_ring(self, shared_components):
        buf = shared_components.buffer
        _reset_buffer(buf)
        cap = buf.cap_local
        buf.reconcile_ingest(
            np.array([cap - 1, 0]), max_priority=1.0
        )
        _, slots = buf.reconcile_ingest(
            np.array([3, 0]), max_priority=1.0
        )
        # 3 rows on a cap-1 cursor: one fills the ring, two wrap.
        local = slots % buf.stride
        np.testing.assert_array_equal(local, [cap - 1, 0, 1])
        assert int(buf._sizes[0]) == cap
        assert int(buf._cursors[0]) == 2


class TestSampleLocal:
    def test_per_stratified_in_range_and_weighted(
        self, shared_components
    ):
        buf = shared_components.buffer
        size, k, b_local = 32, 2, 4
        prios = np.zeros(buf.cap_local + 1, np.float32)
        prios[:size] = np.linspace(1.0, 4.0, size)
        idx, w = jax.device_get(
            buf.sample_local(
                jax.numpy.asarray(prios),
                jax.numpy.int32(size),
                k,
                b_local,
                jax.random.PRNGKey(0),
                jax.numpy.float32(0.4),
            )
        )
        assert idx.shape == (k, b_local) and w.shape == (k, b_local)
        assert (idx >= 0).all() and (idx < size).all()
        # Weights are the UNNORMALIZED (N*p)^-beta — the megastep
        # normalizes by a pmax across shards, not here.
        assert (w > 0).all()


class TestWarmFitWiring:
    def test_warm_and_fit_cover_sharded_family(
        self, tmp_path, tiny_world_configs, monkeypatch
    ):
        """`cli warm` lists the dp-sharded megastep program, the one a
        run on this mesh dispatches (skipped-cpu on this backend, like
        every learner-embedding program) and `estimate_fit(megastep=True)`
        analyzes the sharded family with a per-device ring budget
        (cap_local, not the global capacity). Analyze implementations
        are stubbed — this pins the WIRING inside the tier-1 budget."""
        from alphatriangle_tpu.compile_cache import reset_compile_cache
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
            lambda self, n=None: stub_record("self_play_chunk/t2"),
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
            lambda self, t=None, k=None: stub_record(
                f"megastep/dp{self.dp}_t2_k2"
                if self.sharded
                else "megastep/t2_k2"
            ),
        )

        env_cfg, model_cfg, mcts_cfg = tiny_world_configs
        # dp = the process's full 8-device count: every divisibility
        # condition of the setup gate holds for this geometry.
        ndev = jax.device_count()
        train_cfg = make_cfg(
            "warm_fit_dp", SELF_PLAY_BATCH_SIZE=ndev, MAX_TRAINING_STEPS=2
        )
        # No "mesh": the default spans every device, as `cli train`'s.
        bundle = {
            "env": env_cfg,
            "model": model_cfg,
            "mcts": mcts_cfg,
            "train": train_cfg,
        }
        try:
            reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            report = warm_programs(
                bundle, jobs=1, programs={"megastep", "learner"}
            )
            rows = {r["program"]: r["status"] for r in report["programs"]}
            assert rows == {f"megastep/dp{ndev}_t2_k2": "skipped-cpu"}

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
            assert f"megastep/dp{ndev}_t2_k2" in programs
            assert any(p.startswith("self_play_chunk") for p in programs)
            # Budget charges each device its cap_local ring slice.
            ring = next(
                r
                for r in fit["records"]
                if r.get("category") == "ring"
                and r.get("location") == "device"
            )
            assert ring["shards"] == ndev
            assert (
                fit["budget"]["replay_ring_bytes"]
                == ring["total"] // ndev
            )
        finally:
            reset_compile_cache()


@pytest.mark.slow
class TestShardedLoopEndToEnd:
    def test_dp2_one_dispatch_params_and_per(
        self, tmp_path, tiny_world_configs, monkeypatch
    ):
        monkeypatch.setenv("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")
        c = build(tmp_path, tiny_world_configs, run_name="dp2_e2e")
        loop = TrainingLoop(c)
        status = loop.run()
        assert status == LoopStatus.COMPLETED
        assert loop.global_step == 8

        runner = c.megastep
        # ONE mesh-level dispatch per iteration; the trainer never
        # launched a standalone program.
        assert runner.dispatch_count == loop.megastep_iterations > 0
        assert c.trainer.dispatch_count == 0

        # Params bit-identical on every shard after the K-step groups
        # (the gradient all-reduce is the megastep's psum axis).
        for leaf in jax.tree_util.tree_leaves(c.trainer.state.params):
            shards = [np.asarray(s.data) for s in leaf.addressable_shards]
            assert len(shards) == DP
            for s in shards[1:]:
                np.testing.assert_array_equal(shards[0], s)

        # Per-shard PER reconciliation: each shard's device priority
        # slice matches its host SumTree mirror exactly.
        buf = c.buffer
        prios = np.asarray(runner._priorities)
        for k, tree in enumerate(buf.trees):
            sz = int(buf._sizes[k])
            assert sz > 0
            dev = prios[k * buf.stride : k * buf.stride + sz]
            host = tree.tree[np.arange(sz) + tree._cap2]
            np.testing.assert_allclose(dev, host, rtol=1e-4, atol=1e-6)

        # Ledger gauge: steady-state dispatches_per_iteration == 1.0.
        run_dir = c.persistence_config.get_run_base_dir()
        records = [
            json.loads(line)
            for line in (run_dir / "metrics.jsonl")
            .read_text()
            .splitlines()
        ]
        dpi = [
            r["dispatches_per_iteration"]
            for r in records
            if r.get("kind") == "util"
            and isinstance(
                r.get("dispatches_per_iteration"), (int, float)
            )
        ]
        assert dpi and dpi[-1] == pytest.approx(1.0)
        assert c.checkpoints.latest_step() == 8
        c.stats.close()
        c.checkpoints.close()


@pytest.mark.slow
def test_eight_way_dryrun_with_single_device_resume(tmp_path):
    """The ISSUE's acceptance dryrun: 8 virtual host-platform devices,
    resume from a single-device-mode checkpoint, one dispatch per
    iteration, identical params on all shards, per-shard PER
    reconciliation. Runs in a subprocess so it can set its own
    --xla_force_host_platform_device_count before JAX initialises."""
    proc = subprocess.run(
        [
            sys.executable,
            str(REPO_ROOT / "tests" / "megastep_dp_driver.py"),
            str(tmp_path),
        ],
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
        cwd=REPO_ROOT,
        env={**os.environ, "PYTHONPATH": str(REPO_ROOT)},
        timeout=540,
    )
    out = proc.stdout
    assert proc.returncode == 0, f"driver failed:\n{out}"
    for marker in (
        "BASE_STEP=4",
        "RESUME_STEP=4",
        "DISPATCH_OK",
        "PARAMS_OK",
        "PER_OK",
        "MEGA_DP_OK",
    ):
        assert marker in out, f"missing {marker}:\n{out}"

    def field(key: str) -> str:
        return next(
            line.split("=", 1)[1]
            for line in out.splitlines()
            if line.startswith(key + "=")
        )

    assert float(field("GAUGE")) == pytest.approx(1.0)
