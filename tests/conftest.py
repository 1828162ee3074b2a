"""Test fixtures: a deliberately tiny world on a virtual 8-device CPU mesh.

Mirrors the reference's fixture strategy (`tests/conftest.py:30-125`):
small board, small net, small buffer — plus the JAX twist: tests run on
CPU with `xla_force_host_platform_device_count=8` so multi-device
sharding paths are exercised without TPU hardware.
"""

import os
import tempfile

# AOT executable cache (compile_cache.py): point the process-wide cache
# at a fresh per-run directory BEFORE any package import can build it.
# Within one pytest process programs compile once and reuse in-memory
# executables; what this prevents is DESERIALIZING artifacts a previous
# process left behind — XLA:CPU reloads of the donating learner/rollout
# programs can silently misbehave (see the persistent-cache note
# below), and a stale shared /tmp cache made the suite's pass/fail
# depend on what ran on the machine earlier. test_compile_cache builds
# its own explicit cache dirs and is unaffected.
os.environ["ALPHATRIANGLE_AOT_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="at_test_aot_"
)

# Skip the setup-time cost pre-capture (telemetry/roofline.py): it
# lower+compiles the learner/megastep program purely for
# `cost_analysis()`, seconds of pure overhead in every throwaway
# training run the suite (and its subprocess drivers — children
# inherit this) spins up. The capture path itself is covered by
# tests/test_roofline.py and `make roofline-smoke`.
os.environ["ALPHATRIANGLE_COST_PRECAPTURE"] = "0"

# Must happen before jax import anywhere in the test process. Force CPU
# even when the ambient environment points at a real accelerator: tests
# exercise sharding on virtual CPU devices and must not take the chip
# from a training/bench process.
os.environ["JAX_PLATFORMS"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax  # noqa: E402

# Do NOT enable the XLA persistent compilation cache here. It used to
# be on (jax_compilation_cache_dir=/tmp/jax_test_cache) to speed up
# repeat suite runs, but XLA:CPU persistent-cache RELOADS are broken in
# this image: a reloaded learner-step executable (donated train state)
# runs without error and returns its inputs UNCHANGED — params stop
# updating, silently (reproduced deterministically: cold run passes,
# warm run fails test_params_change_and_metrics; and serializing the
# reloaded executable fails with "Symbols not found"). This is the same
# hazard utils/helpers.enable_persistent_compilation_cache documents
# and guards by skipping the CPU backend — the test override bypassed
# that guard. The repo's own AOT executable cache (compile_cache.py)
# is unaffected (deserialize_executable round-trips correctly on CPU,
# counter-proven in test_compile_cache) and keeps covering the
# expensive programs across processes.

# XLA:CPU's async dispatch can deadlock when one thread blocks on an
# in-flight program while another enqueues programs sharing its buffers
# (the device-replay producer/consumer topology reproduces it at
# flagship program sizes; see rl/device_buffer.py). Latched at CPU
# client creation, so it must be set here, before any backend touch.
jax.config.update("jax_cpu_enable_async_dispatch", False)

import numpy as np  # noqa: E402
import pytest  # noqa: E402

from alphatriangle_tpu.config import (  # noqa: E402
    AlphaTriangleMCTSConfig,
    EnvConfig,
    ModelConfig,
    TrainConfig,
)

rng = np.random.default_rng(0)


@pytest.fixture(scope="session")
def tiny_env_config() -> EnvConfig:
    """3x4 board, 1 slot, tiny shapes => action_dim 12."""
    return EnvConfig(
        ROWS=3,
        COLS=4,
        PLAYABLE_RANGE_PER_ROW=[(0, 4), (0, 4), (0, 4)],
        NUM_SHAPE_SLOTS=1,
        MAX_SHAPE_TRIANGLES=3,
        LINE_MIN_LENGTH=3,
    )


@pytest.fixture(scope="session")
def tiny_model_config(tiny_env_config: EnvConfig) -> ModelConfig:
    from alphatriangle_tpu.config import expected_other_features_dim

    return ModelConfig(
        GRID_INPUT_CHANNELS=1,
        CONV_FILTERS=[4],
        CONV_KERNEL_SIZES=[3],
        CONV_STRIDES=[1],
        NUM_RESIDUAL_BLOCKS=0,
        RESIDUAL_BLOCK_FILTERS=4,
        USE_TRANSFORMER=False,
        TRANSFORMER_DIM=8,
        TRANSFORMER_HEADS=2,
        TRANSFORMER_LAYERS=0,
        TRANSFORMER_FC_DIM=16,
        FC_DIMS_SHARED=[8],
        POLICY_HEAD_DIMS=[8],
        VALUE_HEAD_DIMS=[8],
        NUM_VALUE_ATOMS=11,
        OTHER_NN_INPUT_FEATURES_DIM=expected_other_features_dim(tiny_env_config),
        COMPUTE_DTYPE="float32",
        NORM_TYPE="group",
    )


@pytest.fixture(scope="session")
def tiny_train_config() -> TrainConfig:
    return TrainConfig(
        BATCH_SIZE=4,
        BUFFER_CAPACITY=100,
        MIN_BUFFER_SIZE_TO_TRAIN=10,
        USE_PER=False,
        AUTO_RESUME_LATEST=False,
        RANDOM_SEED=42,
        SELF_PLAY_BATCH_SIZE=4,
        ROLLOUT_CHUNK_MOVES=4,
        NUM_SELF_PLAY_WORKERS=1,
        MAX_TRAINING_STEPS=200,
        N_STEP_RETURNS=3,
        GAMMA=0.99,
        MAX_EPISODE_MOVES=50,
        RUN_NAME="pytest_run",
    )


@pytest.fixture(scope="session")
def tiny_per_train_config() -> TrainConfig:
    return TrainConfig(
        BATCH_SIZE=4,
        BUFFER_CAPACITY=64,
        MIN_BUFFER_SIZE_TO_TRAIN=8,
        USE_PER=True,
        PER_BETA_ANNEAL_STEPS=100,
        AUTO_RESUME_LATEST=False,
        MAX_TRAINING_STEPS=100,
        RUN_NAME="pytest_per_run",
    )


@pytest.fixture(scope="session")
def tiny_mcts_config() -> AlphaTriangleMCTSConfig:
    return AlphaTriangleMCTSConfig(
        max_simulations=8,
        max_depth=5,
        cpuct=1.0,
        dirichlet_alpha=0.3,
        dirichlet_epsilon=0.25,
        discount=1.0,
        mcts_batch_size=4,
    )


@pytest.fixture(scope="session")
def random_state_type(tiny_model_config, tiny_env_config):
    """A random StateType dict with the right shapes."""
    return {
        "grid": rng.random(
            (
                tiny_model_config.GRID_INPUT_CHANNELS,
                tiny_env_config.ROWS,
                tiny_env_config.COLS,
            ),
            dtype=np.float32,
        ),
        "other_features": rng.random(
            (tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM,), dtype=np.float32
        ),
    }
