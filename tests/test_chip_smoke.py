"""chip_smoke.py's control flow, without a chip.

The phase functions take their sizes as arguments, so here they run on
the CPU at the tiny world of tests/conftest.py: every assertion the
chip run makes is made (the device ring under "auto" and the kernel in
the compiled text are expected absent on the CPU, as the phases know).
What only a chip can show — that the same phases pass at preset 3
width on a TPU — is `python chip_smoke.py` itself.
"""

import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

import chip_smoke
from alphatriangle_tpu.config import AlphaTriangleMCTSConfig, TrainConfig

REPO = Path(chip_smoke.__file__).resolve().parent

# Two waves of four leaves; the Gumbel root + playout-cap recipe of
# preset 3 at toy budgets.
K = 2


@pytest.fixture(scope="module")
def tiny_cfgs(tiny_env_config, tiny_model_config):
    return {
        "env": tiny_env_config,
        "model": tiny_model_config,
        "mcts": AlphaTriangleMCTSConfig(
            max_simulations=8,
            max_depth=4,
            mcts_batch_size=4,
            root_selection="gumbel",
            gumbel_m=4,
            fast_simulations=4,
            full_search_prob=0.5,
        ),
        "train": TrainConfig(
            SELF_PLAY_BATCH_SIZE=8,
            BATCH_SIZE=8,
            BUFFER_CAPACITY=512,
            MIN_BUFFER_SIZE_TO_TRAIN=16,
            FUSED_LEARNER_STEPS=K,
            N_STEP_RETURNS=2,
            MAX_EPISODE_MOVES=30,
            RUN_NAME="unused",
        ),
    }


WARMUP = {"min_buffer": 16, "chunk_moves": 4}


@pytest.fixture(scope="module")
def root(tmp_path_factory):
    return tmp_path_factory.mktemp("chip_smoke")


@pytest.fixture(scope="module")
def sync_run(tiny_cfgs, root):
    return chip_smoke.phase_train_sync(
        tiny_cfgs, root, "sync_cold", steps=2 * K, **WARMUP
    )


def test_train_sync_phase(sync_run):
    # "auto" on the CPU is the host buffer; the chip run demands the ring.
    assert sync_run["buffer"] == "ExperienceBuffer"
    assert sync_run["learner_steps"] == 2 * K and sync_run["losses"]


def test_train_megastep_phase(tiny_cfgs, root):
    out = chip_smoke.phase_train_megastep(
        tiny_cfgs, root, "megastep", iterations=2, learner_steps=K, **WARMUP
    )
    assert out["dispatches_per_iteration"] == 1.0
    assert out["ring_bytes"] > 0


def test_serve_phase_serves_the_trained_checkpoint(tiny_cfgs, root, sync_run):
    out = chip_smoke.phase_serve(
        tiny_cfgs, root, sync_run["run"], slots=4, sessions=3, max_moves=3
    )
    assert out["source"] == f"step {2 * K}"
    assert out["sessions"] == 3 and out["shed"] == 0
    assert out["answered"] == out["requests"] > 0


def test_kernels_phase_matches_xla(tiny_cfgs):
    # The interpreter pays per grid program and per unrolled wave
    # member, and tests/test_ops.py already pins parity at more shapes
    # than this: two games, two-leaf waves.
    small = {
        **tiny_cfgs,
        "mcts": AlphaTriangleMCTSConfig(
            max_simulations=4, max_depth=2, mcts_batch_size=2
        ),
        "train": tiny_cfgs["train"].model_copy(
            update={"SELF_PLAY_BATCH_SIZE": 2}
        ),
    }
    out = chip_smoke.phase_kernels(
        small,
        recurrence={"boards": 2, "heads": 2, "tokens": 20, "head_dim": 128, "chunk": 16},
        gdn={"boards": 2, "key_heads": 1, "group": 2, "tokens": 20, "head_dim": 128,
             "chunk": 16},
        experts={"rows": 100, "d": 128, "inter": 256, "held": 4, "limit": 10.0},
    )
    assert out["compiled"] is False  # interpreted here, and it says so
    layer = out["parity"].pop("encoder_layer")
    rules = [out["parity"].pop(name) for name in ("delta_rule", "gated_delta_net")]
    experts = out["parity"].pop("grouped_experts")
    assert {k["parity"] for k in out["parity"].values()} == {"exact"}
    # The recurrences are held to the token-by-token recurrence,
    # and so is the chunked form beside each: both gaps are in the
    # step's line.
    for rule in rules:
        assert rule["vs"] == "recurrent" and "rounding" in rule["parity"]
        assert rule["chunked"].startswith("max |diff| ")
        assert rule["device_ms_a_call"] == {"pallas": {}, "chunked": {}}
    # The experts' kernel is held to the ragged_dot chain it replaces and timed
    # beside it; both round to bfloat16 where the layer does.
    assert experts["vs"] == "ragged"
    assert experts["parity"] == "exact" or "rounding" in experts["parity"]
    assert experts["device_ms_a_call"] == {"pallas": {}, "ragged": {}}
    # Not exact: the kernel's own order of sums against Flax's layer;
    # timed beside it (a CPU trace has no device operations to list).
    assert layer["vs"] == "flax" and "rounding" in layer["parity"]
    assert layer["device_ms_a_call"] == {"pallas": {}, "flax": {}}


def test_native_engine_phase(tiny_cfgs):
    out = chip_smoke.phase_native_engine(tiny_cfgs, games=8, moves=3)
    assert out["library"].startswith("_libat_engine-")


@pytest.mark.slow  # three training runs, ~45 s: the four-chip rehearsal
def test_dp_megastep_phase_on_virtual_devices(tiny_cfgs, root):
    out = chip_smoke.phase_dp_megastep(
        tiny_cfgs, root, dp=2, iterations=2, learner_steps=K, **WARMUP
    )
    assert out["mesh_devices"] == 2
    assert out["ring_devices"] == out["lanes_devices"] == out["params_devices"]
    assert out["resumed_to_step"] == 4 * K and out["losses_resumed"]


def _sync_record(process, seconds, events):
    return {
        "phase": "train-sync",
        "process": process,
        "seconds": seconds,
        "setup_seconds": 1.0,
        "compile_cache": {
            "hits": sum(e == "hit" for _, e in events),
            "misses": sum(e == "miss" for _, e in events),
            "compile_seconds": 0.0,
            "load_seconds": 0.0,
            "events": [
                {"program": p, "event": e, "seconds": 0.0} for p, e in events
            ],
        },
    }


COLD = [("self_play_chunk/t16", "miss"), ("learner_fused_from_ring", "miss")]
WARM = [("self_play_chunk/t16", "hit"), ("learner_fused_from_ring", "hit")]


@pytest.mark.parametrize(
    "cold,warm,ok",
    [
        ((269.2, COLD), (94.0, WARM), True),
        # The learner recompiled: its reload is what was to be shown.
        ((269.2, COLD), (94.0, [WARM[0], COLD[1]]), False),
        ((269.2, COLD), (300.0, WARM), False),  # reloaded, and no sooner
        # A cache that came filled: both processes warm, nothing to beat.
        ((94.0, WARM), (95.0, WARM), True),
    ],
)
def test_cache_verdict_wants_reloaded_programs_and_a_shorter_run(
    cold, warm, ok
):
    verdict = chip_smoke._cache_verdict(
        [_sync_record("cold", *cold), _sync_record("warm", *warm)]
    )
    assert verdict["ok"] is ok


def test_a_failed_check_raises():
    with pytest.raises(chip_smoke.SmokeFailure, match="wrong"):
        chip_smoke._check(False, "wrong")


def test_flagship_shapes_are_preset_three():
    shapes = chip_smoke.kernel_shapes(chip_smoke.flagship_configs())
    assert shapes == {
        "batch": 512, "nodes": 65, "wave": 32, "actions": 360, "depth": 8,
        # The leaf wave of a fast search: 512 lanes x 16 simulations.
        "leaves": 8192, "tokens": 120, "dim": 128, "heads": 4,
        "mlp_dim": 256, "activation": "ReLU", "compute_dtype": "bfloat16",
        # No preset's: ling-flash-rollout's mixer, a block of 64 boards.
        "recurrence": {
            "boards": 64, "heads": 32, "tokens": 252, "head_dim": 128, "chunk": 64,
        },
        # gigachat35-rollout's mixer: 32 key heads, two value heads each.
        "gdn": {
            "boards": 64, "key_heads": 32, "group": 2, "tokens": 252,
            "head_dim": 128, "chunk": 64,
        },
        # ling-flash-rollout's round of routed experts, a block of 64 boards.
        "experts": {"rows": 64512, "d": 2560, "inter": 768, "held": 128, "limit": None},
    }


@pytest.mark.parametrize("chips", ["1", "4"])
def test_cpu_run_fails_before_any_phase(chips, tmp_path):
    """`JAX_PLATFORMS=cpu python chip_smoke.py` can never print a TPU
    result: the first child sees the device and stops there."""
    proc = subprocess.run(
        [sys.executable, str(REPO / "chip_smoke.py"), "--chips", chips],
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True,
        text=True,
        timeout=120,
    )
    assert proc.returncode != 0
    lines = proc.stdout.strip().splitlines()
    last = json.loads(lines[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
    assert [json.loads(x)["phase"] for x in lines[:-1]] == ["device"]
