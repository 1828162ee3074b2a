"""The five BASELINE benchmark configurations as first-class presets.

BASELINE.md lists the configs to measure (the reference publishes no
numbers of its own):

1. Default TrainConfig, CNN-only net, 50 MCTS sims — CPU smoke.
2. CNN-only net, 200 MCTS sims, batched leaf-eval on one TPU core.
3. CNN + 4-layer TransformerEncoder, dp learner on v4-8 — the
   ≥10k-games/hour north-star config.
4. Distributional (C51) value head, 400 MCTS sims, v4-8.
5. Large board + 8-layer Transformer, v5p-16.

The reference's "N self-play workers" knob (Ray actors,
`alphatriangle/config/train_config.py:34-38`) maps here to the number
of lockstep games per device dispatch (`SELF_PLAY_BATCH_SIZE`): one
actor stepping one game becomes one batch lane, so worker counts scale
the lane count (x16, keeping the MXU fed rather than matching actor
count 1:1). Mesh sizes state the intended hardware; on fewer devices
`MeshConfig(DP_SIZE=-1)` resolves to whatever is present, so every
preset also runs single-chip or on the virtual CPU mesh.

The CLI selects a preset via `train --preset N`; `cli warm N`,
`cli fit N` and `cli tune N` answer for that run's shapes.
"""

from .env_config import EnvConfig
from .mcts_config import AlphaTriangleMCTSConfig
from .mesh_config import MeshConfig
from .model_config import ModelConfig
from .train_config import TrainConfig
from .validation import expected_other_features_dim

# Versioned schema tag for `tuned_preset.json` artifacts written by the
# fit-driven autotuner (alphatriangle_tpu/autotune/). Bump when the
# artifact layout changes incompatibly; `load_tuned_preset` refuses
# mismatched versions with an explicit error instead of constructing a
# half-understood config.
TUNED_PRESET_SCHEMA = "alphatriangle.tuned_preset.v1"

PRESET_DESCRIPTIONS = {
    1: "CNN-only, 50 sims, CPU smoke (BASELINE config 1)",
    2: "CNN-only, 200 sims, single TPU core (BASELINE config 2)",
    3: (
        "CNN + 4-layer transformer, dp learner, Gumbel+PCR recipe "
        "(BASELINE config 3, north star)"
    ),
    4: "C51 + 400 sims (BASELINE config 4)",
    5: "Large board + 8-layer transformer (BASELINE config 5)",
}


def _large_board() -> EnvConfig:
    """12x21 symmetric board for preset 5 (same hexagon-ish widening
    as the default 8x15)."""
    rows, cols = 12, 21
    half = rows // 2
    ranges = []
    for r in range(rows):
        d = (half - 1 - r) if r < half else (r - half)
        inset = max(0, d)
        ranges.append((inset, cols - inset))
    return EnvConfig(ROWS=rows, COLS=cols, PLAYABLE_RANGE_PER_ROW=ranges)


def _tiny_board() -> EnvConfig:
    """3x4 fully-playable board, 1 preview slot — the test-world
    geometry (tests/conftest.py) as a named preset so the autotuner can
    search it cheaply."""
    return EnvConfig(
        ROWS=3,
        COLS=4,
        PLAYABLE_RANGE_PER_ROW=[(0, 4), (0, 4), (0, 4)],
        NUM_SHAPE_SLOTS=1,
        MAX_SHAPE_TRIANGLES=3,
        LINE_MIN_LENGTH=3,
    )


# Named board geometries the autotuner's search space can range over
# (docs/AUTOTUNE.md). Values are zero-arg constructors so importing
# this module never validates configs eagerly.
GEOMETRY_PRESETS = {
    "tiny": _tiny_board,
    "default": EnvConfig,
    "large": _large_board,
}


def geometry_preset(name: str) -> EnvConfig:
    """EnvConfig for a named board geometry preset."""
    if name not in GEOMETRY_PRESETS:
        raise ValueError(
            f"Unknown geometry preset {name!r} "
            f"(valid: {', '.join(sorted(GEOMETRY_PRESETS))})"
        )
    return GEOMETRY_PRESETS[name]()


def load_tuned_preset(path) -> dict[str, object]:
    """Round-trip a `tuned_preset.json` artifact into a
    `baseline_preset`-shaped bundle {env, model, train, mcts, mesh,
    description, tuned}.

    `tuned` carries the artifact payload itself (schema, predicted
    throughput, composed budget, search provenance) so consumers like
    `cli train --preset <path>` can ledger predicted-vs-observed
    outcomes after the run. Raises ValueError with a precise reason on
    a missing/garbled file or a schema version mismatch — a tuned
    preset from an incompatible autotuner must fail loudly, not
    half-construct.
    """
    import json
    from pathlib import Path

    p = Path(path)
    try:
        payload = json.loads(p.read_text())
    except OSError as exc:
        raise ValueError(f"tuned preset {p}: unreadable ({exc})") from exc
    except json.JSONDecodeError as exc:
        raise ValueError(f"tuned preset {p}: invalid JSON ({exc})") from exc
    if not isinstance(payload, dict):
        raise ValueError(f"tuned preset {p}: expected a JSON object")
    schema = payload.get("schema")
    if schema != TUNED_PRESET_SCHEMA:
        raise ValueError(
            f"tuned preset {p}: schema {schema!r} does not match this "
            f"build's {TUNED_PRESET_SCHEMA!r} — re-run `cli tune` with "
            "the current code instead of reusing a stale artifact."
        )
    configs = payload.get("configs")
    if not isinstance(configs, dict):
        raise ValueError(f"tuned preset {p}: missing 'configs' section")
    try:
        env = EnvConfig(**configs["env"])
        model = ModelConfig(**configs["model"])
        train = TrainConfig(**configs["train"])
        mcts = AlphaTriangleMCTSConfig(**configs["mcts"])
    except KeyError as exc:
        raise ValueError(
            f"tuned preset {p}: configs section missing {exc}"
        ) from exc
    except Exception as exc:
        raise ValueError(
            f"tuned preset {p}: config validation failed ({exc})"
        ) from exc
    return {
        "env": env,
        "model": model,
        "train": train,
        "mcts": mcts,
        # The artifact records the dp width it tuned FOR; DP_SIZE=-1
        # still resolves to the devices actually present so the preset
        # runs anywhere (same contract as the BASELINE presets).
        "mesh": MeshConfig(DP_SIZE=-1),
        "description": payload.get(
            "description", f"tuned preset ({p.name})"
        ),
        "tuned": payload,
    }


def baseline_preset(
    n: int, run_name: str | None = None
) -> dict[str, object]:
    """Config bundle {env, model, train, mcts, mesh} for BASELINE
    config `n` (1..5). Training-loop knobs not pinned by BASELINE.md
    keep their TrainConfig defaults."""
    if n not in PRESET_DESCRIPTIONS:
        raise ValueError(f"Unknown BASELINE preset {n} (valid: 1..5)")

    env = _large_board() if n == 5 else EnvConfig()
    feat = expected_other_features_dim(env)

    model_kw: dict = {"OTHER_NN_INPUT_FEATURES_DIM": feat}
    if n in (1, 2):
        model_kw["USE_TRANSFORMER"] = False
    elif n in (3, 4):
        model_kw["TRANSFORMER_LAYERS"] = 4
    elif n == 5:
        model_kw["TRANSFORMER_LAYERS"] = 8
        model_kw["REMAT"] = True
    if n == 1:
        model_kw["COMPUTE_DTYPE"] = "float32"  # CPU smoke
    model = ModelConfig(**model_kw)

    train_kw: dict = {}
    if n == 1:
        # "CPU smoke" by definition: pin the platform so the numbers
        # stay comparable even on a TPU host.
        train_kw["DEVICE"] = "cpu"
        train_kw["WORKER_DEVICE"] = "cpu"

    sims = {1: 50, 2: 200, 3: 64, 4: 400, 5: 64}[n]
    mcts_kw: dict = {}
    if n == 3:
        # The flagship preset runs the measured-best training recipe:
        # Gumbel sequential-halving root + playout cap randomization
        # converged +11% above every other arm at under half the
        # search cost (BASELINE.md, config 3; docs/MCTS_DESIGN.md §d-e).
        # The other presets keep reference-parity PUCT so the BASELINE
        # table stays comparable config-for-config.
        mcts_kw.update(
            root_selection="gumbel",
            fast_simulations=16,
            full_search_prob=0.25,
        )
    mcts = AlphaTriangleMCTSConfig(max_simulations=sims, **mcts_kw)

    # Reference worker counts 1/8/32/32/64 -> lockstep lanes x16.
    lanes = {1: 16, 2: 128, 3: 512, 4: 512, 5: 1024}[n]
    train = TrainConfig(
        SELF_PLAY_BATCH_SIZE=lanes,
        RUN_NAME=run_name or f"baseline_preset_{n}",
        FUSED_LEARNER_STEPS=1 if n == 1 else 16,
        **train_kw,
    )

    # Intended hardware: 1 chip (1, 2), v4-8 (3, 4), v5p-16 (5).
    # DP_SIZE=-1 resolves to the devices actually present.
    mesh = MeshConfig(DP_SIZE=-1)

    return {
        "env": env,
        "model": model,
        "train": train,
        "mcts": mcts,
        "mesh": mesh,
        "description": PRESET_DESCRIPTIONS[n],
    }
