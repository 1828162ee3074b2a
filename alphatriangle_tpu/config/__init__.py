"""Config package: pydantic models per concern (reference: alphatriangle/config)."""

from alphatriangle_tpu.config.app_config import APP_NAME
from alphatriangle_tpu.config.env_config import EnvConfig
from alphatriangle_tpu.config.league_config import LeagueConfig
from alphatriangle_tpu.config.mcts_config import AlphaTriangleMCTSConfig, MCTSConfig
from alphatriangle_tpu.config.mesh_config import MeshConfig
from alphatriangle_tpu.config.model_config import ModelConfig, TrunkConfig
from alphatriangle_tpu.config.persistence_config import PersistenceConfig
from alphatriangle_tpu.config.presets import (
    GEOMETRY_PRESETS,
    PRESET_DESCRIPTIONS,
    TUNED_PRESET_SCHEMA,
    baseline_preset,
    geometry_preset,
    load_tuned_preset,
)
from alphatriangle_tpu.config.telemetry_config import TelemetryConfig
from alphatriangle_tpu.config.train_config import TrainConfig
from alphatriangle_tpu.config.validation import (
    expected_other_features_dim,
    print_config_info_and_validate,
)

__all__ = [
    "APP_NAME",
    "AlphaTriangleMCTSConfig",
    "EnvConfig",
    "GEOMETRY_PRESETS",
    "LeagueConfig",
    "MCTSConfig",
    "MeshConfig",
    "ModelConfig",
    "TrunkConfig",
    "PRESET_DESCRIPTIONS",
    "PersistenceConfig",
    "TUNED_PRESET_SCHEMA",
    "TelemetryConfig",
    "TrainConfig",
    "baseline_preset",
    "expected_other_features_dim",
    "geometry_preset",
    "load_tuned_preset",
    "print_config_info_and_validate",
]
