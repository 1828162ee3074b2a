"""Fit-driven autotuner: offline config search that spends HBM, not
chip windows (docs/AUTOTUNE.md).

Searches the `(SELF_PLAY_BATCH_SIZE, BUFFER_CAPACITY, chunk T, fused
K, dp, geometry preset)` space with `estimate_fit`/`compose_budget`
(telemetry/memory.py) as the feasibility oracle — candidates are
AOT-analyzed, never executed — and an analytic throughput model
(utils/flops.py + device peak, calibrated against ledger history) as
the objective. `cli tune` drives it and emits `tuned_preset.json`
artifacts that `cli train --preset`, `cli warm` and `cli fit` consume
directly."""

from .artifact import (
    TUNE_OUTCOME_KIND,
    build_tuned_preset,
    default_artifact_path,
    ledger_tune_outcome,
    write_tuned_preset,
)
from .model import (
    Calibration,
    calibration_from_summary,
    calibration_from_targets,
    default_moves_per_game,
    expected_simulations,
    merge_calibrations,
    predict_throughput,
)
from .search import (
    TuneResult,
    default_oracle,
    materialize_candidate,
    ring_bytes_for,
    run_search,
)
from .space import (
    STATUS_DOMINATED,
    STATUS_FIT,
    STATUS_GATE,
    STATUS_OVER,
    STATUS_RING,
    Candidate,
    SearchSpace,
    divisibility_gate,
    prune_dominated,
)

__all__ = [
    "Calibration",
    "Candidate",
    "STATUS_DOMINATED",
    "STATUS_FIT",
    "STATUS_GATE",
    "STATUS_OVER",
    "STATUS_RING",
    "SearchSpace",
    "TUNE_OUTCOME_KIND",
    "TuneResult",
    "build_tuned_preset",
    "calibration_from_summary",
    "calibration_from_targets",
    "default_artifact_path",
    "default_moves_per_game",
    "default_oracle",
    "divisibility_gate",
    "expected_simulations",
    "ledger_tune_outcome",
    "materialize_candidate",
    "merge_calibrations",
    "predict_throughput",
    "prune_dominated",
    "ring_bytes_for",
    "run_search",
    "write_tuned_preset",
]
