"""RL result types (reference: `alphatriangle/rl/types.py:14-89`).

`SelfPlayResult` carries a *dense* block of experiences — fixed-shape
arrays straight out of the batched rollout — instead of the reference's
list of tuples. Its validator performs the same role as the reference's
(`rl/types.py:32-86`): structurally broken or non-finite rows are
dropped, not propagated into the buffer.
"""

import logging
from typing import Any

import numpy as np
from pydantic import BaseModel, ConfigDict, model_validator

logger = logging.getLogger(__name__)


class SelfPlayResult(BaseModel):
    """One harvest of finished self-play episodes, dense-form."""

    model_config = ConfigDict(arbitrary_types_allowed=True)

    grid: np.ndarray  # (N, C, H, W) float32
    other_features: np.ndarray  # (N, F) float32
    policy_target: np.ndarray  # (N, A) float32
    value_target: np.ndarray  # (N,) float32 n-step returns
    # Per-row policy-loss weight: 0.0 for experiences from fast
    # (playout-cap-randomized) searches whose visit counts are too
    # noisy to train the policy on; 1.0 otherwise. None -> all ones.
    policy_weight: np.ndarray | None = None

    episode_scores: list[float] = []
    episode_lengths: list[int] = []
    # Weights version each finished episode *started* under — the
    # per-episode staleness tag (reference `worker.py:136-139`), finer
    # than the window-level `trainer_step_at_episode_start` below.
    episode_start_versions: list[int] = []
    num_episodes: int = 0
    # Episodes that hit MAX_EPISODE_MOVES instead of a natural game
    # over (a persistently high fraction means the cap is biting).
    num_truncated: int = 0
    total_simulations: int = 0
    # Root visits inherited from carried subtrees (MCTS tree_reuse);
    # 0 with reuse off. simulations + reused = leaf-equivalent search
    # effort per harvest (telemetry leaf-evals/s).
    total_reused_visits: int = 0
    # A routed trunk's counters over the harvest (nn/trunk.py): the
    # token-expert assignments each held expert computed, (sparse
    # layers, held) int64, and the assignments routed to any expert.
    # None / 0 for a net that routes nothing. `linear_tokens`: tokens x
    # linear-attention layers the recurrence took (0 without such layers).
    expert_tokens: np.ndarray | None = None
    routed_assignments: int = 0
    linear_tokens: int = 0
    # Weight version the producing rollout ran with (staleness tag,
    # reference `rl/types.py:22` / `worker.py:136-139`).
    trainer_step_at_episode_start: int = 0
    context: dict[str, Any] = {}

    @property
    def num_experiences(self) -> int:
        return int(self.grid.shape[0])

    @model_validator(mode="after")
    def _drop_invalid_rows(self) -> "SelfPlayResult":
        n = self.grid.shape[0]
        if self.policy_weight is None:
            object.__setattr__(
                self, "policy_weight", np.ones(n, dtype=np.float32)
            )
        assert self.policy_weight is not None
        if self.policy_weight.shape[0] != n:
            raise ValueError(
                f"policy_weight rows {self.policy_weight.shape[0]} != {n}"
            )
        if not (
            self.other_features.shape[0]
            == self.policy_target.shape[0]
            == self.value_target.shape[0]
            == n
        ):
            raise ValueError(
                "Experience arrays disagree on row count: "
                f"{self.grid.shape[0]}/{self.other_features.shape[0]}/"
                f"{self.policy_target.shape[0]}/{self.value_target.shape[0]}"
            )
        if n == 0:
            return self
        keep = (
            np.isfinite(self.grid).all(axis=tuple(range(1, self.grid.ndim)))
            & np.isfinite(self.other_features).all(axis=1)
            & np.isfinite(self.policy_target).all(axis=1)
            & np.isfinite(self.value_target)
            # A policy target must be a distribution (rows sum to ~1).
            & (np.abs(self.policy_target.sum(axis=1) - 1.0) < 1e-3)
        )
        if not keep.all():
            logger.warning(
                "SelfPlayResult: dropping %d invalid experiences of %d.",
                int(n - keep.sum()),
                n,
            )
            object.__setattr__(self, "grid", self.grid[keep])
            object.__setattr__(self, "other_features", self.other_features[keep])
            object.__setattr__(self, "policy_target", self.policy_target[keep])
            object.__setattr__(self, "value_target", self.value_target[keep])
            object.__setattr__(self, "policy_weight", self.policy_weight[keep])
        return self
