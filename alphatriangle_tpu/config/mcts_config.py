"""MCTS search configuration.

Parity with `trimcts.SearchConfiguration` as mirrored by the reference's
`AlphaTriangleMCTSConfig` (`alphatriangle/config/mcts_config.py:10-77`).

`mcts_batch_size` (the reference's C++ leaf-collection size,
`mcts_config.py:57-62`) maps to the TPU search's *wave size*: the
number of simulations whose leaves are collected in parallel per tree
before one fused network evaluation. The effective MXU batch per eval
is SELF_PLAY_BATCH_SIZE games x mcts_batch_size wave members.
"""

import logging

from pydantic import BaseModel, Field, model_validator


class AlphaTriangleMCTSConfig(BaseModel):
    """PUCT search hyperparameters (pydantic)."""

    max_simulations: int = Field(default=64, gt=0)
    max_depth: int = Field(default=8, gt=0)
    cpuct: float = Field(default=1.5, gt=0)
    # alpha=0 legitimately disables root noise (reference allows ge=0).
    dirichlet_alpha: float = Field(default=0.3, ge=0)
    dirichlet_epsilon: float = Field(default=0.25, ge=0, le=1.0)
    discount: float = Field(default=1.0, ge=0, le=1.0)
    # Wave size: simulations selected/evaluated in parallel per tree
    # (the reference's leaf-collection batch; see module docstring).
    # Clamped at runtime to the largest divisor of max_simulations.
    # Default matches the reference (`mcts_config.py:14`).
    mcts_batch_size: int = Field(default=32, gt=0)
    # Gumbel perturbation scale applied to PUCT scores per wave member
    # during parallel descent, so the wave's descents diverge without
    # sequential virtual-loss bookkeeping. 0 disables (wave members
    # then collapse onto one leaf; the duplicate shows up in
    # `SearchOutput.wasted_slots`).
    wave_noise_scale: float = Field(default=0.25, ge=0)
    # How the wave's insertion + discounted backup writes the edge
    # planes: "xla" (the original scatter chain) or "pallas" (one fused
    # per-game VMEM kernel, ops/mcts_backup.py). Parity-pinned; a pure
    # performance knob to be settled by on-hardware benchmarks.
    backup_update: str = Field(default="xla", pattern="^(xla|pallas)$")
    # --- Subtree reuse across moves (the reference's opaque C++ tree
    # handle, `rl/self_play/worker.py:273-280`; KataGo keeps the chosen
    # child's subtree too, arXiv:1902.10565). Off by default: fresh-root
    # search is the v1 reference behavior and stays bit-identical when
    # this is False. When True, the search runs over a widened node
    # budget (max_simulations + tree_reuse_budget + 1 slots) and after
    # each move a static-shape root-promotion pass
    # (ops/subtree_reuse.py) compacts the chosen child's subtree into
    # the leading rows — BFS order, freed slots zeroed — so the next
    # move's search starts with the retained visits already on the
    # root row. Root prior + Dirichlet noise are always re-taken from a
    # fresh root evaluation; only edge statistics and interior priors
    # are carried.
    tree_reuse: bool = Field(default=False)
    # Max nodes retained across a move (root + interior), excluding the
    # +1 root slot. None -> max_simulations (retain up to a full
    # search's worth of subtree).
    tree_reuse_budget: int | None = Field(default=None, gt=0)
    # --- Playout cap randomization (KataGo, arXiv:1902.10565 §3.1;
    # PAPERS.md) — beyond-reference acceleration, off by default. When
    # `fast_simulations` is set, each lockstep move runs the full
    # `max_simulations` search with probability `full_search_prob` and
    # a cheap noiseless `fast_simulations` search otherwise. Only
    # full-search moves produce policy-training targets (their
    # experiences carry policy weight 1, fast moves 0); value targets
    # come from every move. Self-play cost per move drops toward the
    # fast budget while policy targets keep full-search quality.
    fast_simulations: int | None = Field(default=None, gt=0)
    full_search_prob: float = Field(default=0.25, gt=0, le=1.0)
    # KataGo-faithful (default): fast-search positions produce NO
    # training rows at all — they only advance the game cheaply.
    # True keeps them as value-only rows (policy weight 0); measured
    # on the tiny-board harness this degrades the value head (their
    # n-step bootstraps come from the noisy fast-search roots).
    pcr_record_fast_rows: bool = Field(default=False)
    # --- Gumbel root search (Danihelka et al. 2022 / mctx; beyond-
    # reference, mcts/gumbel.py). "gumbel": root actions are explored
    # by sampled Gumbel noise + sequential halving across waves, the
    # played move is the final-candidate argmax (no temperature), and
    # policy targets are the completed-Q improved policy. "puct":
    # reference-parity Dirichlet + visit-count behavior.
    root_selection: str = Field(default="puct", pattern="^(puct|gumbel)$")
    # Max root candidates considered by sequential halving.
    gumbel_m: int = Field(default=16, gt=1)
    # sigma(q) = (c_visit + max_visits) * c_scale * q   (paper Eq. 8).
    # c_scale default follows the paper's 1.0 (mctx ships 0.1): on the
    # tiny-board learning harness 0.1 plateaued the trained net at
    # 7.65 while 0.5/1.0 reach ~7.75 (docs/MCTS_DESIGN.md §d sweep) —
    # too-small sigma keeps completed-Q targets glued to the prior.
    gumbel_c_visit: float = Field(default=50.0, ge=0)
    gumbel_c_scale: float = Field(default=1.0, gt=0)

    @model_validator(mode="after")
    def _check_fast(self) -> "AlphaTriangleMCTSConfig":
        if (
            self.fast_simulations is not None
            and self.fast_simulations >= self.max_simulations
        ):
            raise ValueError(
                "fast_simulations must be < max_simulations "
                f"({self.fast_simulations} >= {self.max_simulations})"
            )
        return self

    @model_validator(mode="after")
    def _check_reuse(self) -> "AlphaTriangleMCTSConfig":
        if self.tree_reuse and self.fast_simulations is not None:
            # PCR's fast/full lax.cond needs both branches to share one
            # carried-tree shape; the fast search has no carried tree.
            raise ValueError(
                "tree_reuse is incompatible with playout cap "
                "randomization (fast_simulations); pick one."
            )
        if self.tree_reuse and self.root_selection == "gumbel":
            # Sequential halving re-plans the root candidate set per
            # move; carrying a PUCT-shaped subtree across moves would
            # bias the halving allocation. Not supported.
            raise ValueError(
                "tree_reuse is incompatible with root_selection='gumbel'"
            )
        return self

    @model_validator(mode="after")
    def _warn_depth(self) -> "AlphaTriangleMCTSConfig":
        if self.max_depth > self.max_simulations + 1:
            # Deeper than the number of expansions wastes fixed-size
            # path buffers in the jitted search.
            logging.getLogger(__name__).warning(
                "max_depth=%d exceeds max_simulations+1=%d; the extra depth "
                "can never be reached and only widens jitted path buffers.",
                self.max_depth,
                self.max_simulations + 1,
            )
        return self


# Short alias used throughout this package.
MCTSConfig = AlphaTriangleMCTSConfig

AlphaTriangleMCTSConfig.model_rebuild(force=True)
