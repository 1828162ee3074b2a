"""Analytic throughput model + ledger calibration for the autotuner.

The objective the search maximizes is PREDICTED games/hour, composed
from first principles so it never needs to execute a candidate:

- per-lane-move model FLOPs: one network forward per MCTS simulation
  leaf (+ ~one root eval per move), with playout-cap randomization
  folding `fast_simulations`/`full_search_prob` into an expected sim
  count, plus the learner's amortized share (each experience is
  consumed once at replay ratio 1: `train_step_flops / BATCH_SIZE`).
  FLOPs come from `utils/flops.py` — the same accounting the live
  `UtilizationMeter` uses, so predictions and observations share a
  currency.
- compute time: FLOPs / (efficiency x peak bf16 FLOP/s x dp). The
  efficiency term is WHERE calibration enters: it is the achieved MFU
  of prior comparable runs (ledger history via
  `telemetry.perf.load_comparable`), falling back to a documented
  default when no history exists.
- dispatch overhead: a per-host-launch constant amortized over the
  rollout chunk T; the fused megastep collapses a sync iteration's
  ~`2 + ceil(B*T/(lbatch*K))` launches to 1, which is exactly why T, K
  and the loop mode appear in the search space at all.

The model is deliberately monotone non-decreasing in B, T and K (the
dominance prune in autotune/space.py relies on monotone-in-B), and
BUFFER_CAPACITY does not appear: ring size costs memory, not time, so
the search spends whatever HBM the feasibility oracle says is left on
capacity — "spend HBM, not chip windows".

Nothing here imports JAX; predictions run beside a wedged chip.
"""

import logging
import math
from dataclasses import dataclass, field

from ..utils.flops import forward_flops, train_step_flops

logger = logging.getLogger(__name__)

# Achieved-MFU prior when no ledger history exists: a conservative
# 1.4% of peak for self-play at B=512 (a pre-chip figure; the on-chip
# benchmark's `mfu.rollout` is in PERF.md). Any comparable run in the
# ledger replaces it.
DEFAULT_EFFICIENCY = 0.014

# Host-side cost of one program dispatch (seconds): queueing + transfer
# + Python driver turnaround. Calibration cannot observe it directly,
# so it stays a documented constant rather than a fitted one.
DEFAULT_DISPATCH_OVERHEAD_S = 0.01

# Peak to assume when the device kind is unknown AND no override/
# history pins one. Only used to rank candidates against each other —
# relative ranking is insensitive to the absolute peak because every
# candidate shares the denominator.
FALLBACK_PEAK_TFLOPS = 1.0


@dataclass
class Calibration:
    """Throughput-model terms learned from ledger history.

    `efficiency` is achieved MFU; `moves_per_game` converts moves/s to
    games/h; `outcome_scale` multiplies predictions by the observed/
    predicted ratio of past tuned runs (`kind:"tune_outcome"` records),
    so every completed run sharpens the next search. `family_seconds`
    is measured p50 dispatch wall per program family (rollout /
    learner / megastep / serve) from the run's flight ring
    (telemetry/flight.py) — ground truth the analytic FLOP model can
    be sanity-checked against. `cost_flops` is compiler-reported FLOPs
    per dispatch per family (XLA `cost_analysis()` records captured by
    the roofline plane, telemetry/roofline.py) — when present it
    anchors `efficiency` to compiler ground truth instead of the
    analytic estimate. `sources` records where each term came from for
    the artifact's provenance block.
    """

    efficiency: float = DEFAULT_EFFICIENCY
    moves_per_game: "float | None" = None
    overhead_s: float = DEFAULT_DISPATCH_OVERHEAD_S
    outcome_scale: float = 1.0
    family_seconds: dict = field(default_factory=dict)
    cost_flops: dict = field(default_factory=dict)
    sources: list = field(default_factory=lambda: ["defaults"])

    def as_dict(self) -> dict:
        return {
            "efficiency": self.efficiency,
            "moves_per_game": self.moves_per_game,
            "overhead_s_per_dispatch": self.overhead_s,
            "outcome_scale": self.outcome_scale,
            "family_seconds": dict(self.family_seconds),
            "cost_flops": dict(self.cost_flops),
            "sources": list(self.sources),
        }


def default_moves_per_game(env_config) -> float:
    """Crude geometry prior for episode length: one move places ~an
    average shape (~(MIN+MAX)/2 triangles) and a game ends when the
    playable area stops absorbing shapes — roughly playable_cells /
    avg_shape_size moves. The CPU smoke reference (3x4 board, shapes
    up to 3 triangles) measures ~4.1 moves/game against this prior's
    4.0; calibration overrides it whenever history exists."""
    playable = sum(
        hi - lo for lo, hi in env_config.PLAYABLE_RANGE_PER_ROW
    )
    avg_shape = max(
        1.0,
        (env_config.MIN_SHAPE_TRIANGLES + env_config.MAX_SHAPE_TRIANGLES)
        / 2.0,
    )
    return max(2.0, playable / avg_shape)


def expected_simulations(mcts_config) -> float:
    """Expected simulations per move under playout cap randomization
    (full searches with prob p, fast ones otherwise)."""
    full = float(mcts_config.max_simulations)
    fast = getattr(mcts_config, "fast_simulations", None)
    if not fast:
        return full
    p = float(getattr(mcts_config, "full_search_prob", 0.25) or 0.25)
    return p * full + (1.0 - p) * float(fast)


def calibration_from_summary(summary: dict) -> "Calibration | None":
    """Calibration terms from one comparable perf summary (a run ledger
    or run normalized by `load_comparable`). None when the
    summary carries nothing usable."""
    if not isinstance(summary, dict):
        return None
    terms: dict = {}
    mfu = summary.get("mfu")
    if isinstance(mfu, (int, float)) and 0 < mfu <= 1:
        terms["efficiency"] = float(mfu)
    moves_s = summary.get("moves_per_sec")
    games_h = summary.get("games_per_hour")
    if (
        isinstance(moves_s, (int, float))
        and isinstance(games_h, (int, float))
        and moves_s > 0
        and games_h > 0
    ):
        terms["moves_per_game"] = moves_s * 3600.0 / games_h
    if not terms:
        return None
    return Calibration(
        efficiency=terms.get("efficiency", DEFAULT_EFFICIENCY),
        moves_per_game=terms.get("moves_per_game"),
        sources=[str(summary.get("source", "summary"))],
    )


def merge_calibrations(calibrations: list) -> Calibration:
    """Fold per-source calibrations into one (arithmetic mean per term;
    later runs carry no more weight than earlier ones — history is
    assumed comparable, not time-decaying)."""
    cals = [c for c in calibrations if isinstance(c, Calibration)]
    if not cals:
        return Calibration()
    effs = [c.efficiency for c in cals]
    mpgs = [
        c.moves_per_game
        for c in cals
        if isinstance(c.moves_per_game, (int, float))
    ]
    scales = [c.outcome_scale for c in cals]
    sources: list = []
    fam_samples: dict = {}
    cost_samples: dict = {}
    for c in cals:
        sources.extend(c.sources)
        for fam, secs in (c.family_seconds or {}).items():
            if isinstance(secs, (int, float)):
                fam_samples.setdefault(fam, []).append(float(secs))
        for fam, flops in (c.cost_flops or {}).items():
            if isinstance(flops, (int, float)):
                cost_samples.setdefault(fam, []).append(float(flops))
    return Calibration(
        efficiency=sum(effs) / len(effs),
        moves_per_game=(sum(mpgs) / len(mpgs)) if mpgs else None,
        overhead_s=cals[0].overhead_s,
        outcome_scale=sum(scales) / len(scales),
        family_seconds={
            fam: sum(v) / len(v) for fam, v in fam_samples.items()
        },
        cost_flops={
            fam: sum(v) / len(v) for fam, v in cost_samples.items()
        },
        sources=sources,
    )


def cost_anchored_efficiency(
    cost_flops: dict, family_seconds: dict, peak_tflops
) -> "float | None":
    """Achieved MFU implied by compiler ground truth: max over families
    of (cost_analysis FLOPs per dispatch / measured p50 dispatch wall)
    / peak FLOP/s. The max (not mean) because the model's efficiency
    term bounds what a well-shaped candidate can reach, and the busiest
    family is the one the search is shaping. None unless some family
    carries both terms and the implied fraction is sane (0 < eff <= 1
    — a torn sidecar or clock skew must not poison the search)."""
    if not isinstance(peak_tflops, (int, float)) or peak_tflops <= 0:
        return None
    best = None
    for fam, flops in (cost_flops or {}).items():
        secs = (family_seconds or {}).get(fam)
        if (
            isinstance(flops, (int, float))
            and flops > 0
            and isinstance(secs, (int, float))
            and secs > 0
        ):
            eff = (flops / secs) / (peak_tflops * 1e12)
            if 0 < eff <= 1 and (best is None or eff > best):
                best = eff
    return best


def calibration_from_targets(
    targets: list, root_dir: "str | None" = None
) -> Calibration:
    """Calibration from ledger history: each target goes through
    `load_comparable` (run name / run dir / metrics.jsonl / perf-summary
    JSON), then any `tune_outcome` records in resolvable run
    ledgers fold in as an observed/predicted scale. Unreadable targets
    are skipped with a log line, never fatal — an empty history just
    means defaults."""
    from ..telemetry.ledger import read_ledger, resolve_ledger_path
    from ..telemetry.perf import load_comparable

    cals = []
    for target in targets or []:
        summary, label = load_comparable(str(target), root_dir=root_dir)
        if summary is None:
            logger.info("tune: calibration target skipped (%s)", label)
            continue
        cal = calibration_from_summary(summary)
        if cal is None:
            logger.info(
                "tune: %s has no usable mfu/throughput fields", label
            )
            continue
        # Prediction-vs-observed feedback: tune_outcome records written
        # by `cli train --preset <tuned>` after the run completed.
        source = summary.get("source")
        ratios = []
        if source:
            from pathlib import Path

            ledger = resolve_ledger_path(Path(str(source)))
            if ledger is not None:
                for rec in read_ledger(ledger, kinds={"tune_outcome"}):
                    ratio = rec.get("observed_over_predicted")
                    if isinstance(ratio, (int, float)) and ratio > 0:
                        ratios.append(float(ratio))
                # Measured per-family dispatch walls from the run's
                # flight ring (telemetry/flight.py): DISPATCH_OVERHEAD
                # was unfittable analytically, but sealed records carry
                # the real dispatch->fetch seconds per family.
                from ..telemetry.flight import (
                    FLIGHT_FILENAME,
                    family_seconds,
                    read_flight,
                )

                fams = family_seconds(
                    read_flight(ledger.parent / FLIGHT_FILENAME)
                )
                if fams:
                    cal.family_seconds = fams
                    cal.sources.append(f"flight x{len(fams)}")
                # Compiler-reported FLOPs per dispatch per family
                # (`kind:"cost"` ledger records — the roofline plane,
                # telemetry/roofline.py). Joined against the measured
                # walls above, they anchor `efficiency` to compiler
                # ground truth; absent sidecars (legacy run, capture
                # off) leave the analytic/MFU estimate in place.
                from ..telemetry.roofline import cost_flops_by_family

                cost = cost_flops_by_family(
                    read_ledger(ledger, kinds={"cost"})
                )
                if cost:
                    cal.cost_flops = cost
                    cal.sources.append(f"cost_flops x{len(cost)}")
                    anchored = cost_anchored_efficiency(
                        cost,
                        cal.family_seconds,
                        summary.get("peak_bf16_tflops"),
                    )
                    if anchored is not None:
                        cal.efficiency = anchored
                        cal.sources.append("efficiency<-cost_flops")
        if ratios:
            cal.outcome_scale = sum(ratios) / len(ratios)
            cal.sources.append(f"tune_outcome x{len(ratios)}")
        cals.append(cal)
    return merge_calibrations(cals)


def predict_throughput(
    candidate,
    env_config,
    model_config,
    mcts_config,
    lbatch: int,
    calibration: "Calibration | None" = None,
    peak_tflops: "float | None" = None,
    megastep: bool = False,
) -> dict:
    """Predicted steady-state throughput for one candidate.

    Returns {games_per_hour, moves_per_sec, learner_steps_per_sec,
    flops_per_lane_move, dispatches_per_iteration, predicted_mfu,
    moves_per_game, peak_tflops} — the same metric names the live
    `UtilizationMeter` ledgers, so `cli compare` and the tune-outcome
    record align predicted rows against observed ones directly.
    """
    cal = calibration or Calibration()
    f = float(forward_flops(model_config, env_config, env_config.action_dim))
    sims = expected_simulations(mcts_config)
    # Self-play: one leaf eval per simulation + ~one root eval per
    # move; learner: each experience is consumed once (replay ratio 1).
    step_f = float(
        train_step_flops(
            model_config, env_config, env_config.action_dim, lbatch
        )
    )
    flops_per_lane_move = (sims + 1.0) * f + step_f / max(1, lbatch)

    peak = peak_tflops if peak_tflops else FALLBACK_PEAK_TFLOPS
    rate = cal.efficiency * peak * 1e12 * max(1, candidate.dp)
    b, t = candidate.sp_batch, candidate.chunk
    compute_s = b * t * flops_per_lane_move / max(rate, 1e-9)
    # Host launches per iteration: the fused megastep is ONE program;
    # a sync iteration pays rollout + ingest + ceil(steps/K) learner
    # groups (the dispatches_per_iteration gauge the ledger records).
    steps_per_iter = b * t / max(1, lbatch)
    dispatches = (
        1.0
        if megastep
        else 2.0 + math.ceil(steps_per_iter / max(1, candidate.fused_k))
    )
    iter_s = compute_s + dispatches * cal.overhead_s
    lane_moves_per_sec = b * t / iter_s if iter_s > 0 else 0.0
    moves_per_game = (
        cal.moves_per_game
        if isinstance(cal.moves_per_game, (int, float))
        and cal.moves_per_game > 0
        else default_moves_per_game(env_config)
    )
    scale = max(1e-6, cal.outcome_scale)
    moves_per_sec = lane_moves_per_sec * scale
    achieved_flops = moves_per_sec * flops_per_lane_move
    return {
        "games_per_hour": moves_per_sec * 3600.0 / moves_per_game,
        "moves_per_sec": moves_per_sec,
        "learner_steps_per_sec": moves_per_sec / max(1, lbatch),
        "flops_per_lane_move": flops_per_lane_move,
        "dispatches_per_iteration": dispatches,
        "predicted_mfu": achieved_flops
        / (peak * 1e12 * max(1, candidate.dp)),
        "moves_per_game": moves_per_game,
        "peak_tflops": peak,
    }
