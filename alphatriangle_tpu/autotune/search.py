"""Pruned feasibility search: pick the feasible config maximizing
predicted games/hour (docs/AUTOTUNE.md).

The expensive operation is the feasibility oracle — `estimate_fit`
(telemetry/memory.py) AOT-lowers and compiles the candidate's hot
programs to read `compiled.memory_analysis()`, seconds per call, never
executing anything. The search exists to call it as few times as
possible:

1. **Gates** (free): divisibility/geometry constraints reject
   candidates a run would refuse or silently de-shard
   (autotune/space.py).
2. **Ring math** (free): `replay_ring_bytes` is pure dtype/shape
   arithmetic; when the ring's per-device slice alone exceeds the byte
   limit, no program analysis can save the candidate.
3. **Monotone-in-B dominance**: within a (geometry, capacity, T, K,
   dp) group the search walks B descending; the first oracle-confirmed
   B wins the group and every smaller B is dominated unseen — both the
   budget and the predicted throughput are monotone in B.

Group winners then rank by predicted games/h (autotune/model.py). The
oracle is injectable so pruning behavior is unit-testable without a
JAX backend (tests/test_autotune.py)."""

import logging
from dataclasses import dataclass, field

from .model import Calibration, predict_throughput
from .space import (
    STATUS_DOMINATED,
    STATUS_FIT,
    STATUS_GATE,
    STATUS_OVER,
    STATUS_RING,
    Candidate,
    SearchSpace,
    divisibility_gate,
)

logger = logging.getLogger(__name__)


@dataclass
class TuneResult:
    """Outcome of one search: per-candidate rows (dicts with candidate
    axes + status + prediction), the winning candidate (None when the
    space is infeasible), its budget/records, and search accounting."""

    rows: list = field(default_factory=list)
    best: "Candidate | None" = None
    best_prediction: "dict | None" = None
    best_budget: "dict | None" = None
    best_records: list = field(default_factory=list)
    oracle_calls: int = 0
    evaluated: int = 0
    limit_bytes: "float | None" = None

    def feasible_rows(self) -> list:
        return [r for r in self.rows if r["status"] == STATUS_FIT]


def materialize_candidate(candidate, base_env, base_model, base_train, mode):
    """(env, model, train) configs for one candidate.

    Geometry "plan" keeps the base configuration's board; a named geometry
    swaps the board in and re-derives the model's feature-dim contract
    (`expected_other_features_dim`) exactly as the presets do. The
    train config rebuilds through the constructor so every validator
    the real run would hit also gates the candidate here."""
    from ..config import (
        TrainConfig,
        expected_other_features_dim,
        geometry_preset,
    )

    if candidate.geometry == "plan":
        env = base_env
        model = base_model
    else:
        env = geometry_preset(candidate.geometry)
        model = base_model.model_copy(
            update={
                "OTHER_NN_INPUT_FEATURES_DIM": expected_other_features_dim(
                    env
                )
            }
        )
    model = model.model_copy(
        update={"INFERENCE_PRECISION": candidate.inference_precision}
    )
    kw = base_train.model_dump()
    kw.update(
        SELF_PLAY_BATCH_SIZE=candidate.sp_batch,
        BUFFER_CAPACITY=candidate.capacity,
        ROLLOUT_CHUNK_MOVES=candidate.chunk,
        FUSED_LEARNER_STEPS=candidate.fused_k,
        PER_SAMPLE_BACKEND=candidate.per_sample,
        MIN_BUFFER_SIZE_TO_TRAIN=min(
            base_train.MIN_BUFFER_SIZE_TO_TRAIN, candidate.capacity
        ),
    )
    if mode == "megastep":
        kw.update(
            FUSED_MEGASTEP=True, DEVICE_REPLAY="on", ASYNC_ROLLOUTS=False
        )
    train = TrainConfig(**kw)
    return env, model, train


def candidate_mcts(base_mcts, candidate):
    """The MCTS config a candidate's programs lower with: the base
    search config carrying the candidate's kernel axes."""
    return base_mcts.model_copy(
        update={
            "descent_gather": candidate.descent_gather,
            "backup_update": candidate.backup_update,
            "tree_reuse": candidate.tree_reuse,
        }
    )


def ring_bytes_for(candidate, env, model) -> int:
    """Per-device replay-ring bytes for a candidate — pure shape math
    (telemetry/memory.py `replay_ring_bytes`), no JAX."""
    from ..config import expected_other_features_dim
    from ..telemetry.memory import replay_ring_bytes

    shards = max(1, candidate.dp)
    return replay_ring_bytes(
        candidate.capacity,
        (model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS),
        expected_other_features_dim(env),
        env.action_dim,
        shards=shards,
    ) // shards


def default_oracle(mcts_config, mode, device_replay=None, progress=None):
    """The real feasibility oracle: `estimate_fit` over the candidate's
    hot programs (rollout chunk + fused learner group, + the megastep
    program when that is the loop being tuned). Returns a callable
    (candidate, env, model, train, limit) -> (fits, budget, records).
    `device_replay` defaults to True exactly when tuning the megastep
    loop (which requires the device ring); pass it explicitly when
    tuning a sync loop that still keeps its ring in HBM."""
    ring_on_device = (
        (mode == "megastep") if device_replay is None else bool(device_replay)
    )

    def oracle(candidate, env, model, train, limit):
        from ..telemetry.memory import FIT_OK, estimate_fit, fit_verdict

        programs = {"self_play_chunk", "learner_fused"}
        if mode == "megastep":
            programs.add("megastep")
        report = estimate_fit(
            env,
            model,
            candidate_mcts(mcts_config, candidate),
            train,
            fused_k=candidate.fused_k,
            device_replay=ring_on_device,
            megastep=(mode == "megastep"),
            programs=programs,
            progress=progress,
        )
        budget = report["budget"]
        code, _reason = fit_verdict(budget["total_bytes"], limit)
        return code == FIT_OK, budget, report["records"]

    return oracle


def run_search(
    space: SearchSpace,
    base_env,
    base_model,
    base_mcts,
    base_train,
    limit_bytes: "float | None",
    calibration: "Calibration | None" = None,
    peak_tflops: "float | None" = None,
    mode: str = "sync",
    device_replay=None,
    oracle=None,
    progress=None,
) -> TuneResult:
    """Search the space for the feasible candidate maximizing predicted
    games/h. `oracle` defaults to the `estimate_fit` oracle; tests
    inject a pure-math one. `limit_bytes` None is allowed (the caller
    decides whether that is an error); the oracle then reports
    FIT_UNKNOWN as infeasible, so callers should resolve a limit first.
    """
    cal = calibration or Calibration()
    oracle = oracle or default_oracle(
        base_mcts, mode, device_replay=device_replay, progress=progress
    )

    def say(msg: str) -> None:
        logger.info(msg)
        if progress is not None:
            progress(msg)

    result = TuneResult(limit_bytes=limit_bytes)
    lbatch = base_train.BATCH_SIZE
    min_buffer = base_train.MIN_BUFFER_SIZE_TO_TRAIN
    rows_by_candidate: dict = {}

    def add_row(candidate, status, prediction=None, detail="", budget=None):
        row = {
            "geometry": candidate.geometry,
            "sp_batch": candidate.sp_batch,
            "capacity": candidate.capacity,
            "chunk": candidate.chunk,
            "fused_k": candidate.fused_k,
            "dp": candidate.dp,
            "kernels": candidate.kernels(),
            "status": status,
            "detail": detail,
            "predicted": prediction,
            "budget_total_bytes": (
                budget.get("total_bytes") if budget else None
            ),
        }
        rows_by_candidate[candidate] = row
        return row

    # Group candidates (B descending within each group, courtesy of
    # SearchSpace.candidates()) and predict throughput for every
    # un-gated candidate up front — predictions are microseconds.
    groups: dict = {}
    for cand in space.candidates():
        groups.setdefault(cand.group_key(), []).append(cand)

    group_frontiers = []
    for key, members in groups.items():
        frontier = []
        for cand in members:
            gate_reason = divisibility_gate(cand, lbatch, min_buffer)
            if gate_reason is not None:
                add_row(cand, STATUS_GATE, detail=gate_reason)
                continue
            env, model, train = materialize_candidate(
                cand, base_env, base_model, base_train, mode
            )
            prediction = predict_throughput(
                cand,
                env,
                model,
                base_mcts,
                lbatch,
                calibration=cal,
                peak_tflops=peak_tflops,
                megastep=(mode == "megastep"),
            )
            ring = ring_bytes_for(cand, env, model)
            if limit_bytes is not None and ring > limit_bytes:
                add_row(
                    cand,
                    STATUS_RING,
                    prediction=prediction,
                    detail=(
                        f"ring alone {ring} B > limit {int(limit_bytes)} B"
                    ),
                )
                continue
            frontier.append((cand, env, model, train, prediction))
        if frontier:
            group_frontiers.append((key, frontier))

    # Evaluate every group's frontier (B descending): the first
    # oracle-confirmed B wins the group; smaller Bs are dominated.
    # Candidates sharing an oracle_key — differing only on the
    # memory-neutral kernel axes (autotune/space.py) — reuse one
    # oracle answer, so those axes multiply the lattice for free.
    best = None
    oracle_memo: dict = {}
    for _key, frontier in group_frontiers:
        winner = None
        for cand, env, model, train, prediction in frontier:
            if winner is not None:
                add_row(
                    cand,
                    STATUS_DOMINATED,
                    prediction=prediction,
                    detail=f"B{winner.sp_batch} fits in this group",
                )
                continue
            memo_key = cand.oracle_key()
            cached = oracle_memo.get(memo_key)
            if cached is None:
                result.oracle_calls += 1
                say(f"tune: oracle {cand.label()} ...")
                cached = oracle(cand, env, model, train, limit_bytes)
                oracle_memo[memo_key] = cached
            fits, budget, records = cached
            result.evaluated += 1
            if fits:
                winner = cand
                add_row(
                    cand, STATUS_FIT, prediction=prediction, budget=budget
                )
                if (
                    best is None
                    or prediction["games_per_hour"]
                    > best[4]["games_per_hour"]
                ):
                    best = (cand, env, model, train, prediction, budget, records)
            else:
                add_row(
                    cand,
                    STATUS_OVER,
                    prediction=prediction,
                    budget=budget,
                    detail="over budget",
                )

    if best is not None:
        (cand, _env, _model, _train, prediction, budget, records) = best
        result.best = cand
        result.best_prediction = prediction
        result.best_budget = budget
        result.best_records = records
    result.rows = sorted(
        rows_by_candidate.values(),
        key=lambda r: (
            -(r["predicted"] or {}).get("games_per_hour", 0.0),
            r["geometry"],
            -r["sp_batch"],
        ),
    )
    return result
