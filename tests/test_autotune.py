"""Fit-driven autotuner tests (alphatriangle_tpu/autotune/).

Everything here is cheap: the feasibility oracle is always a fake (the
real `estimate_fit` oracle compiles programs and belongs to
benchmarks/tune_smoke.py), predictions are pure math, and the cli-level
tests monkeypatch the oracle or rely on the free ring-math prune. The
one "gate" test pins the analytic throughput model against the
checked-in CPU smoke reference summary — the model must predict the
observed throughput within a checked-in factor or the objective the
search maximizes has drifted from reality.
"""

import json
from pathlib import Path

import pytest

from alphatriangle_tpu.autotune import (
    Calibration,
    Candidate,
    SearchSpace,
    build_tuned_preset,
    calibration_from_summary,
    divisibility_gate,
    ledger_tune_outcome,
    predict_throughput,
    prune_dominated,
    run_search,
    write_tuned_preset,
)
from alphatriangle_tpu.autotune.search import materialize_candidate
from alphatriangle_tpu.config import (
    TUNED_PRESET_SCHEMA,
    AlphaTriangleMCTSConfig,
    EnvConfig,
    ModelConfig,
    TrainConfig,
    expected_other_features_dim,
    load_tuned_preset,
)

REFERENCE = (
    Path(__file__).resolve().parent.parent
    / "benchmarks"
    / "perf_reference_cpu_smoke.json"
)

# The model must land within this factor of the reference's observed
# throughput (both directions). Calibrated from the same summary it
# predicts, the model currently lands within ~10%; the factor leaves
# room for FLOPs-accounting drift without letting the objective decouple
# from reality entirely.
CALIBRATION_FACTOR = 3.0


def _smoke_world():
    """The perf-smoke world (benchmarks/perf_smoke.py tiny_configs),
    i.e. the configuration the checked-in reference was measured on."""
    env_cfg = EnvConfig(
        ROWS=3,
        COLS=4,
        PLAYABLE_RANGE_PER_ROW=[(0, 4), (0, 4), (0, 4)],
        NUM_SHAPE_SLOTS=1,
        MAX_SHAPE_TRIANGLES=3,
        LINE_MIN_LENGTH=3,
    )
    model_cfg = ModelConfig(
        GRID_INPUT_CHANNELS=1,
        CONV_FILTERS=[4],
        CONV_KERNEL_SIZES=[3],
        CONV_STRIDES=[1],
        NUM_RESIDUAL_BLOCKS=0,
        RESIDUAL_BLOCK_FILTERS=4,
        USE_TRANSFORMER=False,
        FC_DIMS_SHARED=[16],
        POLICY_HEAD_DIMS=[16],
        VALUE_HEAD_DIMS=[16],
        OTHER_NN_INPUT_FEATURES_DIM=expected_other_features_dim(env_cfg),
        NUM_VALUE_ATOMS=11,
        COMPUTE_DTYPE="float32",
    )
    mcts_cfg = AlphaTriangleMCTSConfig(max_simulations=4, max_depth=4)
    return env_cfg, model_cfg, mcts_cfg


class TestThroughputModelCalibration:
    """Gate: the analytic model vs the checked-in observed reference."""

    def test_reference_exists_and_calibrates(self):
        summary = json.loads(REFERENCE.read_text())
        cal = calibration_from_summary(summary)
        assert cal is not None
        # mfu and moves/s+games/h are all present in the reference, so
        # both calibrated terms must have been picked up.
        assert cal.efficiency == pytest.approx(summary["mfu"])
        assert cal.moves_per_game == pytest.approx(
            summary["moves_per_sec"] * 3600.0 / summary["games_per_hour"]
        )

    def test_model_predicts_reference_within_factor(self):
        summary = json.loads(REFERENCE.read_text())
        cal = calibration_from_summary(summary)
        env_cfg, model_cfg, mcts_cfg = _smoke_world()
        # The reference run's shapes: B=4, T=4, lbatch=8; its observed
        # dispatches/iteration is 3.0 = 2 + ceil(B*T/lbatch/K) at K=2.
        cand = Candidate(
            geometry="plan",
            sp_batch=4,
            capacity=2000,
            chunk=4,
            fused_k=2,
            dp=1,
        )
        pred = predict_throughput(
            cand,
            env_cfg,
            model_cfg,
            mcts_cfg,
            lbatch=8,
            calibration=cal,
            peak_tflops=summary["peak_bf16_tflops"],
            megastep=False,
        )
        for metric in ("moves_per_sec", "games_per_hour"):
            observed = summary[metric]
            predicted = pred[metric]
            assert predicted > 0
            assert (
                observed / CALIBRATION_FACTOR
                <= predicted
                <= observed * CALIBRATION_FACTOR
            ), (
                f"{metric}: predicted {predicted:.1f} vs observed "
                f"{observed:.1f} drifted past {CALIBRATION_FACTOR}x"
            )
        assert pred["dispatches_per_iteration"] == pytest.approx(
            summary["dispatches_per_iteration"]
        )

    def test_model_monotone_in_b_t_k(self):
        """The dominance prune's contract: games/h never decreases when
        B, T or K grows with the other axes fixed."""
        env_cfg, model_cfg, mcts_cfg = _smoke_world()
        cal = Calibration()

        def gph(b, t, k):
            return predict_throughput(
                Candidate("plan", b, 2000, t, k, 1),
                env_cfg,
                model_cfg,
                mcts_cfg,
                lbatch=8,
                calibration=cal,
            )["games_per_hour"]

        assert gph(8, 4, 2) >= gph(4, 4, 2)
        assert gph(4, 8, 2) >= gph(4, 4, 2)
        assert gph(4, 4, 4) >= gph(4, 4, 2)

    def test_capacity_does_not_change_prediction(self):
        """Ring size costs memory, not time — 'spend HBM, not chip
        windows' depends on capacity being absent from the objective."""
        env_cfg, model_cfg, mcts_cfg = _smoke_world()

        def gph(cap):
            return predict_throughput(
                Candidate("plan", 4, cap, 4, 2, 1),
                env_cfg,
                model_cfg,
                mcts_cfg,
                lbatch=8,
            )["games_per_hour"]

        assert gph(2000) == pytest.approx(gph(200_000))


class TestSpacePruning:
    def test_divisibility_gates(self):
        ok = Candidate("plan", 8, 64, 4, 2, 1)
        assert divisibility_gate(ok, lbatch=4, min_buffer=10) is None
        # dp must divide capacity / lbatch / lanes.
        bad_dp = Candidate("plan", 8, 64, 4, 2, 3)
        reason = divisibility_gate(bad_dp, lbatch=4, min_buffer=10)
        assert reason is not None and "dp 3" in reason
        # sharded evenly: passes.
        good_dp = Candidate("plan", 8, 64, 4, 2, 2)
        assert divisibility_gate(good_dp, lbatch=4, min_buffer=10) is None
        # Learner batch can't exceed the ring.
        tiny_cap = Candidate("plan", 8, 2, 4, 2, 1)
        assert (
            "BATCH_SIZE"
            in divisibility_gate(tiny_cap, lbatch=4, min_buffer=1)
        )
        assert (
            "MIN_BUFFER"
            in divisibility_gate(
                Candidate("plan", 8, 8, 4, 2, 1), lbatch=4, min_buffer=10
            )
        )
        assert (
            divisibility_gate(
                Candidate("plan", 0, 64, 4, 2, 1), lbatch=4, min_buffer=1
            )
            == "non-positive axis"
        )

    def test_prune_dominated(self):
        group = [
            Candidate("plan", b, 64, 4, 2, 1) for b in (16, 8, 4)
        ]
        other = Candidate("plan", 4, 128, 4, 2, 1)  # different group
        statuses = prune_dominated(group + [other], feasible={group[1]})
        assert statuses == {group[2]: "dominated"}


class _CountingOracle:
    """Fake feasibility oracle: fits iff sp_batch <= max_b, counts
    calls so tests can assert how much pruning saved."""

    def __init__(self, max_b: int, bytes_per_lane: int = 1000):
        self.max_b = max_b
        self.bytes_per_lane = bytes_per_lane
        self.calls: list = []

    def __call__(self, cand, env, model, train, limit):
        self.calls.append(cand)
        budget = {"total_bytes": cand.sp_batch * self.bytes_per_lane}
        return cand.sp_batch <= self.max_b, budget, []


class TestRunSearch:
    def _base(self, tiny_env_config, tiny_model_config, tiny_mcts_config):
        train = TrainConfig(
            BATCH_SIZE=4,
            BUFFER_CAPACITY=64,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            SELF_PLAY_BATCH_SIZE=4,
            ROLLOUT_CHUNK_MOVES=4,
            AUTO_RESUME_LATEST=False,
            RUN_NAME="tune_test",
        )
        return tiny_env_config, tiny_model_config, tiny_mcts_config, train

    def test_dominance_walk_calls_oracle_minimally(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        env, model, mcts, train = self._base(
            tiny_env_config, tiny_model_config, tiny_mcts_config
        )
        space = SearchSpace(
            geometries=["plan"],
            batches=[4, 8, 16],
            capacities=[64],
            chunks=[4],
            fused_ks=[2],
            dps=[1],
        )
        oracle = _CountingOracle(max_b=8)
        result = run_search(
            space, env, model, mcts, train, 10**9, oracle=oracle
        )
        # B=16 over (1 call), B=8 fits (1 call), B=4 dominated (0).
        assert [c.sp_batch for c in oracle.calls] == [16, 8]
        assert result.best is not None and result.best.sp_batch == 8
        statuses = {r["sp_batch"]: r["status"] for r in result.rows}
        assert statuses == {16: "over", 8: "fit", 4: "dominated"}
        assert result.oracle_calls == 2

    def test_winner_beats_every_feasible_candidate(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        """Acceptance (b): the emitted preset predicts >= games/h of
        every feasible-but-rejected candidate."""
        env, model, mcts, train = self._base(
            tiny_env_config, tiny_model_config, tiny_mcts_config
        )
        space = SearchSpace(
            geometries=["plan"],
            batches=[4, 8],
            capacities=[64, 128],
            chunks=[4, 8],
            fused_ks=[2],
            dps=[1],
        )
        result = run_search(
            space, env, model, mcts, train, 10**9,
            oracle=_CountingOracle(max_b=8),
        )
        assert result.best is not None
        best_gph = result.best_prediction["games_per_hour"]
        for row in result.rows:
            if row["status"] in ("fit", "dominated"):
                assert (
                    best_gph >= row["predicted"]["games_per_hour"] - 1e-9
                )

    def test_ring_math_prunes_without_oracle(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        """A limit below the ring's own bytes ends the search with zero
        oracle calls — the infeasible-space exit is free."""
        env, model, mcts, train = self._base(
            tiny_env_config, tiny_model_config, tiny_mcts_config
        )
        space = SearchSpace(
            geometries=["plan"],
            batches=[4, 8],
            capacities=[64],
            chunks=[4],
            fused_ks=[2],
            dps=[1],
        )

        def exploding_oracle(*a):
            raise AssertionError("oracle must not run under ring prune")

        result = run_search(
            space, env, model, mcts, train, 16, oracle=exploding_oracle
        )
        assert result.best is None
        assert result.oracle_calls == 0
        assert {r["status"] for r in result.rows} == {"ring-over"}
        assert result.feasible_rows() == []

    def test_gated_candidates_never_reach_oracle(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        env, model, mcts, train = self._base(
            tiny_env_config, tiny_model_config, tiny_mcts_config
        )
        space = SearchSpace(
            geometries=["plan"],
            batches=[6],  # 6 % dp(4) != 0 -> gate
            capacities=[64],
            chunks=[4],
            fused_ks=[2],
            dps=[4],
        )
        oracle = _CountingOracle(max_b=99)
        result = run_search(
            space, env, model, mcts, train, 10**9, oracle=oracle
        )
        assert oracle.calls == []
        assert {r["status"] for r in result.rows} == {"gate"}

    def test_megastep_mode_materializes_fused_config(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        env, model, mcts, train = self._base(
            tiny_env_config, tiny_model_config, tiny_mcts_config
        )
        cand = Candidate("plan", 8, 128, 4, 2, 1)
        _env, _model, tuned = materialize_candidate(
            cand, env, model, train, "megastep"
        )
        assert tuned.FUSED_MEGASTEP is True
        assert tuned.DEVICE_REPLAY == "on"
        assert tuned.SELF_PLAY_BATCH_SIZE == 8
        assert tuned.BUFFER_CAPACITY == 128
        assert tuned.FUSED_LEARNER_STEPS == 2


class TestTunedPresetArtifact:
    def _result_and_configs(
        self, tiny_env_config, tiny_model_config, tiny_mcts_config
    ):
        train = TrainConfig(
            BATCH_SIZE=4,
            BUFFER_CAPACITY=64,
            MIN_BUFFER_SIZE_TO_TRAIN=8,
            SELF_PLAY_BATCH_SIZE=8,
            ROLLOUT_CHUNK_MOVES=4,
            AUTO_RESUME_LATEST=False,
            RUN_NAME="tuned_rt",
        )
        space = SearchSpace(
            geometries=["plan"],
            batches=[8],
            capacities=[64],
            chunks=[4],
            fused_ks=[2],
            dps=[1],
        )
        result = run_search(
            space,
            tiny_env_config,
            tiny_model_config,
            tiny_mcts_config,
            train,
            10**9,
            oracle=_CountingOracle(max_b=8),
        )
        assert result.best is not None
        return result, tiny_env_config, tiny_model_config, train

    def test_roundtrip(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
    ):
        result, env, model, train = self._result_and_configs(
            tiny_env_config, tiny_model_config, tiny_mcts_config
        )
        payload = build_tuned_preset(
            result,
            env,
            model,
            tiny_mcts_config,
            train,
            scale="cpu",
            mode="sync",
            backend="cpu",
            device_kind="cpu",
            limit_bytes=10**9,
            limit_source="flag",
            calibration=Calibration(),
            run_name="tuned_rt",
        )
        assert payload["schema"] == TUNED_PRESET_SCHEMA
        path = write_tuned_preset(payload, tmp_path / "tuned_preset.json")
        bundle = load_tuned_preset(path)
        assert bundle["train"].SELF_PLAY_BATCH_SIZE == 8
        assert bundle["train"].BUFFER_CAPACITY == 64
        assert bundle["env"].ROWS == env.ROWS
        assert (
            bundle["model"].OTHER_NN_INPUT_FEATURES_DIM
            == model.OTHER_NN_INPUT_FEATURES_DIM
        )
        assert bundle["mcts"].max_simulations == (
            tiny_mcts_config.max_simulations
        )
        assert bundle["tuned"]["candidate"]["sp_batch"] == 8

    def test_schema_mismatch_is_a_clear_error(self, tmp_path):
        path = tmp_path / "tuned_preset.json"
        path.write_text(
            json.dumps(
                {"schema": "alphatriangle.tuned_preset.v999", "configs": {}}
            )
        )
        with pytest.raises(ValueError, match="v999"):
            load_tuned_preset(path)

    def test_unreadable_and_invalid_presets(self, tmp_path):
        with pytest.raises(ValueError, match="unreadable"):
            load_tuned_preset(tmp_path / "missing.json")
        bad = tmp_path / "bad.json"
        bad.write_text("{not json")
        with pytest.raises(ValueError, match="invalid JSON"):
            load_tuned_preset(bad)
        nodict = tmp_path / "list.json"
        nodict.write_text("[1, 2]")
        with pytest.raises(ValueError):
            load_tuned_preset(nodict)

    def test_ledger_tune_outcome(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
    ):
        result, env, model, train = self._result_and_configs(
            tiny_env_config, tiny_model_config, tiny_mcts_config
        )
        payload = build_tuned_preset(
            result,
            env,
            model,
            tiny_mcts_config,
            train,
            scale="cpu",
            mode="sync",
            backend="cpu",
            device_kind="cpu",
            limit_bytes=10**9,
            limit_source="flag",
            calibration=Calibration(),
            run_name="tuned_rt",
        )
        run_dir = tmp_path / "run"
        run_dir.mkdir()
        predicted = payload["predicted"]["games_per_hour"]
        ledger = run_dir / "metrics.jsonl"
        ledger.write_text(
            json.dumps(
                {
                    "kind": "util",
                    "step": 4,
                    "moves_per_sec": 10.0,
                    "games_per_hour": predicted / 2.0,
                }
            )
            + "\n"
        )
        record = ledger_tune_outcome(run_dir, payload)
        assert record is not None
        assert record["observed_over_predicted"] == pytest.approx(0.5)
        lines = ledger.read_text().splitlines()
        assert json.loads(lines[-1])["kind"] == "tune_outcome"
        # The calibration loop reads it back as an outcome scale.
        from alphatriangle_tpu.autotune import calibration_from_targets

        cal = calibration_from_targets([str(ledger)])
        assert cal.outcome_scale == pytest.approx(0.5)

    def test_ledger_tune_outcome_without_ledger(
        self,
        tmp_path,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
    ):
        result, env, model, train = self._result_and_configs(
            tiny_env_config, tiny_model_config, tiny_mcts_config
        )
        payload = build_tuned_preset(
            result,
            env,
            model,
            tiny_mcts_config,
            train,
            scale="cpu",
            mode="sync",
            backend="cpu",
            device_kind="cpu",
            limit_bytes=10**9,
            limit_source="flag",
            calibration=Calibration(),
            run_name="tuned_rt",
        )
        empty = tmp_path / "empty_run"
        empty.mkdir()
        assert ledger_tune_outcome(empty, payload) is None


@pytest.fixture()
def tiny_tune_target(
    monkeypatch,
    tiny_env_config,
    tiny_model_config,
    tiny_mcts_config,
    tiny_train_config,
):
    """`cli tune <target>` resolving to the tiny world, with a tiny
    lattice: what the retired `tune cpu --smoke` stood for."""
    from alphatriangle_tpu import cli as cli_mod

    bundle = {
        "env": tiny_env_config,
        "model": tiny_model_config,
        "mcts": tiny_mcts_config,
        "train": tiny_train_config,
        "description": "tiny",
    }
    monkeypatch.setattr(cli_mod, "resolve_preset", lambda target: bundle)
    return [
        "tune", "1", "--device", "cpu",
        "--batches", "2,4", "--capacities", "100",
        "--chunks", "4", "--fused-k", "1",
    ]  # fmt: skip


class TestCliTune:
    """cmd_tune end to end with the oracle faked out (the real oracle
    compiles programs; benchmarks/tune_smoke.py covers it)."""

    def test_happy_path_emits_consumable_preset(
        self, monkeypatch, tmp_path, tiny_tune_target
    ):
        from alphatriangle_tpu import cli as cli_mod
        from alphatriangle_tpu.autotune import search as search_mod

        def fake_default_oracle(mcts, mode, device_replay=None, progress=None):
            def oracle(cand, env, model, train, limit):
                return True, {"total_bytes": 12345}, []

            return oracle

        monkeypatch.setattr(
            search_mod, "default_oracle", fake_default_oracle
        )
        out = tmp_path / "tuned_preset.json"
        rc = cli_mod.main(
            tiny_tune_target
            + [
                "--limit-gb",
                "8",
                "--out",
                str(out),
                "--root-dir",
                str(tmp_path),
                "--run-name",
                "tune_unit",
            ]
        )
        assert rc == 0
        payload = json.loads(out.read_text())
        assert payload["schema"] == TUNED_PRESET_SCHEMA
        assert payload["limit_source"] == "flag"
        bundle = load_tuned_preset(out)
        assert bundle["train"].RUN_NAME == "tune_unit"
        # Acceptance (b) at the artifact level: the winner's predicted
        # games/h tops every candidate the search scored as feasible.
        best = payload["predicted"]["games_per_hour"]
        for row in payload["search"]["rows"]:
            if row["status"] in ("fit", "dominated") and row["predicted"]:
                assert best >= row["predicted"]["games_per_hour"] - 1e-9

    def test_infeasible_space_exits_1(self, tmp_path, tiny_tune_target):
        """A byte limit below the replay ring's own size: every
        candidate dies in the free ring prune (no compiles) and the
        command exits FIT_OVER."""
        from alphatriangle_tpu import cli as cli_mod

        rc = cli_mod.main(
            tiny_tune_target
            + [
                "--limit-gb",
                "0.000001",
                "--root-dir",
                str(tmp_path),
            ]
        )
        assert rc == 1

    def test_unknown_limit_exits_2(
        self, monkeypatch, tmp_path, tiny_tune_target
    ):
        from alphatriangle_tpu import cli as cli_mod
        from alphatriangle_tpu.telemetry import health as health_mod
        from alphatriangle_tpu.telemetry import memory as memory_mod

        monkeypatch.delenv(memory_mod.BYTES_LIMIT_ENV, raising=False)
        # resolve_bytes_limit falls through flag -> env -> device stats;
        # blind the device layer so nothing is known.
        monkeypatch.setattr(
            health_mod, "device_memory_stats", lambda: []
        )
        rc = cli_mod.main(
            tiny_tune_target + ["--root-dir", str(tmp_path)]
        )
        assert rc == 2


class TestPerfTolerance:
    """Satellite: historical ledgers without the newer fields still
    summarize and compare instead of being skipped."""

    def test_kindless_legacy_util_records_summarize(self):
        from alphatriangle_tpu.telemetry.perf import summarize_utilization

        legacy = [
            {
                "step": i,
                "moves_per_sec": 10.0 + i,
                "learner_steps_per_sec": 1.0,
                "window_s": 2.0,
            }
            for i in range(4)
        ]
        summary = summarize_utilization(legacy)
        assert summary is not None
        assert summary["ticks"] == 4
        assert summary["moves_per_sec"] == pytest.approx(11.5)
        # Fields the era predates surface as None, not a crash.
        assert summary["mfu"] is None
        assert summary["mem_bytes_limit"] is None

    def test_load_comparable_reads_legacy_ledger(self, tmp_path):
        from alphatriangle_tpu.telemetry.perf import (
            compare_summaries,
            load_comparable,
        )

        ledger = tmp_path / "metrics.jsonl"
        ledger.write_text(
            "\n".join(
                json.dumps(
                    {"step": i, "moves_per_sec": 5.0, "games_per_hour": 99.0}
                )
                for i in range(3)
            )
            + "\n"
        )
        summary, label = load_comparable(str(ledger))
        assert summary is not None, label
        assert summary["games_per_hour"] == pytest.approx(99.0)
        # And a modern summary compares against it: missing metrics are
        # "n/a" rows, never a skipped run.
        modern = json.loads(REFERENCE.read_text())
        rows, regressions = compare_summaries(modern, summary)
        statuses = {m: s for m, _a, _b, _r, s in rows}
        assert statuses.get("mfu") == "n/a"

    def test_fit_json_schema_tag(self):
        """`cli fit --json` output leads with a schema tag so scripts
        can gate on it (satellite: machine-readable fit)."""
        import inspect

        from alphatriangle_tpu import cli as cli_mod

        src = inspect.getsource(cli_mod.cmd_fit)
        assert "alphatriangle.fit.v1" in src
