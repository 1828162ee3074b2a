"""CI autotuner gate: `cli tune` offline search -> consumable preset.

`make tune-smoke` runs this. It proves, on any machine with no
accelerator, the full fit-driven autotune loop (docs/AUTOTUNE.md) end
to end:

1. the search under `cli tune` (`autotune.run_search`, the REAL
   `estimate_fit` oracle: a couple of AOT compiles, nothing executed)
   over a tiny lattice around this script's own tiny configs, under
   `--limit-gb <host cap>`, must find a winner and write a
   `tuned_preset.json` artifact;
2. `cli fit <artifact>` re-runs the OOM pre-flight against the emitted
   preset with the same limit and must exit 0 — the tuner's feasibility
   claim is independently confirmed by the fit gate;
3. the artifact's search table must show the winner's predicted games/h
   >= every other feasible candidate's (the acceptance invariant the
   pruned search guarantees structurally);
4. `cli train --preset <artifact> --dry-setup` must construct every
   training component from the preset and exit 0 — the preset is
   runnable, not just well-formed;
5. optionally (--train-steps N, default 2) a real N-step training run
   consumes the preset and must append a `kind:"tune_outcome"`
   predicted-vs-observed record to its metrics ledger — the calibration
   feedback loop `cli tune --calibrate` reads.

Exit 0 when every stage passes; the first failing stage's code
otherwise.
"""

import argparse
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
RUN_NAME = "tune_smoke"

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# Must precede any jax import: the smoke must not wake an accelerator,
# and a pinned peak makes predicted-vs-observed MFU comparable.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--limit-gb",
        type=float,
        default=4.0,
        help="Host-RAM stand-in for the per-device byte limit "
        "(default 4 GiB — far below any CI host's actual RAM, so the "
        "gate also proves the search respects a cap).",
    )
    parser.add_argument(
        "--root-dir",
        default=None,
        help="Runs root for the smoke (default: a temp dir).",
    )
    parser.add_argument(
        "--train-steps",
        type=int,
        default=2,
        help="Learner steps for the outcome-ledger stage (0 skips it).",
    )
    args = parser.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_enable_async_dispatch", False)

    from alphatriangle_tpu.autotune import (
        SearchSpace,
        build_tuned_preset,
        calibration_from_targets,
        run_search,
        write_tuned_preset,
    )
    from alphatriangle_tpu.autotune.search import (
        candidate_mcts,
        materialize_candidate,
    )
    from alphatriangle_tpu.cli import main as cli_main
    from alphatriangle_tpu.config import (
        AlphaTriangleMCTSConfig,
        EnvConfig,
        ModelConfig,
        TrainConfig,
        expected_other_features_dim,
    )

    root = args.root_dir or tempfile.mkdtemp(prefix="at_tune_smoke_")
    artifact = Path(root) / "tuned_preset.json"

    # The default board and net at a CPU-sized search and lane count.
    env_cfg = EnvConfig()
    model_cfg = ModelConfig(
        OTHER_NN_INPUT_FEATURES_DIM=expected_other_features_dim(env_cfg),
        COMPUTE_DTYPE="float32",
    )
    mcts_cfg = AlphaTriangleMCTSConfig(max_simulations=8, max_depth=4)
    train_cfg = TrainConfig(
        SELF_PLAY_BATCH_SIZE=16,
        ROLLOUT_CHUNK_MOVES=4,
        BATCH_SIZE=32,
        BUFFER_CAPACITY=10_000,
        MIN_BUFFER_SIZE_TO_TRAIN=1_000,
        MAX_TRAINING_STEPS=1_000,
        FUSED_LEARNER_STEPS=4,
        RUN_NAME=RUN_NAME,
    )
    space = SearchSpace(
        batches=[8, 16], capacities=[10_000], chunks=[4], fused_ks=[4]
    )
    limit = args.limit_gb * 2**30
    calibration = calibration_from_targets([])

    print(
        f"tune-smoke: run_search over {space.size()} candidates (limit "
        f"{args.limit_gb} GiB) -> {artifact} ...",
        flush=True,
    )
    result = run_search(
        space,
        env_cfg,
        model_cfg,
        mcts_cfg,
        train_cfg,
        limit,
        calibration=calibration,
        peak_tflops=1.0,
        mode="sync",
        device_replay=False,
        progress=lambda msg: print(msg, file=sys.stderr, flush=True),
    )
    if result.best is None:
        print(
            f"tune-smoke: no feasible candidate under {args.limit_gb} GiB",
            file=sys.stderr,
        )
        return 1
    best_env, best_model, best_train = materialize_candidate(
        result.best, env_cfg, model_cfg, train_cfg, "sync"
    )
    write_tuned_preset(
        build_tuned_preset(
            result,
            best_env,
            best_model,
            candidate_mcts(mcts_cfg, result.best),
            best_train,
            scale="tune_smoke",
            mode="sync",
            backend="cpu",
            device_kind="cpu",
            limit_bytes=limit,
            limit_source="flag",
            calibration=calibration,
            run_name=RUN_NAME,
        ),
        artifact,
    )
    payload = json.loads(artifact.read_text())

    print("tune-smoke: winner-beats-feasible invariant...", flush=True)
    best = (payload.get("predicted") or {}).get("games_per_hour")
    if not isinstance(best, (int, float)) or best <= 0:
        print(
            f"tune-smoke: artifact has no positive predicted games/h "
            f"({best!r})",
            file=sys.stderr,
        )
        return 2
    for row in (payload.get("search") or {}).get("rows", []):
        pred = row.get("predicted") or {}
        gph = pred.get("games_per_hour")
        if (
            row.get("status") in ("fit", "dominated")
            and isinstance(gph, (int, float))
            and gph > best + 1e-9
        ):
            print(
                f"tune-smoke: feasible candidate {row} predicts "
                f"{gph:.1f} games/h > winner's {best:.1f}",
                file=sys.stderr,
            )
            return 2

    print("tune-smoke: cli fit <artifact> (independent confirm)...", flush=True)
    rc = cli_main(
        ["fit", str(artifact), "--limit-gb", str(args.limit_gb)]
    )
    if rc != 0:
        print(
            f"tune-smoke: cli fit rejected the tuned preset (rc={rc}) — "
            "the tuner's feasibility claim did not hold",
            file=sys.stderr,
        )
        return rc

    print("tune-smoke: cli train --preset <artifact> --dry-setup...", flush=True)
    rc = cli_main(
        [
            "train",
            "--preset",
            str(artifact),
            "--dry-setup",
            "--run-name",
            f"{RUN_NAME}_dry",
            "--root-dir",
            root,
            "--no-tensorboard",
            "--no-auto-resume",
            "--log-level",
            "WARNING",
        ]
    )
    if rc != 0:
        print(
            f"tune-smoke: dry component setup from the preset failed "
            f"(rc={rc})",
            file=sys.stderr,
        )
        return rc

    if args.train_steps > 0:
        print(
            f"tune-smoke: {args.train_steps}-step run for the "
            "tune_outcome ledger...",
            flush=True,
        )
        obs_run = f"{RUN_NAME}_obs"
        rc = cli_main(
            [
                "train",
                "--preset",
                str(artifact),
                "--max-steps",
                str(args.train_steps),
                "--min-buffer",
                "16",
                "--run-name",
                obs_run,
                "--root-dir",
                root,
                "--no-tensorboard",
                "--no-auto-resume",
                "--log-level",
                "WARNING",
            ]
        )
        if rc != 0:
            print(
                f"tune-smoke: tuned training run failed (rc={rc})",
                file=sys.stderr,
            )
            return rc
        from alphatriangle_tpu.config import PersistenceConfig

        ledger = (
            PersistenceConfig(ROOT_DATA_DIR=root, RUN_NAME=obs_run)
            .get_run_base_dir()
            / "metrics.jsonl"
        )
        outcomes = [
            r
            for line in ledger.read_text().splitlines()
            for r in [json.loads(line)]
            if r.get("kind") == "tune_outcome"
        ]
        if not outcomes:
            print(
                f"tune-smoke: {ledger} holds no tune_outcome record — "
                "the calibration feedback loop broke",
                file=sys.stderr,
            )
            return 2
        print(
            "tune-smoke: outcome ledgered "
            f"(predicted {outcomes[-1].get('predicted_games_per_hour')}, "
            f"observed {outcomes[-1].get('observed_games_per_hour')})"
        )

    if args.root_dir is None:
        shutil.rmtree(root, ignore_errors=True)
    print("tune-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
