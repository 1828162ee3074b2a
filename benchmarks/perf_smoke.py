"""CI perf-smoke gate: short CPU training run -> `cli perf`/`cli compare`.

`make perf-smoke` runs this. It proves, on any machine with no
accelerator, that the metrics-ledger pipeline end to end still works:

1. a tiny CPU training run (test-sized world, ~8 learner steps) writes
   `metrics.jsonl` with utilization records (non-null MFU via the
   ALPHATRIANGLE_PEAK_TFLOPS override this script sets);
2. the run's ledger carries memory observability records
   (docs/OBSERVABILITY.md "Memory"): `kind: "memory"` attribution
   lines (train state / replay ring / AOT program analysis) and
   `mem_bytes_in_use` on the utilization records;
3. `cli perf <run>` summarizes it — exit 2 there means the ledger
   schema broke;
4. `cli fit 1` composes preset 1's static memory budget (the CPU
   configuration of BASELINE.md) against the host byte limit and must
   exit 0 (the OOM pre-flight gate);
5. `cli compare <run> benchmarks/perf_reference_cpu_smoke.json`
   gates against the checked-in reference summary. The threshold is
   deliberately generous (default 0.9: fail only on a >90% collapse)
   because CI hosts vary wildly in speed — the hard signal here is
   schema alignment plus "not catastrophically slower", not a tight
   perf bar (that's what `cli compare` against same-hardware runs is
   for).

Exit 0 when every stage passes; the first failing stage's code
otherwise. Regenerate the reference with --write-reference after an
intentional schema change.
"""

import argparse
import os
import shutil
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
REFERENCE = Path(__file__).resolve().parent / "perf_reference_cpu_smoke.json"
RUN_NAME = "perf_smoke"

# Runnable as `python benchmarks/perf_smoke.py` without installing the
# package: the repo root is the import root.
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# Must precede any jax import: the smoke must not wake (or wedge on) an
# accelerator, and the peak override is what makes CPU MFU non-null.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")


def tiny_configs():
    """The test suite's tiny world (tests/conftest.py), inlined so the
    smoke needs no pytest machinery."""
    from alphatriangle_tpu.config import (
        AlphaTriangleMCTSConfig,
        EnvConfig,
        ModelConfig,
        TrainConfig,
        expected_other_features_dim,
    )

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
        # The smokes run with the bf16 inference path ON (nn/precision.py,
        # docs/KERNELS.md): rollout + serve forwards consume bf16-cast
        # params while the learner keeps updating the f32 originals —
        # this gate proves the cast path end to end on CPU, not speed.
        INFERENCE_PRECISION="bfloat16",
    )
    mcts_cfg = AlphaTriangleMCTSConfig(max_simulations=4, max_depth=4)
    train_cfg = TrainConfig(
        RUN_NAME=RUN_NAME,
        AUTO_RESUME_LATEST=False,
        MAX_TRAINING_STEPS=8,
        SELF_PLAY_BATCH_SIZE=4,
        ROLLOUT_CHUNK_MOVES=4,
        BATCH_SIZE=8,
        BUFFER_CAPACITY=2000,
        MIN_BUFFER_SIZE_TO_TRAIN=16,
        USE_PER=True,
        PER_BETA_ANNEAL_STEPS=8,
        N_STEP_RETURNS=2,
        WORKER_UPDATE_FREQ_STEPS=2,
        CHECKPOINT_SAVE_FREQ_STEPS=4,
        MAX_EPISODE_MOVES=30,
        RANDOM_SEED=5,
        DEVICE="cpu",
    )
    return env_cfg, model_cfg, mcts_cfg, train_cfg


def dp_child(args) -> int:
    """The 2-device dp-sharded megastep stage (runs in a subprocess).

    The parent spawns this module with
    `XLA_FLAGS=--xla_force_host_platform_device_count=2` so the CPU
    backend presents two devices — the flag must be set before the
    process's first jax import, hence a child process rather than a
    stage in the parent. Runs a 4-step FUSED_MEGASTEP training loop
    sharded over dp=2 and gates on the ledger's mesh-level dispatch
    gauge: one host dispatch per iteration regardless of mesh width.
    """
    import json

    import jax

    if jax.device_count() < 2:
        print(
            f"perf-smoke[dp]: expected >=2 devices, got "
            f"{jax.device_count()} — XLA_FLAGS not applied?",
            file=sys.stderr,
        )
        return 2

    from alphatriangle_tpu.config import (
        MeshConfig,
        PersistenceConfig,
        TrainConfig,
    )
    from alphatriangle_tpu.training import run_training

    env_cfg, model_cfg, mcts_cfg, train_cfg = tiny_configs()
    dp_run = f"{RUN_NAME}_megastep_dp2"
    dp_cfg = TrainConfig(
        **{
            **train_cfg.model_dump(),
            "RUN_NAME": dp_run,
            "FUSED_MEGASTEP": True,
            "DEVICE_REPLAY": "on",
            "FUSED_LEARNER_STEPS": 2,
            "MAX_TRAINING_STEPS": 4,
        }
    )
    dp_pc = PersistenceConfig(ROOT_DATA_DIR=args.root_dir, RUN_NAME=dp_run)
    rc = run_training(
        train_config=dp_cfg,
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        persistence_config=dp_pc,
        mesh_config=MeshConfig(DP_SIZE=2),
        use_tensorboard=False,
        log_level="WARNING",
    )
    if rc != 0:
        print(
            f"perf-smoke[dp]: dp=2 megastep run failed (rc={rc})",
            file=sys.stderr,
        )
        return rc
    ledger = dp_pc.get_run_base_dir() / "metrics.jsonl"
    utils = [
        r
        for line in ledger.read_text().splitlines()
        for r in [json.loads(line)]
        if r.get("kind") == "util"
        and isinstance(r.get("dispatches_per_iteration"), (int, float))
    ]
    if not utils:
        print(
            f"perf-smoke[dp]: {ledger} has no util record with "
            "dispatches_per_iteration",
            file=sys.stderr,
        )
        return 2
    dpi = utils[-1]["dispatches_per_iteration"]
    mesh_devices = utils[-1].get("mesh_devices")
    # The gauge counts mesh-level program launches: a dp=2 iteration is
    # still exactly ONE dispatch. mesh_devices is recorded beside it so
    # readers can recover per-device executions.
    if abs(dpi - 1.0) > 1e-6 or mesh_devices != 2:
        print(
            f"perf-smoke[dp]: expected dispatches_per_iteration=1.0 "
            f"with mesh_devices=2, got {dpi} / {mesh_devices}",
            file=sys.stderr,
        )
        return 2
    print(
        f"perf-smoke[dp]: dp=2 megastep ran; dispatches/iteration "
        f"{dpi:.1f} across {mesh_devices} devices"
    )
    return 0


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--threshold",
        type=float,
        default=0.9,
        help="compare tolerance vs the checked-in reference "
        "(generous by design: CI hosts vary in speed).",
    )
    parser.add_argument(
        "--root-dir",
        default=None,
        help="Runs root for the smoke run (default: a temp dir).",
    )
    parser.add_argument(
        "--write-reference",
        action="store_true",
        help=f"Regenerate {REFERENCE.name} from this run's summary.",
    )
    parser.add_argument(
        "--dp-child",
        action="store_true",
        help=argparse.SUPPRESS,  # internal: the 2-device megastep stage
    )
    args = parser.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_enable_async_dispatch", False)

    if args.dp_child:
        return dp_child(args)

    from alphatriangle_tpu.cli import main as cli_main
    from alphatriangle_tpu.config import PersistenceConfig
    from alphatriangle_tpu.training import run_training

    root = args.root_dir or tempfile.mkdtemp(prefix="at_perf_smoke_")
    env_cfg, model_cfg, mcts_cfg, train_cfg = tiny_configs()
    pc = PersistenceConfig(ROOT_DATA_DIR=root, RUN_NAME=RUN_NAME)
    print(f"perf-smoke: training {RUN_NAME} under {root}...", flush=True)
    rc = run_training(
        train_config=train_cfg,
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        persistence_config=pc,
        use_tensorboard=False,
        log_level="WARNING",
    )
    if rc != 0:
        print(f"perf-smoke: training run failed (rc={rc})", file=sys.stderr)
        return rc

    print("perf-smoke: memory records gate...", flush=True)
    import json as _json

    ledger = pc.get_run_base_dir() / "metrics.jsonl"
    records = []
    for line in ledger.read_text().splitlines():
        try:
            records.append(_json.loads(line))
        except _json.JSONDecodeError:
            continue
    mem_records = [r for r in records if r.get("kind") == "memory"]
    mem_utils = [
        r
        for r in records
        if r.get("kind") == "util"
        and isinstance(r.get("mem_bytes_in_use"), (int, float))
    ]
    if not mem_records or not mem_utils:
        print(
            f"perf-smoke: {ledger} holds {len(mem_records)} memory "
            f"record(s) and {len(mem_utils)} util record(s) with "
            "mem_bytes_in_use — memory observability broke",
            file=sys.stderr,
        )
        return 2
    print(
        f"perf-smoke: {len(mem_records)} memory record(s), "
        f"{len(mem_utils)} util record(s) with live accounting"
    )

    print("perf-smoke: flight recorder gate...", flush=True)
    # The dispatch flight recorder (telemetry/flight.py) must have
    # sealed real dispatches from this run's hot sites — an empty ring
    # means the instrumentation came unwired — and its measured
    # bookkeeping overhead must stay under ~1% of the run's wall time
    # (compared against total wall, not sealed dispatch wall: tiny CPU
    # dispatches make that ratio meaningless).
    from alphatriangle_tpu.telemetry.flight import read_flight
    from alphatriangle_tpu.telemetry.ledger import iter_jsonl_records

    flight_path = pc.get_run_base_dir() / "flight.jsonl"
    flight = read_flight(flight_path)
    seals = [r for r in flight if r.get("phase") == "seal" and r.get("ok")]
    families = {r.get("family") for r in seals}
    if not seals or not {"rollout", "learner"} <= families:
        print(
            f"perf-smoke: {flight_path} holds {len(seals)} sealed "
            f"dispatch(es) across families {sorted(families)} — the "
            "flight recorder came unwired from the hot dispatch sites",
            file=sys.stderr,
        )
        return 2
    run_wall = sum(
        r["window_s"]
        for r in records
        if r.get("kind") == "util"
        and isinstance(r.get("window_s"), (int, float))
    )
    overhead = next(
        (
            r.get("overhead_s")
            for r in reversed(list(iter_jsonl_records(flight_path)))
            if r.get("kind") == "flight_overhead"
        ),
        None,
    )
    if not isinstance(overhead, (int, float)):
        print(
            f"perf-smoke: {flight_path} has no flight_overhead summary "
            "record (FlightRecorder.close never ran?)",
            file=sys.stderr,
        )
        return 2
    if run_wall > 0 and overhead > 0.01 * run_wall:
        print(
            f"perf-smoke: flight overhead {overhead:.3f}s exceeds 1% of "
            f"the run's {run_wall:.1f}s wall — the recorder is on the "
            "hot path",
            file=sys.stderr,
        )
        return 2
    print(
        f"perf-smoke: {len(seals)} sealed dispatch(es) "
        f"({', '.join(sorted(f for f in families if f))}); overhead "
        f"{overhead:.4f}s of {run_wall:.1f}s wall"
    )

    print("perf-smoke: cli perf (schema gate)...", flush=True)
    rc = cli_main(["perf", RUN_NAME, "--root-dir", root])
    if rc != 0:
        print(f"perf-smoke: cli perf failed (rc={rc})", file=sys.stderr)
        return rc

    print("perf-smoke: cli fit 1 (OOM pre-flight gate)...", flush=True)
    rc = cli_main(["fit", "1"])
    if rc != 0:
        print(f"perf-smoke: cli fit 1 failed (rc={rc})", file=sys.stderr)
        return rc

    print("perf-smoke: fused-megastep mode gate...", flush=True)
    # A second, even shorter run in FUSED_MEGASTEP mode: the whole
    # iteration (rollout + ingest + on-device sampling + K learner
    # steps) is one device program, and its ledger must carry the
    # dispatches-per-iteration gauge that makes the win measurable.
    from alphatriangle_tpu.config import TrainConfig

    mega_run = f"{RUN_NAME}_megastep"
    mega_cfg = TrainConfig(
        **{
            **train_cfg.model_dump(),
            "RUN_NAME": mega_run,
            "FUSED_MEGASTEP": True,
            "DEVICE_REPLAY": "on",
            "FUSED_LEARNER_STEPS": 2,
            "MAX_TRAINING_STEPS": 4,
        }
    )
    mega_pc = PersistenceConfig(ROOT_DATA_DIR=root, RUN_NAME=mega_run)
    rc = run_training(
        train_config=mega_cfg,
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        persistence_config=mega_pc,
        use_tensorboard=False,
        log_level="WARNING",
    )
    if rc != 0:
        print(
            f"perf-smoke: megastep run failed (rc={rc})", file=sys.stderr
        )
        return rc
    mega_ledger = mega_pc.get_run_base_dir() / "metrics.jsonl"
    mega_dpi = [
        r.get("dispatches_per_iteration")
        for line in mega_ledger.read_text().splitlines()
        for r in [_json.loads(line)]
        if r.get("kind") == "util"
        and isinstance(r.get("dispatches_per_iteration"), (int, float))
    ]
    if not mega_dpi:
        print(
            f"perf-smoke: {mega_ledger} has no util record with "
            "dispatches_per_iteration — the megastep gauge broke",
            file=sys.stderr,
        )
        return 2
    print(
        f"perf-smoke: megastep ran; dispatches/iteration "
        f"{mega_dpi[-1]:.1f} (last tick)"
    )

    print("perf-smoke: dp-sharded megastep gate (2 devices)...", flush=True)
    # The dp-sharded variant needs a 2-device backend, and
    # --xla_force_host_platform_device_count only takes effect before a
    # process's first jax import — so the stage runs in a child process
    # (dp_child above) with its own XLA_FLAGS.
    import subprocess

    child_env = {
        **os.environ,
        "XLA_FLAGS": "--xla_force_host_platform_device_count=2",
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": str(REPO),
    }
    child = subprocess.run(
        [
            sys.executable,
            str(Path(__file__).resolve()),
            "--dp-child",
            "--root-dir",
            root,
        ],
        cwd=str(REPO),
        env=child_env,
        timeout=600,
    )
    if child.returncode != 0:
        print(
            f"perf-smoke: dp-sharded gate failed (rc={child.returncode})",
            file=sys.stderr,
        )
        return child.returncode

    if args.write_reference:
        import contextlib
        import io
        import json

        buf = io.StringIO()
        with contextlib.redirect_stdout(buf):
            rc = cli_main(["perf", RUN_NAME, "--root-dir", root, "--json"])
        if rc != 0:
            return rc
        summary = json.loads(buf.getvalue())
        summary["source"] = "benchmarks/perf_smoke.py --write-reference"
        # The serve smoke (benchmarks/serve_smoke.py) merges its
        # serve_* SLO rows into this same reference file; preserve
        # them across training-side regenerations.
        if REFERENCE.exists():
            try:
                old = json.loads(REFERENCE.read_text())
                summary.update(
                    {
                        k: v
                        for k, v in old.items()
                        if k.startswith("serve_")
                    }
                )
            except json.JSONDecodeError:
                pass
        REFERENCE.write_text(json.dumps(summary, indent=2) + "\n")
        print(f"perf-smoke: reference written to {REFERENCE}")
        return 0

    print(
        f"perf-smoke: cli compare vs {REFERENCE.name} "
        f"(threshold {args.threshold:.0%})...",
        flush=True,
    )
    rc = cli_main(
        [
            "compare",
            RUN_NAME,
            str(REFERENCE),
            "--root-dir",
            root,
            "--threshold",
            str(args.threshold),
        ]
    )
    if rc != 0:
        print(f"perf-smoke: cli compare failed (rc={rc})", file=sys.stderr)
        return rc
    if args.root_dir is None:
        shutil.rmtree(root, ignore_errors=True)
    print("perf-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
