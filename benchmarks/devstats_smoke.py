"""CI device-telemetry gate: stat-packs in the one fetch + beacons.

`make devstats-smoke` runs this. It proves, on any machine with no
accelerator, that the device telemetry plane
(alphatriangle_tpu/telemetry/device_stats.py, docs/OBSERVABILITY.md
"Device telemetry plane") closes end to end:

1. stat-pack ledger gate: a short FUSED_MEGASTEP CPU training run with
   `TelemetryConfig.DEVICE_STATS` on (the default) must land
   `kind: "device_stats"` records in metrics.jsonl carrying the search
   leg (root entropy / occupancy / depth histogram), and
   `cli perf --json` must fold them into `ds_*` summary fields — while
   the one-dispatch-per-iteration gauge still reads exactly 1.0;
2. overhead gate: the SAME megastep program timed with stat-packs OFF
   vs ON (in-process, warmup excluded, medians) must show <3% added
   wall per iteration, with the runner's dispatch counter advancing
   exactly once per megastep in both modes — the stats ride the
   existing fetch, they do not buy extra dispatches or host syncs;
3. wedge-phase forensics gate: a training child with beacons armed by
   env (`ALPHATRIANGLE_BEACONS=1`) and an injected mid-run dispatch
   hang (`hang-dispatch` fault) must die by the real watchdog's exit
   113 leaving crash-safe beacons.jsonl rows, a wedge_report.json whose
   frozen `last_beacon` names the phase, and a `cli doctor` dispatch-
   hung verdict (run with jax imports hard-blocked) that carries that
   same beacon.

Exit 0 when every stage passes; the first failing stage's code
otherwise.
"""

import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# Must precede any jax import: the smoke must not wake an accelerator.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")

OVERHEAD_BUDGET = 0.03  # stat-pack wall overhead bound (3%)

# Hard import-guard preamble for the doctor subprocess: any jax import
# on the doctor path raises, same contract as doctor_smoke.py.
_NO_JAX_PREAMBLE = (
    "import builtins, sys;"
    "_real = builtins.__import__;\n"
    "def _guard(name, *a, **k):\n"
    "    if name == 'jax' or name.startswith('jax.'):\n"
    "        raise ImportError('cli doctor must not import jax: ' + name)\n"
    "    return _real(name, *a, **k)\n"
    "builtins.__import__ = _guard\n"
)


def run_doctor(run_dir: Path) -> "tuple[int, dict | None]":
    """`cli doctor <run_dir> --json` in a subprocess with jax imports
    blocked."""
    code = (
        _NO_JAX_PREAMBLE
        + "from alphatriangle_tpu.cli import main\n"
        + f"sys.exit(main(['doctor', {str(run_dir)!r}, '--json']))\n"
    )
    proc = subprocess.run(
        [sys.executable, "-c", code],
        cwd=str(REPO),
        env={**os.environ, "PYTHONPATH": str(REPO)},
        capture_output=True,
        text=True,
        timeout=120,
    )
    verdict = None
    for line in proc.stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            try:
                verdict = json.loads(line)
            except json.JSONDecodeError:
                pass
    if verdict is None:
        print(
            f"devstats-smoke: no JSON verdict from cli doctor "
            f"(rc={proc.returncode})\nstdout: {proc.stdout}\n"
            f"stderr: {proc.stderr}",
            file=sys.stderr,
        )
    return proc.returncode, verdict


def tiny_configs(run_name: str):
    """perf_smoke's tiny world in FUSED_MEGASTEP mode, plus a fast
    dispatch watchdog so the injected hang in stage 3 dies in seconds."""
    from alphatriangle_tpu.config import (
        AlphaTriangleMCTSConfig,
        EnvConfig,
        ModelConfig,
        TelemetryConfig,
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
    )
    mcts_cfg = AlphaTriangleMCTSConfig(max_simulations=4, max_depth=4)
    train_cfg = TrainConfig(
        RUN_NAME=run_name,
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
        FUSED_MEGASTEP=True,
        DEVICE_REPLAY="on",
        FUSED_LEARNER_STEPS=2,
    )
    tele_cfg = TelemetryConfig(
        DISPATCH_MIN_DEADLINE_S=2.0,
        DISPATCH_FIRST_DEADLINE_S=120.0,
        DISPATCH_WATCHDOG_POLL_S=0.25,
        HEALTH_WRITE_INTERVAL_S=1.0,
    )
    return env_cfg, model_cfg, mcts_cfg, train_cfg, tele_cfg


def read_records(ledger: Path) -> list:
    records = []
    if not ledger.exists():
        return records
    for line in ledger.read_text().splitlines():
        try:
            records.append(json.loads(line))
        except json.JSONDecodeError:
            continue
    return records


def stage_statpack_ledger(root: Path) -> int:
    """Short megastep run -> device_stats records -> `cli perf --json`
    ds_* fields, with the one-dispatch gauge untouched."""
    import contextlib
    import io

    from alphatriangle_tpu.cli import main as cli_main
    from alphatriangle_tpu.config import PersistenceConfig
    from alphatriangle_tpu.training import run_training

    run = "devstats_ledger"
    env_cfg, model_cfg, mcts_cfg, train_cfg, _tele = tiny_configs(run)
    pc = PersistenceConfig(ROOT_DATA_DIR=str(root), RUN_NAME=run)
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
        print(
            f"devstats-smoke: megastep run failed (rc={rc})",
            file=sys.stderr,
        )
        return 2

    records = read_records(pc.get_run_base_dir() / "metrics.jsonl")
    ds_records = [r for r in records if r.get("kind") == "device_stats"]
    search_legs = [
        r["search"] for r in ds_records if isinstance(r.get("search"), dict)
    ]
    if not ds_records or not search_legs:
        print(
            f"devstats-smoke: ledger holds {len(ds_records)} device_stats "
            f"record(s), {len(search_legs)} with a search leg — the "
            "stat-pack plumbing came unwired",
            file=sys.stderr,
        )
        return 2
    leg = search_legs[-1]
    missing = [
        k
        for k in ("root_entropy", "occupancy", "depth_hist", "value_abs_max")
        if leg.get(k) is None
    ]
    if missing:
        print(
            f"devstats-smoke: search leg lacks {missing}: {leg}",
            file=sys.stderr,
        )
        return 2

    # Stat-packs must NOT buy extra dispatches: the megastep gauge still
    # reads exactly one host dispatch per iteration with stats on.
    dpi = [
        r.get("dispatches_per_iteration")
        for r in records
        if r.get("kind") == "util"
        and isinstance(r.get("dispatches_per_iteration"), (int, float))
    ]
    if not dpi or abs(dpi[-1] - 1.0) > 1e-6:
        print(
            f"devstats-smoke: dispatches_per_iteration "
            f"{dpi[-1] if dpi else None} != 1.0 with stat-packs on",
            file=sys.stderr,
        )
        return 2

    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["perf", run, "--root-dir", str(root), "--json"])
    if rc != 0:
        print(
            f"devstats-smoke: cli perf failed (rc={rc})", file=sys.stderr
        )
        return rc
    summary = json.loads(buf.getvalue())
    if not summary.get("ds_records") or not isinstance(
        summary.get("ds_root_entropy"), (int, float)
    ):
        print(
            "devstats-smoke: cli perf --json lacks ds_* fields: "
            f"ds_records={summary.get('ds_records')} "
            f"ds_root_entropy={summary.get('ds_root_entropy')}",
            file=sys.stderr,
        )
        return 2
    print(
        f"devstats-smoke: {len(ds_records)} device_stats record(s); "
        f"perf summary entropy {summary['ds_root_entropy']} nats, "
        f"occupancy {summary.get('ds_tree_occupancy')}, "
        f"dispatches/iteration {dpi[-1]:.1f}"
    )
    return 0


def _make_runner(run_name: str):
    """A bare MegastepRunner over the tiny world (no training loop, no
    telemetry) — the unit the overhead gate times."""
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl import MegastepRunner, SelfPlayEngine, Trainer
    from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer

    env_cfg, model_cfg, mcts_cfg, train_cfg, _tele = tiny_configs(run_name)
    env = TriangleEnv(env_cfg)
    extractor = get_feature_extractor(env, model_cfg)
    net = NeuralNetwork(model_cfg, env_cfg, seed=0)
    engine = SelfPlayEngine(env, extractor, net, mcts_cfg, train_cfg, seed=0)
    trainer = Trainer(net, train_cfg)
    buf = DeviceReplayBuffer(
        train_cfg,
        grid_shape=(
            model_cfg.GRID_INPUT_CHANNELS,
            env_cfg.ROWS,
            env_cfg.COLS,
        ),
        other_dim=extractor.other_dim,
        action_dim=env_cfg.action_dim,
    )
    return MegastepRunner(engine, trainer, buf, train_cfg)


def _time_megasteps(runner, warmup: int, timed: int) -> list:
    """Per-iteration wall times, warmup (compile + cache fill) excluded.
    The dispatch counter must advance exactly once per megastep."""
    before = runner.dispatch_count
    for _ in range(warmup):
        runner.run_megastep(2, 2)
    times = []
    for _ in range(timed):
        t0 = time.perf_counter()
        runner.run_megastep(2, 2)
        times.append(time.perf_counter() - t0)
    dispatched = runner.dispatch_count - before
    assert dispatched == warmup + timed, (
        f"{dispatched} dispatches for {warmup + timed} megasteps — the "
        "one-dispatch contract broke"
    )
    return times


def stage_overhead(root: Path) -> int:
    """Stat-packs OFF vs ON on the same megastep shape: <3% added wall,
    one dispatch per iteration in both modes."""
    from alphatriangle_tpu.telemetry.device_stats import (
        reset_device_stats_state,
        set_device_stats,
    )

    warmup, timed = 3, 12
    try:
        reset_device_stats_state()
        set_device_stats(False)
        runner_off = _make_runner("devstats_off")
        off = _time_megasteps(runner_off, warmup, timed)
        if runner_off.last_device_stats is not None:
            print(
                "devstats-smoke: stats-off runner produced "
                "last_device_stats — the gate is not gating",
                file=sys.stderr,
            )
            return 2

        reset_device_stats_state()
        set_device_stats(True)
        runner_on = _make_runner("devstats_on")
        on = _time_megasteps(runner_on, warmup, timed)
        if not (runner_on.last_device_stats or {}).get("search"):
            print(
                "devstats-smoke: stats-on runner has no search leg in "
                f"last_device_stats: {runner_on.last_device_stats}",
                file=sys.stderr,
            )
            return 2
    finally:
        reset_device_stats_state()

    med_off = statistics.median(off)
    med_on = statistics.median(on)
    overhead = (med_on - med_off) / med_off if med_off > 0 else 0.0
    print(
        f"devstats-smoke: megastep median {med_off * 1e3:.2f}ms off / "
        f"{med_on * 1e3:.2f}ms on -> {overhead:+.1%} stat-pack overhead "
        f"(budget {OVERHEAD_BUDGET:.0%}); one dispatch per iteration in "
        "both modes"
    )
    if overhead > OVERHEAD_BUDGET:
        print(
            f"devstats-smoke: stat-pack overhead {overhead:.1%} exceeds "
            f"the {OVERHEAD_BUDGET:.0%} budget — the pack left the "
            "device program",
            file=sys.stderr,
        )
        return 2
    return 0


def wedge_child(args) -> int:
    """Stage-3 child: tiny megastep run with a fast watchdog; the armed
    hang-dispatch fault wedges it mid-run and the watchdog exits 113."""
    from alphatriangle_tpu.config import PersistenceConfig
    from alphatriangle_tpu.training import run_training

    env_cfg, model_cfg, mcts_cfg, train_cfg, tele_cfg = tiny_configs(
        args.run_name
    )
    pc = PersistenceConfig(ROOT_DATA_DIR=args.root_dir, RUN_NAME=args.run_name)
    return run_training(
        train_config=train_cfg,
        env_config=env_cfg,
        model_config=model_cfg,
        mcts_config=mcts_cfg,
        persistence_config=pc,
        telemetry_config=tele_cfg,
        use_tensorboard=False,
        log_level="WARNING",
    )


def stage_wedge_beacon(root: Path) -> int:
    """Beacons armed by env + injected dispatch hang -> watchdog 113 ->
    wedge report + doctor verdict naming the beacon phase."""
    from alphatriangle_tpu.config import PersistenceConfig

    run = "devstats_wedge"
    run_dir = PersistenceConfig(
        ROOT_DATA_DIR=str(root), RUN_NAME=run
    ).get_run_base_dir()
    child_env = {
        **os.environ,
        "PYTHONPATH": str(REPO),
        "JAX_PLATFORMS": "cpu",
        # Arm beacons the way an operator (or `cli supervise`'s
        # TELEMETRY__BEACONS respawn override) would: by env, every
        # wave, so the beacon trail is dense around the wedge.
        "ALPHATRIANGLE_BEACONS": "1",
        "ALPHATRIANGLE_BEACON_EVERY": "1",
        # Wedge mid-run: past the first compiles, with beacon rows from
        # completed dispatches already durable on disk.
        "ALPHATRIANGLE_FAULTS": "hang-dispatch@after=6",
        "ALPHATRIANGLE_FAULT_STATE_DIR": str(root / "faults_wedge"),
    }
    proc = subprocess.run(
        [
            sys.executable,
            str(Path(__file__).resolve()),
            "--wedge-child",
            "--root-dir",
            str(root),
            "--run-name",
            run,
        ],
        cwd=str(REPO),
        env=child_env,
        capture_output=True,
        text=True,
        timeout=600,
    )
    if proc.returncode != 113:
        print(
            f"devstats-smoke: wedge child exited {proc.returncode}, "
            f"expected the watchdog's 113\nstdout: {proc.stdout[-2000:]}\n"
            f"stderr: {proc.stderr[-2000:]}",
            file=sys.stderr,
        )
        return 2

    beacons = read_records(run_dir / "beacons.jsonl")
    if not beacons or not all(
        b.get("phase") and isinstance(b.get("index"), int) for b in beacons
    ):
        print(
            f"devstats-smoke: {run_dir}/beacons.jsonl holds "
            f"{len(beacons)} well-formed beacon row(s) — the armed "
            "beacon channel wrote nothing durable",
            file=sys.stderr,
        )
        return 2

    wedge_path = run_dir / "wedge_report.json"
    try:
        wedge = json.loads(wedge_path.read_text())
    except (OSError, json.JSONDecodeError) as exc:
        print(
            f"devstats-smoke: unreadable {wedge_path}: {exc}",
            file=sys.stderr,
        )
        return 2
    frozen = wedge.get("last_beacon")
    if not isinstance(frozen, dict) or not frozen.get("phase"):
        print(
            f"devstats-smoke: wedge report froze no beacon: {frozen}",
            file=sys.stderr,
        )
        return 2

    rc, verdict = run_doctor(run_dir)
    if verdict is None:
        return 2
    if verdict.get("verdict") not in ("dispatch-hung", "compile-hung"):
        print(
            f"devstats-smoke: doctor verdict {verdict.get('verdict')!r}, "
            "expected a hung classification",
            file=sys.stderr,
        )
        return 2
    doc_beacon = verdict.get("last_beacon")
    if (
        not isinstance(doc_beacon, dict)
        or doc_beacon.get("phase") != frozen["phase"]
        or "last beacon" not in str(verdict.get("detail"))
    ):
        print(
            "devstats-smoke: doctor verdict does not carry the frozen "
            f"beacon: verdict {verdict}",
            file=sys.stderr,
        )
        return 2
    print(
        f"devstats-smoke: wedge died by watchdog 113; {len(beacons)} "
        f"beacon row(s); doctor {verdict['verdict']} at phase "
        f"{doc_beacon['phase']}#{doc_beacon.get('index')} "
        f"(program {verdict.get('program')})"
    )
    return 0


def main() -> int:
    import argparse

    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--root-dir", default=None)
    parser.add_argument("--run-name", default="devstats_wedge")
    parser.add_argument(
        "--wedge-child",
        action="store_true",
        help=argparse.SUPPRESS,  # internal: the stage-3 training child
    )
    args = parser.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_enable_async_dispatch", False)

    if args.wedge_child:
        return wedge_child(args)

    root = Path(args.root_dir or tempfile.mkdtemp(prefix="at_devstats_"))
    stages = [
        ("stat-pack ledger", stage_statpack_ledger),
        ("overhead", stage_overhead),
        ("wedge beacon", stage_wedge_beacon),
    ]
    try:
        for name, stage in stages:
            print(f"devstats-smoke: {name} gate...", flush=True)
            rc = stage(root)
            if rc != 0:
                return rc
    finally:
        if args.root_dir is None:
            shutil.rmtree(root, ignore_errors=True)
    print("devstats-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
