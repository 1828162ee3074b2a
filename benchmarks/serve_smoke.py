"""CI serve-smoke gate: `cli serve --smoke` -> `cli perf`.

`make serve-smoke` runs this. It proves, on any machine with no
accelerator, that the policy-serving front end (docs/SERVING.md) works
end to end:

1. a run dir with the test-sized world's configs.json is staged —
   with int8 weight-only inference ON (`INFERENCE_PRECISION="int8"`,
   nn/precision.py) — and `cli serve --smoke` storms the serve-shape
   ladder (`--buckets 16,32,64`, serving/buckets.py): the burst of
   96 sessions against a 16-slot base rung drives the micro-batcher
   up >= 1 rung (to 64 concurrent at the top) and the drain walks it
   back down; sessions admit AND retire mid-run, AOT warm start (every
   rung) and the OOM pre-flight (every rung) on the way up. Gates:
   every rung switch is zero-recompile (the compile-cache event count
   stays at exactly one entry per rung — the warm), and zero requests
   are lost (every session serves to completion);
2. the serve run's `metrics.jsonl` must carry `kind: "util"` records
   with per-request latency SLO fields (`serve_move_latency_ms_p50/
   p95`, `serve_queue_wait_ms_*`, `serve_requests_per_sec`) plus the
   ladder gauges (`serve_bucket`, `serve_fill`), and the folded
   buckets must show the walk (max above the base rung, final below
   the max);
3. `cli perf <serve_run> --json` must summarize them, serve_bucket /
   serve_fill included (exit 2 = the ledger schema broke).

Exit 0 when every stage passes; the first failing stage's code
otherwise.
"""

import argparse
import contextlib
import io
import json
import os
import shutil
import sys
import tempfile
from pathlib import Path

REPO = Path(__file__).resolve().parent.parent
RUN_NAME = "serve_smoke"

if str(REPO) not in sys.path:
    sys.path.insert(0, str(REPO))

# Must precede any jax import: the smoke must not wake (or wedge on) an
# accelerator.
os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("ALPHATRIANGLE_PEAK_TFLOPS", "1.0")

BASE_RUNG = 16  # starting serve shape — the burst must outgrow it
BUCKETS = "16,32,64"  # the ladder the storm walks (serving/buckets.py)
SLOTS = 64  # top rung: >= 64 concurrent sessions (the acceptance bar)
SESSIONS = 96  # > SLOTS forces admit/retire churn mid-run


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument(
        "--root-dir",
        default=None,
        help="Runs root for the smoke (default: a temp dir).",
    )
    args = parser.parse_args()

    import jax

    jax.config.update("jax_platforms", "cpu")
    jax.config.update("jax_cpu_enable_async_dispatch", False)

    from alphatriangle_tpu.cli import main as cli_main
    from alphatriangle_tpu.config import PersistenceConfig

    # The training perf smoke's tiny world — one definition, reused.
    from perf_smoke import tiny_configs  # noqa: E402

    root = args.root_dir or tempfile.mkdtemp(prefix="at_serve_smoke_")
    env_cfg, model_cfg, _mcts_cfg, _train_cfg = tiny_configs()
    # int8 weight-only inference ON (nn/precision.py): the smoke
    # proves the quantized serve path end to end on CPU — per-channel
    # int8 weights + f32 scales dispatch through every ladder rung.
    model_cfg = model_cfg.model_copy(
        update={"INFERENCE_PRECISION": "int8"}
    )

    # Stage a run dir whose configs.json pins the tiny world, so
    # `cli serve --run-name` serves it instead of the flagship net.
    src_pc = PersistenceConfig(ROOT_DATA_DIR=root, RUN_NAME=RUN_NAME)
    src_dir = src_pc.get_run_base_dir()
    src_dir.mkdir(parents=True, exist_ok=True)
    (src_dir / "configs.json").write_text(
        json.dumps(
            {"env": env_cfg.model_dump(), "model": model_cfg.model_dump()}
        )
    )

    print(
        f"serve-smoke: storming {SESSIONS} sessions over the "
        f"{{{BUCKETS}}} ladder (base rung {BASE_RUNG}, int8) "
        f"under {root}...",
        flush=True,
    )
    from alphatriangle_tpu.compile_cache import get_compile_cache

    events_before = len(get_compile_cache().stats()["events"])
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(
            [
                "serve",
                "--smoke",
                "--run-name", RUN_NAME,
                "--root-dir", root,
                "--slots", str(BASE_RUNG),
                "--buckets", BUCKETS,
                "--sessions", str(SESSIONS),
                "--sims", "4",
                "--max-moves", "40",
                "--tick-every", "4",
                "--seed", "0",
            ]
        )
    sys.stdout.write(buf.getvalue())
    if rc != 0:
        print(f"serve-smoke: cli serve failed (rc={rc})", file=sys.stderr)
        return rc
    report = json.loads(buf.getvalue().strip().splitlines()[-1])
    # Zero lost requests: every session of the burst served to
    # completion despite the mid-stream rung switches.
    if report["sessions_served"] < SESSIONS:
        print(
            f"serve-smoke: only {report['sessions_served']} of "
            f"{SESSIONS} sessions served",
            file=sys.stderr,
        )
        return 1
    # Churn proof: more sessions than slots can only complete by
    # retiring finished sessions and admitting replacements mid-run.
    if report["sessions_served"] <= SLOTS:
        print("serve-smoke: no churn exercised", file=sys.stderr)
        return 1
    # Ladder walk proof, part 1 (the service's own counter): the burst
    # must force at least one walk-up and the drain one walk-down.
    if report.get("rung_switches", 0) < 2:
        print(
            f"serve-smoke: only {report.get('rung_switches')} rung "
            "switch(es) — the storm never walked the ladder",
            file=sys.stderr,
        )
        return 1
    # Zero-recompile gate: after the up-front all-rung warm, rung
    # switches must never touch the compiler — the compile-cache event
    # log (one entry per compile/deserialize, never per dispatch) may
    # hold exactly one entry per serve rung for this run.
    serve_events = [
        e
        for e in get_compile_cache().stats()["events"][events_before:]
        if str(e.get("program", "")).startswith("serve/b")
    ]
    rungs = len(BUCKETS.split(","))
    if len(serve_events) != rungs:
        print(
            f"serve-smoke: {len(serve_events)} serve compile events for "
            f"{rungs} rungs — a rung switch recompiled: {serve_events}",
            file=sys.stderr,
        )
        return 1
    print(
        f"serve-smoke: {report['rung_switches']} rung switches, "
        f"{len(serve_events)} compiles for {rungs} rungs (zero "
        "recompiles after warm)"
    )

    serve_run = f"serve_{RUN_NAME}"
    serve_pc = PersistenceConfig(ROOT_DATA_DIR=root, RUN_NAME=serve_run)
    ledger = serve_pc.get_run_base_dir() / "metrics.jsonl"
    lat_records = []
    for line in ledger.read_text().splitlines():
        try:
            rec = json.loads(line)
        except json.JSONDecodeError:
            continue
        if rec.get("kind") == "util" and isinstance(
            rec.get("serve_move_latency_ms_p95"), (int, float)
        ):
            lat_records.append(rec)
    if not lat_records:
        print(
            f"serve-smoke: {ledger} holds no util record with serve "
            "latency fields — the SLO ledger broke",
            file=sys.stderr,
        )
        return 2
    # Ladder walk proof, part 2 (the ledger's view): every util record
    # carries the serve_bucket/serve_fill gauges, the folded buckets
    # climb above the base rung, and the final record sits below the
    # max (the drain walked back down).
    buckets_seen = [
        r.get("serve_bucket")
        for r in lat_records
        if isinstance(r.get("serve_bucket"), int)
    ]
    fills_seen = [
        r.get("serve_fill")
        for r in lat_records
        if isinstance(r.get("serve_fill"), (int, float))
    ]
    if not buckets_seen or not fills_seen:
        print(
            "serve-smoke: ledger util records lack serve_bucket/"
            "serve_fill gauges",
            file=sys.stderr,
        )
        return 2
    if max(buckets_seen) <= BASE_RUNG:
        print(
            f"serve-smoke: ledger never saw a rung above the base "
            f"({sorted(set(buckets_seen))})",
            file=sys.stderr,
        )
        return 1
    if buckets_seen[-1] >= max(buckets_seen):
        print(
            f"serve-smoke: final rung {buckets_seen[-1]} never walked "
            f"back down from the max {max(buckets_seen)}",
            file=sys.stderr,
        )
        return 1
    print(
        f"serve-smoke: {len(lat_records)} ledger record(s) with "
        f"per-request latency fields; rungs {sorted(set(buckets_seen))}, "
        f"final {buckets_seen[-1]}"
    )

    print("serve-smoke: cli perf --json (schema gate)...", flush=True)
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        rc = cli_main(["perf", serve_run, "--root-dir", root, "--json"])
    if rc != 0:
        print(f"serve-smoke: cli perf failed (rc={rc})", file=sys.stderr)
        return rc
    summary = json.loads(buf.getvalue())
    for key in (
        "serve_move_latency_ms_p50",
        "serve_move_latency_ms_p95",
        "serve_requests_per_sec",
        "serve_bucket",
        "serve_fill",
    ):
        if not isinstance(summary.get(key), (int, float)):
            print(
                f"serve-smoke: cli perf --json lacks {key}",
                file=sys.stderr,
            )
            return 2
    print(
        "serve-smoke: move latency p50 "
        f"{summary['serve_move_latency_ms_p50']:.1f}ms, p95 "
        f"{summary['serve_move_latency_ms_p95']:.1f}ms, "
        f"{summary['serve_requests_per_sec']:.0f} req/s"
    )

    if args.root_dir is None:
        shutil.rmtree(root, ignore_errors=True)
    print("serve-smoke: OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
