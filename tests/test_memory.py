"""Memory observability (telemetry/memory.py; docs/OBSERVABILITY.md
"Memory"): estimator math, AOT memory_analysis capture, the leak
detector, live accounting, and the `cli fit`/`cli mem` surfaces —
including the acceptance bar that the static pre-flight estimate lands
within 2x of a real smoke run's observed peak."""

import json
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.compile_cache import reset_compile_cache
from alphatriangle_tpu.config import PersistenceConfig, TrainConfig
from alphatriangle_tpu.telemetry.anomaly import AnomalyDetector
from alphatriangle_tpu.telemetry.memory import (
    attribution_rows,
    compose_budget,
    estimate_fit,
    fit_verdict,
    fmt_bytes,
    program_memory_record,
    replay_ring_bytes,
    replay_ring_record,
    summarize_device_memory,
    train_state_record,
    tree_bytes,
)
from alphatriangle_tpu.telemetry.perf import (
    LOWER_IS_BETTER,
    UtilizationMeter,
    compare_summaries,
    summarize_utilization,
)


class TestRingBytes:
    def test_matches_allocated_device_ring(
        self, tiny_train_config, tiny_env_config, tiny_model_config
    ):
        """The pure byte math must equal the bytes the single-device
        ring actually allocates (dtype/shape drift here would skew
        every fit estimate)."""
        from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer

        grid_shape = (
            tiny_model_config.GRID_INPUT_CHANNELS,
            tiny_env_config.ROWS,
            tiny_env_config.COLS,
        )
        buf = DeviceReplayBuffer(
            tiny_train_config,
            grid_shape=grid_shape,
            other_dim=7,
            action_dim=tiny_env_config.action_dim,
        )
        est = replay_ring_bytes(
            tiny_train_config.BUFFER_CAPACITY,
            grid_shape,
            7,
            tiny_env_config.action_dim,
        )
        assert buf.storage_nbytes() == est
        rec = buf.memory_record()
        assert rec["kind"] == "memory"
        assert rec["category"] == "ring"
        assert rec["total"] == est
        assert rec["location"] == "device"
        assert rec["shards"] == 1

    def test_sharded_ring_counts_per_shard_trash_rows(self):
        # 4 shards => 4 trash rows; the single-shard math has 1.
        one = replay_ring_bytes(1024, (1, 3, 4), 8, 12, shards=1)
        four = replay_ring_bytes(1024, (1, 3, 4), 8, 12, shards=4)
        row = 1 * 3 * 4 + 4 * 8 + 4 * 12 + 4 + 4
        assert four - one == 3 * row


class TestTreeAccounting:
    def test_tree_bytes_exact(self):
        tree = {
            "a": jnp.zeros((4, 5), jnp.float32),
            "b": jnp.zeros(7, jnp.int8),
            "c": None,
        }
        assert tree_bytes(tree) == 4 * 5 * 4 + 7

    def test_train_state_record_splits_params_and_opt(
        self, tiny_env_config, tiny_model_config, tiny_train_config
    ):
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl import Trainer

        net = NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)
        trainer = Trainer(net, tiny_train_config)
        rec = train_state_record(trainer.state)
        assert rec["category"] == "state"
        assert rec["bytes"]["params"] == tree_bytes(trainer.state.params)
        assert rec["bytes"]["opt_state"] > 0  # adam moments exist
        # total covers params + opt + batch_stats + step/rng leaves
        assert rec["total"] >= sum(
            v for v in rec["bytes"].values() if isinstance(v, int)
        )


class TestProgramCapture:
    def test_capture_on_compile_and_sidecar_on_hit(self, tmp_path):
        """A wrapped program's memory_analysis is recorded at compile
        time, persisted beside the executable, and reloaded from the
        sidecar on a cross-process AOT hit."""
        cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
        try:
            fn = cache.wrap("memtest", jax.jit(lambda x: x @ x + 1.0))
            x = jnp.ones((16, 16), jnp.float32)
            np.testing.assert_allclose(fn(x), np.ones((16, 16)) * 17.0)
            recs = cache.memory_summary()
            assert len(recs) == 1
            rec = recs[0]
            assert rec["program"] == "memtest"
            assert rec["bytes"]["argument"] == 16 * 16 * 4
            assert rec["bytes"]["output"] == 16 * 16 * 4
            assert rec["origin"] == "compile"
            sidecars = list((tmp_path / "aot").glob("*.mem.json"))
            assert len(sidecars) == 1
            assert json.loads(sidecars[0].read_text())["program"] == "memtest"

            # Fresh cache object, same dir: the AOT hit re-attributes
            # from the persisted sidecar without re-analyzing.
            cache2 = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            fn2 = cache2.wrap("memtest", jax.jit(lambda x: x @ x + 1.0))
            fn2(x)
            assert cache2.hits == 1
            [rec2] = cache2.memory_summary()
            assert rec2["origin"] == "sidecar"
            assert rec2["bytes"] == rec["bytes"]
        finally:
            reset_compile_cache()

    def test_analyze_works_on_cpu_bypassed_program(self, tmp_path):
        """cpu_aot=False programs (the learner family on XLA:CPU) never
        touch the AOT artifact path, but `analyze` still produces a
        memory record — compiling fresh for analysis only, executing
        nothing, serializing nothing."""
        calls = []

        def impl(x):
            calls.append(1)
            return x * 2.0

        cache = reset_compile_cache(cache_dir=str(tmp_path / "aot"))
        try:
            fn = cache.wrap("bypassed", jax.jit(impl), cpu_aot=False)
            assert not fn.aot_active
            rec = fn.analyze(jnp.ones(8, jnp.float32))
            assert rec is not None
            assert rec["bytes"]["argument"] == 32
            # Tracing happened (impl ran under trace) but nothing was
            # dispatched and no artifact/sidecar was written.
            assert list((tmp_path / "aot").glob("*.jaxexe")) == []
            assert list((tmp_path / "aot").glob("*.mem.json")) == []
            # A second analyze is a registry hit, no recompile/retrace.
            calls.clear()
            assert fn.analyze(jnp.ones(8, jnp.float32)) == rec
            assert calls == []
        finally:
            reset_compile_cache()


class TestComposeBudget:
    def _records(self):
        return [
            {
                "kind": "memory",
                "category": "state",
                "component": "train_state",
                "bytes": {"params": 100, "opt_state": 200, "batch_stats": 0},
                "total": 308,
            },
            replay_ring_record(5000, 128, location="device"),
            {
                "kind": "memory",
                "category": "program",
                "component": "program/self_play_chunk/t4",
                "program": "self_play_chunk/t4",
                "bytes": {"argument": 700, "output": 50, "temp": 40,
                          "generated_code": 0, "alias": 10},
                "total": 790,
                "transient": 80,
            },
            {
                "kind": "memory",
                "category": "program",
                "component": "program/learner_step",
                "program": "learner_step",
                "bytes": {"argument": 400, "output": 320, "temp": 90,
                          "generated_code": 0, "alias": 300},
                "total": 810,
                "transient": 110,
            },
        ]

    def test_composition(self):
        budget = compose_budget(self._records())
        assert budget["train_state_bytes"] == 308
        assert budget["replay_ring_bytes"] == 5000
        # chunk argument (700) minus shared params (100)
        assert budget["rollout_resident_bytes"] == 600
        # worst transient: learner 110 vs chunk 80
        assert budget["program_transient_bytes"] == 110
        assert budget["total_bytes"] == 308 + 5000 + 600 + 110
        assert budget["programs"] == 2

    def test_host_ring_excluded(self):
        recs = self._records()
        recs[1] = replay_ring_record(5000, 128, location="host")
        assert compose_budget(recs)["replay_ring_bytes"] == 0

    def test_latest_record_wins_and_rows_sorted(self):
        recs = self._records()
        recs.append(dict(recs[0], total=999, bytes={"params": 999}))
        rows = attribution_rows(recs)
        by_name = {r[0]: r[1] for r in rows}
        assert by_name["train_state"] == 999
        assert [r[1] for r in rows] == sorted(
            (r[1] for r in rows), reverse=True
        )

    def test_fit_verdict_codes(self):
        assert fit_verdict(100, 1000)[0] == 0
        assert fit_verdict(2000, 1000)[0] == 1
        assert fit_verdict(100, None)[0] == 2
        assert fit_verdict(100, 0)[0] == 2

    def test_fmt_bytes(self):
        assert fmt_bytes(None) == "—"
        assert fmt_bytes(512) == "512 B"
        assert fmt_bytes(3 * 2**30) == "3.00 GiB"


class TestLeakDetector:
    def test_monotonic_growth_fires_once_and_rearms(self):
        det = AnomalyDetector(
            memory_growth_ticks=4, memory_growth_fraction=0.05
        )
        fired = []
        value = 1000.0
        for step in range(10):
            value *= 1.03  # strictly growing, ~3%/tick
            fired += det.observe_memory(value, step)
        assert len(fired) == 1
        assert fired[0].kind == "memory_growth"
        assert fired[0].metric == "Memory/bytes_in_use"
        assert "leak" in fired[0].describe()
        # Latched: continued growth in the same excursion stays quiet.
        assert det.observe_memory(value * 1.5, 10) == []
        # A release re-arms and restarts the monotonic run.
        assert det.observe_memory(value * 0.5, 11) == []
        v = value * 0.5
        fired2 = []
        for step in range(12, 20):
            v *= 1.05
            fired2 += det.observe_memory(v, step)
        assert len(fired2) == 1

    def test_sawtooth_and_flat_stay_quiet(self):
        det = AnomalyDetector(
            memory_growth_ticks=4, memory_growth_fraction=0.05
        )
        out = []
        for step in range(40):
            # healthy allocator: climbs 3 ticks, releases
            v = 1000 + 100 * (step % 4)
            out += det.observe_memory(v, step)
        assert out == []
        det2 = AnomalyDetector(memory_growth_ticks=4)
        assert all(
            det2.observe_memory(500.0, s) == [] for s in range(20)
        )

    def test_tiny_monotonic_drift_below_fraction_stays_quiet(self):
        det = AnomalyDetector(
            memory_growth_ticks=4, memory_growth_fraction=0.5
        )
        v = 1000.0
        out = []
        for step in range(20):
            v += 1  # monotonic but far below the 50% growth floor
            out += det.observe_memory(v, step)
        assert out == []


class TestLiveAccounting:
    def test_meter_memory_fields_and_high_water(self):
        t = {"now": 0.0}
        meter = UtilizationMeter(device_kind="cpu", clock=lambda: t["now"])

        def dev(in_use, peak=None, limit=1000):
            return [
                {
                    "device": 0,
                    "kind": "cpu",
                    "bytes_in_use": in_use,
                    "peak_bytes_in_use": peak,
                    "bytes_limit": limit,
                }
            ]

        meter.tick(step=0, device_memory=dev(500))
        t["now"] = 1.0
        rec = meter.tick(step=1, device_memory=dev(400))
        # High water remembers the baseline tick's 500 even though the
        # current in-use dropped to 400.
        assert rec["mem_bytes_in_use"] == 400
        assert rec["mem_peak_bytes_in_use"] == 500
        assert rec["mem_bytes_limit"] == 1000
        assert rec["mem_utilization"] == pytest.approx(0.4)
        assert rec["mem_devices"][0]["bytes_in_use"] == 400
        # A backend-reported peak above the high water wins.
        t["now"] = 2.0
        rec = meter.tick(step=2, device_memory=dev(450, peak=900))
        assert rec["mem_peak_bytes_in_use"] == 900

    def test_meter_without_memory_keeps_schema(self):
        t = {"now": 0.0}
        meter = UtilizationMeter(device_kind="cpu", clock=lambda: t["now"])
        meter.tick(step=0)
        t["now"] = 1.0
        rec = meter.tick(step=1)
        assert "mem_bytes_in_use" not in rec

    def test_summarize_device_memory_totals(self):
        rows = [
            {"bytes_in_use": 10, "peak_bytes_in_use": 20, "bytes_limit": 100},
            {"bytes_in_use": 5, "peak_bytes_in_use": None, "bytes_limit": None},
        ]
        totals = summarize_device_memory(rows)
        assert totals == {
            "bytes_in_use": 15,
            "peak_bytes_in_use": 25,  # missing peak falls back to in-use
            "bytes_limit": 100,
        }
        assert summarize_device_memory([]) is None

    def test_cpu_device_memory_synthesized_from_live_arrays(self):
        from alphatriangle_tpu.telemetry.health import device_memory_stats

        anchor = jnp.ones((128, 128), jnp.float32)  # keep alive
        stats = device_memory_stats()
        assert stats, "CPU fallback should synthesize per-device rows"
        row = stats[0]
        assert row.get("source") == "live_arrays"
        assert row["bytes_in_use"] >= anchor.nbytes
        assert row["bytes_limit"] and row["bytes_limit"] > 0
        del anchor

    def test_compare_memory_metrics_lower_is_better(self):
        a = {"mem_peak_bytes_in_use": 2000, "memory_budget_bytes": 100}
        b = {"mem_peak_bytes_in_use": 1000, "memory_budget_bytes": 100}
        rows, regressions = compare_summaries(a, b, threshold=0.1)
        verdicts = {m: status for m, _, _, _, status in rows}
        assert verdicts["mem_peak_bytes_in_use"] == "regression"
        assert "mem_peak_bytes_in_use" in regressions
        assert verdicts["memory_budget_bytes"] == "ok"
        # Shrinking memory is an improvement, not a regression.
        rows, regressions = compare_summaries(b, a, threshold=0.1)
        assert {m: s for m, _, _, _, s in rows}[
            "mem_peak_bytes_in_use"
        ] == "improved"
        assert regressions == []
        assert LOWER_IS_BETTER <= {m for m, *_ in rows}


class TestRenderers:
    def test_watch_memory_line(self):
        from alphatriangle_tpu.stats.watch import WatchState, memory_line, render_frame

        util = {
            "mem_bytes_in_use": 2 * 2**30,
            "mem_peak_bytes_in_use": 3 * 2**30,
            "mem_bytes_limit": 16 * 2**30,
            "mem_utilization": 0.125,
        }
        line = memory_line(util)
        assert "2.00 GiB in use" in line
        assert "peak 3.00 GiB" in line
        assert "limit 16.00 GiB (12.5%)" in line
        assert memory_line({"mfu": 0.5}) is None
        state = WatchState()
        state.util = dict(util, kind="util")
        assert "memory" in render_frame(state, "r")

    def test_cli_health_prints_peak(self, tmp_path, capsys):
        from alphatriangle_tpu.cli import main as cli_main

        run_dir = tmp_path / "AlphaTriangleTPU" / "runs" / "hrun"
        run_dir.mkdir(parents=True)
        import time as _time

        (run_dir / "health.json").write_text(
            json.dumps(
                {
                    "run": "hrun",
                    "time": _time.time(),
                    "watchdog_deadline_s": 300,
                    "learner_step": 3,
                    "device_memory": [
                        {
                            "device": 0,
                            "kind": "TPU v4",
                            "bytes_in_use": 2**30,
                            "peak_bytes_in_use": 2 * 2**30,
                            "bytes_limit": 4 * 2**30,
                        }
                    ],
                }
            )
        )
        rc = cli_main(["health", "hrun", "--root-dir", str(tmp_path)])
        out = capsys.readouterr().out
        assert rc == 0
        assert "peak 2.00 GiB" in out
        assert "/ 4.00 GiB (25%)" in out


class TestFitCLI:
    def test_cli_fit_tiny_plan_fits_on_cpu(
        self,
        tmp_path,
        monkeypatch,
        capsys,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
        tiny_train_config,
    ):
        from alphatriangle_tpu import cli
        from alphatriangle_tpu.config import MeshConfig
        from alphatriangle_tpu.rl.megastep import MegastepRunner

        # A FUSED_MEGASTEP bundle: `cli fit` then also analyzes the
        # megastep program. Stubbed here (its real compile/record path
        # is pinned in tests/test_megastep.py) so this test stays
        # inside the tier-1 compile budget while still proving the
        # wiring reaches it.
        monkeypatch.setattr(
            MegastepRunner,
            "analyze_megastep",
            lambda self, t=None, k=None: {
                "kind": "memory",
                "category": "program",
                "component": "program/megastep/t4_k2",
                "program": "megastep/t4_k2",
                "bytes": {"argument": 64, "output": 8, "temp": 8,
                          "generated_code": 0},
                "total": 80,
                "transient": 16,
            },
        )
        bundle = {
            "env": tiny_env_config,
            "model": tiny_model_config,
            "mcts": tiny_mcts_config,
            "train": tiny_train_config.model_copy(
                update={"FUSED_MEGASTEP": True, "FUSED_LEARNER_STEPS": 2}
            ),
            "mesh": MeshConfig(DP_SIZE=1),
            "description": "tiny",
        }
        monkeypatch.setattr(cli, "resolve_preset", lambda target: bundle)
        try:
            reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            rc = cli.main(["fit", "1", "--json"])
            report = json.loads(capsys.readouterr().out.strip())
            # A tiny world against host RAM must fit.
            assert rc == 0
            assert report["exit"] == 0
            assert report["budget"]["total_bytes"] > 0
            assert report["budget"]["programs"] >= 3
            assert "megastep/t4_k2" in {
                r.get("program") for r in report["records"]
            }
            # The run's own ring (100 rows here), on the device: the
            # megastep's home.
            [ring] = [r for r in report["records"] if r["category"] == "ring"]
            assert ring["capacity"] == 100 and ring["location"] == "device"
            assert report["bytes_limit"] > report["budget"]["total_bytes"]
            categories = {r["category"] for r in report["records"]}
            assert categories == {"state", "ring", "program"}

            # An asserted tiny limit flips the verdict to over-budget.
            reset_compile_cache(cache_dir=str(tmp_path / "aot"))
            rc = cli.main(["fit", "1", "--limit-gb", "0.0000001"])
            assert rc == 1
        finally:
            reset_compile_cache()


@pytest.fixture(scope="module")
def memory_smoke_run(
    tmp_path_factory, tiny_env_config, tiny_model_config, tiny_mcts_config
):
    """One tiny end-to-end training run whose ledger carries the full
    memory-observability record set (module-scoped: several tests read
    it)."""
    from alphatriangle_tpu.training import (
        LoopStatus,
        TrainingLoop,
        setup_training_components,
    )

    root = tmp_path_factory.mktemp("memory_run")
    train_cfg = TrainConfig(
        RUN_NAME="mem_smoke",
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
        # No save inside the run, only the final one (which the loop
        # waits out before its last tick): while an async save is in
        # flight orbax 0.11.32 holds a single-device copy of every
        # state leaf per device, and on the CPU backend — where
        # bytes-in-use is synthesized from jax.live_arrays() — those
        # staging copies read as 5x the run's real resident set.
        CHECKPOINT_SAVE_FREQ_STEPS=1000,
        MAX_EPISODE_MOVES=30,
        RANDOM_SEED=5,
    )
    # The run's live-memory accounting synthesizes bytes-in-use from
    # jax.live_arrays(): collect cycle-held garbage from earlier test
    # modules first, or their dead engines/rings inflate the observed
    # peak this fixture's 2x acceptance band is measured against.
    import gc

    gc.collect()
    pc = PersistenceConfig(ROOT_DATA_DIR=str(root), RUN_NAME="mem_smoke")
    c = setup_training_components(
        train_config=train_cfg,
        env_config=tiny_env_config,
        model_config=tiny_model_config,
        mcts_config=tiny_mcts_config,
        persistence_config=pc,
        use_tensorboard=False,
    )
    loop = TrainingLoop(c)
    status = loop.run()
    c.stats.close()
    c.checkpoints.close()
    assert status == LoopStatus.COMPLETED
    run_dir = pc.get_run_base_dir()
    records = [
        json.loads(line)
        for line in (run_dir / "metrics.jsonl").read_text().splitlines()
    ]
    return {
        "run_dir": run_dir,
        "records": records,
        "train_cfg": train_cfg,
        "root": root,
    }


class TestSmokeRunLedger:
    def test_ledger_carries_attribution_and_live_memory(
        self, memory_smoke_run
    ):
        records = memory_smoke_run["records"]
        mems = [r for r in records if r["kind"] == "memory"]
        components = {m["component"] for m in mems}
        assert "train_state" in components
        assert "replay_ring" in components
        assert any(c.startswith("program/self_play_chunk") for c in components)
        utils = [r for r in records if r["kind"] == "util"]
        assert utils
        for u in utils:
            assert isinstance(u["mem_bytes_in_use"], int)
            assert u["mem_peak_bytes_in_use"] >= u["mem_bytes_in_use"]
            assert u["mem_devices"]
        # Ring is host-resident on the CPU backend (DEVICE_REPLAY auto)
        ring = next(m for m in mems if m["component"] == "replay_ring")
        assert ring["location"] == "host"
        # Heartbeat carries the trimmed memory fields too.
        health = json.loads(
            (memory_smoke_run["run_dir"] / "health.json").read_text()
        )
        assert health["utilization"]["mem_bytes_in_use"] > 0

    def test_fit_estimate_within_2x_of_observed_peak(
        self,
        memory_smoke_run,
        tiny_env_config,
        tiny_model_config,
        tiny_mcts_config,
    ):
        """Acceptance bar: the static `cli fit` estimate for the smoke
        preset lands within 2x of the run's observed peak_bytes_in_use."""
        utils = [
            r for r in memory_smoke_run["records"] if r["kind"] == "util"
        ]
        observed = max(r["mem_peak_bytes_in_use"] for r in utils)
        report = estimate_fit(
            tiny_env_config,
            tiny_model_config,
            tiny_mcts_config,
            memory_smoke_run["train_cfg"],
            fused_k=1,
            device_replay=False,
        )
        estimate = report["budget"]["total_bytes"]
        assert estimate > 0 and observed > 0
        ratio = estimate / observed
        assert 0.5 <= ratio <= 2.0, (
            f"static estimate {estimate} vs observed peak {observed} "
            f"(ratio {ratio:.2f}) left the 2x band"
        )

    def test_cli_mem_renders_attribution_table(
        self, memory_smoke_run, capsys
    ):
        from alphatriangle_tpu.cli import main as cli_main

        rc = cli_main(
            [
                "mem",
                "mem_smoke",
                "--root-dir",
                str(memory_smoke_run["root"]),
            ]
        )
        out = capsys.readouterr().out
        assert rc == 0
        assert "train_state" in out
        assert "replay_ring" in out
        assert "program/self_play_chunk" in out
        assert "static budget" in out
        assert "observed:" in out

    def test_cli_mem_json(self, memory_smoke_run, capsys):
        from alphatriangle_tpu.cli import main as cli_main

        rc = cli_main(
            [
                "mem",
                str(memory_smoke_run["run_dir"] / "metrics.jsonl"),
                "--json",
            ]
        )
        payload = json.loads(capsys.readouterr().out)
        assert rc == 0
        assert payload["budget"]["total_bytes"] > 0
        assert payload["observed"]["mem_bytes_in_use"] > 0

    def test_cli_mem_never_imports_jax(self, memory_smoke_run):
        """`cli mem` must attribute from artifacts alone: run it in a
        subprocess whose import machinery refuses jax outright."""
        ledger = memory_smoke_run["run_dir"] / "metrics.jsonl"
        code = (
            "import builtins, sys\n"
            "real = builtins.__import__\n"
            "def guard(name, *a, **k):\n"
            "    if name == 'jax' or name.startswith('jax.'):\n"
            "        raise AssertionError('cli mem imported ' + name)\n"
            "    return real(name, *a, **k)\n"
            "builtins.__import__ = guard\n"
            "from alphatriangle_tpu.cli import main\n"
            f"sys.exit(main(['mem', {str(ledger)!r}]))\n"
        )
        proc = subprocess.run(
            [sys.executable, "-c", code],
            capture_output=True,
            text=True,
            cwd=str(Path(__file__).resolve().parent.parent),
            timeout=120,
        )
        assert proc.returncode == 0, proc.stderr
        assert "train_state" in proc.stdout

    def test_cli_mem_missing_run_exits_2(self, tmp_path, capsys):
        from alphatriangle_tpu.cli import main as cli_main

        rc = cli_main(
            ["mem", "no_such_run", "--root-dir", str(tmp_path)]
        )
        assert rc == 2

    def test_cli_mem_ledger_without_memory_records_exits_2(
        self, tmp_path, capsys
    ):
        from alphatriangle_tpu.cli import main as cli_main

        ledger = tmp_path / "metrics.jsonl"
        ledger.write_text(
            json.dumps({"kind": "tick", "step": 1, "means": {}}) + "\n"
        )
        assert cli_main(["mem", str(ledger)]) == 2

    def test_perf_summary_and_compare_pick_up_memory(
        self, memory_smoke_run
    ):
        from alphatriangle_tpu.telemetry.perf import load_comparable

        utils = [
            r for r in memory_smoke_run["records"] if r["kind"] == "util"
        ]
        summary = summarize_utilization(utils)
        assert summary["mem_peak_bytes_in_use"] == max(
            r["mem_peak_bytes_in_use"] for r in utils
        )
        loaded, _ = load_comparable(
            str(memory_smoke_run["run_dir"]), None
        )
        assert loaded["memory_budget_bytes"] > 0
        rows, regressions = compare_summaries(loaded, loaded)
        verdicts = {m: s for m, _, _, _, s in rows}
        assert verdicts["mem_peak_bytes_in_use"] == "ok"
        assert verdicts["memory_budget_bytes"] == "ok"
        assert regressions == []
