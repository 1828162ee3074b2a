"""Benchmark: batched self-play throughput on the available accelerator.

Prints exactly ONE JSON line on stdout:
  {"metric": ..., "value": ..., "unit": ..., "vs_baseline": ..., "extra": {...}}

(As each section completes it additionally streams a cumulative
snapshot line tagged `extra.partial`; the last line of stdout is the
complete result.)

Primary metric: **self-play games/hour**, measured directly (episodes
completed / wall-clock) with the flagship configuration - default 8x15
board, conv+residual+transformer net, 64-sim batched MCTS - on one
chip. `vs_baseline` divides by the BASELINE.json north star (10,000
games/hour on v4-8 with a 4-layer transformer net); the reference
itself publishes no numbers (BASELINE.md).

`extra` carries the secondary BASELINE metrics: MCTS leaf-evals/sec
(per chip) and learner steps/sec on a 256 batch.

One process, one device: the bench runs on whatever JAX finds. Where
JAX finds no accelerator it exits non-zero and prints no result, unless
the CPU was asked for by name (`JAX_PLATFORMS=cpu`, the BENCH_SMOKE
sanity path) - a CPU number is never printed under a device metric's
name by accident. A crash after at least one completed section re-emits
the newest snapshot with the error beside it; the exit code is non-zero
either way.

Env knobs:
  BENCH_SMOKE=1         shrink everything for a fast CPU sanity run
  BENCH_TUNED_PRESET=P  bench the shapes from a tuned_preset.json
                        emitted by `cli tune` (wins over every other
                        shape knob; docs/AUTOTUNE.md)
  BENCH_SECONDS=N       override the self-play measurement window
  BENCH_PROFILE=1       capture an XLA trace of the first ~3 measured
                        chunks (BENCH_PROFILE_DIR, default
                        benchmarks/bench_profile); read with cli analyze
  BENCH_TREE_REUSE=0    skip the subtree-reuse A/B section (the headline
                        sections always measure fresh-root either way)
  JAX_PLATFORMS=cpu     run on the CPU, by request
"""

import json
import os
import sys
import time


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def emit(payload: dict) -> None:
    print(json.dumps(payload), flush=True)


def run_bench(smoke: bool, seconds: float) -> dict:
    import jax
    import numpy as np

    from alphatriangle_tpu.bench_config import resolve_bench_plan
    from alphatriangle_tpu.compile_cache import get_compile_cache
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl import SelfPlayEngine, Trainer
    from alphatriangle_tpu.utils.helpers import (
        enable_persistent_compilation_cache,
    )

    backend = jax.default_backend()
    # Sections repeat the flagship programs; cache executables across
    # runs (the helper itself skips the CPU backend).
    enable_persistent_compilation_cache()
    # The AOT executable cache (compile_cache.py) covers the gap the
    # XLA persistent cache leaves: it works on CPU too, skips tracing/
    # lowering bookkeeping inside the window on a hit, and `cli warm`
    # fills it ahead of a run.
    compile_cache = get_compile_cache()
    device = jax.devices()[0]
    log(
        "bench: backend="
        f"{backend} device={getattr(device, 'device_kind', device)}"
    )

    # The plan is shared with `cli warm` so the warmer precompiles
    # exactly the shapes measured here (alphatriangle_tpu/bench_config.py).
    plan = resolve_bench_plan(smoke, backend)
    env_cfg, model_cfg = plan.env, plan.model
    mcts_cfg, train_cfg = plan.mcts, plan.train
    scale, sims = plan.scale, plan.sims
    sp_batch, chunk, lbatch = plan.sp_batch, plan.chunk, plan.lbatch
    if os.environ.get("BENCH_CONFIG"):
        log(f"bench: {scale}: {plan.description}")
    log(f"bench: scale={scale} sims={sims} batch={sp_batch} chunk={chunk}")

    env = TriangleEnv(env_cfg)
    extractor = get_feature_extractor(env, model_cfg)
    net = NeuralNetwork(model_cfg, env_cfg, seed=0)
    engine = SelfPlayEngine(env, extractor, net, mcts_cfg, train_cfg, seed=0)

    # --- self-play games/hour (primary) --------------------------------
    log("bench: compiling self-play chunk (first dispatch)...")
    t0 = time.time()
    engine.play_chunk()
    compile_s = time.time() - t0
    log(f"bench: first chunk (compile) {compile_s:.1f}s; measuring {seconds:.0f}s...")
    engine.harvest()  # reset counters after warmup

    # BENCH_PROFILE=1: capture a jax.profiler (XLA) trace of the first
    # few measured chunks — the ground truth for where self-play MFU
    # goes (tree ops vs network matmuls vs dispatch gaps). Kept out of
    # the headline sections; `cli analyze <dir>` reads the result.
    profile_dir = None
    if os.environ.get("BENCH_PROFILE") == "1":
        profile_dir = os.environ.get(
            "BENCH_PROFILE_DIR", "benchmarks/bench_profile"
        )
        jax.profiler.start_trace(profile_dir)

    def stop_profile() -> None:
        nonlocal profile_dir
        if profile_dir is not None:
            jax.profiler.stop_trace()
            log(f"bench: profiler trace written to {profile_dir}")
            profile_dir = None

    t0 = time.time()
    moves = 0
    try:
        while time.time() - t0 < seconds:
            engine.play_chunk()
            moves += chunk
            if moves >= 3 * chunk:
                # ~3 chunks of trace is plenty; tracing is not free, so
                # stop before it skews the rest of the window.
                stop_profile()
    finally:
        # Flush the trace even if a chunk raises: the partial capture
        # is exactly the diagnosis data we want.
        stop_profile()
    elapsed = time.time() - t0
    result = engine.harvest()
    episodes = result.num_episodes
    games_per_hour = episodes / elapsed * 3600.0
    # Engine-reported sims (exact under playout cap randomization too)
    # + visits inherited through subtree reuse (0 on the fresh-root
    # default plan) + one root eval per move.
    leaf_evals_per_sec = (
        result.total_simulations
        + result.total_reused_visits
        + moves * sp_batch
    ) / elapsed
    reused_fraction = result.total_reused_visits / max(
        1, result.total_simulations + result.total_reused_visits
    )
    moves_per_sec = moves * sp_batch / elapsed
    log(
        f"bench: {moves} lockstep moves x {sp_batch} games in {elapsed:.1f}s "
        f"-> {episodes} episodes, {games_per_hour:.0f} games/h, "
        f"{leaf_evals_per_sec:.0f} leaf-evals/s"
    )

    # Result assembled incrementally; after each completed section a
    # cumulative SNAPSHOT line tagged extra.partial is emitted, so a run
    # that dies mid-way still leaves the sections that finished on
    # stdout. The flagship games/h — the headline — therefore lands
    # ~BENCH_SECONDS after first compile no matter what the later
    # sections do.
    north_star = 10_000.0  # games/hour, BASELINE.json north star (v4-8)
    from alphatriangle_tpu.utils.flops import (
        forward_flops,
        mfu,
        peak_bf16_tflops_info,
        train_step_flops,
    )

    device_kind = str(getattr(device, "device_kind", backend))
    # Explicit "unknown" beats a null nobody can distinguish from a
    # missing field; ALPHATRIANGLE_PEAK_TFLOPS (peak_source "env") lets
    # CPU/smoke runs still publish an MFU ratio.
    peak_tflops, peak_source = peak_bf16_tflops_info(device_kind)
    fwd = forward_flops(model_cfg, env_cfg, env_cfg.action_dim)
    sp_flops_s = leaf_evals_per_sec * fwd
    extra = {
        "backend": backend,
        "scale": scale,
        "search_recipe": {
            "root_selection": mcts_cfg.root_selection,
            "fast_simulations": mcts_cfg.fast_simulations,
            "full_search_prob": mcts_cfg.full_search_prob,
        },
        "descent_gather": mcts_cfg.descent_gather,
        # Kernel-library provenance (docs/KERNELS.md): which lowering
        # of each hot kernel + the rollout inference precision this
        # measurement ran with — a bench row without these would be a
        # mislabeled A/B the moment a non-default backend is flipped on.
        "kernels": {
            "descent_gather": mcts_cfg.descent_gather,
            "backup_update": mcts_cfg.backup_update,
            "per_sample": train_cfg.PER_SAMPLE_BACKEND,
            "inference_precision": model_cfg.INFERENCE_PRECISION,
            "tree_reuse": mcts_cfg.tree_reuse,
            "tree_reuse_backend": mcts_cfg.tree_reuse_backend,
        },
        "self_play_batch": sp_batch,
        "mcts_simulations": sims,
        "rollout_chunk_moves": chunk,
        "episodes_completed": episodes,
        "measure_seconds": round(elapsed, 1),
        "mean_episode_length": (
            round(float(np.mean(result.episode_lengths)), 1)
            if result.episode_lengths
            else None
        ),
        "moves_per_sec": round(moves_per_sec, 1),
        "mcts_leaf_evals_per_sec": round(leaf_evals_per_sec, 1),
        # Compare-facing aliases (telemetry/perf.py _summary_from_bench
        # reads these into the `cli compare` rows).
        "leaf_evals_per_sec": round(leaf_evals_per_sec, 1),
        "mcts_reused_visit_fraction": round(reused_fraction, 4),
        "first_chunk_compile_seconds": round(compile_s, 1),
        "device_kind": device_kind,
        "flops": {
            "forward_flops_per_eval": fwd,
            "peak_bf16_tflops": (
                peak_tflops if peak_tflops is not None else "unknown"
            ),
            "peak_source": peak_source,
            "self_play_tflops_per_sec": round(sp_flops_s / 1e12, 3),
            "self_play_mfu": (
                round(m, 4) if (m := mfu(sp_flops_s, device_kind)) else None
            ),
        },
    }
    # Device-stats plane (telemetry/device_stats.py): when the engine
    # compiled stat-packs in (ALPHATRIANGLE_DEVICE_STATS / config), the
    # newest in-program search/rollout fold rides the BENCH snapshot.
    ds_legs = getattr(engine, "last_device_stats", None)
    if ds_legs:
        from alphatriangle_tpu.telemetry.device_stats import (
            device_stats_json,
            device_stats_record,
        )

        ds_rec = device_stats_record(moves, **ds_legs)
        if ds_rec is not None:
            extra["device_stats"] = device_stats_json([ds_rec])

    def snapshot(partial: "str | None") -> dict:
        global _last_partial
        # Refreshed at every snapshot: later sections (learner, fused,
        # device-replay, overlapped) add their own compiles/hits — and
        # their own program memory records + device memory high water.
        extra["compile_cache"] = compile_cache.stats()
        from alphatriangle_tpu.telemetry.health import device_memory_stats

        extra["memory"] = {
            "device": device_memory_stats(),
            "programs": compile_cache.memory_summary(),
        }
        # Compiler cost attribution (telemetry/roofline.py): every
        # program's FLOPs/bytes-accessed next to its memory record, so
        # a BENCH snapshot carries the roofline inputs too.
        extra["roofline"] = {"programs": compile_cache.cost_summary()}
        r = {
            "metric": "self_play_games_per_hour",
            "value": round(games_per_hour, 1),
            "unit": "games/hour",
            "vs_baseline": round(games_per_hour / north_star, 4),
            "extra": json.loads(json.dumps(extra)),  # deep copy
        }
        if partial:
            r["extra"]["partial"] = partial
            _last_partial = r
        return r

    emit(snapshot("self_play"))

    # --- subtree-reuse A/B (MCTSConfig.tree_reuse) ----------------------
    # Same plan with reuse flipped on: the carried-tree engine measures
    # its own leaf-evals/s window against a matched fresh-root rate.
    # The headline sections always run fresh-root, so BENCH_TREE_REUSE=0
    # (skip) and =1 (run the extra section) emit identical headline
    # numbers — the A/B only ADDS extra["tree_reuse"]. Skipped under
    # recipes reuse cannot compose with (gumbel roots, playout cap
    # randomization — config/mcts_config.py validators).
    reuse_compatible = (
        mcts_cfg.root_selection != "gumbel"
        and mcts_cfg.fast_simulations is None
    )
    if os.environ.get("BENCH_TREE_REUSE", "1") != "0" and reuse_compatible:
        # A single-wave plan (wave >= sims) builds a depth-1 tree whose
        # promoted child has no expanded edges — nothing to carry. The
        # A/B then drops to a 2-wave geometry on BOTH sides and measures
        # its own matched fresh-root baseline; otherwise the headline
        # rate above is already the matched comparator.
        reuse_wave = mcts_cfg.mcts_batch_size
        fresh_comparator = leaf_evals_per_sec
        if reuse_wave >= sims:
            reuse_wave = max(1, sims // 2)
            match_cfg = mcts_cfg.model_copy(
                update={"mcts_batch_size": reuse_wave}
            )
            match_engine = SelfPlayEngine(
                env, extractor, net, match_cfg, train_cfg, seed=0
            )
            log("bench: compiling matched fresh-root chunk (2-wave)...")
            match_engine.play_chunk()
            match_engine.harvest()
            m_seconds = min(seconds, 15.0)
            t0 = time.time()
            m_moves = 0
            while time.time() - t0 < m_seconds:
                match_engine.play_chunk()
                m_moves += chunk
            m_elapsed = time.time() - t0
            m_result = match_engine.harvest()
            fresh_comparator = (
                m_result.total_simulations
                + m_result.total_reused_visits
                + m_moves * sp_batch
            ) / m_elapsed
        reuse_cfg = mcts_cfg.model_copy(
            update={"tree_reuse": True, "mcts_batch_size": reuse_wave}
        )
        reuse_engine = SelfPlayEngine(
            env, extractor, net, reuse_cfg, train_cfg, seed=0
        )
        log("bench: compiling reuse self-play chunk (first dispatch)...")
        t0 = time.time()
        reuse_engine.play_chunk()
        reuse_compile_s = time.time() - t0
        reuse_engine.harvest()
        reuse_seconds = min(seconds, 15.0)
        t0 = time.time()
        r_moves = 0
        while time.time() - t0 < reuse_seconds:
            reuse_engine.play_chunk()
            r_moves += chunk
        r_elapsed = time.time() - t0
        r_result = reuse_engine.harvest()
        r_leaf = (
            r_result.total_simulations
            + r_result.total_reused_visits
            + r_moves * sp_batch
        ) / r_elapsed
        r_fraction = r_result.total_reused_visits / max(
            1,
            r_result.total_simulations + r_result.total_reused_visits,
        )
        extra["tree_reuse"] = {
            "backend": reuse_cfg.tree_reuse_backend,
            "wave": reuse_wave,
            "seconds": round(r_elapsed, 1),
            "compile_seconds": round(reuse_compile_s, 1),
            "moves_per_sec": round(r_moves * sp_batch / r_elapsed, 1),
            "leaf_evals_per_sec": round(r_leaf, 1),
            "reused_visit_fraction": round(r_fraction, 4),
            # The acceptance ratio: reuse-on leaf-equivalent search
            # effort per wall second over the matched fresh-root rate
            # at equal sims and wave.
            "speedup_vs_fresh": (
                round(r_leaf / fresh_comparator, 3)
                if fresh_comparator > 0
                else None
            ),
        }
        log(f"bench: tree_reuse {extra['tree_reuse']}")
        emit(snapshot("tree_reuse"))

    # --- learner steps/sec (secondary) ----------------------------------
    trainer = Trainer(net, train_cfg)
    b = train_cfg.BATCH_SIZE
    rng = np.random.default_rng(0)
    policy = rng.random((b, env_cfg.action_dim)).astype(np.float32)
    policy /= policy.sum(axis=1, keepdims=True)
    batch = {
        "grid": rng.integers(-1, 2, size=(b, 1, env_cfg.ROWS, env_cfg.COLS)).astype(
            np.float32
        ),
        "other_features": rng.random(
            (b, model_cfg.OTHER_NN_INPUT_FEATURES_DIM)
        ).astype(np.float32),
        "policy_target": policy,
        "value_target": rng.uniform(-5, 5, b).astype(np.float32),
        "weights": np.ones(b, np.float32),
    }
    trainer.train_step(batch)  # compile
    n_steps = 5 if smoke else 30
    t0 = time.time()
    for _ in range(n_steps):
        trainer.train_step(batch)
    jax.block_until_ready(trainer.state.params)
    learner_steps_per_sec = n_steps / (time.time() - t0)
    log(f"bench: learner {learner_steps_per_sec:.2f} steps/s (batch {b})")

    # Fused groups: K steps per dispatch (one round trip per group) —
    # the FUSED_LEARNER_STEPS path of the training loop.
    # CPU unrolls the group (see Trainer._train_steps_impl), so keep K
    # small there to bound compile time. (K values live in the shared
    # plan so `cli warm` precompiles the same fused programs.)
    fused_k = plan.fused_k
    fused_batches = [batch] * fused_k
    trainer.train_steps(fused_batches)  # compile
    n_groups = 2 if smoke else 5
    t0 = time.time()
    for _ in range(n_groups):
        trainer.train_steps(fused_batches)
    jax.block_until_ready(trainer.state.params)
    fused_steps_per_sec = n_groups * fused_k / (time.time() - t0)
    log(
        f"bench: fused learner {fused_steps_per_sec:.2f} steps/s "
        f"(batch {b}, K={fused_k})"
    )
    step_flops = train_step_flops(model_cfg, env_cfg, env_cfg.action_dim, b)
    ln_flops_s = fused_steps_per_sec * step_flops
    extra.update(
        {
            "learner_steps_per_sec": round(learner_steps_per_sec, 2),
            "learner_steps_per_sec_fused": round(fused_steps_per_sec, 2),
            "fused_group_size": fused_k,
            "learner_batch": b,
        }
    )
    extra["flops"].update(
        {
            "train_flops_per_step": step_flops,
            "learner_tflops_per_sec": round(ln_flops_s / 1e12, 3),
            "learner_mfu": (
                round(m, 4) if (m := mfu(ln_flops_s, device_kind)) else None
            ),
        }
    )
    emit(snapshot("learner"))

    # Device-resident replay (rl/device_buffer.py): batches are gathered
    # on device from sampled indices, so a fused group uploads ~K*B*4
    # bytes of indices instead of K full batches — the difference
    # between link-bound and compute-bound on a PCIe-fed chip.
    # Measured on every backend except CPU (where host and "device"
    # memory are the same RAM and the comparison is meaningless).
    device_replay = plan.device_replay
    dev_buffer = None
    dev_steps_per_sec = None
    if device_replay:
        from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer

        dev_buffer = DeviceReplayBuffer(
            train_cfg,
            grid_shape=(
                model_cfg.GRID_INPUT_CHANNELS,
                env_cfg.ROWS,
                env_cfg.COLS,
            ),
            other_dim=extractor.other_dim,
            action_dim=env_cfg.action_dim,
        )
        fill = batch["grid"].astype(np.int8).astype(np.float32)
        for _ in range(max(1, (train_cfg.MIN_BUFFER_SIZE_TO_TRAIN // b) + 1)):
            dev_buffer.add_dense(
                fill,
                batch["other_features"],
                batch["policy_target"],
                batch["value_target"],
            )

        def dev_samples(k: int) -> list:
            return [
                dev_buffer.sample(b, current_train_step=trainer.global_step)
                for _ in range(k)
            ]

        trainer.train_steps_from(dev_buffer, dev_samples(fused_k))  # compile
        t0 = time.time()
        for _ in range(n_steps // fused_k + 1):
            trainer.train_steps_from(dev_buffer, dev_samples(fused_k))
        jax.block_until_ready(trainer.state.params)
        dev_steps_per_sec = (
            (n_steps // fused_k + 1) * fused_k / (time.time() - t0)
        )
        log(
            f"bench: device-replay learner {dev_steps_per_sec:.2f} steps/s "
            f"(batch {b}, K={fused_k}, index-only uploads)"
        )
        extra["learner_steps_per_sec_device_replay"] = round(
            dev_steps_per_sec, 2
        )
        extra["flops"]["learner_device_replay_mfu"] = (
            round(m, 4)
            if (m := mfu(dev_steps_per_sec * step_flops, device_kind))
            else None
        )
        emit(snapshot("device_replay"))
    else:
        extra["learner_steps_per_sec_device_replay"] = None

    # --- overlapped producer/consumer (combined rates) ------------------
    # The phases above run each side alone; this measures both at once
    # (the training loop's ASYNC_ROLLOUTS topology): producer thread(s)
    # drive self-play chunks while the main thread trains. Two devices-
    # share mechanisms from the training loop are reproduced here:
    #   * async chunk auto-sizing — producer dispatches are shrunk to
    #     ~BENCH_ASYNC_CHUNK_SECONDS of device time each, bounding how
    #     long a learner dispatch queues behind a rollout program;
    #   * the pipelined learner — fused group N+1 is dispatched before
    #     group N's results are fetched, so the learner always has a
    #     program in the device FIFO and never idles a host round
    #     trip per group.
    # BENCH_WORKERS > 1 measures the multi-stream topology
    # (NUM_SELF_PLAY_WORKERS).
    import threading

    overlap_seconds = 5.0 if smoke else min(40.0, seconds)
    per_move_s = elapsed / max(moves, 1)
    async_target_s = float(os.environ.get("BENCH_ASYNC_CHUNK_SECONDS", "2.0"))
    async_chunk = max(1, min(chunk, round(async_target_s / per_move_s)))
    # Larger fused groups amortize the producer interleave: the learner
    # runs K steps per time slice between rollout chunks.
    overlap_k = plan.overlap_k
    overlap_batches = [batch] * overlap_k
    if device_replay:
        # Warm the K-sized device-gather program OUTSIDE the timed
        # window (the host-path program is never dispatched here).
        if overlap_k != fused_k:
            assert dev_buffer is not None
            trainer.train_steps_from(dev_buffer, dev_samples(overlap_k))
    elif overlap_k != fused_k:
        trainer.train_steps(overlap_batches)  # compile
    if async_chunk != chunk:
        log(
            f"bench: overlap auto-chunk {async_chunk} moves/dispatch "
            f"(~{per_move_s:.2f}s/move, target {async_target_s:.1f}s)"
        )
        engine.play_chunk(async_chunk)  # compile the tuned size
    n_streams = max(1, int(os.environ.get("BENCH_WORKERS", "1")))
    engines = [engine]
    for i in range(1, n_streams):
        engines.append(
            SelfPlayEngine(
                env,
                extractor,
                net,
                mcts_cfg,
                train_cfg,
                seed=100 + i,
                share_compiled=engine,
            )
        )
    for e in engines:
        e.harvest()  # reset counters
    stop = threading.Event()
    produced = {"moves": 0, "episodes": 0, "errors": []}
    lock = threading.Lock()
    payloads: "queue.Queue | None" = None
    import queue

    if device_replay:
        # Mirror the real overlapped loop's device-replay topology:
        # producers enqueue device-resident payloads (no bulk fetch),
        # the learner thread ingests them into the on-device ring and
        # trains from index-only samples.
        payloads = queue.Queue(maxsize=4)

    def producer(e) -> None:
        try:
            while not stop.is_set():
                if payloads is not None:
                    stats, payload = e.play_moves_device(async_chunk)
                    with lock:
                        produced["moves"] += async_chunk
                        produced["episodes"] += stats.num_episodes
                    while not stop.is_set():
                        try:
                            payloads.put(payload, timeout=0.2)
                            break
                        except queue.Full:
                            continue
                else:
                    e.play_chunk(async_chunk)
                    with lock:
                        produced["moves"] += async_chunk
        except Exception as exc:  # surface, don't hang the bench
            with lock:
                produced["errors"].append(f"{type(exc).__name__}: {exc}")

    def dispatch_total(*comps) -> int:
        """Cumulative device-program dispatches across components (the
        per-mode dispatches-per-iteration counter; rl components each
        count their own dispatches)."""
        seen = {}
        for comp in comps:
            if comp is not None:
                seen[id(comp)] = comp
        return sum(
            int(getattr(x, "dispatch_count", 0)) for x in seen.values()
        )

    threads = [
        threading.Thread(target=producer, args=(e,), daemon=True)
        for e in engines
    ]
    for th in threads:
        th.start()
    t0 = time.time()
    o_steps = 0
    o_ingested = 0
    o_iters = 0
    o_disp0 = dispatch_total(trainer, dev_buffer, *engines)
    pending = None
    while time.time() - t0 < overlap_seconds:
        o_iters += 1
        if payloads is not None:
            assert dev_buffer is not None
            while True:
                try:
                    o_ingested += dev_buffer.ingest_payload(
                        payloads.get_nowait()
                    )
                except queue.Empty:
                    break
            nxt = trainer.train_steps_from_begin(
                dev_buffer, dev_samples(overlap_k)
            )
        else:
            nxt = trainer.train_steps_begin(overlap_batches)
        if pending is not None:
            o_steps += len(trainer.train_steps_finish(pending))
        pending = nxt
    if pending is not None:
        o_steps += len(trainer.train_steps_finish(pending))
    jax.block_until_ready(trainer.state.params)
    stop.set()
    for th in threads:
        th.join(timeout=120)
    o_elapsed = time.time() - t0
    if payloads is not None:
        o_episodes = produced["episodes"]
    else:
        o_episodes = sum(e.harvest().num_episodes for e in engines)
    o_games_per_hour = o_episodes / o_elapsed * 3600.0
    o_moves_per_sec = produced["moves"] * sp_batch / o_elapsed
    o_dpi = (
        dispatch_total(trainer, dev_buffer, *engines) - o_disp0
    ) / max(o_iters, 1)
    overlapped = {
        "seconds": round(o_elapsed, 1),
        "streams": n_streams,
        "chunk_moves": async_chunk,
        "fused_group": overlap_k,
        # Device dispatches per consumer pump beat — the host-round-
        # trip count the fused megastep collapses to 1.
        "dispatches_per_iteration": round(o_dpi, 2),
        "games_per_hour": round(o_games_per_hour, 1),
        "vs_serialized_self_play": round(
            o_games_per_hour / games_per_hour, 3
        )
        if games_per_hour
        else None,
        "moves_per_sec": round(
            produced["moves"] * sp_batch / o_elapsed, 1
        ),
        "learner_steps_per_sec": round(o_steps / o_elapsed, 2),
    }
    if device_replay:
        overlapped["device_replay"] = True
        overlapped["experiences_ingested_per_sec"] = round(
            o_ingested / o_elapsed, 1
        )
    if produced["errors"]:
        overlapped["producer_errors"] = produced["errors"]
    log(f"bench: overlapped {overlapped}")
    extra["overlapped"] = overlapped
    emit(snapshot("overlapped"))

    # --- fused megastep (Anakin): the whole iteration as ONE program ----
    # rollout chunk + ring ingest + on-device PER sampling + K learner
    # steps in a single jitted dispatch (rl/megastep.py) — the loop's
    # FUSED_MEGASTEP mode. vs_overlapped is the headline: the round-5
    # overlapped mode ran at 0.774x of serialized self-play because
    # every phase paid a host round trip; the megastep removes them.
    # BENCH_MEGASTEP=0 skips the section (compile-budget escape hatch).
    if os.environ.get("BENCH_MEGASTEP", "1") != "0":
        from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer
        from alphatriangle_tpu.rl.megastep import MegastepRunner

        mega_buffer = dev_buffer
        if mega_buffer is None:
            # CPU/smoke path: the device-replay learner section didn't
            # run, so build + prefill the ring here (DEVICE_REPLAY="on"
            # works on the CPU backend; this section is single-threaded
            # so the XLA:CPU async-dispatch caveat does not apply).
            mega_buffer = DeviceReplayBuffer(
                train_cfg,
                grid_shape=(
                    model_cfg.GRID_INPUT_CHANNELS,
                    env_cfg.ROWS,
                    env_cfg.COLS,
                ),
                other_dim=extractor.other_dim,
                action_dim=env_cfg.action_dim,
            )
            fill = batch["grid"].astype(np.int8).astype(np.float32)
            for _ in range(
                max(1, (train_cfg.MIN_BUFFER_SIZE_TO_TRAIN // b) + 1)
            ):
                mega_buffer.add_dense(
                    fill,
                    batch["other_features"],
                    batch["policy_target"],
                    batch["value_target"],
                )
        runner = MegastepRunner(engine, trainer, mega_buffer, train_cfg)
        mega_k = fused_k
        engine.harvest()  # drop pre-section episode stats
        log(
            f"bench: compiling megastep t{chunk}_k{mega_k} "
            "(first dispatch)..."
        )
        t0 = time.time()
        runner.run_megastep(chunk, mega_k)
        mega_compile_s = time.time() - t0
        engine.harvest()
        mega_seconds = 5.0 if smoke else min(30.0, seconds)
        m_disp0 = dispatch_total(
            trainer, dev_buffer, mega_buffer, runner, *engines
        )
        t0 = time.time()
        m_moves = 0
        m_steps = 0
        m_iters = 0
        while time.time() - t0 < mega_seconds:
            runner.run_megastep(chunk, mega_k)
            m_moves += chunk
            m_steps += mega_k
            m_iters += 1
        m_elapsed = time.time() - t0
        m_dpi = (
            dispatch_total(
                trainer, dev_buffer, mega_buffer, runner, *engines
            )
            - m_disp0
        ) / max(m_iters, 1)
        m_result = engine.harvest()
        m_games_per_hour = m_result.num_episodes / m_elapsed * 3600.0
        m_moves_per_sec = m_moves * sp_batch / m_elapsed
        m_steps_per_sec = m_steps / m_elapsed
        # vs_overlapped: games/h when both windows completed episodes,
        # else the exact moves/s ratio (short smoke windows may finish
        # zero episodes; the ratio must still land — acceptance bar).
        if o_games_per_hour > 0 and m_games_per_hour > 0:
            vs_overlapped = m_games_per_hour / o_games_per_hour
            vs_basis = "games_per_hour"
        else:
            vs_overlapped = (
                m_moves_per_sec / o_moves_per_sec
                if o_moves_per_sec > 0
                else None
            )
            vs_basis = "moves_per_sec"
        megastep_section = {
            "seconds": round(m_elapsed, 1),
            "iterations": m_iters,
            "chunk_moves": chunk,
            "learner_steps_per_iteration": mega_k,
            "compile_seconds": round(mega_compile_s, 1),
            "games_per_hour": round(m_games_per_hour, 1),
            "moves_per_sec": round(m_moves_per_sec, 1),
            "learner_steps_per_sec": round(m_steps_per_sec, 2),
            "leaf_evals_per_sec": round(
                (
                    m_result.total_simulations
                    + m_result.total_reused_visits
                    + m_moves * sp_batch
                )
                / m_elapsed,
                1,
            ),
            "vs_overlapped": (
                round(vs_overlapped, 3) if vs_overlapped else None
            ),
            "vs_overlapped_basis": vs_basis,
            # All three loop modes' host-round-trip gauges side by
            # side (the overlapped/megastep values are measured; the
            # sync loop's is fixed by construction: rollout + ingest +
            # one fused learner group per iteration).
            "dispatches_per_iteration": {
                "sync": 3.0,
                "overlapped": round(o_dpi, 2),
                "megastep": round(m_dpi, 2),
            },
        }
        log(f"bench: megastep {megastep_section}")
        extra["megastep"] = megastep_section
        emit(snapshot("megastep"))

        # --- dp-sharded megastep scaling (megastep/dp<D>_t<T>_k<K>) -
        # The same fused program sharded over the mesh's dp axis: each
        # device runs its rollout lanes, scatters into its ring shard,
        # samples its PER stratum and psums gradients in-program.
        # Measures games/h + learner steps/s at 1 vs N devices and the
        # vs_single_device ratio against the window just measured.
        # BENCH_MEGASTEP_DP=0 skips (compile-budget escape hatch).
        from alphatriangle_tpu.telemetry.memory import (
            sharded_megastep_dp,
        )

        mega_dp = sharded_megastep_dp(train_cfg)
        if (
            os.environ.get("BENCH_MEGASTEP_DP", "1") != "0"
            and mega_dp > 1
        ):
            from alphatriangle_tpu.config import MeshConfig
            from alphatriangle_tpu.rl import SelfPlayEngine, Trainer
            from alphatriangle_tpu.rl.sharded_device_buffer import (
                ShardedDeviceReplayBuffer,
            )

            mesh = MeshConfig(DP_SIZE=mega_dp).build_mesh()
            dp_engine = SelfPlayEngine(
                env, extractor, net, mcts_cfg, train_cfg, seed=11,
                mesh=mesh,
            )
            dp_trainer = Trainer(net, train_cfg, mesh=mesh)
            dp_ring = ShardedDeviceReplayBuffer(
                train_cfg,
                grid_shape=(
                    model_cfg.GRID_INPUT_CHANNELS,
                    env_cfg.ROWS,
                    env_cfg.COLS,
                ),
                other_dim=extractor.other_dim,
                action_dim=env_cfg.action_dim,
                mesh=mesh,
            )
            fill = batch["grid"].astype(np.int8).astype(np.float32)
            for _ in range(
                max(1, (train_cfg.MIN_BUFFER_SIZE_TO_TRAIN // b) + 1)
            ):
                dp_ring.add_dense(
                    fill,
                    batch["other_features"],
                    batch["policy_target"],
                    batch["value_target"],
                )
            dp_runner = MegastepRunner(
                dp_engine, dp_trainer, dp_ring, train_cfg
            )
            log(
                f"bench: compiling megastep dp{mega_dp}_t{chunk}"
                f"_k{mega_k} (first dispatch)..."
            )
            t0 = time.time()
            dp_runner.run_megastep(chunk, mega_k)
            s_compile_s = time.time() - t0
            dp_engine.harvest()
            s_disp0 = dispatch_total(dp_trainer, dp_ring, dp_runner)
            t0 = time.time()
            s_moves = 0
            s_steps = 0
            s_iters = 0
            while time.time() - t0 < mega_seconds:
                dp_runner.run_megastep(chunk, mega_k)
                s_moves += chunk
                s_steps += mega_k
                s_iters += 1
            s_elapsed = time.time() - t0
            s_dpi = (
                dispatch_total(dp_trainer, dp_ring, dp_runner)
                - s_disp0
            ) / max(s_iters, 1)
            s_result = dp_engine.harvest()
            s_games_per_hour = (
                s_result.num_episodes / s_elapsed * 3600.0
            )
            s_moves_per_sec = s_moves * sp_batch / s_elapsed
            s_steps_per_sec = s_steps / s_elapsed
            if m_games_per_hour > 0 and s_games_per_hour > 0:
                vs_single = s_games_per_hour / m_games_per_hour
                vs_single_basis = "games_per_hour"
            else:
                vs_single = (
                    s_moves_per_sec / m_moves_per_sec
                    if m_moves_per_sec > 0
                    else None
                )
                vs_single_basis = "moves_per_sec"
            scaling_section = {
                "devices": mega_dp,
                "seconds": round(s_elapsed, 1),
                "iterations": s_iters,
                "compile_seconds": round(s_compile_s, 1),
                "games_per_hour": {
                    "1": round(m_games_per_hour, 1),
                    str(mega_dp): round(s_games_per_hour, 1),
                },
                "learner_steps_per_sec": {
                    "1": round(m_steps_per_sec, 2),
                    str(mega_dp): round(s_steps_per_sec, 2),
                },
                "moves_per_sec": round(s_moves_per_sec, 1),
                "vs_single_device": (
                    round(vs_single, 3) if vs_single else None
                ),
                "vs_single_device_basis": vs_single_basis,
                "dispatches_per_iteration": round(s_dpi, 2),
            }
            log(f"bench: megastep scaling {scaling_section}")
            megastep_section["scaling"] = scaling_section
            emit(snapshot("megastep_scaling"))
        elif mega_dp > 1:
            log("bench: megastep scaling skipped (BENCH_MEGASTEP_DP=0)")
        else:
            log(
                "bench: megastep scaling skipped (single device or "
                "geometry does not divide the mesh)"
            )

    # --- policy-serving latency (serving/service.py) --------------------
    # The serving front end's SLO numbers next to the training numbers:
    # simulated concurrent sessions with admit/retire churn through the
    # continuous batcher at the plan's `serve/b<B>` shape (the shape
    # `cli warm` precompiles). Overall p50/p95 per-move latency,
    # requests/s and batch fill land in extra["serve"] — the same
    # metrics `cli perf` summarizes from a real serve run's ledger and
    # `cli compare` gates. BENCH_SERVE=0 skips.
    if os.environ.get("BENCH_SERVE", "1") != "0":
        from alphatriangle_tpu.nn.precision import (
            cast_params_for_inference,
            quantized_param_bytes,
        )
        from alphatriangle_tpu.serving import (
            PolicyService,
            run_simulated_load,
        )

        def serve_param_bytes(cfg) -> int:
            """Bytes of weights one serve wave reads from HBM under
            `cfg`'s inference precision policy (nn/precision.py)."""
            return int(
                quantized_param_bytes(
                    cast_params_for_inference(net.variables, cfg)
                )
            )

        serve_slots = plan.serve_batch
        serve_gumbel = (
            getattr(mcts_cfg, "root_selection", "puct") == "gumbel"
        )
        if serve_gumbel:
            # Mirror `cli warm`'s construction exactly: serving
            # dispatches the deterministic exploit-mode Gumbel arm.
            from alphatriangle_tpu.mcts import GumbelMCTS

            serve_mcts = GumbelMCTS(
                env, extractor, net.model, mcts_cfg, net.support,
                exploit=True,
            )
        else:
            serve_mcts = engine.mcts
        serve_service = PolicyService(
            env, extractor, net, serve_mcts,
            slots=serve_slots, use_gumbel=serve_gumbel,
            ladder=plan.serve_buckets,
        )
        log(f"bench: warming serve/b{serve_slots}...")
        t0 = time.time()
        serve_service.warm()
        serve_compile_s = time.time() - t0
        serve_stats = run_simulated_load(
            serve_service,
            total_sessions=serve_slots + max(8, serve_slots // 2),
            max_moves=8 if smoke else 32,
            seed=0,
            max_dispatches=4000,
        )
        # No telemetry ticks drained the service's windows, so these
        # percentiles cover every request of the section.
        slo = serve_service.serve_stats(drain=False)
        serve_section = {
            "slots": serve_slots,
            "sessions_served": serve_stats["sessions_served"],
            "moves_served": serve_stats["moves_served"],
            "seconds": serve_stats["seconds"],
            "compile_seconds": round(serve_compile_s, 1),
            "requests_per_sec": serve_stats["moves_per_sec"],
            # Device search effort per wall second: full-array sims +
            # reused visits (0 unless the plan serves with tree_reuse)
            # + one root eval per dispatched lane.
            "leaf_evals_per_sec": (
                round(
                    (
                        serve_service.simulations_total
                        + serve_service.reused_visits_total
                        + serve_service.dispatch_count * serve_slots
                    )
                    / serve_stats["seconds"],
                    1,
                )
                if serve_stats["seconds"]
                else None
            ),
            "move_latency_ms_p50": slo["serve_move_latency_ms_p50"],
            "move_latency_ms_p95": slo["serve_move_latency_ms_p95"],
            "queue_wait_ms_p95": slo["serve_queue_wait_ms_p95"],
            "batch_ms_p50": slo["serve_batch_ms_p50"],
            "batch_fill": slo["serve_batch_fill"],
            "precision": model_cfg.INFERENCE_PRECISION,
            "buckets": list(serve_service.ladder.rungs),
            "rung_switches": serve_service.rung_switches,
            "param_bytes": serve_param_bytes(model_cfg),
        }
        log(f"bench: serve {serve_section}")

        def serve_arm(precision: str, ladder_spec) -> dict:
            """One alternate serve arm: same weights and traffic shape
            as the main section, different inference precision and/or
            bucket ladder — the paired-measurement A/B the serve
            speedup/fill ratios are computed from."""
            from alphatriangle_tpu.features.core import (
                get_feature_extractor,
            )
            from alphatriangle_tpu.nn.network import NeuralNetwork

            model_arm = model_cfg.model_copy(
                update={"INFERENCE_PRECISION": precision}
            )
            extractor_arm = get_feature_extractor(env, model_arm)
            net_arm = NeuralNetwork(
                model_arm, env_cfg, seed=0, variables=net.variables
            )
            if serve_gumbel:
                from alphatriangle_tpu.mcts import GumbelMCTS

                mcts_arm = GumbelMCTS(
                    env, extractor_arm, net_arm.model, mcts_cfg,
                    net_arm.support, exploit=True,
                )
            else:
                from alphatriangle_tpu.mcts import BatchedMCTS

                mcts_arm = BatchedMCTS(
                    env, extractor_arm, net_arm.model, mcts_cfg,
                    net_arm.support,
                )
            svc = PolicyService(
                env, extractor_arm, net_arm, mcts_arm,
                slots=serve_slots, use_gumbel=serve_gumbel,
                ladder=ladder_spec,
            )
            svc.warm()
            stats = run_simulated_load(
                svc,
                total_sessions=serve_slots + max(8, serve_slots // 2),
                max_moves=8 if smoke else 32,
                seed=0,
                max_dispatches=4000,
            )
            arm_slo = svc.serve_stats(drain=False)
            return {
                "precision": precision,
                "buckets": list(svc.ladder.rungs),
                "requests_per_sec": stats["moves_per_sec"],
                "batch_fill": arm_slo["serve_batch_fill"],
                "rung_switches": svc.rung_switches,
                "param_bytes": serve_param_bytes(model_arm),
            }

        # Precision A/B (BENCH_SERVE_PRECISION=int8): the named
        # precision arm against a bf16 baseline arm on identical
        # weights and traffic — speedup_vs_bf16 is the serve fast
        # path's headline, param_bytes_ratio the HBM-read reduction
        # the int8 weight tensors buy.
        ab_precision = os.environ.get("BENCH_SERVE_PRECISION")
        if ab_precision:
            arm = serve_arm(ab_precision, plan.serve_buckets)
            base = (
                serve_section
                if model_cfg.INFERENCE_PRECISION == "bfloat16"
                else serve_arm("bfloat16", plan.serve_buckets)
            )
            serve_section["precision_ab"] = {
                "arm": arm,
                "baseline_precision": "bfloat16",
                "baseline_requests_per_sec": base["requests_per_sec"],
                "speedup_vs_bf16": (
                    round(
                        arm["requests_per_sec"]
                        / base["requests_per_sec"],
                        3,
                    )
                    if base["requests_per_sec"]
                    else None
                ),
                "param_bytes_ratio": (
                    round(arm["param_bytes"] / base["param_bytes"], 3)
                    if base["param_bytes"]
                    else None
                ),
            }
            log(f"bench: serve precision A/B {serve_section['precision_ab']}")
        # Bucket-ladder A/B (BENCH_SERVE_BUCKETS=...): the laddered
        # main section against a fixed single-rung arm — fill_vs_fixed
        # > 1 means the micro-batcher's rung walking kept waves fuller
        # than the fixed flagship shape under the same churn.
        if plan.serve_buckets:
            fixed = serve_arm(model_cfg.INFERENCE_PRECISION, None)
            serve_section["buckets_ab"] = {
                "fixed": fixed,
                "fill_vs_fixed": (
                    round(
                        serve_section["batch_fill"]
                        / fixed["batch_fill"],
                        3,
                    )
                    if fixed["batch_fill"]
                    else None
                ),
            }
            log(f"bench: serve buckets A/B {serve_section['buckets_ab']}")
        extra["serve"] = serve_section
    log(f"bench: flops/mfu {extra['flops']}")
    return snapshot(None)


# Most recent partial snapshot emitted by run_bench: the crash path
# must finish with the best real measurement, not bury it.
_last_partial: "dict | None" = None


def main() -> int:
    smoke = os.environ.get("BENCH_SMOKE") == "1"
    seconds = float(os.environ.get("BENCH_SECONDS", "8" if smoke else "75"))
    import jax

    asked_for_cpu = (
        os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    )
    if jax.default_backend() == "cpu" and not asked_for_cpu:
        log(
            "bench: JAX found no accelerator and JAX_PLATFORMS=cpu was "
            "not asked for; no result."
        )
        return 1
    try:
        out = run_bench(smoke, seconds)
    except Exception as exc:
        import traceback

        traceback.print_exc(file=sys.stderr)
        if _last_partial is not None:
            # Sections that completed before the crash are a real
            # measurement; re-emit the newest snapshot (still tagged
            # extra.partial) with the crash recorded beside it.
            _last_partial["extra"]["error_after_partial"] = (
                f"{type(exc).__name__}: {exc}"
            )
            emit(_last_partial)
        return 1
    emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
