"""AOT precompilation of a run's hot programs (`cli warm`).

The compile-latency story (docs/COMPILE_CACHE.md): every program a
training run dispatches in its loop — the self-play rollout chunk (with
its embedded PUCT/Gumbel search), the fused K-step learner group and
its K=1 tail, or the megastep that holds both — can be lowered and
compiled BEFORE a healthy chip window opens, with the executables
serialized through `compile_cache.CompileCache`. A later process with
the same configs then deserializes in milliseconds instead of compiling
for the better part of a minute per program.

`warm_programs` takes the config bundle `cli train --preset` runs
(`config.presets.baseline_preset(n)` or a `cli tune` artifact through
`load_tuned_preset`) and builds its objects through
`training.setup.build_run_programs`, the constructor sequence
`setup_training_components` itself uses: a program's cache key covers
its whole config digest, the device-stats flag, the mesh and the ring
it takes as an argument, so only the run's own construction yields the
run's own keys (held by tests/test_compile_cache.py
`test_warm_then_setup_hits`). Each program goes through `.warm()` in
parallel threads — XLA compilation releases the GIL, so N programs
compile concurrently, Podracer-style (arXiv:2104.06272 amortizes
program build cost off the critical path).
"""

import concurrent.futures
import logging
import time

logger = logging.getLogger(__name__)


def warm_programs(
    bundle: dict,
    jobs: int = 4,
    programs: "set[str] | None" = None,
    progress=None,
) -> dict:
    """AOT-compile the programs a run of `bundle` dispatches.

    `bundle`: {env, model, mcts, train, mesh} configs, plus `tuned` (a
    `cli tune` artifact's payload, whose `kernels.serve_buckets` names
    the serve ladder) when it came from one. `programs`: optional name
    filter (substring match against the rows below). `progress`:
    optional callable(str) for per-program lines. Returns {"programs":
    [...rows...], "stats": CompileCache.stats(), "seconds": total wall}.
    """
    import jax

    from .autotune.artifact import serve_ladder
    from .compile_cache import get_compile_cache
    from .config import TelemetryConfig
    from .config.validation import print_config_info_and_validate
    from .training.setup import build_run_programs

    def say(msg: str) -> None:
        logger.info(msg)
        if progress is not None:
            progress(msg)

    t_start = time.time()
    cache = get_compile_cache()
    configs = print_config_info_and_validate(
        env=bundle["env"],
        model=bundle["model"],
        train=bundle["train"],
        mcts=bundle["mcts"],
        mesh=bundle.get("mesh"),
    )
    train, mcts_config = configs["train"], configs["mcts"]
    chunk = train.ROLLOUT_CHUNK_MOVES
    lbatch = train.BATCH_SIZE
    fused_k = max(1, train.FUSED_LEARNER_STEPS)
    say(
        f"warm: backend={jax.default_backend()} "
        f"batch={train.SELF_PLAY_BATCH_SIZE} chunk={chunk} "
        f"sims={mcts_config.max_simulations} k={fused_k} "
        f"ring={train.BUFFER_CAPACITY} cache={cache.cache_dir}"
    )
    # `cli train` runs with the default TelemetryConfig unless told
    # otherwise; its DEVICE_STATS flag is part of the chunk's key.
    built = build_run_programs(
        configs["env"],
        configs["model"],
        mcts_config,
        train,
        configs["mesh"],
        TelemetryConfig(),
    )
    trainer, buffer = built.trainer, built.buffer

    # Learner programs cannot AOT-cache on the CPU backend (reloaded
    # executables return the donated train state unchanged — see the
    # cpu_aot note in rl/trainer.py); report them as skipped instead of
    # as failures so a CPU warm still exits 0 when everything warmable
    # is warm.
    learner_fn = (lambda fn: fn) if trainer.aot_enabled else (lambda fn: None)
    targets: list[tuple[str, object]] = [
        (
            f"self_play_chunk/t{chunk}",
            lambda: built.self_play.warm_chunk(chunk),
        )
    ]
    # The learner's side, by the loop mode the configs select
    # (training/loop.py): the megastep alone; else fused groups of K
    # from the device ring or from host batches, and the K=1 program a
    # group shorter than K falls back to.
    if built.megastep is not None:
        runner = built.megastep
        mega_k = train.LEARNER_STEPS_PER_ROLLOUT or fused_k
        name = (
            f"megastep/dp{runner.dp}_t{chunk}_k{mega_k}"
            if runner.sharded
            else f"megastep/t{chunk}_k{mega_k}"
        )
        targets.append(
            (name, learner_fn(lambda: runner.warm_megastep(chunk, mega_k)))
        )
    elif getattr(buffer, "is_device", False):
        for k in sorted({fused_k, 1}, reverse=True):
            targets.append(
                (
                    f"learner_from_ring/k{k}",
                    learner_fn(
                        lambda k=k: trainer.warm_steps_from(buffer, k, lbatch)
                    ),
                )
            )
    else:
        if fused_k > 1:
            targets.append(
                (
                    f"learner_fused/k{fused_k}",
                    learner_fn(lambda: trainer.warm_steps(fused_k, lbatch)),
                )
            )
        targets.append(
            (
                f"learner_step/b{lbatch}",
                learner_fn(lambda: trainer.warm_step(lbatch)),
            )
        )
    # Policy-service search shape (serving/service.py): warming
    # `serve/b<B>` is what turns `cli serve` startup from a flagship
    # search compile into a ~0.5s deserialize. The search program has
    # no donated buffers, so (unlike the learner family) its AOT
    # artifacts are safe on every backend. One rung at the self-play
    # lane count (the same MXU-batch family as the rollout's search),
    # or the ladder a tuned artifact's winner was scored with. The
    # service's search kind follows the bundle's root-selection recipe:
    # Gumbel recipes serve exploit-mode Gumbel (the deterministic arm
    # `cli eval --gumbel` and `cli serve --gumbel` dispatch), PUCT
    # recipes serve PUCT.
    from .mcts import BatchedMCTS, GumbelMCTS
    from .serving import PolicyService

    env, extractor, net = built.env, built.extractor, built.net
    serve_gumbel = mcts_config.root_selection == "gumbel"
    if serve_gumbel:
        serve_mcts = GumbelMCTS(
            env, extractor, net.model, mcts_config, net.support, exploit=True
        )
    else:
        serve_mcts = BatchedMCTS(
            env, extractor, net.model, mcts_config, net.support
        )
    serve_service = PolicyService(
        env,
        extractor,
        net,
        serve_mcts,
        slots=train.SELF_PLAY_BATCH_SIZE,
        use_gumbel=serve_gumbel,
        ladder=serve_ladder(bundle),
    )
    # One row per ladder rung (serving/buckets.py): the micro-batcher
    # promises zero-recompile rung switches, which only holds if EVERY
    # rung's program is warmed up front — for the active inference
    # precision (the precision digest keys the cache entries apart).
    for rung in serve_service.ladder.rungs:
        targets.append(
            (f"serve/b{rung}", lambda r=rung: serve_service.warm_rung(r))
        )
    if programs:
        targets = [
            (name, fn)
            for name, fn in targets
            if any(p in name for p in programs)
        ]

    def run_one(name: str, fn) -> dict:
        t0 = time.time()
        if fn is None:
            status = "skipped-cpu"
        else:
            try:
                aot = bool(fn())
                status = "aot" if aot else "jit-fallback"
            except Exception as exc:  # a warm failure must not kill the rest
                logger.exception("warm: %s failed", name)
                status = f"error: {type(exc).__name__}: {exc}"
        dt = time.time() - t0
        say(f"warm: {name}: {status} ({dt:.1f}s)")
        return {"program": name, "status": status, "seconds": round(dt, 1)}

    # Parallel lower+compile: XLA releases the GIL during compilation,
    # so distinct programs genuinely overlap.
    with concurrent.futures.ThreadPoolExecutor(
        max_workers=max(1, jobs)
    ) as pool:
        futures = [pool.submit(run_one, name, fn) for name, fn in targets]
        rows = [f.result() for f in futures]

    stats = cache.stats()
    total = time.time() - t_start
    say(
        f"warm: done in {total:.1f}s — {stats['hits']} hit(s), "
        f"{stats['misses']} miss(es) now serialized for the next process"
    )
    return {"programs": rows, "stats": stats, "seconds": round(total, 1)}

