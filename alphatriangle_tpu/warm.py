"""AOT precompilation of the hot bench/training programs (`cli warm`).

The compile-latency story (docs/COMPILE_CACHE.md): every program the
bench dispatches inside its measurement window — the self-play rollout
chunk (with its embedded PUCT/Gumbel search), the learner step, the
fused K-step group, the device-replay gather variant, the overlapped
dispatch's bigger fused group — can be lowered and compiled BEFORE a
healthy chip window opens, with the executables serialized through
`compile_cache.CompileCache`. A later bench/training process with the
same shapes then deserializes in milliseconds instead of compiling for
the better part of a minute per program.

`warm_bench_programs` builds the exact objects `bench.py` builds (via
the shared `bench_config.resolve_bench_plan`) and pushes each hot
program through `.warm()` in parallel threads — XLA compilation
releases the GIL, so N programs compile concurrently, Podracer-style
(arXiv:2104.06272 amortizes program build cost off the critical path).

`cli warm <tuned_preset.json>` warms an autotuned configuration's
shapes instead (the artifact rides in as BENCH_TUNED_PRESET through
the same `resolve_bench_plan` path; docs/AUTOTUNE.md), so a tuned run
launched afterwards starts hot.
"""

import concurrent.futures
import logging
import time

logger = logging.getLogger(__name__)


def warm_bench_programs(
    plan,
    jobs: int = 4,
    programs: "set[str] | None" = None,
    progress=None,
) -> dict:
    """AOT-compile the hot programs for one bench plan.

    `programs`: optional name filter (substring match against the rows
    below). `progress`: optional callable(str) for per-program lines.
    Returns {"programs": [...rows...], "stats": CompileCache.stats(),
    "seconds": total wall}.
    """
    import jax

    from .compile_cache import get_compile_cache
    from .env.engine import TriangleEnv
    from .features.core import get_feature_extractor
    from .nn.network import NeuralNetwork
    from .rl import SelfPlayEngine, Trainer

    def say(msg: str) -> None:
        logger.info(msg)
        if progress is not None:
            progress(msg)

    t_start = time.time()
    backend = jax.default_backend()
    cache = get_compile_cache()
    say(
        f"warm: backend={backend} scale={plan.scale} "
        f"batch={plan.sp_batch} chunk={plan.chunk} sims={plan.sims} "
        f"cache={cache.cache_dir}"
    )

    # Exactly the construction sequence run_bench performs — the cache
    # signatures must match the bench's dispatch arguments bit for bit.
    env = TriangleEnv(plan.env)
    extractor = get_feature_extractor(env, plan.model)
    net = NeuralNetwork(plan.model, plan.env, seed=0)
    engine = SelfPlayEngine(
        env, extractor, net, plan.mcts, plan.train, seed=0
    )
    trainer = Trainer(net, plan.train)

    # Learner programs cannot AOT-cache on the CPU backend (reloaded
    # executables return the donated train state unchanged — see the
    # cpu_aot note in rl/trainer.py); report them as skipped instead of
    # as failures so `cli warm cpu/smoke` still exits 0 when everything
    # warmable is warm.
    learner_fn = (lambda fn: fn) if trainer.aot_enabled else (lambda fn: None)
    targets: list[tuple[str, object]] = [
        (
            f"self_play_chunk/t{plan.chunk}",
            lambda: engine.warm_chunk(plan.chunk),
        ),
        (
            f"learner_step/b{plan.lbatch}",
            learner_fn(lambda: trainer.warm_step(plan.lbatch)),
        ),
        (
            f"learner_fused/k{plan.fused_k}",
            learner_fn(
                lambda: trainer.warm_steps(plan.fused_k, plan.lbatch)
            ),
        ),
    ]
    if plan.overlap_k != plan.fused_k and not plan.device_replay:
        targets.append(
            (
                f"learner_fused/k{plan.overlap_k}",
                learner_fn(
                    lambda: trainer.warm_steps(plan.overlap_k, plan.lbatch)
                ),
            )
        )
    if plan.device_replay:
        from .rl.device_buffer import DeviceReplayBuffer

        dev_buffer = DeviceReplayBuffer(
            plan.train,
            grid_shape=(
                plan.model.GRID_INPUT_CHANNELS,
                plan.env.ROWS,
                plan.env.COLS,
            ),
            other_dim=extractor.other_dim,
            action_dim=plan.env.action_dim,
        )
        targets.append(
            (
                f"learner_from_ring/k{plan.fused_k}",
                learner_fn(
                    lambda: trainer.warm_steps_from(
                        dev_buffer, plan.fused_k, plan.lbatch
                    )
                ),
            )
        )
        if plan.overlap_k != plan.fused_k:
            targets.append(
                (
                    f"learner_from_ring/k{plan.overlap_k}",
                    learner_fn(
                        lambda: trainer.warm_steps_from(
                            dev_buffer, plan.overlap_k, plan.lbatch
                        )
                    ),
                )
            )
    # Fused megastep (rl/megastep.py): the whole iteration as one
    # program. Contains learner steps, so it is CPU-bypassed like the
    # learner family (row reports skipped-cpu there); the runner/ring
    # are only constructed when the warm will actually run.
    mega_fn = None
    if trainer.aot_enabled:
        from .rl.device_buffer import DeviceReplayBuffer
        from .rl.megastep import MegastepRunner

        mega_buffer = DeviceReplayBuffer(
            plan.train,
            grid_shape=(
                plan.model.GRID_INPUT_CHANNELS,
                plan.env.ROWS,
                plan.env.COLS,
            ),
            other_dim=extractor.other_dim,
            action_dim=plan.env.action_dim,
        )
        runner = MegastepRunner(engine, trainer, mega_buffer, plan.train)
        mega_fn = lambda: runner.warm_megastep(plan.chunk, plan.fused_k)
    targets.append(
        (f"megastep/t{plan.chunk}_k{plan.fused_k}", mega_fn)
    )
    # dp-sharded megastep family (megastep/dp<D>_t<T>_k<K>): when this
    # process has a multi-device mesh and the plan's geometry divides
    # (same gate as training/setup.py), warm the program a sharded run
    # will actually dispatch — mesh-built engine/trainer/ring, because
    # the cache signature covers the shardings.
    from .telemetry.memory import sharded_megastep_dp

    mega_dp = sharded_megastep_dp(plan.train)
    if mega_dp > 1:
        mega_dp_fn = None
        if trainer.aot_enabled:
            from .config.mesh_config import MeshConfig
            from .rl.megastep import MegastepRunner
            from .rl.sharded_device_buffer import ShardedDeviceReplayBuffer

            mesh = MeshConfig(DP_SIZE=mega_dp).build_mesh()
            dp_engine = SelfPlayEngine(
                env, extractor, net, plan.mcts, plan.train, seed=0,
                mesh=mesh,
            )
            dp_trainer = Trainer(net, plan.train, mesh=mesh)
            dp_ring = ShardedDeviceReplayBuffer(
                plan.train,
                grid_shape=(
                    plan.model.GRID_INPUT_CHANNELS,
                    plan.env.ROWS,
                    plan.env.COLS,
                ),
                other_dim=extractor.other_dim,
                action_dim=plan.env.action_dim,
                mesh=mesh,
            )
            dp_runner = MegastepRunner(
                dp_engine, dp_trainer, dp_ring, plan.train
            )
            mega_dp_fn = lambda: dp_runner.warm_megastep(
                plan.chunk, plan.fused_k
            )
        targets.append(
            (
                f"megastep/dp{mega_dp}_t{plan.chunk}_k{plan.fused_k}",
                mega_dp_fn,
            )
        )
    # Policy-service search shape (serving/service.py): warming
    # `serve/b<B>` is what turns `cli serve` startup from a flagship
    # search compile into a ~0.5s deserialize. The search program has
    # no donated buffers, so (unlike the learner family) its AOT
    # artifacts are safe on every backend. The service's search kind
    # follows the plan's root-selection recipe: Gumbel recipes serve
    # exploit-mode Gumbel (the deterministic arm `cli eval --gumbel`
    # and `cli serve --gumbel` dispatch), PUCT recipes serve PUCT.
    if plan.serve_batch > 0:
        from .serving import PolicyService

        serve_gumbel = (
            getattr(plan.mcts, "root_selection", "puct") == "gumbel"
        )
        if serve_gumbel:
            from .mcts import GumbelMCTS

            serve_mcts = GumbelMCTS(
                env, extractor, net.model, plan.mcts, net.support,
                exploit=True,
            )
        else:
            from .mcts import BatchedMCTS

            serve_mcts = BatchedMCTS(
                env, extractor, net.model, plan.mcts, net.support
            )
        serve_service = PolicyService(
            env,
            extractor,
            net,
            serve_mcts,
            slots=plan.serve_batch,
            use_gumbel=serve_gumbel,
            ladder=plan.serve_buckets,
        )
        # One row per ladder rung (serving/buckets.py): the
        # micro-batcher promises zero-recompile rung switches, which
        # only holds if EVERY rung's program is warmed up front — for
        # the active inference precision (the precision digest keys the
        # cache entries apart).
        for rung in serve_service.ladder.rungs:
            targets.append(
                (
                    f"serve/b{rung}",
                    lambda r=rung: serve_service.warm_rung(r),
                )
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
