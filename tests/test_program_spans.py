"""The program's own spans and phase names (docs/OBSERVABILITY.md
"Spans", "Profiling"): the default `SpanTracer` on the profiler's clock,
the spans inside `Trainer` / `DeviceReplayBuffer` / `SelfPlayEngine`,
the `jax.named_scope` phases of the three device programs, and the
phase reader `profiling.phase_seconds`.

One file, so the tiny programs compile once in one worker.
"""

import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu import profiling
from alphatriangle_tpu.rl.device_buffer import ring_read
from alphatriangle_tpu.telemetry import (
    RunTelemetry,
    SpanTracer,
    default_tracer,
    set_default_tracer,
)
from alphatriangle_tpu.telemetry.phases import PHASES


@pytest.fixture
def tracer():
    """A fresh default tracer; the process's own comes back after."""
    before = default_tracer()
    fresh = set_default_tracer(SpanTracer())
    yield fresh
    set_default_tracer(before)


def _names(tracer) -> list[str]:
    return [r[1] for r in tracer.records()]


def _host_events(trace_dir) -> list:
    path = sorted(trace_dir.glob("**/*.xplane.pb"))[-1]
    data = jax.profiler.ProfileData.from_file(str(path))
    return [
        e
        for plane in data.planes
        if not plane.name.startswith("/device:")
        for line in plane.lines
        for e in line.events
    ]


# --- (a) the profiler's trace ------------------------------------------------


class TestProfilerAnnotation:
    def test_span_lands_in_the_xplane_host_plane(self, tmp_path):
        tr = SpanTracer()
        jax.profiler.start_trace(str(tmp_path))
        try:
            with tr.span("x", k=3):
                jnp.square(jnp.arange(8.0)).block_until_ready()
        finally:
            jax.profiler.stop_trace()
        found = [e for e in _host_events(tmp_path) if e.name == "at:x"]
        assert len(found) == 1
        # On the profiler's clock, as long as the tracer's own record.
        (_, _, _, dur_ns, *_), = tr.records()
        assert abs(found[0].duration_ns - dur_ns) < 5e6

    def test_no_session_no_effect(self):
        tr = SpanTracer()
        with tr.span("x"):
            pass
        off = SpanTracer(enabled=False)
        with off.span("x") as args:
            args["rows"] = 1  # a count added inside never raises
        assert _names(tr) == ["x"] and off.recorded == 0


# --- (b) nesting, ids, the clock ---------------------------------------------


class TestNestingAndClock:
    def test_parent_is_the_open_span(self):
        tr = SpanTracer()
        with tr.span("train"):
            with tr.span("learner.results", k=2):
                pass
            tr.instant("mark")
        with tr.span("alone"):
            pass
        by_name = {r[1]: r for r in tr.records()}
        train_id = by_name["train"][7]
        assert by_name["learner.results"][8] == train_id
        assert by_name["mark"][8] == train_id
        assert by_name["train"][8] == 0 and by_name["alone"][8] == 0

    def test_ids_unique_across_threads_and_stacks_are_per_thread(self):
        tr = SpanTracer()
        started = threading.Event()

        def work():
            for _ in range(200):
                with tr.span("worker"):
                    started.set()

        t = threading.Thread(target=work)
        with tr.span("main_outer"):
            t.start()
            started.wait(5)
            for _ in range(200):
                with tr.span("main_inner"):
                    pass
            t.join()
        records = tr.records()
        ids = [r[7] for r in records]
        assert len(set(ids)) == len(ids) == 401
        outer = next(r[7] for r in records if r[1] == "main_outer")
        # The other thread's spans are nobody's children here.
        assert {r[8] for r in records if r[1] == "worker"} == {0}
        assert {r[8] for r in records if r[1] == "main_inner"} == {outer}

    def test_args_added_inside_are_recorded(self):
        tr = SpanTracer()
        with tr.span("replay.ingest_wait") as args:
            args["rows"] = 7
        assert tr.records()[0][6] == {"rows": 7}

    def test_export_is_epoch_microseconds(self, tmp_path):
        tr = SpanTracer()
        with tr.span("outer"):
            with tr.span("inner"):
                pass
        wall_ns = time.time_ns()
        tr.complete("late", wall_ns - 5_000_000, wall_ns)
        tr.export(tmp_path / "trace.json")
        events = [
            e
            for e in json.loads((tmp_path / "trace.json").read_text())[
                "traceEvents"
            ]
            if e["ph"] == "X"
        ]
        now_us = time.time() * 1e6
        for e in events:
            assert abs(e["ts"] - now_us) < 1e6, e
        by_name = {e["name"]: e for e in events}
        assert by_name["inner"]["parent"] == by_name["outer"]["id"]
        assert 4000 <= by_name["late"]["dur"] <= 6000

    def test_run_telemetry_installs_its_tracer(self, tmp_path, tracer):
        from alphatriangle_tpu.config import TelemetryConfig

        on = RunTelemetry(
            TelemetryConfig(WATCHDOG_ENABLED=False, FLIGHT_ENABLED=False),
            run_dir=tmp_path,
        )
        assert default_tracer() is on.tracer and on.tracer.enabled
        off = RunTelemetry(TelemetryConfig(ENABLED=False), run_dir=tmp_path)
        assert default_tracer() is off.tracer and not off.tracer.enabled


# --- a tiny world for (c) and (d) --------------------------------------------


@pytest.fixture(scope="module")
def world(
    tiny_env_config, tiny_model_config, tiny_per_train_config, tiny_mcts_config
):
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer
    from alphatriangle_tpu.rl.self_play import SelfPlayEngine
    from alphatriangle_tpu.rl.trainer import Trainer

    # A net with every stage, so that every `net/` phase exists.
    model = tiny_model_config.model_copy(
        update={
            "NUM_RESIDUAL_BLOCKS": 1,
            "USE_TRANSFORMER": True,
            "TRANSFORMER_LAYERS": 1,
        }
    )
    train = tiny_per_train_config.model_copy(
        update={"SELF_PLAY_BATCH_SIZE": 4, "N_STEP_RETURNS": 3}
    )
    # The flagship's search: Gumbel root, playout cap.
    mcts = tiny_mcts_config.model_copy(
        update={
            "root_selection": "gumbel",
            "gumbel_m": 4,
            "fast_simulations": 4,
            "full_search_prob": 0.5,
        }
    )
    env = TriangleEnv(tiny_env_config)
    extractor = get_feature_extractor(env, model)
    net = NeuralNetwork(model, tiny_env_config, seed=5)
    engine = SelfPlayEngine(env, extractor, net, mcts, train, seed=7)
    buffer = DeviceReplayBuffer(
        train,
        grid_shape=(model.GRID_INPUT_CHANNELS, 3, 4),
        other_dim=extractor.other_dim,
        action_dim=tiny_env_config.action_dim,
        seed=0,
    )
    return {
        "engine": engine,
        "buffer": buffer,
        "trainer": Trainer(net, train),
        "env": env,
        "extractor": extractor,
        "net": net,
        "mcts": mcts,
        "train": train,
    }


# --- (c) the spans the hot path leaves ----------------------------------------


class TestProgramSpans:
    def test_rollout_and_ingest_spans_in_order(self, world, tracer):
        engine, buffer = world["engine"], world["buffer"]
        rows = 0
        while rows < 16:  # enough rows for the learner's test below
            mark = len(tracer.records())
            _, payload = engine.play_moves_device(4)
            cursor = buffer._pos
            added = buffer.ingest_payload(payload)
            rows += added
        records = tracer.records()[mark:]
        assert [r[1] for r in records] == [
            "rollout.dispatch",
            "rollout.wait",
            "rollout.fold",
            "replay.ingest_dispatch",
            "replay.ingest_wait",
            "replay.tree_update",
        ]
        args = {r[1]: r[6] for r in records}
        for name in ("rollout.dispatch", "rollout.wait"):
            assert args[name] == {"t": 4, "lanes": 4}
        # The fold carries the chunk's full searches, which its time
        # follows: the moves that ran the full simulation count.
        is_full = engine.last_trace["is_full"]
        assert args["rollout.fold"] == {
            "t": 4, "lanes": 4, "full_moves": int(is_full.sum()),
        }
        assert is_full.shape == (4,)
        # A tiny ring is one window wide: one window unless the rows
        # wrap its end, none when there are no rows.
        windows = (added > 0) + (cursor + added > buffer.capacity)
        assert args["replay.ingest_wait"] == {"rows": added, "windows": windows}
        assert args["replay.tree_update"] == {"rows": added}
        assert args["replay.ingest_dispatch"] is None

    def test_ingest_wait_counts_the_windows_it_wrote(
        self, tracer, tiny_train_config, monkeypatch
    ):
        """`windows` on `replay.ingest_wait`: how many windows of the
        ring the ingest's device loop wrote, worked out on the host from
        the count, the cursor and the window's rows (nothing more is
        fetched). Here a window is 4 rows wide (the block's 4, the
        module's least brought down to it) and the ring 10."""
        from alphatriangle_tpu.rl import device_buffer

        monkeypatch.setattr(device_buffer, "_WINDOW_ROWS_AT_LEAST", 4)
        buffer = device_buffer.DeviceReplayBuffer(
            tiny_train_config.model_copy(
                update={"BUFFER_CAPACITY": 10, "USE_PER": False}
            ),
            grid_shape=(1, 3, 4), other_dim=5, action_dim=12,
        )
        rng = np.random.default_rng(0)

        def block(lead, valid):
            n = int(np.prod(lead))
            policy = rng.random((n, 12), dtype=np.float32) + 0.01
            policy /= policy.sum(axis=1, keepdims=True)
            return {
                "grid": np.zeros((*lead, 1, 3, 4), np.float32),
                "other": np.zeros((*lead, 5), np.float32),
                "policy": policy.reshape(*lead, 12),
                "ret": np.zeros(lead, np.float32),
                "pw": np.ones(lead, np.float32),
                "mask": (np.arange(n) < valid).reshape(lead),
            }

        seen = []
        # (rows of the second block that are valid): with the first
        # block's 3 of 4, the ingests write 3, 9, 0, 3 and 15 rows.
        for more in (0, 6, None, 0, 12):
            mark = len(tracer.records())
            first = block((4,), 0 if more is None else 3)
            count, _ = buffer._ingest_blocks((first, block((2, 6), more or 0)))
            waits = [
                r[6] for r in tracer.records()[mark:]
                if r[1] == "replay.ingest_wait"
            ]
            assert len(waits) == 1 and waits[0]["rows"] == count
            seen.append((count, waits[0]["windows"]))
        # 3 rows at 0: one window. 9 at 3: rows 3-6, 7-9 (the ring's
        # end), then 0-1: three. None: none. 3 at 2: one. 15 into a
        # ring of 10 keeps 10, from slot 0 on: 4, 4 and 2: three.
        assert seen == [(3, 1), (9, 3), (0, 0), (3, 1), (15, 3)]
        assert buffer._pos == 0 and len(buffer) == 10

    def test_learner_group_spans_under_the_open_phase(self, world, tracer):
        trainer, buffer = world["trainer"], world["buffer"]
        assert len(buffer) >= 8
        with tracer.span("train"):
            samples = [
                buffer.sample(4, current_train_step=trainer.global_step)
                for _ in range(2)
            ]
            outs = trainer.train_steps_from(buffer, samples)
            for sample, (_, td) in zip(samples, outs):
                buffer.update_priorities(sample["indices"], td)
        instants = [r for r in tracer.records() if r[0] == "i"]
        records = [r for r in tracer.records() if r[0] == "X"]
        assert [r[1] for r in records] == [
            "replay.sample",
            "replay.sample",
            "learner.dispatch",
            "learner.wait",
            "learner.results",
            "replay.priorities",
            "replay.priorities",
            "train",
        ]
        train_id = records[-1][7]
        assert {r[8] for r in records[:-1]} == {train_id}
        args = {r[1]: r[6] for r in records}
        for name in ("learner.wait", "learner.results"):
            assert args[name] == {"k": 2}
        assert args["learner.dispatch"] == {
            "k": 2, "ring_read": ring_read(buffer.storage)
        }
        # The group's program is traced inside its first dispatch, and
        # the net says there which path its encoder layers took: a
        # learner's keep Flax's modules, on any backend.
        layers = world["net"].model_config.TRANSFORMER_LAYERS
        assert [(r[1], r[8], r[6]) for r in instants] == [
            (
                "net.encoder",
                records[2][7],
                {"fused_layers": 0, "flax_layers": layers, "batch": 4, "seq": 12},
            )
        ]

    def test_the_dispatch_says_how_the_ring_is_read(
        self, tracer, monkeypatch, tiny_env_config, tiny_model_config,
        tiny_train_config,
    ):
        """`ring_read` on `learner.dispatch`: the arrays the group's
        program reads through the view of sublane tiles and those it
        reads as they are, by the shape rule `read_rows` itself goes by,
        on the dispatch that traces the program and on the next one,
        which only calls it. Here a ring 16 actions wide beside the tiny
        world's 12."""
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl import trainer as trainer_module
        from alphatriangle_tpu.rl.device_buffer import (
            DeviceReplayBuffer,
            read_rows,
        )
        from alphatriangle_tpu.rl.trainer import Trainer

        env = tiny_env_config.model_copy(
            update={"ROWS": 4, "PLAYABLE_RANGE_PER_ROW": [(0, 4)] * 4}
        )
        assert env.action_dim == 16
        train = tiny_train_config.model_copy(
            update={"BUFFER_CAPACITY": 32, "MIN_BUFFER_SIZE_TO_TRAIN": 8}
        )
        trainer = Trainer(NeuralNetwork(tiny_model_config, env, seed=2), train)
        buffer = DeviceReplayBuffer(
            train,
            grid_shape=(tiny_model_config.GRID_INPUT_CHANNELS, 4, 4),
            other_dim=tiny_model_config.OTHER_NN_INPUT_FEATURES_DIM,
            action_dim=16,
            seed=0,
        )
        rng = np.random.default_rng(1)
        policy = rng.random((12, 16), dtype=np.float32) + 0.01
        buffer.add_dense(
            rng.integers(-1, 2, size=(12, *buffer.storage["grid"].shape[1:])),
            rng.random((12, buffer.storage["other_features"].shape[1])),
            policy / policy.sum(axis=1, keepdims=True),
            rng.normal(size=12),
        )
        want = ring_read(buffer.storage)
        assert want["in_place"] == ["policy_target"]
        traces = []  # `read_rows` runs when the program is traced

        def counted(storage, idx):
            traces.append(ring_read(storage))
            return read_rows(storage, idx)

        monkeypatch.setattr(trainer_module, "read_rows", counted)
        for _ in range(2):  # traced, then only called
            trainer.train_steps_from(buffer, [buffer.sample(4, 0)])
        assert traces == [want]
        assert [r[6] for r in tracer.records() if r[1] == "learner.dispatch"] == [
            {"k": 1, "ring_read": want}
        ] * 2

    def test_host_batches_group_has_the_same_three(self, world, tracer):
        trainer = world["trainer"]
        rng = np.random.default_rng(0)
        n = 4
        batch = {
            "grid": rng.integers(-1, 2, size=(n, 1, 3, 4)).astype(np.float32),
            "other_features": rng.random(
                (n, world["extractor"].other_dim), dtype=np.float32
            ),
            "policy_target": np.full((n, 12), 1 / 12, np.float32),
            "value_target": np.zeros(n, np.float32),
            "weights": np.ones(n, np.float32),
        }
        trainer.train_steps([batch])
        spans = [r for r in tracer.records() if r[0] == "X"]
        assert [r[1] for r in spans] == [
            "learner.dispatch", "learner.wait", "learner.results"
        ]
        assert spans[0][6] == {"k": 1}


# --- (d) the names in the lowered programs ------------------------------------


def _lowered_text(jitted, *args) -> str:
    return jitted.lower(*args).as_text(debug_info=True)


def _phases(*prefixes) -> list[str]:
    return [p for p in PHASES if p.startswith(prefixes)]


# What only a stack with state-space layers and experts in a latent has.
SSM_PHASES = (
    "net/trunk/state_space", "net/trunk/state_space/scan", "net/trunk/latent_proj",
)


class TestPhaseNamesInPrograms:
    def test_chunk_program(self, world):
        engine = world["engine"]
        text = _lowered_text(
            engine._chunk_fn(2)._jit_fn,
            engine.net.variables,
            engine._carry,
            jnp.int32(0),
        )
        # `rollout/promote` is the subtree-reuse chunk's, `net/trunk*`
        # the chunk's of a net with a decoder stack (both below).
        wanted = set(_phases("rollout/", "search/", "gumbel/", "net/"))
        wanted.remove("rollout/promote")
        wanted -= set(_phases("net/trunk"))
        assert {p for p in wanted if p not in text} == set()
        assert "net/trunk" not in text
        # The attention's phase lies inside the encoder's and wins (the
        # Flax path's; a fused layer is one call under `net/encoder`).
        assert "net/encoder/attention" in wanted
        assert profiling.phase_of(
            "jit(chunk)/search/evaluate/net/encoder/TransformerEncoderLayer_0/"
            "MultiHeadDotProductAttention_0/net/encoder/attention/"
            "dot_general"
        ) == "net/encoder/attention"
        assert profiling.phase_of(
            "jit(chunk)/search/evaluate/net/encoder/TransformerEncoderLayer_0/"
            "jit(encoder_layer)/encoder_layer/pallas_call"
        ) == "net/encoder"

    @pytest.mark.parametrize(
        "stack,absent",
        [
            (
                dict(
                    num_key_value_heads=1, sliding_window=4,
                    layer_types=["sliding_attention", "full_attention"],
                ),
                {"net/trunk/linear_attn", "net/trunk/linear_attn/scan",
                 "net/trunk/latent_attn", *SSM_PHASES},
            ),
            (
                dict(
                    num_key_value_heads=2, norm_position="pre", qk_norm="l2",
                    rope_layers="latent", kv_lora_rank=8, qk_nope_head_dim=16,
                    qk_rope_head_dim=8, v_head_dim=16, linear_chunk=16,
                    n_group=2, topk_group=1,
                    layer_types=["linear_attention", "latent_attention"],
                ),
                {"net/trunk/attn_window", "net/trunk/attn_full", *SSM_PHASES},
            ),
            (
                dict(
                    num_key_value_heads=1, norm_position="pre", qk_norm="none",
                    mamba_num_heads=4, mamba_head_dim=8, ssm_state_size=8,
                    n_groups=2, chunk_size=8, moe_latent_size=8,
                    mlp_hidden_act="relu2", router_bias=True,
                    layer_types=["state_space", "none", "full_attention"],
                    mlp_layer_types=["none", "sparse", "none"],
                ),
                {"net/trunk/attn_window", "net/trunk/linear_attn",
                 "net/trunk/linear_attn/scan", "net/trunk/latent_attn",
                 "net/trunk/dense_mlp"},
            ),
        ],
        ids=["softmax", "hybrid", "state_space"],
    )
    def test_trunk_phases_in_the_chunk_of_a_decoder_stack(
        self, world, tiny_mcts_config, stack, absent
    ):
        """Each stack's chunk carries the phases of the layers it has,
        and the three stacks together every `net/trunk` phase."""
        from alphatriangle_tpu.config import TrunkConfig
        from alphatriangle_tpu.features.core import get_feature_extractor
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine

        trunk = TrunkConfig(**{
            **dict(
                hidden_size=32, num_attention_heads=2,
                head_dim=16, intermediate_size=48, moe_intermediate_size=16,
                num_experts=4, num_experts_per_tok=2,
                mlp_layer_types=["dense", "sparse"], experts_held=(0, 2),
            ),
            **stack,
        })
        env = world["env"]
        model = world["net"].model_config.model_copy(update={"TRUNK": trunk})
        net = NeuralNetwork(model, env.cfg, seed=0)
        engine = SelfPlayEngine(
            env, get_feature_extractor(env, model), net, tiny_mcts_config,
            world["train"], seed=7,
        )
        text = _lowered_text(
            engine._chunk_fn(2)._jit_fn, net.variables, engine._carry, jnp.int32(0)
        )
        assert {p for p in _phases("net/trunk") if p not in text} == absent
        assert len(_phases("net/trunk")) == 13 and "net/encoder" not in text
        assert profiling.phase_of(
            "jit(chunk)/search/evaluate/net/trunk/net/trunk/linear_attn/"
            "net/trunk/linear_attn/scan/while/body/dot_general"
        ) == "net/trunk/linear_attn/scan"
        assert profiling.phase_of(
            "jit(chunk)/search/evaluate/net/trunk/net/trunk/state_space/"
            "net/trunk/state_space/scan/while/body/dot_general"
        ) == "net/trunk/state_space/scan"
        assert profiling.phase_of(
            "jit(chunk)/search/evaluate/net/trunk/net/trunk/experts/ragged_dot"
        ) == "net/trunk/experts"
        assert profiling.phase_of(
            "jit(chunk)/search/evaluate/net/trunk/add"
        ) == "net/trunk"

    def test_promote_phase_in_the_reuse_chunk(self, world, tiny_mcts_config):
        from alphatriangle_tpu.rl.self_play import SelfPlayEngine

        engine = SelfPlayEngine(
            world["env"],
            world["extractor"],
            world["net"],
            tiny_mcts_config.model_copy(update={"tree_reuse": True}),
            world["train"],
            seed=7,
        )
        text = _lowered_text(
            engine._chunk_fn(2)._jit_fn,
            engine.net.variables,
            engine._carry,
            jnp.int32(0),
        )
        assert "rollout/promote" in text and "gumbel/root" not in text

    def test_fused_learner_program(self, world):
        trainer, buffer = world["trainer"], world["buffer"]
        text = _lowered_text(
            trainer._from_fn._jit_fn,
            trainer.state,
            buffer.storage,
            np.zeros((2, 4), np.int32),
            np.ones((2, 4), np.float32),
        )
        wanted = set(_phases("learner/", "net/")) - set(_phases("net/trunk"))
        wanted.remove("learner/backward")  # drawn by autodiff:
        assert "transpose(jvp(learner/forward_loss))" in text
        # A step taken whole, of a net without routers, draws neither.
        blocked = {"learner/block", "learner/accumulate", "learner/router_bias"}
        assert {p for p in wanted if p not in text} == blocked
        assert profiling.phase_of(
            "jit(f)/transpose(jvp(learner/forward_loss))/net/encoder/dot_general"
        ) == "learner/backward"

    def test_learner_program_of_a_routed_trunk_taken_in_blocks(self, world):
        """The phases a blocked step of a routed decoder stack adds, in
        the program `Trainer.train_steps_from` dispatches; and where the
        reader puts the operations of its backward pass."""
        from alphatriangle_tpu.config import TrunkConfig
        from alphatriangle_tpu.nn.network import NeuralNetwork
        from alphatriangle_tpu.rl.trainer import Trainer

        trunk = TrunkConfig(
            hidden_size=32, num_attention_heads=2, num_key_value_heads=2,
            intermediate_size=48,
            moe_intermediate_size=16, num_experts=4, num_experts_per_tok=2,
            layer_types=["latent_attention"] * 2,
            mlp_layer_types=["dense", "sparse"], experts_held=(0, 2),
            kv_lora_rank=8, q_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
            v_head_dim=8, norm_position="pre", rope_layers="latent",
            router_bias=True, latent_gate=False, learner_block_boards=2,
        )
        env = world["env"]
        model = world["net"].model_config.model_copy(
            update={"TRUNK": trunk, "REMAT": True}
        )
        trainer = Trainer(NeuralNetwork(model, env.cfg, seed=0), world["train"])
        text = _lowered_text(
            trainer._from_fn._jit_fn,
            trainer.state,
            world["buffer"].storage,
            np.zeros((1, 4), np.int32),
            np.ones((1, 4), np.float32),
        )
        wanted = set(_phases("learner/")) | {
            "net/trunk", "net/trunk/latent_attn", "net/trunk/dense_mlp",
            "net/trunk/router", "net/trunk/experts", "net/trunk/shared_expert",
        }
        wanted.remove("learner/backward")
        assert {p for p in wanted if p not in text} == set()
        backward = (
            "jit(f)/while/body/learner/block/transpose(jvp(learner/forward_loss))/"
            "net/trunk/checkpoint/rematted_computation/net/trunk/experts/ragged_dot"
        )
        assert profiling.phase_of(backward) == "learner/backward"
        assert profiling.forward_phase_of(backward) == "net/trunk/experts"
        assert profiling.phase_of(
            "jit(f)/while/body/learner/block/jvp(learner/forward_loss)/net/trunk/"
            "net/trunk/latent_attn/dot_general"
        ) == "net/trunk/latent_attn"
        assert profiling.phase_of("jit(f)/while/body/learner/block/dynamic_slice") == (
            "learner/block"
        )
        events = [
            ("%a = f32[] fusion()", 0, 3_000_000_000),
            ("%b = f32[] fusion()", 0, 1_000_000_000),
        ]
        names = {"%a": backward, "%b": "jit(f)/learner/router_bias/add"}
        assert profiling.phase_seconds(events, names) == {
            "learner/backward": 3.0, "learner/router_bias": 1.0, "other": 0.0
        }
        assert profiling.backward_seconds(events, names) == {"net/trunk/experts": 3.0}

    def test_ingest_program(self, world):
        buffer = world["buffer"]
        _, payload = world["engine"].play_moves_device(4)
        text = _lowered_text(
            buffer._ingest_jit,
            buffer.storage,
            jnp.int32(0),
            (payload["mat"], payload["flush"]),
        )
        assert _phases("replay/") == ["replay/ingest_scatter"]
        assert "replay/ingest_scatter" in text

    def test_the_three_programs_are_the_parents(self, world, monkeypatch):
        """The count on `rollout.fold` is a host sum of an array the
        fetch already brought: the chunk, the fused group and the ingest
        lower to the text they had on the parent commit (5be2e37, where
        these digests were taken with this test's code), so the compile
        caches hit. The chunk is a fresh engine's with the stat-pack
        off, whatever an earlier test of this process left switched on
        (`set_device_stats` is the process's, and it adds outputs)."""
        import hashlib

        from alphatriangle_tpu.rl.self_play import SelfPlayEngine
        from alphatriangle_tpu.telemetry.device_stats import DEVICE_STATS_ENV

        monkeypatch.setenv(DEVICE_STATS_ENV, "0")
        engine = SelfPlayEngine(
            world["env"], world["extractor"], world["net"], world["mcts"],
            world["train"], seed=7,
        )
        assert not engine.device_stats
        trainer, buffer = world["trainer"], world["buffer"]
        _, payload = world["engine"].play_moves_device(4)
        texts = {
            "chunk": engine._chunk_fn(2)._jit_fn.lower(
                engine.net.variables, engine._carry, jnp.int32(0)
            ),
            "group": trainer._from_fn._jit_fn.lower(
                trainer.state,
                buffer.storage,
                np.zeros((2, 4), np.int32),
                np.ones((2, 4), np.float32),
            ),
            "ingest": buffer._ingest_jit.lower(
                buffer.storage, jnp.int32(0), (payload["mat"], payload["flush"])
            ),
        }
        digests = {
            name: hashlib.sha256(lowered.as_text().encode()).hexdigest()[:16]
            for name, lowered in texts.items()
        }
        assert digests == {
            "chunk": "1ea86bcbac2ad792",
            "group": "b87c1a12373f0a77",
            "ingest": "3bff02d6c77e22d5",
        }

    def test_every_phase_is_held_by_some_test_above(self):
        covered = _phases(
            "rollout/", "search/", "gumbel/", "net/", "learner/", "replay/"
        )
        assert covered == list(PHASES)


# --- (e) the phase reader -----------------------------------------------------


OP_NAMES = {
    "%fusion.1": "jit(chunk)/while/body/search/descend/dot_general",
    "%fusion.2": "jit(chunk)/while/body/search/evaluate/net/encoder/dot_general",
    "%fusion.3": "jit(chunk)/while/body/search/evaluate/search/expand/gather",
    "%fusion.4": "jit(f)/transpose(jvp(learner/forward_loss))/net/conv/conv",
    "%fusion.5": "jit(f)/jvp(learner/forward_loss)/learner/td/reduce_sum",
    "%copy.9": "jit(f)/convert_element_type",
    "%while.7": "jit(chunk)/while",
}


class TestPhaseSeconds:
    def test_containers_innermost_transpose_and_other(self):
        ms = 1_000_000
        events = [
            ("%while.7 = (s32[]) while((s32[]) %tuple.1)", 0, 100 * ms),
            ("%conditional.2 = f32[4] conditional(...)", 0, 50 * ms),
            ("%call.3 = f32[4] call(f32[4] %x)", 0, 25 * ms),
            ("%fusion.1 = f32[4] fusion(...)", 0, 10 * ms),
            ("%fusion.1 = f32[4] fusion(...)", 20, 10 * ms),
            ("%fusion.2 = f32[4] fusion(...)", 40, 30 * ms),
            ("%fusion.3 = f32[4] fusion(...)", 50, 5 * ms),
            ("%fusion.4 = f32[4] fusion(...)", 60, 7 * ms),
            ("%fusion.5 = f32[4] fusion(...)", 70, 2 * ms),
            ("%copy.9 = f32[4] copy(...)", 80, 3 * ms),
            ("%unknown.1 = f32[4] add(...)", 90, 4 * ms),
        ]
        seconds = profiling.phase_seconds(events, OP_NAMES)
        assert seconds == pytest.approx(
            {
                "search/descend": 0.020,
                "search/expand": 0.005,
                "net/encoder": 0.030,
                "learner/td": 0.002,
                "learner/backward": 0.007,
                "other": 0.007,
            }
        )
        assert list(seconds)[-1] == "other"
        # In the order of `telemetry/phases.py`.
        named = [p for p in seconds if p != "other"]
        assert named == [p for p in PHASES if p in named]

    def test_an_op_name_as_the_event_name(self):
        seconds = profiling.phase_seconds(
            [("jit(f)/learner/optimizer/mul", 0, 2_000_000)], {}
        )
        assert seconds == pytest.approx({"learner/optimizer": 0.002, "other": 0})

    def test_parse_op_names_and_inheritance(self):
        text = "\n".join(
            [
                "HloModule jit_f, is_scheduled=true",
                "%fused_computation (p: f32[4]) -> f32[4] {",
                "  %p = f32[4]{0} parameter(0)",
                '  ROOT %add.1 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/learner/gather/add"}',
                "}",
                "ENTRY %main (x: f32[4]) -> f32[4] {",
                '  %x = f32[4]{0} parameter(0), metadata={op_name="x"}',
                "  %copy.1 = f32[4]{0} copy(%x), metadata={op_name=\"storage['policy_target']\"}",
                "  %bitcast.2 = f32[4]{0} bitcast(%copy.1)",
                '  %fusion.3 = f32[4]{0} fusion(%bitcast.2), kind=kLoop, calls=%fused_computation, metadata={op_name="jit(f)/learner/gather/gather"}',
                "  ROOT %copy.4 = f32[4]{0} copy(%fusion.3)",
                "}",
            ]
        )
        names = profiling.parse_op_names(text)
        assert names["%fusion.3"] == "jit(f)/learner/gather/gather"
        # The compiler's own copies: from the user, through a bitcast;
        # else from the operand. An argument's name is no place, on the
        # parameter or on the copy of it (the learner's 4.3 GB one).
        assert names["%copy.1"] == names["%copy.4"] == names["%fusion.3"]
        assert names.get("%x") != "x"
        assert profiling.phase_of(names["%copy.1"]) == "learner/gather"


class TestIdleGapSeconds:
    """`profiling.idle_gap_seconds`: a program's idle gaps on `XLA
    Modules` put down to the `at:` span that covered them."""

    MS = 1_000_000

    def test_gaps_go_to_the_innermost_covering_span(self):
        ms = self.MS
        modules = [
            ("jit_chunk(1)", 0, 100 * ms),
            ("jit_ingest(2)", 104 * ms, 3 * ms),  # inside the chunk's gap
            ("jit_chunk(1)", 114 * ms, 100 * ms),
            ("jit_chunk(1)", 220 * ms, 100 * ms),
        ]
        host = [
            ("rollout", 90 * ms, 13 * ms),  # a phase around the three below
            ("rollout.wait", 91 * ms, 10 * ms),  # 1 ms of it in the gap
            ("rollout.fold", 101 * ms, 2 * ms),
            ("replay.ingest_wait", 104 * ms, 4 * ms),
            ("rollout.dispatch", 111 * ms, 5 * ms),  # 3 ms in the gap
            ("rollout.fold", 214 * ms, 4 * ms),
        ]
        gaps = profiling.idle_gap_seconds(modules, host)
        assert list(gaps) == ["jit_chunk"]  # one run of the ingest: no gap
        assert gaps["jit_chunk"] == pytest.approx(
            {
                "rollout.fold": 0.006,
                "replay.ingest_wait": 0.004,
                "rollout.dispatch": 0.003,
                "rollout.wait": 0.001,
                # 103-104 and 108-111 of the first gap, 2 of the second.
                "other": 0.006,
            }
        )
        # Busiest first, `other` last; the gaps are all accounted for.
        assert list(gaps["jit_chunk"]) == [
            "rollout.fold", "replay.ingest_wait", "rollout.dispatch",
            "rollout.wait", "other",
        ]
        assert sum(gaps["jit_chunk"].values()) == pytest.approx(0.020)

    def test_no_span_no_gap(self):
        ms = self.MS
        two = [("jit_f(1)", 0, ms), ("jit_f(1)", 3 * ms, ms)]
        assert profiling.idle_gap_seconds(two, []) == {
            "jit_f": {"other": 0.002}
        }
        # The program that ran longest comes first.
        small = [("jit_g(2)", 1 * ms, 100), ("jit_g(2)", 2 * ms, 100)]
        assert list(profiling.idle_gap_seconds(small + two, [])) == [
            "jit_f", "jit_g"
        ]
        assert profiling.idle_gap_seconds(two[:1], []) == {}
        # Runs that touch or overlap leave nothing to put down.
        touching = [("jit_f(1)", 0, 2 * ms), ("jit_f(1)", 2 * ms, ms)]
        assert profiling.idle_gap_seconds(touching, []) == {
            "jit_f": {"other": 0.0}
        }


class TestProgramOpNames:
    def test_compile_cache_hands_out_live_executables(self, world):
        """`ProfileSession` writes `op_names.json` from these."""
        from alphatriangle_tpu.compile_cache import get_compile_cache

        world["engine"].play_moves_device(4)
        names = [n for n, _ in get_compile_cache().executables()]
        assert "self_play_chunk/t4" in names
        op_names = profiling.program_op_names()
        chunk = next(v for k, v in op_names.items() if "chunk" in k)
        phases = {profiling.phase_of(v) for v in chunk.values()}
        assert {"search/descend", "search/evaluate", "gumbel/root"} <= phases
