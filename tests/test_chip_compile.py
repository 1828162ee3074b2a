"""The TPU compiler sees every Pallas kernel, without a chip.

libtpu compiles for a chip that is described and not attached
(`jax.experimental.topologies`). Interpret-mode tests (tests/test_ops.py)
pin what the kernels compute; they cannot see what the compiler refuses
— a block that is not a whole (8, 128) tile, a scalar operand laid out
for VMEM, more VMEM than the scoped limit. These cases hold the
compiled-for-v5e property at the widths the chip runs: preset 3 (the
flagship, what `chip_smoke.py` executes on the chip) and preset 4's
400-simulation tree (the largest planes); and the search's and the
replay programs' own ops as XLA lowers them, at the same two.

A compile that passes here is not a chip run: nothing executes.
"""

import functools
import os
import re

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import pytest  # noqa: E402

import chip_smoke  # noqa: E402
from alphatriangle_tpu.config.presets import baseline_preset  # noqa: E402

KERNELS = (
    "backup_update", "encoder_layer", "delta_rule", "gated_delta_net",
    "grouped_experts",
)


@pytest.fixture(scope="module")
def v5e_2x2():
    """The four described chips of one host."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu in this image
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    return topo.devices


@pytest.fixture(scope="module")
def one_v5e_chip(v5e_2x2):
    from jax.sharding import SingleDeviceSharding

    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it off here."""
    from jax._src import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cases():
    return {
        preset: {
            case["name"]: case
            for case in chip_smoke.kernel_cases(
                chip_smoke.kernel_shapes(baseline_preset(preset))
            )
        }
        for preset in (3, 4)
    }


def _compiled_text(case: dict, one_v5e_chip) -> str:
    """`case`'s Pallas lowering compiled for the described chip."""
    operands = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip),
        jax.eval_shape(case["operands"], jax.random.PRNGKey(0)),
    )
    return (
        jax.jit(functools.partial(case["run"], "pallas"))
        .lower(*operands)
        .compile()
        .as_text()
    )


def _case(shapes: dict, name: str) -> dict:
    (case,) = [c for c in chip_smoke.kernel_cases(shapes) if c["name"] == name]
    return case


def _layer_case(shapes: dict) -> dict:
    return _case(shapes, "encoder_layer")


@pytest.mark.parametrize("preset", [3, 4])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(
    kernel, preset, cases, one_v5e_chip, monkeypatch
):
    # The dispatchers interpret the kernel unless the backend is a TPU;
    # the backend here is the CPU and the target is not.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_text(cases[preset][kernel], one_v5e_chip)
    assert "tpu_custom_call" in text


def _xla_op(name: str, preset: int, sharding):
    """(the op at `preset`'s shapes, its operands as described avals):
    B lanes of trees of N nodes x A actions searched W leaves at a time
    to depth D, the carried tree of `tree_reuse` at that budget, a ring
    of `capacity` rows sampled K x batch at a time."""
    import jax.numpy as jnp

    from alphatriangle_tpu.mcts.search import tree_geometry
    from alphatriangle_tpu.ops import (
        backup_update,
        gather_rows,
        per_sample,
        subtree_promote,
    )

    cfgs = baseline_preset(preset)
    mcts, train = cfgs["mcts"], cfgs["train"]
    b, a, d = train.SELF_PLAY_BATCH_SIZE, cfgs["env"].action_dim, mcts.max_depth
    n, w = tree_geometry(mcts)
    reuse_n, _ = tree_geometry(mcts.model_copy(update={"tree_reuse": True}))

    def aval(shape, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    wave = (aval((b, w), jnp.int32),) * 2 + (aval((b, w)),) * 2
    record = (aval((b, w, d), jnp.int32),) * 2 + (
        aval((b, w, d), jnp.bool_), aval((b, w, d)),
    )
    cap, k, batch = train.BUFFER_CAPACITY, train.FUSED_LEARNER_STEPS, train.BATCH_SIZE
    return {
        "gather_rows": (gather_rows, (aval((b, n, 6 * a)), aval((b, w), jnp.int32))),
        "backup_update": (backup_update, (aval((b, n, a)),) * 4 + wave + record),
        "per_sample": (
            lambda p, key: per_sample(p, cap, k, batch, key),
            (aval((cap + 1,)), aval((2,), jnp.uint32)),
        ),
        "subtree_promote": (
            functools.partial(subtree_promote, max_retained=reuse_n // 2, bfs_rounds=d),
            (aval((b, reuse_n, a)),) * 6
            + (aval((b, reuse_n), jnp.bool_), aval((b,), jnp.int32)),
        ),
    }[name]


@pytest.mark.parametrize("preset", [3, 4])
@pytest.mark.parametrize(
    "op", ["gather_rows", "backup_update", "per_sample", "subtree_promote"]
)
def test_xla_op_compiles_for_v5e(op, preset, one_v5e_chip):
    """Each of the search's and the replay programs' ops, as XLA lowers
    it by default for the described chip at the preset's shapes (512
    lanes): no Mosaic kernel, and what it reads, writes and keeps fits the chip's
    16 GB (the carried tree's promotion at preset 4 needs 14.7 of them).
    The descent's row gather rides the MXU: a one-hot product, no
    gather."""
    fn, operands = _xla_op(op, preset, one_v5e_chip)
    compiled = jax.jit(fn).lower(*operands).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" not in text
    memory = compiled.memory_analysis()
    assert (
        memory.argument_size_in_bytes
        + memory.output_size_in_bytes
        + memory.temp_size_in_bytes
    ) < 16e9
    if op == "gather_rows":
        assert "convolution(" in text and " gather(" not in text


def test_encoder_layer_compiles_at_252_tokens(one_v5e_chip, monkeypatch):
    """Preset 5's board: 252 tokens, a wave of 1,024 lanes x 32 leaves,
    8 boards a grid step."""
    shapes = chip_smoke.kernel_shapes(baseline_preset(5))
    assert (shapes["leaves"], shapes["tokens"]) == (32768, 252)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "tpu_custom_call" in _compiled_text(_layer_case(shapes), one_v5e_chip)


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_delta_rule_compiles_at_the_cells_shape(one_v5e_chip, monkeypatch, dtype):
    """`ling-flash-rollout`'s recurrence: a block of 64 boards x 32
    heads of 128 over 252 tokens (the last chunk four tokens short),
    bfloat16 operands as the configuration states them, and float32
    ones (every product then at the highest precision)."""
    shapes = {
        **chip_smoke.kernel_shapes(baseline_preset(3)), "compute_dtype": dtype,
    }
    assert shapes["recurrence"] == {
        "boards": 64, "heads": 32, "tokens": 252, "head_dim": 128, "chunk": 64,
    }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_text(_case(shapes, "delta_rule"), one_v5e_chip)
    assert "tpu_custom_call" in text and "delta_rule" in text


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_gated_delta_net_compiles_at_the_cells_shape(one_v5e_chip, monkeypatch, dtype):
    """`gigachat35-rollout`'s recurrence: a block of 64 boards x 32 key
    heads of 128, each read by two value heads of 128, over 252 tokens
    (the last chunk four tokens short), v read from `[q | k | v]`;
    bfloat16 operands as the configuration states them, and float32
    ones (every product then at the highest precision)."""
    shapes = {
        **chip_smoke.kernel_shapes(baseline_preset(3)), "compute_dtype": dtype,
    }
    assert shapes["gdn"] == {
        "boards": 64, "key_heads": 32, "group": 2, "tokens": 252,
        "head_dim": 128, "chunk": 64,
    }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_text(_case(shapes, "gated_delta_net"), one_v5e_chip)
    assert re.search(r"gated_delta_net[.0-9]* = f32\[64,252,8192\]", text)


@pytest.mark.parametrize("activation", ["GELU", "SiLU", "Tanh", "Sigmoid"])
def test_encoder_layer_compiles_with_every_activation(
    one_v5e_chip, monkeypatch, activation
):
    """`layer_path` does not look at the activation: each one the
    config allows lowers inside the kernel (ReLU is the flagship's,
    compiled above), at a root batch of the flagship."""
    from alphatriangle_tpu.nn.model import _ACTIVATIONS

    assert set(_ACTIVATIONS) == {"ReLU", "GELU", "SiLU", "Tanh", "Sigmoid"}
    shapes = {
        **chip_smoke.kernel_shapes(baseline_preset(3)),
        "leaves": 512,
        "activation": activation,
    }
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert "tpu_custom_call" in _compiled_text(_layer_case(shapes), one_v5e_chip)


@pytest.mark.parametrize("chips,fused", [(4, False), (1, True)])
def test_lane_sharded_chunk_compiles_for_v5e(
    v5e_2x2, monkeypatch, chips, fused
):
    """The rollout chunk of the flagship's net with its lanes sharded
    over dp on the described 2x2 (`SelfPlayEngine(mesh=)`, the
    megastep's rollout half; a small tree, one move). The compiler
    refuses to lower a Mosaic call it would have to partition, so the
    net keeps Flax's layers there; on one chip every layer is the
    kernel, and the attention kernel it replaced is in neither."""
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl import SelfPlayEngine

    cfgs = chip_smoke.flagship_configs()
    env = TriangleEnv(cfgs["env"])
    net = NeuralNetwork(cfgs["model"], cfgs["env"], seed=0)
    engine = SelfPlayEngine(
        env,
        get_feature_extractor(env, cfgs["model"]),
        net,
        cfgs["mcts"].model_copy(
            update={
                "max_simulations": 8,
                "fast_simulations": None,
                "mcts_batch_size": 4,
            }
        ),
        chip_smoke._train_config(cfgs["train"], SELF_PLAY_BATCH_SIZE=8),
        seed=0,
    )
    # The engine as `mesh=` would place it, on devices that are
    # described and cannot hold an array.
    mesh = Mesh(np.array(v5e_2x2[:chips]), ("dp",))
    engine._lane_sharding = NamedSharding(mesh, PartitionSpec("dp"))
    engine._replicated = NamedSharding(mesh, PartitionSpec())

    def described(x, sharding):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = (
        jax.jit(functools.partial(engine._chunk, 1))
        .lower(
            jax.tree_util.tree_map(
                lambda x: described(x, engine._replicated),
                engine._inference_variables(net.variables, 0),
            ),
            jax.tree_util.tree_map(
                described, engine._carry, engine._carry_shardings()
            ),
            described(jnp.int32(0), engine._replicated),
        )
        .compile()
    )
    text = compiled.as_text()
    assert ("encoder_layer" in text) == fused
    assert ("tpu_custom_call" in text) == fused
    assert "encoder_attention" not in text


# A cell's one-move chunk as it dispatches it (16 lanes, waves of 32,
# the net in blocks of `block_boards` 64) at its published widths: the
# described chip's compiler takes the recurrence as this repo's kernel,
# one custom call a layer wherever a block of boards is evaluated, each
# the block's whole `(b, 252, 8192)` output, and the experts' rounds as
# two calls a layer, each writing its round's rows in bfloat16 (`up` and
# `down`: rows at blocks of 16 and 64 boards, width); and the program
# fits (`weights`: its argument bytes; `temp_below`; the ring's 0.85 GB
# stands beside them on the chip). `absent`: (pattern, what the line
# must also hold) of what the kernels replaced.
PUBLISHED_WIDTH_CHUNKS = {
    # The state-space scan in five Mamba-2 layers; the latent
    # experts' top 22 of 512 in five. Temporaries: 3.66 GB with the
    # scan in `jax.numpy`, 3.44 with its kernel and `ragged_dot`'s
    # float32 products, 2.89 with the experts' kernels too. `chunked`'s
    # decays and weights `f32[64,2,128,128,128]` are gone, and no
    # float32 grouped product is left.
    "nemotron-super-ep4": {
        "reference": "reference_nemotron_h",
        "weights": (9.0e9, 9.1e9),
        "temp_below": 3.0e9,
        "kernel": "state_space_scan",
        "layers": 5,
        "up": ("44352|177408", 2688),
        "down": ("44352|177408", 1024),
        "absent": ((r"f32\[64,2,128,128,128\]", ""), (r"f32\[177408,2688\]", "")),
    },
    # The gated-delta-net recurrence in four layers; YaRN, the
    # gate a channel and the sandwich norms. Temporaries: 3.84 GB with
    # the recurrence's and the experts' kernels, 4.12 with `ragged_dot`'s
    # float32 products instead, 3.90 with `chunked_scalar` and those,
    # 2.25 at blocks of 32 then (PERF.md section 4). `chunked_scalar`'s
    # pairwise decays and products `f32[4,2048,2,64,64]` are gone; the
    # shared expert's float32 `[16128, 2048]` stays, the routed ones'
    # does not.
    "gigachat35-ep16": {
        "reference": "reference_gigachat35",
        "weights": (9.4e9, 9.5e9),
        "temp_below": 3.9e9,
        "kernel": "gated_delta_net",
        "layers": 4,
        "up": ("4032|16128", 2048),
        "down": ("4032|16128", 7168),
        "absent": (
            (r"f32\[4,2048,2,64,64\]", ""),
            (r"= f32\[16128,2048\]", "net/trunk/experts"),
        ),
    },
}


@pytest.mark.parametrize("config", sorted(PUBLISHED_WIDTH_CHUNKS))
def test_the_chunk_compiles_at_its_published_widths(one_v5e_chip, monkeypatch, config):
    import importlib

    import jax.numpy as jnp

    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.nn.model import AlphaTriangleNet
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl import SelfPlayEngine
    from chipbench import manifest

    want = PUBLISHED_WIDTH_CHUNKS[config]
    plain = importlib.import_module(f"chipbench.{want['reference']}")
    cfg = manifest.load_json(manifest.HERE / "configs" / f"{config}.json")
    configs = manifest.program_configs(cfg)
    model = configs["model"].model_copy(
        update={"TRUNK": TrunkConfig(**plain.trunk_settings(cfg))}
    )
    assert model.TRUNK.block_boards == 64
    env_cfg = configs["env"]
    module = AlphaTriangleNet(model, env_cfg.action_dim)
    shapes = jax.eval_shape(
        lambda k: module.init(
            k, jnp.zeros((1, 1, env_cfg.ROWS, env_cfg.COLS)),
            jnp.zeros((1, model.OTHER_NN_INPUT_FEATURES_DIM)), train=False,
        ),
        jax.random.PRNGKey(0),
    )
    net = NeuralNetwork(model, env_cfg, variables=shapes)
    env = TriangleEnv(env_cfg)
    engine = SelfPlayEngine(
        env, get_feature_extractor(env, model), net, configs["mcts"],
        configs["train"], seed=0,
    )

    def described(x):
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    compiled = (
        jax.jit(functools.partial(engine._chunk, 1))
        .lower(
            jax.tree_util.tree_map(
                described, engine._inference_variables(net.variables, 0)
            ),
            jax.tree_util.tree_map(described, engine._carry),
            described(jnp.int32(0)),
        )
        .compile()
    )
    memory = compiled.memory_analysis()
    low, high = want["weights"]
    assert low < memory.argument_size_in_bytes < high
    assert memory.temp_size_in_bytes < want["temp_below"]
    text = compiled.as_text()
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    rules = [line for line in calls if want["kernel"] in line]
    assert rules and len(rules) % want["layers"] == 0
    assert all(re.search(r"= f32\[(16|64),252,8192\]", line) for line in rules)
    ups = [line for line in calls if re.match(r"\s*%grouped_experts_up[.0-9]* =", line)]
    downs = [line for line in calls if re.match(r"\s*%grouped_experts_down[.0-9]* =", line)]
    assert ups and len(ups) % want["layers"] == 0 and len(downs) == len(ups)
    for lines, (rows, width) in ((ups, want["up"]), (downs, want["down"])):
        assert all(re.search(rf"= bf16\[({rows}),{width}\]", line) for line in lines)
    assert len(calls) == len(rules) + 2 * len(ups)
    assert "ragged" not in text
    for pattern, scope in want["absent"]:
        assert not [
            line for line in text.splitlines()
            if re.search(pattern, line) and scope in line
        ]


# ring rows, (grid, other features, actions), the blocks' leading dims
INGEST_SHAPES = {
    # flagship-rollout: a chunk of 16 moves x 512 lanes, 49,152 candidates
    "flagship-chunk": (3_000_000, ((1, 8, 15), 30, 360), ((16, 512), (16, 512, 5))),
    # k-exaone-rollout, ling-flash-rollout: one move of 16 lanes, 96 candidates
    "trunk-cells-chunk": (250_000, ((1, 12, 21), 30, 756), ((1, 16), (1, 16, 5))),
    # flagship-learner's ring fill: 125,000 rows, all valid
    "fill-block": (3_000_000, ((1, 8, 15), 30, 360), ((62_500,), (62_500,))),
    # `add_dense` of one row
    "one-row": (3_000_000, ((1, 8, 15), 30, 360), ((1,),)),
}


@pytest.mark.parametrize("name", sorted(INGEST_SHAPES))
def test_the_ingest_writes_the_ring_where_it_lies(one_v5e_chip, name):
    """The chip lays the ring's rows along its lanes. The scatter the
    ingest was made XLA re-lay-out `policy_target` and `grid` whole,
    there and back, on every ingest (43 of its 60 ms at the flagship's
    ring, all 8 ms at the trunk cells'), and a window narrower than a
    lane tile does the same. Held here: the compiled ingest copies no
    whole-ring array, scatters nothing into one, and returns the ring
    in the buffers it was given."""
    import re

    import jax.numpy as jnp

    from alphatriangle_tpu.rl.device_buffer import ring_scatter

    cap, (grid, other, actions), leads = INGEST_SHAPES[name]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)

    storage = {
        "grid": shape((cap + 1, *grid), jnp.int8),
        "other_features": shape((cap + 1, other), jnp.float32),
        "policy_target": shape((cap + 1, actions), jnp.float32),
        "value_target": shape((cap + 1,), jnp.float32),
        "policy_weight": shape((cap + 1,), jnp.float32),
    }
    blocks = tuple(
        {
            "grid": shape((*lead, *grid), jnp.float32),
            "other": shape((*lead, other), jnp.float32),
            "policy": shape((*lead, actions), jnp.float32),
            "ret": shape(lead, jnp.float32),
            "pw": shape(lead, jnp.float32),
            "mask": shape(lead, jnp.bool_),
        }
        for lead in leads
    )
    compiled = (
        jax.jit(
            lambda ring, cursor, blocks: ring_scatter(ring, cursor, blocks, cap),
            donate_argnums=(0,),
        )
        .lower(storage, shape((), jnp.int32), blocks)
        .compile()
    )
    whole_ring = re.compile(rf"^\s*(?:ROOT )?%\S+ = \w+\[{cap + 1}[,\]]\S* (\w[\w-]*)\(")
    made = [
        m.group(1)
        for m in map(whole_ring.match, compiled.as_text().splitlines())
        if m
    ]
    assert "copy" not in made and "scatter" not in made, made
    assert "dynamic-update-slice" in made or "fusion" in made
    ring_bytes = (cap + 1) * (
        grid[0] * grid[1] * grid[2] + 4 * (other + actions + 2)
    )
    memory = compiled.memory_analysis()
    assert memory.alias_size_in_bytes >= ring_bytes
    assert memory.temp_size_in_bytes < ring_bytes // 8


# ring rows, (grid, other features, actions), the indices' shape
READ_SHAPES = {
    # flagship-learner: a fused group of 16 steps at batch 256
    "flagship-group": (3_000_000, ((1, 8, 15), 30, 360), (16, 256)),
    # one flagship step (`cli train` at FUSED_LEARNER_STEPS 1)
    "flagship-step": (3_000_000, ((1, 8, 15), 30, 360), (1, 256)),
    # glm-flash-learner's step, and the rollout trunk cells' ring
    "trunk-cells-step": (250_000, ((1, 12, 21), 30, 756), (1, 256)),
    # the dp-sharded ring's local gather: a quarter of a 12,000,000-row
    # ring and of the batch on each of four chips
    "dp4-shard-group": (3_000_000, ((1, 8, 15), 30, 360), (16, 64)),
}


@pytest.mark.parametrize("name", sorted(READ_SHAPES))
def test_the_learner_reads_the_ring_where_it_lies(one_v5e_chip, name):
    """The chip lays the ring's rows along its lanes, and a gather of
    whole rows 360 wide made XLA re-lay-out `policy_target` whole at
    the head of every learner group (13.6 ms and 4.3 GB of temporaries
    at the flagship's ring). Held here: for every array `ring_read`
    says is read in place, the compiled `read_rows` makes no whole-ring
    array but the parameter and its bitcast, and holds no temporaries
    to speak of. An array the rule leaves as it is may be copied: the
    trunk cells' `policy_target`, 756 wide, is (2.35 ms of a 2,120 ms
    step: PERF.md section 7), and the case says so."""
    import re

    import jax.numpy as jnp

    from alphatriangle_tpu.rl.device_buffer import read_rows, ring_read

    cap, (grid, other, actions), lead = READ_SHAPES[name]

    def shape(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_v5e_chip)

    storage = {
        "grid": shape((cap + 1, *grid), jnp.int8),
        "other_features": shape((cap + 1, other), jnp.float32),
        "policy_target": shape((cap + 1, actions), jnp.float32),
        "value_target": shape((cap + 1,), jnp.float32),
        "policy_weight": shape((cap + 1,), jnp.float32),
    }
    how = ring_read(storage)
    assert sorted(how["in_place"] + how["as_is"]) == sorted(storage)
    assert how["in_place"] == (["policy_target"] if actions % 8 == 0 else [])
    compiled = jax.jit(read_rows).lower(storage, shape(lead, jnp.int32)).compile()
    text = compiled.as_text()

    def made(ring):
        """The operations whose result is `ring` whole, under its own
        shape or the view's."""
        rows, width = ring.shape
        whole = re.compile(
            rf"^\s*(?:ROOT )?%\S+ = \w+\[{rows},(?:{width}|{width // 8},8)\]\S* "
            r"(\w[\w-]*)\("
        )
        return {m.group(1) for m in map(whole.match, text.splitlines()) if m}

    for array in how["in_place"]:
        assert made(storage[array]) == {"parameter", "bitcast"}, array
    ring_bytes = (cap + 1) * (
        grid[0] * grid[1] * grid[2] + 4 * (other + actions + 2)
    )
    temporaries = compiled.memory_analysis().temp_size_in_bytes
    if how["in_place"]:
        assert temporaries < ring_bytes // 8
    else:
        assert "copy" in made(storage["policy_target"])
        assert temporaries >= 4 * (cap + 1) * actions
