"""The TPU compiler sees every Pallas kernel, without a chip.

libtpu compiles for a chip that is described and not attached
(`jax.experimental.topologies`). Interpret-mode tests (tests/test_ops.py)
pin what the kernels compute; they cannot see what the compiler refuses
— a block that is not a whole (8, 128) tile, a scalar operand laid out
for VMEM, more VMEM than the scoped limit. The first four kernels had
passed every interpret-mode test and none compiled. These cases hold the
compiled-for-v5e property at the widths the chip runs: preset 3 (the
flagship, what `chip_smoke.py` executes on the chip) and preset 4's
400-simulation tree (the largest planes).

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
    "gather_rows", "backup_update", "per_sample", "subtree_promote",
    "encoder_layer", "delta_rule",
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


def _layer_case(shapes: dict) -> dict:
    case = chip_smoke.kernel_cases(shapes)[-2]
    assert case["name"] == "encoder_layer"
    return case


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
    case = chip_smoke.kernel_cases(shapes)[-1]
    assert case["name"] == "delta_rule"
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    text = _compiled_text(case, one_v5e_chip)
    assert "tpu_custom_call" in text and "delta_rule" in text


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


def test_nemotron_supers_chunk_compiles_at_its_published_widths(
    one_v5e_chip, monkeypatch
):
    """`nemotron-super-ep4`'s one-move chunk as the cell dispatches it
    (16 lanes, waves of 32, the net in blocks of `block_boards` 64):
    the described chip's compiler takes the state-space scan as this
    repo's kernel (PR 39), the latent experts' grouped products and top
    22 of 512 at their published widths, and the program fits: 9.03 GB
    of weights and under 4 GB of temporaries (3.66 at PR 38 with the
    scan in `jax.numpy`, 3.44 with the kernel; the ring's 0.85 GB stands
    beside them on the chip)."""
    import jax.numpy as jnp

    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.nn.model import AlphaTriangleNet
    from alphatriangle_tpu.nn.network import NeuralNetwork
    from alphatriangle_tpu.rl import SelfPlayEngine
    from chipbench import manifest
    from chipbench import reference_nemotron_h as plain

    cfg = manifest.load_json(manifest.HERE / "configs" / "nemotron-super-ep4.json")
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
    assert 9.0e9 < memory.argument_size_in_bytes < 9.1e9
    assert memory.temp_size_in_bytes < 4.0e9
    text = compiled.as_text()
    # The scan is one custom call a layer wherever a block of boards is
    # evaluated, each the block's whole `(b, 252, 128 x 64)` y, beside
    # the compiler's own grouped products; `chunked`'s decays and
    # weights `f32[64,2,128,128,128]` are gone.
    calls = [line for line in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in line]
    scans = [line for line in calls if "state_space_scan" in line]
    assert scans and len(scans) % 5 == 0
    assert all(re.search(r"= f32\[(16|64),252,8192\]", line) for line in scans)
    assert all("ragged" in line for line in calls if line not in scans)
    assert "f32[64,2,128,128,128]" not in text


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
