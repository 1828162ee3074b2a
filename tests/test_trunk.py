"""A decoder stack as the net's trunk (`ModelConfig.TRUNK`, nn/trunk.py):
what the program holds by itself. The comparison with the plain
reference lives beside it, in tests/chipbench/test_chipbench_k_exaone.py.
"""

import hashlib
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.config import ModelConfig, TrainConfig, TrunkConfig
from alphatriangle_tpu.nn import trunk
from alphatriangle_tpu.nn.model import AlphaTriangleNet
from alphatriangle_tpu.nn.network import NeuralNetwork

TINY = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, routed_scaling_factor=2.5,
    sliding_window=4,
    layer_types=["sliding_attention"] * 3 + ["full_attention", "sliding_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 4, experts_held=(2, 2),
)
# The published widths of the benchmark's k-exaone-ep8 (one chip's share).
PUBLISHED = dict(
    TINY, hidden_size=6144, num_attention_heads=64, num_key_value_heads=8,
    head_dim=128, intermediate_size=18432, moe_intermediate_size=2048,
    num_experts=128, num_experts_per_tok=8, sliding_window=128,
    experts_held=(0, 16),
)


@pytest.fixture(scope="module")
def tiny_trunk_model(tiny_model_config):
    return tiny_model_config.model_copy(update={"TRUNK": TrunkConfig(**TINY)})


def test_window_mask_against_a_hand_built_one():
    seen = trunk.causal_mask(12, 4)
    want = np.zeros((12, 12), bool)
    for i in range(12):
        for j in range(12):
            want[i, j] = j <= i and i - j < 4
    assert (seen == want).all()
    assert seen[3, 0] and not seen[4, 0]  # beyond the window, cell 0 is gone
    assert not seen[2, 3]  # and nothing ahead is seen
    full = trunk.causal_mask(12, None)
    assert full[11, 0] and (full == np.tril(np.ones((12, 12), bool))).all()


def test_a_query_beyond_the_window_does_not_read_cell_0():
    """Change cell 0's token: a sliding layer's output moves at cells
    0-3 and nowhere else; a full layer's moves at every cell."""
    cfg = TrunkConfig(**TINY)
    shapes = trunk.param_shapes(cfg)
    keys = jax.random.split(jax.random.PRNGKey(0), len(shapes))
    params = {
        name: jax.random.normal(k, shape) / np.sqrt(max(fan_in, 1)) + (fan_in == 0)
        for k, (name, (shape, fan_in)) in zip(keys, shapes.items())
    }
    x = jax.random.normal(jax.random.PRNGKey(1), (1, 12, 64))
    moved = x.at[0, 0].add(1.0)
    for layer, reach in ((0, 4), (3, 12)):
        p = trunk.layer_params(params, layer)
        sliding = cfg.layer_types[layer] == "sliding_attention"
        a = trunk.attention(p, x, cfg, sliding, jnp.float32)
        b = trunk.attention(p, moved, cfg, sliding, jnp.float32)
        differs = np.asarray(jnp.abs(a - b).max(axis=-1)[0] > 0)
        assert differs[:reach].all() and not differs[reach:].any(), layer


def test_rotary_positions_are_relative():
    """q.k after the turn depends on the distance of the two cells."""
    cos, sin = trunk.rotary_table(12, 16, 1e6)
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 16))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 16))
    qs = trunk.rotate(jnp.repeat(q, 12, axis=1), cos, sin)
    ks = trunk.rotate(jnp.repeat(k, 12, axis=1), cos, sin)
    scores = np.asarray(qs[0] @ ks[0].T)
    assert scores[5, 2] == pytest.approx(scores[9, 6], rel=1e-4)
    assert scores[5, 2] != pytest.approx(scores[5, 3], rel=1e-4)


def test_parameters_are_made_in_bfloat16_and_served_without_a_copy(
    tiny_trunk_model, tiny_env_config
):
    from alphatriangle_tpu.nn.precision import cast_params_for_inference

    model = tiny_trunk_model.model_copy(
        update={"PARAM_DTYPE": "bfloat16", "INFERENCE_PRECISION": "bfloat16",
                "COMPUTE_DTYPE": "bfloat16"}
    )
    net = NeuralNetwork(model, tiny_env_config, seed=0)
    leaves = jax.tree_util.tree_leaves(net.variables)
    assert {leaf.dtype for leaf in leaves} == {jnp.dtype("bfloat16")}
    assert set(net.variables) == {"params"}  # the counters are not state
    assert cast_params_for_inference(net.variables, model) is net.variables
    probs, values = net.evaluate_features(
        np.zeros((2, 1, 3, 4), np.float32),
        np.zeros((2, model.OTHER_NN_INPUT_FEATURES_DIM), np.float32),
    )
    assert np.isfinite(probs).all() and np.isfinite(values).all()


def test_a_trainer_at_the_published_widths_refuses_by_its_bytes(
    tiny_model_config, tiny_env_config, tiny_train_config, monkeypatch
):
    from alphatriangle_tpu.rl.trainer import Trainer
    from alphatriangle_tpu.telemetry.memory import BYTES_LIMIT_ENV

    model = tiny_model_config.model_copy(
        update={"TRUNK": TrunkConfig(**PUBLISHED), "PARAM_DTYPE": "bfloat16"}
    )
    module = AlphaTriangleNet(model, tiny_env_config.action_dim)
    shapes = jax.eval_shape(
        lambda k: module.init(
            k, jnp.zeros((1, 1, 3, 4)),
            jnp.zeros((1, model.OTHER_NN_INPUT_FEATURES_DIM)), train=False,
        ),
        jax.random.PRNGKey(0),
    )
    count = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(shapes))
    assert count == pytest.approx(3.477e9, rel=2e-3)
    net = NeuralNetwork(model, tiny_env_config, variables=shapes)
    monkeypatch.setenv(BYTES_LIMIT_ENV, str(16 * 2**30))  # one v5e chip
    with pytest.raises(ValueError, match=f"{8 * count:,} B of training state"):
        Trainer(net, tiny_train_config)


def test_the_search_counts_the_experts_assignments(
    tiny_trunk_model, tiny_env_config, tiny_mcts_config, tiny_train_config
):
    """One chunk of self-play: the harvest carries the two counters,
    the engine sums them, and the arena plays with the same net."""
    from alphatriangle_tpu.arena import greedy_mcts_policy, play
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.rl.self_play import SelfPlayEngine

    model = tiny_trunk_model.model_copy(
        update={"TRUNK": TrunkConfig(**{**TINY, "block_boards": 8})}
    )
    env = TriangleEnv(tiny_env_config)
    extractor = get_feature_extractor(env, model)
    net = NeuralNetwork(model, tiny_env_config, seed=0)
    engine = SelfPlayEngine(
        env, extractor, net, tiny_mcts_config, tiny_train_config, seed=0
    )
    result = engine.play_moves(2)
    lanes, sims = tiny_train_config.SELF_PLAY_BATCH_SIZE, tiny_mcts_config.max_simulations
    evaluations = 2 * lanes * (sims + 1)
    assert result.routed_assignments == evaluations * 12 * 2 * 4
    assert result.expert_tokens.shape == (4, 2)
    assert 0 < result.expert_tokens.sum() < result.routed_assignments
    assert engine.last_trace["expert_tokens"].shape == (2, 4, 2)
    assert engine.harvest().expert_tokens is None  # summed anew each harvest
    scores, lengths, _ = play(
        env, greedy_mcts_policy(net, engine.mcts), games=2, max_moves=3, seed=1
    )
    assert scores.shape == (2,) and (lengths > 0).all()


def test_block_size_divides_the_batch():
    assert trunk.block_size(512, 64) == 64
    assert trunk.block_size(16, 64) == 16
    assert trunk.block_size(272, 64) == 34
    assert trunk.block_size(7, None) == 7


def test_the_flagship_is_untouched():
    """With the trunk group absent the net is the parent commit's: the
    same parameter tree (paths, shapes, types) and the same lowered
    program, so the same bits on every seed. Both digests were taken on
    the parent (commit 248e103) with this file's code."""
    from chipbench import manifest

    cfg = manifest.load_json(manifest.HERE / "configs" / "flagship-p3.json")
    configs = manifest.program_configs(cfg)
    model, env = configs["model"], configs["env"]
    assert model.TRUNK is None and model.PARAM_DTYPE == "float32"
    module = AlphaTriangleNet(model, env.action_dim)
    grid = jnp.zeros((2, model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS))
    other = jnp.zeros((2, model.OTHER_NN_INPUT_FEATURES_DIM))
    variables = module.init(jax.random.PRNGKey(5), grid, other, train=False)
    tree = [
        (jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
        for k, v in jax.tree_util.tree_leaves_with_path(variables)
    ]
    assert len(tree) == 110
    assert hashlib.sha256(json.dumps(tree).encode()).hexdigest() == (
        "866d75c4eb02faff7ca301c34205b050877f68cd4e961849d92df78805dc3820"
    )
    text = (
        jax.jit(lambda v, g, o: module.apply(v, g, o, train=False))
        .lower(variables, grid, other)
        .as_text()
    )
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "38e83a843ed4b28038158af0da96781ef86f017e95ce3ce5ee1c2f347727ef47"
    )
