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


# --- the hybrid stack: linear and latent layers, a router with groups ------

HYBRID = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4, head_dim=16,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, routed_scaling_factor=2.5,
    n_group=2, topk_group=1, rms_norm_eps=1e-6, rope_theta=6e6,
    kv_lora_rank=16, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
    layer_types=["linear_attention", "linear_attention", "latent_attention"],
    mlp_layer_types=["dense", "sparse", "sparse"], experts_held=(2, 2),
    norm_position="pre", qk_norm="l2", rope_layers="latent", linear_chunk=16,
    router_bias=True,
)
# The published widths of the benchmark's ling-flash-ep4 (one chip's share).
HYBRID_PUBLISHED = dict(
    HYBRID, hidden_size=2560, num_attention_heads=32, num_key_value_heads=32,
    head_dim=128, intermediate_size=6144, moe_intermediate_size=768,
    num_experts=512, num_experts_per_tok=8, n_group=8, topk_group=4,
    kv_lora_rank=512, qk_nope_head_dim=128, qk_rope_head_dim=64, v_head_dim=128,
    layer_types=["linear_attention"] * 5 + ["latent_attention", "linear_attention"],
    mlp_layer_types=["dense"] + ["sparse"] * 6, experts_held=(0, 128),
    linear_chunk=64,
)


def _delta_rule_inputs(seq, dk=32, dv=16, n=6, seed=0):
    keys = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa: E731
    q = unit(jax.random.normal(keys[0], (n, seq, dk)))
    k = unit(jax.random.normal(keys[1], (n, seq, dk)))
    v = jax.random.normal(keys[2], (n, seq, dv))
    g = -5.0 * jax.nn.sigmoid(jax.random.normal(keys[3], (n, seq, dk)))
    beta = jax.nn.sigmoid(jax.random.normal(keys[4], (n, seq)))
    return q, k, v, g, beta


@pytest.mark.parametrize("seq", [12, 77, 252])
@pytest.mark.parametrize("chunk", [16, 64])
@pytest.mark.parametrize("decay", ["drawn", "lower_bound", "none"])
def test_the_chunked_recurrence_is_the_token_by_token_one(seq, chunk, decay):
    """At sequences that are no multiple of the chunk, with decays
    drawn over (-5, 0), with g = -5 on every step of every chunk (16
    rows gather exp 80: no inf, no nan, the same answer) and with next
    to no decay (the state keeps everything)."""
    from alphatriangle_tpu.nn import linear_attention as delta_rule

    q, k, v, g, beta = _delta_rule_inputs(seq)
    if decay == "lower_bound":
        g = jnp.full_like(g, -5.0)
    elif decay == "none":
        g = jnp.full_like(g, -1e-4)
    want = delta_rule.recurrent(q, k, v, g, beta)
    got = delta_rule.chunked(q, k, v, g, beta, chunk, -5.0, jnp.float32)
    assert got.shape == want.shape == (6, seq, 16)
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 2e-5 * max(1.0, float(jnp.abs(want).max()))


def test_the_chunked_recurrence_refuses_what_float32_cannot_hold():
    from alphatriangle_tpu.nn import linear_attention as delta_rule

    q, k, v, g, beta = _delta_rule_inputs(12)
    with pytest.raises(ValueError, match="sub-blocks"):
        delta_rule.chunked(q, k, v, g, beta, 24, -5.0, jnp.float32)
    with pytest.raises(ValueError, match="float32"):
        delta_rule.chunked(q, k, v, g, beta, 16, -6.0, jnp.float32)


def test_the_recurrence_is_causal_and_its_state_decays():
    """Change token 5: outputs before it stay, outputs from it on move;
    at the lower bound what token 5 wrote is gone sixteen tokens on."""
    from alphatriangle_tpu.nn import linear_attention as delta_rule

    q, k, v, g, beta = _delta_rule_inputs(40)
    g = jnp.full_like(g, -5.0)
    a = delta_rule.chunked(q, k, v, g, beta, 16, -5.0, jnp.float32)
    b = delta_rule.chunked(q, k, v.at[:, 5].add(1.0), g, beta, 16, -5.0, jnp.float32)
    moved = np.asarray(jnp.abs(a - b).max(axis=(0, 2)))
    assert (moved[:5] == 0).all() and moved[5] > 1e-3
    assert moved[21:].max() < 1e-30


def test_the_short_convolution_is_causal_and_ends_on_the_token():
    x = jax.random.normal(jax.random.PRNGKey(0), (2, 9, 3))
    taps = jnp.asarray([[0.0] * 3, [0.0] * 3, [0.0] * 3, [1.0] * 3])
    assert np.allclose(trunk.short_conv(x, taps), jax.nn.silu(x), atol=1e-6)
    taps = jax.random.normal(jax.random.PRNGKey(1), (4, 3))
    a = trunk.short_conv(x, taps)
    b = trunk.short_conv(x.at[:, 4].add(1.0), taps)
    differs = np.asarray(jnp.abs(a - b).max(axis=(0, 2)) > 0)
    assert differs.tolist() == [False] * 4 + [True] * 4 + [False]


def test_interleaved_rotary_positions_are_relative():
    q = jax.random.normal(jax.random.PRNGKey(2), (1, 1, 8))
    k = jax.random.normal(jax.random.PRNGKey(3), (1, 1, 8))
    qs = trunk.rotate_pairs(jnp.repeat(q, 12, axis=1), 6e6)
    ks = trunk.rotate_pairs(jnp.repeat(k, 12, axis=1), 6e6)
    scores = np.asarray(qs[0] @ ks[0].T)
    assert scores[5, 2] == pytest.approx(scores[9, 6], rel=1e-4)
    assert scores[5, 2] != pytest.approx(scores[5, 3], rel=1e-4)
    # Neighbours are a pair: entries 0 and 1 turn by the position itself.
    one = trunk.rotate_pairs(jnp.zeros((1, 3, 8)).at[:, :, 0].set(1.0), 6e6)
    assert np.allclose(one[0, 2, :2], [np.cos(2.0), np.sin(2.0)], atol=1e-6)
    assert np.allclose(one[0, 2, 2:], 0.0)


def test_the_grouped_choice_against_a_plain_sort_ties_included():
    """Scores rounded to halves tie in every row: of groups that tie
    the first stays, of experts that tie the first is chosen."""
    x = np.asarray(
        jnp.round(jax.random.normal(jax.random.PRNGKey(0), (512, 16)) * 2) / 2 + 0.0
    )
    among = np.asarray(trunk.among_groups(jnp.asarray(x), 4, 2))
    chosen = np.asarray(jax.lax.top_k(jnp.asarray(among), 3)[1])
    ties = 0
    for row, picked in zip(x, chosen):
        worth = np.sort(row.reshape(4, 4), axis=1)[:, -2:].sum(axis=1)
        kept = sorted(range(4), key=lambda j: (-worth[j], j))[:2]
        allowed = [e for e in range(16) if e // 4 in kept]
        want = sorted(allowed, key=lambda e: (-row[e], e))[:3]
        assert picked.tolist() == want
        ties += len(set(row[allowed])) < len(allowed)
    assert ties > 100
    assert np.isneginf(among).sum(axis=1).tolist() == [8] * 512


@pytest.mark.parametrize(
    "change,message",
    [
        (dict(qk_norm=True), 'qk_norm "l2"'),
        (dict(rope_layers="sliding"), 'rope_layers "latent"'),
        (dict(kv_lora_rank=None), "kv_lora_rank"),
        (dict(qk_rope_head_dim=7), "even"),
        (dict(n_group=3), "groups"),
        (dict(topk_group=3), "groups"),
        (dict(n_group=8), "fewer than 2"),
        (dict(num_experts_per_tok=6), "fewer than 2 experts, or"),
        (dict(layer_types=["sliding_attention"] * 3), "qk_norm True"),
        (dict(layer_types=["linear_attention"] * 3, norm_position="mid"), "norm_position"),
    ],
)
def test_a_stack_the_layers_were_not_written_for_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        TrunkConfig(**{**HYBRID, **change})
    TrunkConfig(**HYBRID)
    TrunkConfig(**{**TINY, "norm_position": "pre"})  # either placement, any mixer


def test_the_hybrid_stack_at_its_published_widths_counts_its_bytes_and_flops(
    tiny_model_config, tiny_env_config, tiny_train_config, monkeypatch
):
    """4.97B parameters in the stack (issue 32's arithmetic: a KDA mixer
    52.6M, the MLA mixer 32.0M, a sparse layer's share 128 x 5.90M +
    7.2M, the dense MLP 47.2M); a trainer refuses it by its bytes."""
    from alphatriangle_tpu.rl.trainer import Trainer
    from alphatriangle_tpu.telemetry.memory import BYTES_LIMIT_ENV

    cfg = TrunkConfig(**HYBRID_PUBLISHED)
    shapes = trunk.param_shapes(cfg)
    size = lambda i: sum(  # noqa: E731
        int(np.prod(shape)) for name, (shape, _) in shapes.items()
        if name.startswith(f"l{i}_")
    )
    mixer = lambda i, names: sum(  # noqa: E731
        int(np.prod(shapes[f"l{i}_{name}"][0])) for name in names
    )
    kda = mixer(0, ["wq", "wk", "wv", "wf", "wo", "conv_q", "conv_k", "conv_v",
                    "wb", "wg", "A_log", "dt_bias", "o_norm"])
    mla = mixer(5, ["wq", "wkv_a", "kv_norm", "wkv_b", "wg", "wo"])
    assert kda == 5 * 2560 * 4096 + 3 * 4 * 4096 + 2 * 2560 * 32 + 4096 + 32 + 128
    assert kda == pytest.approx(52.6e6, rel=2e-3)
    assert mla == 2560 * 6144 + 2560 * 576 + 512 + 512 * 8192 + 2560 * 32 + 4096 * 2560
    assert mla == pytest.approx(32.0e6, rel=2e-3)
    assert size(0) == kda + 2 * 2560 + 3 * 2560 * 6144
    expert = 3 * 2560 * 768
    assert size(1) == kda + 2 * 2560 + 2560 * 512 + 512 + 129 * expert
    count = sum(int(np.prod(shape)) for shape, _ in shapes.values())
    assert count == pytest.approx(4.968e9, rel=1e-3)
    # The recurrence by its recurrent form, the routed experts if even.
    per_token = trunk.forward_flops(cfg, 252) / 252
    assert per_token == pytest.approx(1.040e9, rel=2e-3)
    assert 6 * 3 * 2 * 4096 * 128 / per_token == pytest.approx(0.018, abs=1e-3)

    model = tiny_model_config.model_copy(
        update={"TRUNK": cfg, "PARAM_DTYPE": "bfloat16"}
    )
    module = AlphaTriangleNet(model, tiny_env_config.action_dim)
    net_shapes = jax.eval_shape(
        lambda k: module.init(
            k, jnp.zeros((1, 1, 3, 4)),
            jnp.zeros((1, model.OTHER_NN_INPUT_FEATURES_DIM)), train=False,
        ),
        jax.random.PRNGKey(0),
    )
    whole = sum(int(np.prod(x.shape)) for x in jax.tree_util.tree_leaves(net_shapes))
    net = NeuralNetwork(model, tiny_env_config, variables=net_shapes)
    monkeypatch.setenv(BYTES_LIMIT_ENV, str(16 * 2**30))  # one v5e chip
    with pytest.raises(ValueError, match="of training state") as refusal:
        Trainer(net, tiny_train_config)
    # Four copies in the parameters' own type and the biases' float32:
    # 39.8 GB for a 16 GiB chip, and named to the byte.
    assert 8 * whole < int(
        str(refusal.value).split(" need ")[1].split(" B of")[0].replace(",", "")
    ) < 8 * whole + 4 * 4 * 6 * 512 * 2
    assert 8 * whole == pytest.approx(39.8e9, rel=5e-3)


def test_the_search_counts_the_tokens_the_recurrence_took(
    tiny_model_config, tiny_env_config, tiny_mcts_config, tiny_train_config
):
    """One chunk of self-play through the hybrid stack: the harvest
    carries `linear_tokens` beside the experts' two counters, the
    engine sums it, and a stack without linear layers sows none."""
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.rl.self_play import SelfPlayEngine
    from alphatriangle_tpu.telemetry.tracer import default_tracer

    model = tiny_model_config.model_copy(
        update={"TRUNK": TrunkConfig(**{**HYBRID, "block_boards": 8})}
    )
    env = TriangleEnv(tiny_env_config)
    net = NeuralNetwork(model, tiny_env_config, seed=0)
    seen = []
    tracer = default_tracer()
    monkey = pytest.MonkeyPatch()
    monkey.setattr(
        tracer, "instant", lambda name, **fields: seen.append((name, fields))
    )
    try:
        engine = SelfPlayEngine(
            env, get_feature_extractor(env, model), net, tiny_mcts_config,
            tiny_train_config, seed=0,
        )
        result = engine.play_moves(2)
    finally:
        monkey.undo()
    lanes, sims = tiny_train_config.SELF_PLAY_BATCH_SIZE, tiny_mcts_config.max_simulations
    evaluations = 2 * lanes * (sims + 1)
    assert result.linear_tokens == evaluations * 12 * 2  # two linear layers
    assert result.routed_assignments == evaluations * 12 * 2 * 2
    assert result.expert_tokens.shape == (2, 2)
    assert engine.last_trace["linear_tokens"].shape == (2,)
    assert engine.harvest().linear_tokens == 0  # summed anew each harvest
    instants = [fields for name, fields in seen if name == "net.trunk"]
    assert instants and instants[0] == {
        "linear_attention": 2, "latent_attention": 1, "linear_chunk": 16,
        "linear_path": {"kernel": 0, "chunked": 2},  # the CPU, heads of 16
        # no state-space layer, so no `ssm_chunk`; gated experts on the
        # hidden size
        "moe_latent_size": None, "mlp_hidden_act": "silu",
        "block_boards": 8, "batch": instants[0]["batch"], "seq": 12,
        # its latent layer's query has no latent of its own; the search
        # trains nothing
        "latent_q_compressed": 0, "learner_block_boards": None, "remat_layers": 0,
    }

    softmax = tiny_model_config.model_copy(update={"TRUNK": TrunkConfig(**TINY)})
    other = NeuralNetwork(softmax, tiny_env_config, seed=0)
    _, state = other.model.apply(
        other.variables, jnp.zeros((2, 1, 3, 4)),
        jnp.zeros((2, softmax.OTHER_NN_INPUT_FEATURES_DIM)),
        train=False, mutable=["counters"],
    )
    assert set(trunk.counters_of(state)) == {"expert_tokens", "routed"}


def _trunk_instants(monkeypatch):
    from alphatriangle_tpu.telemetry.tracer import default_tracer

    seen = []
    monkeypatch.setattr(
        default_tracer(), "instant",
        lambda name, **fields: seen.append(fields) if name == "net.trunk" else None,
    )
    return seen


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_the_hybrid_net_through_the_kernel(
    tiny_model_config, tiny_env_config, monkeypatch, compute
):
    """Heads of 128 on a backend said to be a TPU: every linear layer's
    recurrence runs as ops/delta_rule.py's kernel (interpreted here),
    the instant says so, and the net's answer is the chunked path's:
    to float32 rounding with float32 operands, within what bfloat16
    moves a logit of this net with bfloat16 ones."""
    import functools

    model = tiny_model_config.model_copy(
        update={
            "COMPUTE_DTYPE": compute,
            "TRUNK": TrunkConfig(
                **{**HYBRID, "head_dim": 128, "num_attention_heads": 2,
                   "num_key_value_heads": 2}
            ),
        }
    )
    net = NeuralNetwork(model, tiny_env_config, seed=0)
    grid = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 3, 4))
    other = jax.random.normal(
        jax.random.PRNGKey(2), (3, model.OTHER_NN_INPUT_FEATURES_DIM)
    )
    seen = _trunk_instants(monkeypatch)
    chunked = net.model.apply(net.variables, grid, other, train=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        trunk, "gated_delta_rule",
        functools.partial(trunk.gated_delta_rule, interpret=True),
    )
    kernel = net.model.apply(net.variables, grid, other, train=False)
    assert [fields["linear_path"] for fields in seen] == [
        {"kernel": 0, "chunked": 2}, {"kernel": 2, "chunked": 0},
    ]
    limit = 1e-4 if compute == "float32" else 0.05
    for got, want in zip(kernel, chunked):
        assert got.shape == want.shape and bool(jnp.isfinite(got).all())
        assert float(jnp.abs(got - want).max()) < limit


def test_ling_flash_on_the_cpu_is_untouched():
    """Where the kernel does not engage (here: the CPU) the hybrid
    stack's program is the one it was: the parameter tree and the
    lowered forward of `ling-flash-ep4` at its published widths, with
    and without the counters, are the parent commit's (39f919d), where
    the three digests were taken with this test's code."""
    from chipbench import manifest
    from chipbench import reference_ling_hybrid as plain

    cfg = manifest.load_json(manifest.HERE / "configs" / "ling-flash-ep4.json")
    configs = manifest.program_configs(cfg)
    model = configs["model"].model_copy(
        update={"TRUNK": TrunkConfig(**plain.trunk_settings(cfg))}
    )
    env = configs["env"]
    module = AlphaTriangleNet(model, env.action_dim)
    grid = jax.ShapeDtypeStruct(
        (2, model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS), jnp.float32
    )
    other = jax.ShapeDtypeStruct((2, model.OTHER_NN_INPUT_FEATURES_DIM), jnp.float32)
    shapes = jax.eval_shape(
        lambda k: module.init(
            k, jnp.zeros(grid.shape), jnp.zeros(other.shape), train=False
        ),
        jax.random.PRNGKey(0),
    )
    tree = [
        (jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
        for k, v in jax.tree_util.tree_leaves_with_path(shapes)
    ]
    assert len(tree) == 196
    assert hashlib.sha256(json.dumps(tree).encode()).hexdigest() == (
        "09cbe9a0414e0acda3b839ac8b50ee7a4692b2a1d1484f921ca861a18d02232d"
    )
    digests = [
        hashlib.sha256(
            jax.jit(lambda v, g, o: module.apply(v, g, o, train=False, **more))
            .lower(shapes, grid, other).as_text().encode()
        ).hexdigest()
        for more in ({}, {"mutable": ["counters"]})
    ]
    assert digests == [
        "a83088e49001785d71ca1ff2facda2c6a1abe9eff65bffde98b985de1b95290b",
        "66a80929039be090eb0c1616ea3d9014d0d507f1917f182e816b6e8abaff087b",
    ]


def test_k_exaone_is_untouched():
    """With the hybrid layers beside them the softmax layers, the
    ungrouped router and the post-norm placement trace as they did: the
    parameter tree and the lowered forward of `k-exaone-ep8` at its
    published widths, with and without the counters, are the parent
    commit's (565ec99), where the three digests were taken with this
    test's code."""
    from chipbench import manifest
    from chipbench import reference_exaone_moe as plain

    cfg = manifest.load_json(manifest.HERE / "configs" / "k-exaone-ep8.json")
    configs = manifest.program_configs(cfg)
    model = configs["model"].model_copy(
        update={"TRUNK": TrunkConfig(**plain.trunk_settings(cfg))}
    )
    env = configs["env"]
    module = AlphaTriangleNet(model, env.action_dim)
    grid = jax.ShapeDtypeStruct(
        (2, model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS), jnp.float32
    )
    other = jax.ShapeDtypeStruct((2, model.OTHER_NN_INPUT_FEATURES_DIM), jnp.float32)
    shapes = jax.eval_shape(
        lambda k: module.init(
            k, jnp.zeros(grid.shape), jnp.zeros(other.shape), train=False
        ),
        jax.random.PRNGKey(0),
    )
    tree = [
        (jax.tree_util.keystr(k), tuple(v.shape), str(v.dtype))
        for k, v in jax.tree_util.tree_leaves_with_path(shapes)
    ]
    assert len(tree) == 122
    assert hashlib.sha256(json.dumps(tree).encode()).hexdigest() == (
        "c5a84736dac4a96309f2175d3e72e69c34736629c97e2d7f1b1446f7843d292b"
    )
    digests = [
        hashlib.sha256(
            jax.jit(lambda v, g, o: module.apply(v, g, o, train=False, **more))
            .lower(shapes, grid, other).as_text().encode()
        ).hexdigest()
        for more in ({}, {"mutable": ["counters"]})
    ]
    assert digests == [
        "64faad0ab2b6cda7fe8847fcdc8fcc8dc1464e02177810b5ac1244abe9966de3",
        "49981403830c91696af6d0e849e3ee7414f4f3c70dc7b0834089fca436bd3154",
    ]


# --- a latent stack that is trained: compressed query, no gate ---------------

LATENT = dict(
    hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
    intermediate_size=96, moe_intermediate_size=32, num_experts=8,
    num_experts_per_tok=2, num_shared_experts=1, routed_scaling_factor=1.8,
    kv_lora_rank=16, q_lora_rank=24, qk_nope_head_dim=16, qk_rope_head_dim=8,
    v_head_dim=16, layer_types=["latent_attention"] * 3,
    mlp_layer_types=["dense", "sparse", "sparse"], experts_held=(4, 4),
    norm_position="pre", rope_layers="latent", router_bias=True,
    latent_gate=False, learner_block_boards=4,
)


def test_latent_attention_without_a_query_latent_and_gated_is_what_it_was():
    """`q_lora_rank` None and the gate on (the defaults: `ling-flash-ep4`'s
    latent layer) declare the parameters they declared and lower to the
    parent commit's text (7fee68d, where both digests were taken with
    this test's code), in float32 and in bfloat16."""
    cfg = TrunkConfig(**HYBRID)
    assert cfg.q_lora_rank is None and cfg.latent_gate is True
    shapes = {
        name[3:]: jax.ShapeDtypeStruct(shape, jnp.float32)
        for name, (shape, _) in trunk.param_shapes(cfg).items()
        if name.startswith("l2_")
    }
    assert {"wq", "wg"} <= set(shapes) and "wq_a" not in shapes
    x = jax.ShapeDtypeStruct((3, 12, 64), jnp.float32)
    digests = [
        hashlib.sha256(
            jax.jit(lambda p, x: trunk.latent_attention(p, x, cfg, dtype))
            .lower(shapes, x).as_text().encode()
        ).hexdigest()
        for dtype in (jnp.float32, jnp.bfloat16)
    ]
    assert digests == [
        "c3277b35c0d0b82af11db5d1b8171266661afd98c446484aeb60ad16a0b0ef20",
        "4061d4106d6945be0b85d5df22cdd65eb4b6271eda8fe6642d760c93688f52fb",
    ]


def test_a_latent_stack_needs_no_head_dim_and_declares_its_query_latent():
    cfg = TrunkConfig(**LATENT)
    assert cfg.head_dim is None
    shapes = trunk.param_shapes(cfg)
    assert shapes["l0_wq_a"] == ((64, 24), 64)
    assert shapes["l0_q_a_norm"] == ((24,), 0)
    assert shapes["l0_wq_b"] == ((24, 4 * 24), 24)
    assert "l0_wq" not in shapes and "l0_wg" not in shapes
    assert shapes["l1_router_bias"] == ((8,), -1)
    # The compressed query and the missing gate in the count of FLOP.
    gated = TrunkConfig(**{**LATENT, "q_lora_rank": None, "latent_gate": True})
    seq = 12
    assert trunk.forward_flops(gated, seq) - trunk.forward_flops(cfg, seq) == (
        3 * seq * 2 * (64 * 96 + 64 * 4 - 24 * (64 + 96))
    )
    with pytest.raises(ValueError, match="head_dim may be left out only"):
        TrunkConfig(**{
            **LATENT, "layer_types": ["latent_attention", "full_attention",
                                      "latent_attention"],
        })


def test_the_bias_rule_alone():
    """Loads in, biases out, exact: + rate where an expert was chosen
    less than the mean, - rate where more, nothing where it met it."""
    cfg = TrunkConfig(**{**LATENT, "router_bias_rate": 0.01})
    params = {
        "l1_router_bias": jnp.linspace(-0.1, 0.1, 8, dtype=jnp.float32),
        "l2_router_bias": jnp.zeros((8,), jnp.float32),
        "l1_w_router": jnp.ones((64, 8)),
    }
    loads = jnp.asarray(
        [[10, 2, 6, 6, 0, 12, 6, 6], [6, 6, 6, 6, 6, 6, 6, 6]], jnp.int32
    )
    moved = trunk.moved_router_biases(params, loads, cfg)
    step = np.float32(0.01) * np.asarray([-1, 1, 0, 0, 1, -1, 0, 0], np.float32)
    np.testing.assert_array_equal(
        moved["l1_router_bias"], np.asarray(params["l1_router_bias"]) + step
    )
    np.testing.assert_array_equal(moved["l2_router_bias"], np.zeros(8, np.float32))
    assert moved["l1_w_router"] is params["l1_w_router"]
    assert moved["l1_router_bias"].dtype == jnp.float32


def test_a_training_forward_counts_every_experts_load_and_recomputes_by_layer(
    tiny_model_config, tiny_env_config
):
    model = tiny_model_config.model_copy(
        update={"TRUNK": TrunkConfig(**LATENT), "REMAT": True}
    )
    module = AlphaTriangleNet(model, tiny_env_config.action_dim)
    grid = jnp.zeros((4, 1, 3, 4))
    other = jnp.zeros((4, model.OTHER_NN_INPUT_FEATURES_DIM))
    variables = module.init(jax.random.PRNGKey(0), grid, other, train=False)
    recomputed = {
        train: str(
            jax.make_jaxpr(
                lambda v, g, o: module.apply(v, g, o, train=train, mutable=["counters"])
            )(variables, grid, other)
        ).count("remat2[")
        for train in (False, True)
    }
    # The trunk's three layers, in a training forward alone (the tiny
    # stem has no residual block to recompute).
    assert recomputed == {False: 0, True: 3}
    _, state = module.apply(variables, grid, other, train=True, mutable=["counters"])
    counted = trunk.counters_of(state)
    assert counted["expert_loads"].shape == (2, 8)
    assert counted["expert_loads"].sum(axis=1).tolist() == [4 * 12 * 2] * 2
    np.testing.assert_array_equal(
        counted["expert_tokens"], counted["expert_loads"][:, 4:]
    )


# --- a state-space stack: layers of one half, experts in a latent -------------

SSM = dict(
    hidden_size=32, num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=24, moe_intermediate_size=24, num_experts=8,
    num_experts_per_tok=3, num_shared_experts=1, routed_scaling_factor=5.0,
    mamba_num_heads=8, mamba_head_dim=8, ssm_state_size=8, n_groups=2,
    conv_kernel=4, chunk_size=8, moe_latent_size=16,
    moe_shared_expert_intermediate_size=48, mlp_hidden_act="relu2",
    layer_types=["state_space", "none", "state_space", "full_attention", "none"],
    mlp_layer_types=["none", "sparse", "none", "none", "sparse"],
    experts_held=(2, 2), norm_position="pre", qk_norm="none", router_bias=True,
)


def _scan_inputs(seq, heads=6, groups=2, p=4, n=5, boards=2, seed=0):
    from alphatriangle_tpu.nn import state_space

    keys = jax.random.split(jax.random.PRNGKey(seed), 7)
    x = jax.random.normal(keys[0], (boards, seq, heads, p))
    step = jax.nn.softplus(
        jax.random.normal(keys[1], (boards, seq, heads))
        + state_space.init_dt_bias(keys[2], (heads,))
    )
    log_a = -step * jnp.exp(state_space.init_a_log(keys[3], (heads,)))
    b = jax.random.normal(keys[4], (boards, seq, groups, n))
    c = jax.random.normal(keys[5], (boards, seq, groups, n))
    return x, step, log_a, b, c, state_space.init_skip(keys[6], (heads,))


@pytest.mark.parametrize("seq", [12, 77, 252])
@pytest.mark.parametrize("chunk", [16, 128])
@pytest.mark.parametrize("decay", ["drawn", "fast", "none"])
def test_the_chunked_scan_is_the_token_by_token_one(seq, chunk, decay):
    """At sequences that are no multiple of the chunk, three heads a
    group, float32: with steps and decays as Mamba-2 draws them, with a
    decay of e^-8 a token (exp(G_t - G_i) falls to nought within a
    chunk, and nothing is inf or nan) and with none (the state keeps
    everything)."""
    from alphatriangle_tpu.nn import state_space

    x, step, log_a, b, c, skip = _scan_inputs(seq)
    if decay == "fast":
        log_a = jnp.full_like(log_a, -8.0)
    elif decay == "none":
        log_a = jnp.zeros_like(log_a)
    want = state_space.recurrent(x, step, log_a, b, c, skip)
    got = state_space.chunked(x, step, log_a, b, c, skip, chunk, jnp.float32)
    assert got.shape == want.shape == (2, seq, 6, 4) and got.dtype == jnp.float32
    assert bool(jnp.isfinite(got).all())
    assert float(jnp.abs(got - want).max()) < 2e-5 * max(1.0, float(jnp.abs(want).max()))


def test_the_scan_is_causal_reads_its_group_and_keeps_its_skip():
    """Change token 5's input: outputs before it stay, outputs from it
    on move. Change group 1's B: the heads of group 0 stay. With C at
    nought what is left is D x."""
    from alphatriangle_tpu.nn import state_space

    x, step, log_a, b, c, skip = _scan_inputs(40)
    run = lambda x, b, c: state_space.chunked(  # noqa: E731
        x, step, log_a, b, c, skip, 16, jnp.float32
    )
    a = run(x, b, c)
    moved = np.asarray(jnp.abs(a - run(x.at[:, 5].add(1.0), b, c)).max(axis=(0, 2, 3)))
    assert (moved[:5] == 0).all() and (moved[5:] > 1e-6).all()
    by_head = np.asarray(
        jnp.abs(a - run(x, b.at[:, :, 1].add(1.0), c)).max(axis=(0, 1, 3))
    )
    assert (by_head[:3] == 0).all() and (by_head[3:] > 1e-3).all()
    np.testing.assert_allclose(
        run(x, b, jnp.zeros_like(c)), skip[:, None] * x, atol=1e-6
    )


def test_mamba_draws_its_own_parameters():
    from alphatriangle_tpu.nn import state_space

    key = jax.random.PRNGKey(0)
    a_log = state_space.init_a_log(key, (4096,))
    assert a_log.dtype == jnp.float32
    assert 0.0 <= float(a_log.min()) < 0.1 and 2.7 < float(a_log.max()) <= np.log(16.0)
    step = jax.nn.softplus(state_space.init_dt_bias(key, (4096,)))
    assert 1e-3 * 0.999 < float(step.min()) < 1.2e-3 and 0.08 < float(step.max()) < 0.1001
    skip = state_space.init_skip(key, (4096,))
    assert abs(float(skip.mean()) - 1.0) < 0.05 and 0.4 < float(skip.std()) < 0.6


@pytest.mark.parametrize(
    "change,message",
    [
        (dict(layer_types=["state_space", "none", "none", "full_attention", "none"],
              mlp_layer_types=["none", "sparse", "none", "none", "sparse"]),
         "layer 2 has neither"),
        (dict(mamba_num_heads=None), "mamba_num_heads"),
        (dict(ssm_state_size=None), "ssm_state_size"),
        (dict(n_groups=3), "groups"),
        (dict(mlp_layer_types=["none", "dense", "none", "none", "sparse"]), "silu"),
        (dict(layer_types=["state_space", "none", "sliding_attention",
                           "full_attention", "none"], sliding_window=4),
         "qk_norm True"),
        (dict(layer_types=["linear_attention", "none", "state_space",
                           "full_attention", "none"]), 'qk_norm "l2"'),
        (dict(mlp_hidden_act="gelu"), "mlp_hidden_act"),
        (dict(layer_types=["state_space"] * 4), "same layers"),
    ],
)
def test_a_state_space_stack_no_layer_was_written_for_is_refused(change, message):
    with pytest.raises(ValueError, match=message):
        TrunkConfig(**{**SSM, **change})
    TrunkConfig(**SSM)
    # A stack with neither softmax nor linear layers reads no head_dim.
    TrunkConfig(**{**SSM, "head_dim": None,
                   "layer_types": ["state_space", "none", "state_space",
                                   "state_space", "none"]})


def test_a_layer_of_one_half_has_one_norm_and_the_experts_their_latent():
    cfg = TrunkConfig(**SSM)
    shapes = trunk.param_shapes(cfg)
    of = lambda i: {n[3:] for n in shapes if n.startswith(f"l{i}_")}  # noqa: E731
    assert of(0) == {"attn_norm", "w_in", "conv", "conv_bias", "A_log", "D",
                     "dt_bias", "gated_norm", "w_out"}
    assert of(1) == {"mlp_norm", "w_router", "router_bias", "w_latent_down",
                     "w_latent_up", "e_up", "e_down", "s_up", "s_down"}
    assert of(3) == {"attn_norm", "wq", "wk", "wv", "wo"}  # no q/k norm
    assert shapes["l0_w_in"] == ((32, 64 + 64 + 2 * 2 * 8 + 8), 32)
    assert shapes["l0_conv"] == ((4, 96), 4) and shapes["l0_conv_bias"] == ((96,), 4)
    assert shapes["l0_A_log"] == ((8,), "A_log") and shapes["l0_D"] == ((8,), "D")
    assert shapes["l1_e_up"] == ((2, 16, 24), 16) and shapes["l1_e_down"] == ((2, 24, 16), 24)
    assert shapes["l1_s_up"] == ((32, 48), 32) and shapes["l1_w_latent_up"] == ((16, 32), 16)
    assert not trunk.param_shapes(TrunkConfig(**{**SSM, "use_conv_bias": False})).get(
        "l0_conv_bias"
    )
    # The count of FLOP: a board of 12 tokens, by hand.
    mamba = 2 * (32 * 168 + 64 * 32) + 2 * 4 * 96 + 2 * 2 * 64 * 8
    attention = 2 * (32 * (32 + 2 * 16) + 32 * 32)
    experts = 2 * 32 * 8 + 2 * 2 * 32 * 16 + 2 * 2 * 32 * 48 + 3 * 2 / 8 * 2 * 2 * 16 * 24
    assert trunk.forward_flops(cfg, 12) == (
        12 * (2 * mamba + attention + 2 * experts) + 2 * 2 * 32 * 78
    )


def test_the_search_counts_the_tokens_the_scan_took(
    tiny_model_config, tiny_env_config, tiny_mcts_config, tiny_train_config,
    monkeypatch,
):
    """A whole chunk of self-play through the state-space stack and its
    rows into the ring, as `cli train` runs them in sync mode: the
    harvest carries `ssm_tokens` beside the experts' two counters (in
    `last_trace`, where the benchmark reads it; the engine keeps no sum
    nothing reads), the instant names the stack, parameters a decay is
    made of are float32 whatever the rest, and a stack without such
    layers sows no such counter."""
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.rl.device_buffer import DeviceReplayBuffer
    from alphatriangle_tpu.rl.self_play import SelfPlayEngine

    model = tiny_model_config.model_copy(
        update={"TRUNK": TrunkConfig(**{**SSM, "block_boards": 8}),
                "PARAM_DTYPE": "bfloat16", "INFERENCE_PRECISION": "bfloat16"}
    )
    env = TriangleEnv(tiny_env_config)
    net = NeuralNetwork(model, tiny_env_config, seed=0)
    stack = net.variables["params"]["DecoderTrunk_0"]
    assert {name.split("_", 1)[1] for name, v in stack.items()
            if v.dtype == jnp.float32} == {"A_log", "D", "dt_bias", "router_bias"}
    seen = _trunk_instants(monkeypatch)
    engine = SelfPlayEngine(
        env, get_feature_extractor(env, model), net, tiny_mcts_config,
        tiny_train_config, seed=0,
    )
    result, payload = engine.play_moves_device(2)
    lanes, sims = tiny_train_config.SELF_PLAY_BATCH_SIZE, tiny_mcts_config.max_simulations
    evaluations = 2 * lanes * (sims + 1)
    assert result.routed_assignments == evaluations * 12 * 3 * 2
    assert result.expert_tokens.shape == (2, 2) and result.linear_tokens == 0
    # a move's count each; two state-space layers
    assert engine.last_trace["ssm_tokens"].shape == (2,)
    assert int(engine.last_trace["ssm_tokens"].sum()) == evaluations * 12 * 2
    assert "linear_tokens" not in engine.last_trace
    assert seen and seen[0] == {
        "state_space": 2, "full_attention": 1, "ssm_chunk": 8,
        "ssm_path": {"kernel": 0, "chunked": 2},  # the CPU
        "moe_latent_size": 16, "mlp_hidden_act": "relu2",
        "linear_path": {"kernel": 0, "chunked": 0}, "linear_chunk": 64,
        "block_boards": 8, "batch": seen[0]["batch"], "seq": 12,
        "latent_q_compressed": 0, "learner_block_boards": None, "remat_layers": 0,
    }
    buffer = DeviceReplayBuffer(
        tiny_train_config, (model.GRID_INPUT_CHANNELS, 3, 4),
        model.OTHER_NN_INPUT_FEATURES_DIM, tiny_env_config.action_dim, seed=0,
    )
    added = buffer.ingest_payload(payload)
    assert added == len(buffer) > 0

    hybrid = tiny_model_config.model_copy(update={"TRUNK": TrunkConfig(**HYBRID)})
    other = NeuralNetwork(hybrid, tiny_env_config, seed=0)
    _, state = other.model.apply(
        other.variables, jnp.zeros((2, 1, 3, 4)),
        jnp.zeros((2, hybrid.OTHER_NN_INPUT_FEATURES_DIM)),
        train=False, mutable=["counters"],
    )
    assert "ssm_tokens" not in trunk.counters_of(state)


@pytest.mark.parametrize(
    "stack,path",
    [(SSM, {"kernel": 0, "chunked": 2}), (HYBRID, None)],
    ids=["state_space", "none"],
)
def test_the_trunk_instant_says_which_path_the_scans_took(
    tiny_model_config, tiny_env_config, monkeypatch, stack, path
):
    """On the CPU every state-space layer's scan is `chunked`, and the
    `net.trunk` instant says so for the stack's two; a stack without
    such layers carries no `ssm_path`."""
    model = tiny_model_config.model_copy(update={"TRUNK": TrunkConfig(**stack)})
    net = NeuralNetwork(model, tiny_env_config, seed=0)
    seen = _trunk_instants(monkeypatch)
    net.model.apply(
        net.variables, jnp.zeros((2, 1, 3, 4)),
        jnp.zeros((2, model.OTHER_NN_INPUT_FEATURES_DIM)), train=False,
    )
    assert len(seen) == 1 and seen[0].get("ssm_path") == path


@pytest.mark.parametrize("compute", ["float32", "bfloat16"])
def test_the_state_space_net_through_the_kernel(
    tiny_model_config, tiny_env_config, monkeypatch, compute
):
    """Heads of 64 and a state of 128 on a backend said to be a TPU:
    every state-space layer's scan runs as ops/state_space_scan.py's
    kernel (interpreted here), the instant says so, and the net's
    answer is the chunked path's: to float32 rounding with float32
    operands, within what bfloat16 moves a logit of this net with
    bfloat16 ones."""
    import functools

    model = tiny_model_config.model_copy(
        update={
            "COMPUTE_DTYPE": compute,
            "TRUNK": TrunkConfig(
                **{**SSM, "mamba_head_dim": 64, "ssm_state_size": 128,
                   "chunk_size": 128}
            ),
        }
    )
    net = NeuralNetwork(model, tiny_env_config, seed=0)
    grid = jax.random.normal(jax.random.PRNGKey(1), (3, 1, 3, 4))
    other = jax.random.normal(
        jax.random.PRNGKey(2), (3, model.OTHER_NN_INPUT_FEATURES_DIM)
    )
    seen = _trunk_instants(monkeypatch)
    chunked = net.model.apply(net.variables, grid, other, train=False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        trunk, "state_space_scan",
        functools.partial(trunk.state_space_scan, interpret=True),
    )
    kernel = net.model.apply(net.variables, grid, other, train=False)
    assert [fields["ssm_path"] for fields in seen] == [
        {"kernel": 0, "chunked": 2}, {"kernel": 2, "chunked": 0},
    ]
    limit = 1e-4 if compute == "float32" else 0.05
    for got, want in zip(kernel, chunked):
        assert got.shape == want.shape and bool(jnp.isfinite(got).all())
        assert float(jnp.abs(got - want).max()) < limit


def test_a_trainer_refuses_nemotron_super_by_its_bytes(
    tiny_model_config, tiny_env_config, tiny_train_config, monkeypatch
):
    """`nemotron-super-ep4` at its published widths: 4,379,728,256
    parameters in the stack (a Mamba-2 mixer 109,640,064), 4.51 G with
    the test's stem and heads; four copies are 36 GB and 16 B a
    parameter would be 72 GB for a 16 GiB chip: refused, to the byte."""
    from alphatriangle_tpu.rl.trainer import Trainer
    from alphatriangle_tpu.telemetry.memory import BYTES_LIMIT_ENV
    from chipbench import manifest
    from chipbench import reference_nemotron_h as plain

    cfg = manifest.load_json(manifest.HERE / "configs" / "nemotron-super-ep4.json")
    stack = TrunkConfig(**plain.trunk_settings(cfg))
    shapes = trunk.param_shapes(stack)
    count = sum(int(np.prod(shape)) for shape, _ in shapes.values())
    assert count == 4_379_728_256
    assert sum(
        int(np.prod(shape)) for name, (shape, _) in shapes.items()
        if name.startswith("l0_")
    ) == 109_640_064
    assert 16 * count == pytest.approx(70.1e9, rel=1e-3)  # 72 GB with the heads
    model = tiny_model_config.model_copy(
        update={"TRUNK": stack, "PARAM_DTYPE": "bfloat16"}
    )
    module = AlphaTriangleNet(model, tiny_env_config.action_dim)
    net_shapes = jax.eval_shape(
        lambda k: module.init(
            k, jnp.zeros((1, 1, 3, 4)),
            jnp.zeros((1, model.OTHER_NN_INPUT_FEATURES_DIM)), train=False,
        ),
        jax.random.PRNGKey(0),
    )
    net = NeuralNetwork(model, tiny_env_config, variables=net_shapes)
    monkeypatch.setenv(BYTES_LIMIT_ENV, str(16 * 2**30))  # one v5e chip
    with pytest.raises(ValueError, match="of training state"):
        Trainer(net, tiny_train_config)


def test_glm_flash_trained_is_untouched():
    """With the one-half layers, the latent experts and the ungated
    activation beside it, `glm-flash-ep8`'s training forward and its
    backward at published widths (a gated expert layer on the hidden
    size, differentiated through its rounds) lower to the parent
    commit's text (ec84fef, where the digest was taken with this test's
    code)."""
    from chipbench import manifest
    from chipbench import reference_glm_moe as plain

    cfg = manifest.load_json(manifest.HERE / "configs" / "glm-flash-ep8.json")
    configs = manifest.program_configs(cfg)
    model = configs["model"].model_copy(
        update={"TRUNK": TrunkConfig(**plain.trunk_settings(cfg))}
    )
    env = configs["env"]
    module = AlphaTriangleNet(model, env.action_dim)
    grid = jax.ShapeDtypeStruct(
        (2, model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS), jnp.float32
    )
    other = jax.ShapeDtypeStruct((2, model.OTHER_NN_INPUT_FEATURES_DIM), jnp.float32)
    shapes = jax.eval_shape(
        lambda k: module.init(
            k, jnp.zeros(grid.shape), jnp.zeros(other.shape), train=False
        ),
        jax.random.PRNGKey(0),
    )

    def loss(v, g, o):
        (policy, value), _ = module.apply(v, g, o, train=True, mutable=["counters"])
        return policy.sum() + value.sum()

    text = jax.jit(jax.grad(loss)).lower(shapes, grid, other).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "68fc6dfdce69984d1693bfb630faabb81b239b3aab1adb1e89402092701b7c48"
    )
