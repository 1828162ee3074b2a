"""Model + evaluator contract tests.

Mirrors the reference test matrix (`tests/nn/test_model.py:31-131`,
`tests/nn/test_network.py:61-322`): forward shapes/dtypes with the
transformer on and off, eval contracts (probs sum to 1, full action
mapping, finite values), weight get/set round trip, NaN-input guard.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from alphatriangle_tpu.config import ModelConfig, expected_other_features_dim
from alphatriangle_tpu.env import GameState
from alphatriangle_tpu.nn import (
    AlphaTriangleNet,
    NetworkEvaluationError,
    NeuralNetwork,
    count_parameters,
    expected_value_from_logits,
    sinusoidal_positional_encoding,
    value_support,
)


def _model_cfg(base: ModelConfig, **overrides) -> ModelConfig:
    return ModelConfig(**{**base.model_dump(), **overrides})


@pytest.fixture(scope="module", params=[False, True], ids=["cnn", "transformer"])
def model_variant(request, tiny_model_config):
    return _model_cfg(
        tiny_model_config,
        USE_TRANSFORMER=request.param,
        TRANSFORMER_LAYERS=1 if request.param else 0,
        NUM_RESIDUAL_BLOCKS=1,
    )


def test_forward_shapes_and_dtype(model_variant, tiny_env_config):
    net = AlphaTriangleNet(model_variant, tiny_env_config.action_dim)
    b = 3
    grid = jnp.zeros((b, 1, tiny_env_config.ROWS, tiny_env_config.COLS))
    other = jnp.zeros((b, model_variant.OTHER_NN_INPUT_FEATURES_DIM))
    variables = net.init(jax.random.PRNGKey(0), grid, other, train=False)
    pol, val = jax.jit(lambda v, g, o: net.apply(v, g, o, train=False))(
        variables, grid, other
    )
    assert pol.shape == (b, tiny_env_config.action_dim)
    assert val.shape == (b, model_variant.NUM_VALUE_ATOMS)
    assert pol.dtype == jnp.float32 and val.dtype == jnp.float32
    assert np.all(np.isfinite(np.asarray(pol)))


def test_bfloat16_compute_path(tiny_model_config, tiny_env_config):
    cfg = _model_cfg(tiny_model_config, COMPUTE_DTYPE="bfloat16")
    net = AlphaTriangleNet(cfg, tiny_env_config.action_dim)
    grid = jnp.zeros((2, 1, tiny_env_config.ROWS, tiny_env_config.COLS))
    other = jnp.zeros((2, cfg.OTHER_NN_INPUT_FEATURES_DIM))
    variables = net.init(jax.random.PRNGKey(0), grid, other)
    pol, val = net.apply(variables, grid, other)
    # Params stay f32, outputs are f32 despite bf16 internals.
    leaf = jax.tree_util.tree_leaves(variables["params"])[0]
    assert leaf.dtype == jnp.float32
    assert pol.dtype == jnp.float32 and val.dtype == jnp.float32


def test_batch_norm_variant_has_batch_stats(tiny_model_config, tiny_env_config):
    cfg = _model_cfg(tiny_model_config, NORM_TYPE="batch")
    net = AlphaTriangleNet(cfg, tiny_env_config.action_dim)
    grid = jnp.zeros((2, 1, tiny_env_config.ROWS, tiny_env_config.COLS))
    other = jnp.zeros((2, cfg.OTHER_NN_INPUT_FEATURES_DIM))
    variables = net.init(jax.random.PRNGKey(0), grid, other, train=True)
    assert "batch_stats" in variables
    out, mutated = net.apply(
        variables, grid, other, train=True,
        mutable=["batch_stats"], rngs={"dropout": jax.random.PRNGKey(1)},
    )
    assert "batch_stats" in mutated


def test_positional_encoding_table():
    pe = sinusoidal_positional_encoding(10, 8)
    assert pe.shape == (10, 8)
    # Row 0 is sin(0)=0 interleaved with cos(0)=1.
    np.testing.assert_allclose(pe[0, 0::2], 0.0, atol=1e-7)
    np.testing.assert_allclose(pe[0, 1::2], 1.0, atol=1e-7)
    assert np.all(np.abs(pe) <= 1.0)


def test_value_support_and_expectation(tiny_model_config):
    support = value_support(tiny_model_config)
    assert support.shape == (tiny_model_config.NUM_VALUE_ATOMS,)
    assert float(support[0]) == tiny_model_config.VALUE_MIN
    assert float(support[-1]) == tiny_model_config.VALUE_MAX
    # A one-hot distribution on atom k has expected value z_k.
    logits = jnp.full((1, tiny_model_config.NUM_VALUE_ATOMS), -1e9)
    logits = logits.at[0, 3].set(0.0)
    ev = expected_value_from_logits(logits, support)
    assert float(ev[0]) == pytest.approx(float(support[3]), rel=1e-5)


@pytest.fixture(scope="module")
def network(tiny_model_config, tiny_env_config) -> NeuralNetwork:
    return NeuralNetwork(tiny_model_config, tiny_env_config, seed=0)


@pytest.fixture()
def game(tiny_env_config) -> GameState:
    return GameState(tiny_env_config, initial_seed=4)


def test_evaluate_state_contract(network, game, tiny_env_config):
    policy, value = network.evaluate_state(game)
    assert len(policy) == tiny_env_config.action_dim
    assert sum(policy.values()) == pytest.approx(1.0, abs=1e-4)
    assert all(p >= 0 for p in policy.values())
    assert network.v_min <= value <= network.v_max
    assert np.isfinite(value)


def test_evaluate_batch_contract(network, tiny_env_config):
    states = [GameState(tiny_env_config, initial_seed=s) for s in range(5)]
    results = network.evaluate_batch(states)
    assert len(results) == 5
    for policy, value in results:
        assert sum(policy.values()) == pytest.approx(1.0, abs=1e-4)
        assert np.isfinite(value)
    assert network.evaluate_batch([]) == []


def test_evaluate_batch_matches_single(network, tiny_env_config):
    state = GameState(tiny_env_config, initial_seed=7)
    single_policy, single_value = network.evaluate_state(state)
    [(batch_policy, batch_value)] = network.evaluate_batch([state])
    assert single_value == pytest.approx(batch_value, abs=1e-5)
    np.testing.assert_allclose(
        np.array(list(single_policy.values())),
        np.array(list(batch_policy.values())),
        atol=1e-5,
    )


def test_weights_roundtrip_and_version(network, game):
    w = network.get_weights()
    policy_before, value_before = network.evaluate_state(game)
    v0 = network.weights_version
    # Perturb weights -> output changes; restore -> output matches.
    perturbed = jax.tree_util.tree_map(lambda a: a + 0.5, w)
    network.set_weights(perturbed)
    assert network.weights_version == v0 + 1
    _, value_perturbed = network.evaluate_state(game)
    network.set_weights(w)
    policy_after, value_after = network.evaluate_state(game)
    assert value_after == pytest.approx(value_before, abs=1e-5)
    assert value_perturbed != pytest.approx(value_before, abs=1e-6)
    np.testing.assert_allclose(
        np.array(list(policy_before.values())),
        np.array(list(policy_after.values())),
        atol=1e-6,
    )


def test_nan_features_raise(network, game, monkeypatch):
    import alphatriangle_tpu.nn.network as netmod

    def bad_extract(gs, mc):
        feats = extract_real(gs, mc)
        feats["other_features"] = np.full_like(feats["other_features"], np.nan)
        return feats

    extract_real = netmod.extract_state_features
    monkeypatch.setattr(netmod, "extract_state_features", bad_extract)
    with pytest.raises(NetworkEvaluationError):
        network.evaluate_state(game)


def test_count_parameters(network):
    n = count_parameters(network.params)
    assert n > 0
    total = sum(
        int(np.prod(p.shape))
        for p in jax.tree_util.tree_leaves(network.variables["params"])
    )
    assert n == total


# --- the encoder's layers: which path, and what says so ------------------------


@pytest.fixture(scope="module")
def flagship():
    """Preset 3's net as the benchmark builds it, at batch 2."""
    from alphatriangle_tpu.config.presets import baseline_preset

    cfgs = baseline_preset(3)
    model, env = cfgs["model"], cfgs["env"]
    module = AlphaTriangleNet(model, env.action_dim)
    keys = jax.random.split(jax.random.PRNGKey(5), 3)
    grid = jax.random.normal(
        keys[0], (2, model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS)
    )
    other = jax.random.normal(keys[1], (2, model.OTHER_NN_INPUT_FEATURES_DIM))
    variables = module.init(keys[2], grid, other, train=False)
    return module, variables, grid, other


@pytest.fixture(autouse=True)
def own_default_tracer():
    """The instants below are read from the process's default tracer. A
    test that ran earlier in this worker may have left a `RunTelemetry`'s
    there, a disabled one among them: give these tests their own and
    put the other back."""
    from alphatriangle_tpu.telemetry import tracer

    before = tracer._default_tracer
    tracer.set_default_tracer(tracer.SpanTracer())
    yield
    tracer._default_tracer = before


def _encoder_instant() -> dict:
    from alphatriangle_tpu.telemetry.tracer import default_tracer

    found = [r for r in default_tracer().records() if r[1] == "net.encoder"]
    kind, _, _, duration, *_ = found[-1]
    assert (kind, duration) == ("i", 0)
    return found[-1][6]


@pytest.fixture
def interpreted_kernel(monkeypatch):
    """The backend said to be a TPU, and the layer kernel run in the
    Pallas interpreter: what a CPU test can run of the fused path."""
    import functools

    from alphatriangle_tpu.nn import model as nn_model

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(
        nn_model,
        "encoder_layer",
        functools.partial(nn_model.encoder_layer, interpret=True),
    )


@pytest.mark.parametrize(
    "backend,train,fused",
    [("cpu", False, 0), ("tpu", False, 4), ("tpu", True, 0), ("cpu", True, 0)],
)
def test_net_encoder_instant_says_which_path(
    flagship, monkeypatch, backend, train, fused
):
    """One instant a traced net program: how many encoder layers ran as
    the fused kernel and how many as Flax's modules, at what batch."""
    module, variables, grid, other = flagship
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    # Traced, not lowered: the kernel's TPU lowering needs no chip here.
    jax.eval_shape(
        lambda v, g, o: module.apply(
            v, g, o, train=train, rngs={"dropout": jax.random.PRNGKey(0)}
        ),
        variables, grid, other,
    )
    assert _encoder_instant() == {
        "fused_layers": fused, "flax_layers": 4 - fused, "batch": 2, "seq": 120,
    }


def test_a_net_placed_on_a_mesh_keeps_flax(flagship, monkeypatch):
    """Lanes sharded over dp, weights replicated (`SelfPlayEngine(mesh=)`,
    the megastep's rollout half): the compiler partitions that program
    and cannot split a Mosaic call, so every layer runs Flax's modules.
    A mesh of one device is a program of one device."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    module, variables, grid, other = flagship
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for devices, fused in ((2, 0), (1, 4)):
        mesh = Mesh(np.array(jax.devices()[:devices]), ("dp",))
        lanes = NamedSharding(mesh, PartitionSpec("dp"))
        jax.eval_shape(
            lambda v, g, o: module.apply(v, g, o, train=False),
            jax.device_put(variables, NamedSharding(mesh, PartitionSpec())),
            jax.device_put(grid, lanes),
            jax.device_put(other, lanes),
        )
        assert _encoder_instant()["fused_layers"] == fused


def test_a_handed_in_attention_fn_keeps_precedence(flagship, monkeypatch):
    from flax import linen as nn

    module, variables, grid, other = flagship
    calls = []

    def handed_in(query, key, value, **kwargs):
        calls.append(query.shape)
        return nn.dot_product_attention(query, key, value)

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    jax.eval_shape(
        lambda v, g, o: module.clone(attention_fn=handed_in).apply(
            v, g, o, train=False
        ),
        variables, grid, other,
    )
    assert calls == [(2, 120, 4, 32)] * 4
    assert _encoder_instant() == {
        "fused_layers": 0, "flax_layers": 4, "batch": 2, "seq": 120,
    }


# Every leaf of an encoder layer, as the parent commit's checkpoints
# hold it: the fused path reads these and declares none of its own.
LAYER_LEAVES = {
    "LayerNorm_0/scale": (128,),
    "LayerNorm_0/bias": (128,),
    "MultiHeadDotProductAttention_0/query/kernel": (128, 4, 32),
    "MultiHeadDotProductAttention_0/query/bias": (4, 32),
    "MultiHeadDotProductAttention_0/key/kernel": (128, 4, 32),
    "MultiHeadDotProductAttention_0/key/bias": (4, 32),
    "MultiHeadDotProductAttention_0/value/kernel": (128, 4, 32),
    "MultiHeadDotProductAttention_0/value/bias": (4, 32),
    "MultiHeadDotProductAttention_0/out/kernel": (4, 32, 128),
    "MultiHeadDotProductAttention_0/out/bias": (128,),
    "LayerNorm_1/scale": (128,),
    "LayerNorm_1/bias": (128,),
    "Dense_0/kernel": (128, 256),
    "Dense_0/bias": (256,),
    "Dense_1/kernel": (256, 128),
    "Dense_1/bias": (128,),
}


def _leaves(tree) -> dict:
    return {
        "/".join(k.key for k in path): leaf
        for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]
    }


@pytest.mark.parametrize("layer", range(4))
def test_layer_leaves_are_the_parents(flagship, layer):
    _, variables, _, _ = flagship
    got = _leaves(variables["params"][f"TransformerEncoderLayer_{layer}"])
    assert {k: v.shape for k, v in got.items()} == LAYER_LEAVES
    assert {v.dtype for v in got.values()} == {jnp.dtype(jnp.float32)}


def test_init_is_one_on_both_paths(flagship, interpreted_kernel):
    """`init` where the fused path would engage (a TPU, inference) runs
    the Flax modules: the same variables to the bit, and the instant
    says so."""
    module, variables, grid, other = flagship
    key = jax.random.split(jax.random.PRNGKey(5), 3)[2]  # the fixture's
    again = module.init(key, grid, other, train=False)
    assert _encoder_instant()["fused_layers"] == 0
    want, got = _leaves(variables), _leaves(again)
    assert want.keys() == got.keys()
    for name, leaf in want.items():
        assert leaf.shape == got[name].shape and leaf.dtype == got[name].dtype
        assert bool(jnp.array_equal(leaf, got[name])), name


def test_fused_inference_forward_matches_flax(
    flagship, interpreted_kernel, monkeypatch
):
    """The flagship's eval forward through the layer kernel
    (interpreted) against the same weights through Flax's modules, in
    the configuration's bfloat16: within the gaps the two attention
    paths showed on the chip (0.038 policy, 0.034 value at 8,192
    boards; PERF.md), and on average no further from the float32 net
    than Flax's bfloat16 path is (the widest of 720 logits is either
    path's by chance: the conv stem and the heads round alike in both)."""
    module, variables, grid, other = flagship
    fused = module.apply(variables, grid, other, train=False)
    assert _encoder_instant()["fused_layers"] == 4
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    flax = module.apply(variables, grid, other, train=False)
    assert _encoder_instant()["fused_layers"] == 0
    exact = module.clone(
        config=_model_cfg(module.config, COMPUTE_DTYPE="float32")
    ).apply(variables, grid, other, train=False)
    for got, want, true, limit in zip(fused, flax, exact, (0.038, 0.034)):
        assert got.dtype == jnp.float32
        assert float(jnp.max(jnp.abs(got - want))) < limit
        assert float(jnp.mean(jnp.abs(got - true))) <= 1.1 * float(
            jnp.mean(jnp.abs(want - true))
        )


@pytest.mark.parametrize("activation", ["GELU", "Tanh"])
def test_fused_forward_with_another_activation(
    flagship, interpreted_kernel, monkeypatch, activation
):
    """The net hands its layers' activation to the kernel."""
    module, variables, grid, other = flagship
    module = module.clone(
        config=_model_cfg(
            module.config, ACTIVATION_FUNCTION=activation, COMPUTE_DTYPE="float32"
        )
    )
    fused = module.apply(variables, grid, other, train=False)
    assert _encoder_instant()["fused_layers"] == 4
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    flax = module.apply(variables, grid, other, train=False)
    for got, want in zip(fused, flax):
        assert float(jnp.max(jnp.abs(got - want))) < 1e-4


def test_train_lowering_is_the_parents():
    """`train=True` keeps Flax's layers with their dropout: the lowered
    forward is the text it was before either kernel (digest taken on
    be19ed3 with this test's code), so the learner's program did not
    change."""
    import hashlib

    from chipbench import manifest

    cfg = manifest.load_json(manifest.HERE / "configs" / "flagship-p3.json")
    configs = manifest.program_configs(cfg)
    model, env = configs["model"], configs["env"]
    module = AlphaTriangleNet(model, env.action_dim)
    grid = jnp.zeros((2, model.GRID_INPUT_CHANNELS, env.ROWS, env.COLS))
    other = jnp.zeros((2, model.OTHER_NN_INPUT_FEATURES_DIM))
    variables = module.init(jax.random.PRNGKey(5), grid, other, train=False)
    text = (
        jax.jit(
            lambda v, g, o, key: module.apply(
                v, g, o, train=True, rngs={"dropout": key}
            )
        )
        .lower(variables, grid, other, jax.random.PRNGKey(1))
        .as_text()
    )
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "5d5f7fe70fb0eb4cfd36a49bdee70f0cfcfba10e875199b59cc73abc3ba90580"
    )


def test_recomputation_leaves_the_parameter_tree_what_it_is(tiny_env_config):
    """`REMAT` wraps the residual and encoder blocks in `nn.remat`; their
    variables keep the names and the values they have without it, so a
    checkpoint of one loads into the other."""
    model = ModelConfig(
        CONV_FILTERS=[8], CONV_KERNEL_SIZES=[3], CONV_STRIDES=[1],
        RESIDUAL_BLOCK_FILTERS=8, NUM_RESIDUAL_BLOCKS=2, TRANSFORMER_DIM=8,
        TRANSFORMER_HEADS=2, TRANSFORMER_LAYERS=2, TRANSFORMER_FC_DIM=16,
    )
    grid = jnp.zeros((2, 1, tiny_env_config.ROWS, tiny_env_config.COLS))
    other = jnp.zeros((2, model.OTHER_NN_INPUT_FEATURES_DIM))
    trees = [
        AlphaTriangleNet(
            model.model_copy(update={"REMAT": remat}), tiny_env_config.action_dim
        ).init(jax.random.PRNGKey(2), grid, other, train=False)
        for remat in (False, True)
    ]
    assert "ResidualBlock_1" in trees[1]["params"]
    assert "TransformerEncoderLayer_1" in trees[1]["params"]
    assert jax.tree_util.tree_structure(trees[0]) == jax.tree_util.tree_structure(
        trees[1]
    )
    for a, b in zip(*map(jax.tree_util.tree_leaves, trees)):
        np.testing.assert_array_equal(a, b)
