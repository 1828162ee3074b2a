"""`k-exaone-ep8` / `k-exaone-rollout`: the configuration's file against
the catalog's published keys, the FLOP count against hand figures, and
at a tiny size on the CPU the program against the plain reference
(`reference_exaone_moe`): logits through `NeuralNetwork`, one dispatch
of the cell end to end with the control in the program's place, the
serve dispatch, one learner step, the shares of the experts adding up
to the uncut layer, and how often bfloat16 rounding flips a top-k
choice.

Tolerances. The tiny net computes in float32 on both sides, so the two
differ by summation order alone: logits of size about 1 agree to 1e-3
(seen: 2e-4; five post-norm layers each renormalise a sum whose terms
were added in another order), loss and gradient norm to 1e-3 relative.
"""

import json
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny_trunk_cell import tiny_trunk_cell, tiny_trunk_cfg

from chipbench import flops_exaone_moe, manifest, reference, router_balance, run
from chipbench import reference_exaone_moe as plain
from chipbench.drivers import rollout_trunk

SEED = 2**31 + 27
LOGIT_TOLERANCE = 1e-3

# The `config` of the catalog's row K-EXAONE-236B-A23B
# (model-configs/architectures.jsonl), copied: the lists are the 48
# published entries, written by their period.
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
CATALOG = {
    "first_k_dense_replace": 1,
    "head_dim": 128,
    "hidden_act": "silu",
    "hidden_size": 6144,
    "intermediate_size": 18432,
    "layer_types": PERIOD * 12,
    "max_position_embeddings": 262144,
    "mlp_layer_types": ["dense"] + ["sparse"] * 47,
    "model_type": "exaone_moe",
    "moe_intermediate_size": 2048,
    "mtp_layer_types": ["full_attention"],
    "mtp_sliding_windows": [0],
    "n_group": 1,
    "norm_topk_prob": True,
    "num_attention_heads": 64,
    "num_experts": 128,
    "num_experts_per_tok": 8,
    "num_hidden_layers": 48,
    "num_key_value_heads": 8,
    "num_nextn_predict_layers": 1,
    "num_shared_experts": 1,
    "rms_norm_eps": 1e-05,
    "rope_parameters": {"rope_theta": 1000000, "rope_type": "default"},
    "routed_scaling_factor": 2.5,
    "scoring_func": "sigmoid",
    "sliding_window": 128,
    "sliding_window_pattern": "LLLG",
    "sliding_windows": [128, 128, 128, 0] * 12,
    "tie_word_embeddings": False,
    "topk_group": 1,
    "vocab_size": 153600,
}
SOURCE = "https://huggingface.co/LGAI-EXAONE/K-EXAONE-236B-A23B/blob/main/config.json"


def config_file() -> dict:
    return manifest.load_json(manifest.HERE / "configs" / "k-exaone-ep8.json")


# --- the configuration's file ------------------------------------------------


def test_every_published_key_stands_unchanged_but_the_two_reduced():
    cfg = config_file()
    assert cfg["source"] == SOURCE and cfg["reduced"] == [
        "num_hidden_layers", "num_experts"
    ]
    differ = {k for k, v in CATALOG.items() if cfg.get(k) != v}
    assert differ == set(cfg["reduced"])
    assert cfg["published"] == {"num_hidden_layers": 48, "num_experts": 128}
    assert (cfg["num_hidden_layers"], cfg["num_experts"]) == (5, 16)
    entry = next(
        c for c in manifest.benchmark()["configs"] if c["name"] == "k-exaone-ep8"
    )
    assert entry["source"] == SOURCE and entry["reduced"] == cfg["reduced"]
    # The guide's floors: a whole period and four layers after the dense
    # one, at least 8 routed experts held.
    t = plain.trunk_settings(cfg)
    assert t["layer_types"] == PERIOD + ["sliding_attention"]
    assert t["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert t["experts_held"] == [0, 16] and t["num_experts"] == 128


def test_the_file_states_deployment_choices_and_departures():
    cfg = config_file()
    assert cfg["deployment"]["expert_parallel"] == 8
    assert "8 chips share each layer" in cfg["deployment"]["stated"]
    assert cfg["trunk_choices"] == {
        "norm_position": "post", "qk_norm": True, "rope_layers": "sliding",
        "router_bias": True, "block_boards": cfg["trunk_choices"]["block_boards"],
    }
    assert set(cfg["trunk_choices"]) | {"router_bias", "board", "mcts"} <= set(
        cfg["assumed"]
    )
    assert set(cfg["departures"]) >= {"embedding", "output_head", "mtp"}
    assert (cfg["env"]["ROWS"], cfg["env"]["COLS"], cfg["action_dim"]) == (12, 21, 756)
    assert cfg["model"]["PARAM_DTYPE"] == cfg["model"]["INFERENCE_PRECISION"] == "bfloat16"


def test_the_programs_trunk_takes_the_files_keys_and_counts_its_bytes():
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn.trunk import param_shapes

    trunk = TrunkConfig(**plain.trunk_settings(config_file()))
    # 113.25M attention + 339.7M dense; 755.8M a sparse layer with 16
    # experts and the shared one; five layers 3.476B = 6.95 GB bfloat16.
    count = sum(int(np.prod(shape)) for shape, _ in param_shapes(trunk).values())
    assert count == pytest.approx(3.476e9, rel=1e-3)


def test_names_units_and_lines_with_a_reduced_configuration():
    """`test_chipbench_manifest.py::test_names_units_and_lines` holds
    every configuration to `reduced == []` and fails since this cell's
    is a cut of a published model (CHANGES.md, PR 27: that file is not
    this PR's to edit). The same lines here, an accepted configuration
    still held to `reduced == []` and this one to its two names."""
    name = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
    unit = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
    bench = manifest.benchmark()
    metrics = bench["end_to_end"] + bench["per_layer"]
    names = [m["name"] for m in metrics]
    cells = [w["name"] for w in bench["workloads"]]
    assert len(set(names)) == len(names) and len(set(cells)) == len(cells)
    for m in metrics:
        assert name.match(m["name"]) and unit.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in {
            "device_trace", "program_span", "program_counter", "host_clock"
        }
    for w in bench["workloads"]:
        assert all(name.match(w[k]) for k in ("name", "config", "traffic"))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
    reduced = {"k-exaone-ep8": ["num_hidden_layers", "num_experts"]}
    for c in bench["configs"]:
        assert name.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert len(c["source"]) <= 200
        assert c["reduced"] == reduced.get(c["name"], []), c["name"]
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)


def test_flops_against_hand_figures():
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.utils.flops import forward_flops as the_programs

    cfg = config_file()
    t = plain.trunk_settings(cfg)
    configs = manifest.program_configs(cfg)
    model = configs["model"].model_copy(update={"TRUNK": TrunkConfig(**t)})
    assert the_programs(model, configs["env"], 756) == flops_exaone_moe.forward_flops(
        cfg, flops_exaone_moe.even_assignments(cfg)
    )
    # Per token: layer 0 = attention 2 x 113.25M + dense 2 x 339.7M.
    attention = 2 * (6144 * (8192 + 2 * 1024) + 8192 * 6144)
    assert attention == pytest.approx(0.2265e9, rel=1e-3)
    expert = 2 * 3 * 6144 * 2048
    assert flops_exaone_moe.expert_flops(t) == expert == 75_497_472
    router = 2 * 6144 * 128
    keys = 4 * 95.746 + 126.5  # keys a query sees, summed over the layers
    assert flops_exaone_moe.seen_keys(252, 128) / 252 == pytest.approx(95.746, rel=1e-4)
    assert flops_exaone_moe.seen_keys(252, None) / 252 == 126.5
    per_token = (
        5 * attention + 2 * 3 * 6144 * 18432 + 4 * (expert + router)
        + 2 * 2 * 8192 * keys
    )
    assert flops_exaone_moe.trunk_fixed_flops(t, 252) == pytest.approx(
        252 * per_token, rel=1e-6
    )
    assert flops_exaone_moe.even_assignments(cfg) == 252 * 4 * 8 * 16 / 128
    whole = flops_exaone_moe.forward_flops(cfg, flops_exaone_moe.even_assignments(cfg))
    assert whole == pytest.approx(615e9, rel=3e-3)  # 2.44 GFLOP a token x 252
    assert whole == pytest.approx(cfg["forward_gflop"] * 1e9, rel=3e-3)


# --- the program against the reference, tiny ---------------------------------


@pytest.fixture(scope="module")
def world():
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn.network import NeuralNetwork

    cfg = tiny_trunk_cfg(config_file())
    configs = manifest.program_configs(cfg)
    model = configs["model"].model_copy(
        update={"TRUNK": TrunkConfig(**plain.trunk_settings(cfg))}
    )
    net = NeuralNetwork(model, configs["env"], seed=3)
    rng = np.random.default_rng(0)
    grid = rng.integers(-1, 2, (6, 1, 3, 4)).astype(np.float32)
    other = rng.random((6, model.OTHER_NN_INPUT_FEATURES_DIM)).astype(np.float32)
    return {
        "cfg": cfg, "configs": {**configs, "model": model}, "net": net,
        "grid": grid, "other": other,
    }


def test_logits_through_the_network_wrapper(world):
    net, cfg = world["net"], world["cfg"]
    probs, values = net.evaluate_features(world["grid"], world["other"])
    logits, value_logits = plain.forward(
        net.variables["params"], cfg, world["grid"], world["other"]
    )
    want = jax.nn.log_softmax(logits, axis=-1)
    assert np.abs(np.log(probs) - np.asarray(want)).max() < LOGIT_TOLERANCE
    support = np.linspace(
        cfg["model"]["VALUE_MIN"], cfg["model"]["VALUE_MAX"],
        cfg["model"]["NUM_VALUE_ATOMS"],
    )
    want_value = (np.asarray(jax.nn.softmax(value_logits, axis=-1)) * support).sum(-1)
    assert np.abs(values - want_value).max() < LOGIT_TOLERANCE


def test_one_dispatch_end_to_end_and_the_control_in_its_place():
    """`play_moves_device` through the cell's own driver and comparison:
    the program is correct; the fp8 net in its place is not."""
    result = run.run_cell(tiny_trunk_cell(), SEED, 0.3, False, require_chip=False)
    assert result["correct"] and result["failed"] == 0
    assert result["compared"]["window_compiles"]["value"] == 0

    from chipbench.spans import Spans

    cell = tiny_trunk_cell()
    driver = rollout_trunk.Driver(
        cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
    )
    driver.setup()
    driver.unit()
    driver.release()
    limits = {k: v for k, v in cell["limits"].items() if k != "window_compiles"}
    assert run.compare(driver.check(), limits)[0]
    assert driver.read["roots"] > 0 and len(driver.read["routed_here_by_layer"]) == 4
    assert not run.compare(driver.check(quant=reference.fp8), limits)[0]
    counters = driver.counters()
    tokens = np.asarray(counters["expert_tokens"])
    assert tokens.shape == (4, 2) and tokens.sum() > 0
    assert 0 < tokens.sum() <= counters["routed"]
    fixed = flops_exaone_moe.forward_fixed_flops(cell["config_file"])
    assert counters["forward_flops"] > fixed


def test_the_serve_dispatch(world):
    """The serve program's search of the sessions' boards: its root
    prior is the reference's masked softmax, and it counts the experts'
    assignments like the rollout's."""
    from alphatriangle_tpu.env.engine import TriangleEnv
    from alphatriangle_tpu.features.core import get_feature_extractor
    from alphatriangle_tpu.mcts.search import BatchedMCTS
    from alphatriangle_tpu.serving.service import PolicyService

    configs, net, cfg = world["configs"], world["net"], world["cfg"]
    env = TriangleEnv(configs["env"])
    extractor = get_feature_extractor(env, configs["model"])
    mcts_cfg = configs["mcts"].model_copy(
        update={"root_selection": "puct", "fast_simulations": 0,
                "dirichlet_epsilon": 0.0, "max_simulations": 4}
    )
    mcts = BatchedMCTS(env, extractor, net.model, mcts_cfg, net.support)
    service = PolicyService(env, extractor, net, mcts, slots=4)
    sessions = service.open_sessions(jax.random.split(jax.random.PRNGKey(2), 4))
    variables, states, key = service._sample_args()[:3]
    out = service._search(variables, states, key)
    out = out[0] if isinstance(out, tuple) else out
    grid, other = extractor.extract_batch(states)
    logits, _ = plain.forward(net.variables["params"], cfg, grid, other)
    valid = np.asarray(jax.vmap(env.valid_action_mask)(states))
    want = jax.nn.softmax(jnp.where(valid, logits, -jnp.inf), axis=-1)
    assert np.abs(np.asarray(out.root_prior) - np.asarray(want)).max() < LOGIT_TOLERANCE
    assert int(out.net_counters["routed"]) == (4 + 4 * 4) * 12 * 2 * 4
    for s in sessions:
        service.request_move(s.sid)
    assert len(service.dispatch()) == 4


def test_one_learner_step_against_the_gradient_of_the_reference(world):
    from alphatriangle_tpu.rl.trainer import Trainer

    configs, net, cfg = world["configs"], world["net"], world["cfg"]
    train = configs["train"].model_copy(update={"BATCH_SIZE": 6, "USE_PER": False})
    trainer = Trainer(net, train)
    rng = np.random.default_rng(1)
    policy = rng.random((6, 12)).astype(np.float32)
    policy /= policy.sum(axis=1, keepdims=True)
    batch = {
        "grid": world["grid"], "other": world["other"], "policy": policy,
        "ret": rng.uniform(-3, 3, 6).astype(np.float32),
        "pw": np.ones(6, np.float32), "weights": np.ones(6, np.float32),
    }
    (metrics, _), = trainer.train_steps([{
        "grid": batch["grid"], "other_features": batch["other"],
        "policy_target": batch["policy"], "value_target": batch["ret"],
        "weights": batch["weights"],
    }])
    params = net.variables["params"]
    (loss, _), grads = jax.value_and_grad(plain.loss, has_aux=True)(params, cfg, batch)
    assert metrics["total_loss"] == pytest.approx(float(loss), rel=1e-3)
    assert metrics["grad_norm"] == pytest.approx(
        float(reference.global_norm(grads)), rel=1e-3
    )
    moved = {
        jax.tree_util.keystr(path): float(jnp.abs(a - b).max())
        for (path, a), b in zip(
            jax.tree_util.tree_leaves_with_path(trainer.state.params),
            jax.tree_util.tree_leaves(params),
        )
    }
    # Every leaf trains but the selection biases: the choice has no
    # gradient, and the rule that moves them is not the optimizer's.
    still = {name for name, gap in moved.items() if gap == 0}
    assert still == {f"['DecoderTrunk_0']['l{i}_router_bias']" for i in (1, 2, 3, 4)}


# --- the share and the model --------------------------------------------------


def _layer(world, i=1):
    t = plain.trunk_settings(world["cfg"])
    trunk = world["net"].variables["params"]["DecoderTrunk_0"]
    x = jnp.asarray(
        np.random.default_rng(4).normal(size=(3, 12, t["hidden_size"])), jnp.float32
    )
    return t, trunk, x


def _whole_layer_weights(world, key=11):
    """Layer 1's weights with all 8 experts: the share's router, and
    experts drawn afresh so that each of the 4 shares holds 2 of them."""
    t, trunk, x = _layer(world)
    p = plain.layer_weights(trunk, 1)
    keys = jax.random.split(jax.random.PRNGKey(key), 3)
    d, im = t["hidden_size"], t["moe_intermediate_size"]
    p["e_gate"] = jax.random.normal(keys[0], (8, d, im)) / np.sqrt(d)
    p["e_up"] = jax.random.normal(keys[1], (8, d, im)) / np.sqrt(d)
    p["e_down"] = jax.random.normal(keys[2], (8, im, d)) / np.sqrt(im)
    return t, p, x


def test_the_shares_add_up_to_the_uncut_layer(world):
    """8 experts as 4 shares of 2: the programs' routed parts of the four
    shares, plus the shared expert once, are the reference's uncut
    layer output."""
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk as program

    t, p, x = _whole_layer_weights(world)
    uncut = plain.sparse_mlp(p, x, t, None, held=(0, 8))
    flat = x.reshape(-1, x.shape[-1])
    parts = jnp.zeros_like(flat)
    for chip in range(4):
        cfg = TrunkConfig(**{**t, "experts_held": (2 * chip, 2)})
        mine = {
            **p,
            **{k: p[k][2 * chip : 2 * chip + 2] for k in ("e_gate", "e_up", "e_down")},
        }
        chosen, weight = program.route(p, flat, cfg, jnp.float32)
        routed, sizes = program.routed_experts(
            mine, flat, chosen, weight, cfg, jnp.float32
        )
        assert int(sizes.sum()) == int(((chosen // 2) == chip).sum())
        parts = parts + routed
    shared = plain.swiglu(flat, p["s_gate"], p["s_up"], p["s_down"], None)
    assert np.abs(np.asarray(parts + shared) - np.asarray(uncut.reshape(flat.shape))).max() < 1e-4


def test_a_share_that_holds_no_chosen_expert_adds_the_shared_expert_alone(world):
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk as program

    t, p, x = _whole_layer_weights(world)
    cfg = TrunkConfig(**{**t, "experts_held": (6, 2)})
    mine = {**p, **{k: p[k][6:8] for k in ("e_gate", "e_up", "e_down")}}
    out, _ = program.sparse_mlp(mine, x, cfg, jnp.float32)
    chosen, _ = program.route(p, x.reshape(-1, x.shape[-1]), cfg, jnp.float32)
    none_here = np.asarray((chosen < 6).all(axis=-1))
    assert none_here.any() and not none_here.all()
    shared = program.swiglu(x, p["s_gate"], p["s_up"], p["s_down"], jnp.float32)
    flat_out, flat_shared = (np.asarray(a).reshape(-1, x.shape[-1]) for a in (out, shared))
    assert (flat_out[none_here] == flat_shared[none_here]).all()
    assert (flat_out[~none_here] != flat_shared[~none_here]).any()


def test_more_assignments_than_the_usual_room_go_round_again(world):
    """A router that sends every token to the two held experts fills the
    expert layer's buffers twice over; nothing is dropped."""
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk as program

    t, p, x = _whole_layer_weights(world)
    cfg = TrunkConfig(**{**t, "experts_held": (0, 2)})
    x = jnp.abs(x) + 0.1  # every token scores experts 0 and 1 highest
    p["w_router"] = jnp.ones_like(p["w_router"]).at[:, 2:].set(-1.0)
    mine = {**p, **{k: p[k][:2] for k in ("e_gate", "e_up", "e_down")}}
    flat = x.reshape(-1, x.shape[-1])
    chosen, weight = program.route(p, flat, cfg, jnp.float32)
    routed, sizes = program.routed_experts(mine, flat, chosen, weight, cfg, jnp.float32)
    assert int(sizes.sum()) == 2 * flat.shape[0]  # twice the usual room
    want = plain.sparse_mlp(mine, x, {**t, "num_shared_experts": 0}, None, held=(0, 2))
    assert np.abs(np.asarray(routed) - np.asarray(want).reshape(flat.shape)).max() < 1e-4


def test_how_often_bfloat16_rounding_flips_a_top_k_choice(world):
    """The program's router reads tokens rounded to bfloat16; the
    reference reads them in float32. Count the tokens whose chosen set
    differs: some do (a flip moves one expert in or out, 1 / k of the
    routed sum, and reaches this share only if that expert is held
    here), most do not."""
    t, trunk, _ = _layer(world)
    x = jnp.asarray(
        np.random.default_rng(5).normal(size=(4096, t["hidden_size"])), jnp.float32
    )
    p = plain.layer_weights(trunk, 1)
    exact, _ = plain.route(p, x, t, None)
    rounded, _ = plain.route(p, x, t, reference.bf16)
    flipped = (np.sort(exact, axis=-1) != np.sort(rounded, axis=-1)).any(axis=-1).mean()
    assert 0.0 < flipped < 0.05, flipped


# --- the routers' selection biases ---------------------------------------------


def test_the_balancing_rule_evens_a_router_that_sends_all_cells_one_way():
    """Scores whose spread over the experts is a hundred times their
    spread over the tokens: unbiased, every token picks the same 2 of
    8; the bias the rule rests at gives each expert its share."""
    key = jax.random.PRNGKey(0)
    scores = jax.nn.sigmoid(
        2.0 * jax.random.normal(key, (8,))
        + 0.02 * jax.random.normal(jax.random.fold_in(key, 1), (4096, 8))
    )

    def loads(bias):
        _, chosen = jax.lax.top_k(scores + bias, 2)
        return np.bincount(np.asarray(chosen).reshape(-1), minlength=8)

    assert loads(0.0).max() == 4096
    bias = router_balance.balanced_bias(scores, 2)
    assert bias.dtype == jnp.float32
    assert loads(bias).max() / loads(bias).mean() < 1.02


def test_balancing_sets_the_biases_and_nothing_else(world):
    """By the reference's layers alone; the program, handed the tree,
    then loads this share (2 of 8 experts, top 2) with a quarter of the
    sample's assignments in every sparse layer, on the sample and on
    other boards of its kind. (Random planes: games of a few moves on
    the 12-cell test board repeat each other, and boards that are the
    same route the same way whatever the bias.)"""
    from alphatriangle_tpu.nn.trunk import counters_of

    net, cfg, configs = world["net"], world["cfg"], world["configs"]
    params = net.variables["params"]
    games = router_balance.sample_boards(configs, jax.random.PRNGKey(1), 64, 3)
    assert games.shape == (64, 1, 3, 4) and len(np.unique(games, axis=0)) > 8
    assert (games[::4] == games[0]).all()  # every fourth is a fresh game
    rng = np.random.default_rng(0)
    grid = rng.integers(-1, 2, (64, 1, 3, 4)).astype(np.float32)
    balanced = router_balance.balance(params, cfg, grid, block=16)
    before, after = params["DecoderTrunk_0"], balanced["DecoderTrunk_0"]
    for name in before:
        if name.endswith("router_bias"):
            assert after[name].dtype == jnp.float32
            assert float(jnp.abs(after[name]).max()) > 0
        else:
            assert after[name] is before[name]
    assert all(balanced[k] is params[k] for k in params if k != "DecoderTrunk_0")

    def share(boards):
        other = np.zeros((len(boards), configs["model"].OTHER_NN_INPUT_FEATURES_DIM))
        _, state = net.model.apply(
            {"params": balanced}, boards, other.astype(np.float32),
            train=False, mutable=["counters"],
        )
        counted = counters_of(state)
        return np.asarray(4 * counted["expert_tokens"].sum(axis=1) / counted["routed"])

    assert np.abs(share(grid) - 0.25).max() < 0.03, share(grid)
    fresh = rng.integers(-1, 2, (64, 1, 3, 4)).astype(np.float32)
    assert np.abs(share(fresh) - 0.25).max() < 0.08, share(fresh)


def test_the_unit_of_the_window_is_a_whole_period_of_the_playout_cap():
    """Dispatches up to and with the next full search; the warm-up and
    `calibrate` drive single ones."""
    from chipbench.spans import Spans

    cell = tiny_trunk_cell()
    cell["traffic_file"] = {**cell["traffic_file"], "chunk_moves": 1}
    driver = rollout_trunk.Driver(
        cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
    )
    driver.setup()
    assert driver.dispatches == 1
    # The engine's key 0 draws fast x 5 (the first the warm-up's), full,
    # fast x 3, full, full: periods of 5, 4 and 1 dispatches.
    lanes = driver.lanes
    for dispatches in (5, 4, 1):
        before = driver.dispatches
        assert driver.unit() == dispatches * lanes
        assert driver.dispatches - before == dispatches
        assert bool(driver.engine.last_trace["is_full"][0])
    driver.whole_periods = False
    assert driver.unit() == lanes


# --- the new metrics' readers, and the parent ---------------------------------


def test_expert_counter_readers():
    ctx = {
        "window_s": 2.0,
        "counters": {"expert_tokens": [[10, 30], [20, 20]], "routed": 640},
    }
    read = manifest.layer_reader
    assert read("expert_assignments_per_s")(ctx) == 40.0
    assert read("expert_load_max_over_mean")(ctx) == 1.5
    assert read("routed_here_share")(ctx) == 12.5
    silent = {"window_s": 2.0, "counters": {"simulations": 3}}
    for name in (
        "expert_assignments_per_s", "expert_load_max_over_mean", "routed_here_share"
    ):
        assert read(name)(silent) is None  # a program without the counters


def test_a_program_without_the_trunk_is_refused_at_once(monkeypatch):
    from alphatriangle_tpu.config import ModelConfig
    from chipbench.spans import Spans

    fields = {k: v for k, v in ModelConfig.model_fields.items() if k != "TRUNK"}
    monkeypatch.setattr(ModelConfig, "model_fields", fields, raising=False)
    cell = tiny_trunk_cell()
    with pytest.raises(SystemExit, match="TRUNK"):
        rollout_trunk.Driver(cell, {}, SEED, Spans())
