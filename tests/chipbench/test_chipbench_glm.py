"""`glm-flash-ep8` / `glm-flash-learner`: the configuration's file against
the catalog's published keys, the FLOP count against hand figures and
the program's, and at a tiny size on the CPU the program's LEARNER
against the plain reference (`reference_glm_moe`): the forward, the
loss, the TD errors, every leaf's gradient, the state after three
steps, the routers' loads and the rule that moves their biases; one
block against four, recomputation on against off; the shares of the
experts adding up to the uncut layer, forward and backward; the cell
end to end through its own driver with the control in the program's
place, and the new readers.

Tolerances. With float32 compute the program and the reference differ
by summation order alone (the sort and grouped products against a loop
over experts, blocks of boards against the whole batch): values of size
about 1 agree to 1e-4 and better (seen: 1e-6). Adam's first update is
lr x sign(g) in every entry, so entries whose gradient is nought to
rounding may move either way: parameters after three steps are held by
the change's norm, leaf by leaf, as the cell's `change_gap` holds them.
With bfloat16 compute the program reads what the real cell's limits are
made for, and the fp8 control has to read past them.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from tiny_glm_cell import tiny_glm_cell, tiny_glm_cfg

from chipbench import flops_exaone_moe, flops_glm_moe, manifest, reference, rows, run
from chipbench import reference_glm_moe as plain
from chipbench.drivers import learner_trunk
from chipbench.spans import Spans

SEED = 2**31 + 34
ROW = "/opt/skills/guides/model-configs/architectures.jsonl"

# The `config` of the catalog's row GLM-4.7-Flash
# (model-configs/architectures.jsonl), copied.
CATALOG = {
    "attention_bias": False,
    "hidden_act": "silu",
    "hidden_size": 2048,
    "intermediate_size": 10240,
    "max_position_embeddings": 202752,
    "model_type": "glm4_moe_lite",
    "moe_intermediate_size": 1536,
    "topk_method": "noaux_tc",
    "norm_topk_prob": True,
    "num_attention_heads": 20,
    "n_group": 1,
    "topk_group": 1,
    "n_routed_experts": 64,
    "n_shared_experts": 1,
    "routed_scaling_factor": 1.8,
    "num_experts_per_tok": 4,
    "first_k_dense_replace": 1,
    "num_hidden_layers": 47,
    "num_key_value_heads": 20,
    "num_nextn_predict_layers": 1,
    "partial_rotary_factor": 1,
    "rms_norm_eps": 1e-05,
    "rope_scaling": None,
    "rope_theta": 1000000,
    "tie_word_embeddings": False,
    "q_lora_rank": 768,
    "kv_lora_rank": 512,
    "qk_nope_head_dim": 192,
    "qk_rope_head_dim": 64,
    "v_head_dim": 256,
    "vocab_size": 154880,
}
SOURCE = "https://huggingface.co/zai-org/GLM-4.7-Flash/blob/main/config.json"
REDUCED = ["num_hidden_layers", "n_routed_experts"]


def config_file() -> dict:
    return manifest.load_json(manifest.HERE / "configs" / "glm-flash-ep8.json")


# --- the configuration's file ------------------------------------------------


def test_every_published_key_stands_unchanged_but_the_two_reduced():
    cfg = config_file()
    assert cfg["source"] == SOURCE and cfg["reduced"] == REDUCED
    differ = {k for k, v in CATALOG.items() if k not in cfg or cfg[k] != v}
    assert differ == set(REDUCED)
    assert cfg["published"] == {"num_hidden_layers": 47, "n_routed_experts": 64}
    assert [cfg[k] for k in REDUCED] == [5, 8]
    entry = next(
        c for c in manifest.benchmark()["configs"] if c["name"] == "glm-flash-ep8"
    )
    assert entry["source"] == SOURCE and entry["reduced"] == REDUCED
    assert entry["file"] == "chipbench/configs/glm-flash-ep8.json"
    assert len(entry["source"]) <= 200 and len(entry["why"]) <= 200
    # No width is among the reduced, nor differs: the guide's rule.
    assert not any(
        k.endswith(("_dim", "_rank", "_size")) or k == "num_experts_per_tok"
        for k in REDUCED
    )
    # The guide's floors: four layers after the leading dense one, 8
    # routed experts held; the router as wide as published.
    t = plain.trunk_settings(cfg)
    assert t["layer_types"] == ["latent_attention"] * 5
    assert t["mlp_layer_types"] == ["dense"] + ["sparse"] * 4
    assert t["experts_held"] == [0, 8] and t["num_experts"] == 64
    assert (t["n_group"], t["topk_group"], t["num_experts_per_tok"]) == (1, 1, 4)
    assert "head_dim" not in t and t["q_lora_rank"] == 768


def test_the_copy_of_the_row_is_the_catalogs_where_the_catalog_is_at_hand():
    try:
        catalog = [json.loads(line) for line in open(ROW)]
    except OSError:
        pytest.skip("the catalog is not on this machine")
    row = next(r for r in catalog if r["name"] == "GLM-4.7-Flash")
    assert row["config"] == CATALOG and row["source_url"] == SOURCE
    assert row["head_dim"] is None


def test_the_file_states_deployment_choices_assumptions_and_departures():
    cfg = config_file()
    assert cfg["deployment"]["expert_parallel"] == 8 and cfg["deployment"]["chip"] == 0
    assert "8 chips share each layer" in cfg["deployment"]["stated"]
    assert cfg["trunk_choices"] == {
        "norm_position": "pre", "rope_layers": "latent", "router_bias": True,
        "latent_gate": False, "block_boards": 64,
        "learner_block_boards": cfg["trunk_choices"]["learner_block_boards"],
        "router_bias_rate": 0.001,
    }
    assert {
        "head_dim", "norm_position", "mla_form", "rope", "router",
        "router_bias_rate", "aux_loss", "router_bias_start", "board",
        "BUFFER_CAPACITY", "BATCH_SIZE", "learner_block_boards", "REMAT",
        "weights", "optimizer",
    } <= set(cfg["assumed"])
    assert set(cfg["departures"]) >= {
        "embedding", "output_head", "loss", "mtp", "decoding"
    }
    assert (cfg["env"]["ROWS"], cfg["env"]["COLS"], cfg["action_dim"]) == (12, 21, 756)
    model, train = cfg["model"], cfg["train"]
    assert (model["PARAM_DTYPE"], model["COMPUTE_DTYPE"]) == ("float32", "bfloat16")
    assert model["REMAT"] is True
    assert (train["BATCH_SIZE"], train["FUSED_LEARNER_STEPS"]) == (256, 1)
    assert train["BUFFER_CAPACITY"] == 250000 and train["RANDOM_SEED"] == 42
    flagship = manifest.load_json(manifest.HERE / "configs" / "flagship-p3.json")
    assert cfg["optimizer"] == flagship["optimizer"]
    for key in (
        "OPTIMIZER_TYPE", "WEIGHT_DECAY", "GRADIENT_CLIP_VALUE",
        "LR_SCHEDULER_TYPE", "LR_SCHEDULER_T_MAX", "LR_SCHEDULER_ETA_MIN",
        "PER_ALPHA", "ENTROPY_BONUS_WEIGHT",
    ):
        assert train[key] == flagship["train"][key], key
    # The floor of the flagship's schedule, flat (`assumed` says why).
    assert train["LEARNING_RATE"] == flagship["train"]["LR_SCHEDULER_ETA_MIN"] == 1e-6
    assert "LEARNING_RATE" in cfg["assumed"]


def test_the_cell_and_its_metrics_are_entries_at_the_end_of_their_lists():
    bench = manifest.benchmark()
    assert bench["configs"][-1]["name"] == "glm-flash-ep8"
    cell = bench["workloads"][-1]
    assert (cell["name"], cell["config"], cell["traffic"], cell["chips"]) == (
        "glm-flash-learner", "glm-flash-ep8", "learner-trunk", 1
    )
    assert len(cell["why"]) <= 200
    new = [m["name"] for m in bench["per_layer"][-3:]]
    assert new == [
        "expert_assignments_per_s.learner", "expert_load_max_over_mean.learner",
        "trunk_tokens_per_s.learner",
    ]
    for m in bench["per_layer"][-3:]:
        assert m["workloads"] == ["glm-flash-learner"]
        assert m["moves"] == "learner_steps_per_s" and m["layer"] == "net trunk"
    reported = {m["name"] for m in manifest.metrics_of("glm-flash-learner", True)}
    assert reported == {
        "compile_s", "host_gap_ms.learner", "replay_host_ms.learner",
        "group_device_ms", "mfu.learner", "device_idle_share.learner",
        "finish_host_ms.learner", *new,
    }
    assert {m["name"] for m in manifest.metrics_of("glm-flash-learner", False)} == {
        "learner_steps_per_s", "setup_s"
    }
    for m in bench["end_to_end"] + bench["per_layer"]:
        if "glm-flash-learner" in m.get("workloads", []):
            assert m["workloads"][-1] == "glm-flash-learner"
    # What the flagship's learner cell reports it still reports.
    assert {m["name"] for m in manifest.metrics_of("flagship-learner", True)} == (
        reported - set(new)
    )
    traffic = manifest.cell("glm-flash-learner")["traffic_file"]
    assert traffic["driver"] == "learner_trunk"
    assert traffic["rate_metric"] == "learner_steps_per_s"
    assert 2 <= traffic["trace_units"] <= 4
    limits = manifest.cell("glm-flash-learner")["limits"]
    assert set(limits) == {*learner_trunk.NUMBERS, "window_compiles"}
    assert limits["bias_rule_mismatch"] == limits["window_compiles"] == 0


def test_names_units_and_lines_with_the_reduced_configurations():
    """`test_chipbench_manifest.py` holds every configuration to
    `reduced == []` and to a preset and fails one case more since this
    configuration is a cut of a published model (PERF.md section 7; the
    file is the benchmark's and not this PR's to edit). The same lines
    here, each accepted configuration held to its own names."""
    import re

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
        assert set(m) <= {
            "name", "unit", "better", "source", "layer", "moves", "bound", "workloads"
        }
        assert set(m.get("workloads", cells)) <= set(cells)
    for w in bench["workloads"]:
        assert all(name.match(w[k]) for k in ("name", "config", "traffic"))
        assert 1 <= len(w["why"]) <= 200 and "\n" not in w["why"]
        assert w["chips"] in (1, 4)
    reduced = {
        "k-exaone-ep8": ["num_hidden_layers", "num_experts"],
        "ling-flash-ep4": ["num_hidden_layers", "first_k_dense_replace", "num_experts"],
        "glm-flash-ep8": REDUCED,
    }
    for c in bench["configs"]:
        assert name.match(c["name"]) and 1 <= len(c["why"]) <= 200
        assert len(c["source"]) <= 200
        assert c["reduced"] == reduced.get(c["name"], []), c["name"]
        assert all(name.match(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in bench["workloads"]]
    assert len(set(pairs)) == len(pairs)
    assert len((manifest.ROOT / "BENCHMARK.json").read_bytes()) < 64 * 1024


def _program_model(cfg: dict):
    from alphatriangle_tpu.config import TrunkConfig

    configs = manifest.program_configs(cfg)
    trunk = TrunkConfig(**plain.trunk_settings(cfg))
    return {**configs, "model": configs["model"].model_copy(update={"TRUNK": trunk})}


def test_the_programs_trunk_takes_the_files_keys_and_counts_its_bytes():
    from alphatriangle_tpu.nn.trunk import param_shapes

    trunk = _program_model(config_file())["model"].TRUNK
    assert trunk.head_dim is None and trunk.latent_gate is False
    shapes = param_shapes(trunk)
    count = lambda names: sum(int(np.prod(shapes[n][0])) for n in names)  # noqa: E731
    mixer = ("wq_a", "q_a_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo")
    assert "l0_wg" not in shapes and "l0_wq" not in shapes
    # 2048 x 768 + 768 x 5120 + 2048 x 576 + 512 x 8960 + 5120 x 2048, two norms
    assert count(f"l1_{n}" for n in mixer) == 21_757_952 + 768 + 512
    assert count(["l0_w_gate", "l0_w_up", "l0_w_down"]) == 3 * 2048 * 10240
    assert count(["l1_e_gate", "l1_e_up", "l1_e_down"]) == 8 * 3 * 2048 * 1536
    assert shapes["l1_w_router"][0] == (2048, 64)
    assert shapes["l1_router_bias"] == ((64,), -1)
    total = sum(int(np.prod(shape)) for shape, _ in shapes.values())
    assert total == 511_996_416  # 512.0M: dense layer 84.7M + 4 x 106.8M


def test_flops_against_hand_figures_and_the_programs():
    from alphatriangle_tpu.utils.flops import forward_flops, model_step_flops

    cfg = config_file()
    t = plain.trunk_settings(cfg)
    configs = _program_model(cfg)
    even = flops_glm_moe.even_assignments(cfg)
    assert even == 252 * 4 * 4 * 8 / 64
    whole = forward_flops(configs["model"], configs["env"], 756)
    assert whole == flops_glm_moe.forward_flops(cfg, even)
    assert model_step_flops(
        configs["model"], configs["env"], 756, 256
    ) == flops_glm_moe.train_step_flops(cfg, 256, 256 * even)
    # An MLA mixer a token: the five products, and the score products
    # over the keys a query sees, 256 wide for scores and for values.
    mla_token = 2 * (
        2048 * 768 + 768 * 5120 + 2048 * 576 + 512 * 8960 + 5120 * 2048
    )
    assert mla_token == 43_515_904
    pairs = flops_exaone_moe.seen_keys(252, None)
    assert pairs == 252 * 253 // 2
    assert flops_glm_moe.latent_mixer_flops(t, 252) == (
        252 * mla_token + 2 * 20 * 512 * pairs
    )
    expert = 2 * 3 * 2048 * 1536
    assert flops_exaone_moe.expert_flops(t) == expert == 18_874_368
    # The trunk alone, a token: 470.7 MFLOP (ISSUE 34's figure).
    per_token = (
        5 * (mla_token + 2 * 20 * 512 * 126.5)
        + 2 * 3 * 2048 * 10240
        + 4 * (2 * 2048 * 64 + expert + 4 * 8 / 64 * expert)
    )
    trunk = flops_glm_moe.trunk_fixed_flops(t, 252) + even * expert
    assert trunk == pytest.approx(252 * per_token, rel=1e-12)
    assert per_token / 1e6 == pytest.approx(470.657, abs=1e-3)
    assert whole == pytest.approx(119.214e9, rel=1e-5)
    # A step at batch 256: 91.56 TFLOP, the recomputed forward not credited.
    assert 3 * 256 * whole == pytest.approx(91.556e12, rel=1e-5)
    # Where the work is, if routing is even: the mixers 49 %.
    assert 5 * (mla_token + 2 * 20 * 512 * 126.5) / per_token == pytest.approx(
        0.490, abs=2e-3
    )


# --- the program's learner against the reference, tiny -------------------------


def _world(compute="float32", block=4, remat=True, chip=1):
    from alphatriangle_tpu.nn.network import NeuralNetwork

    cfg = tiny_glm_cfg(config_file(), chip=chip, compute=compute)
    cfg["trunk_choices"]["learner_block_boards"] = block
    cfg["model"]["REMAT"] = remat
    configs = _program_model(cfg)
    net = NeuralNetwork(configs["model"], configs["env"], seed=3)
    params = jax.tree_util.tree_map(np.asarray, net.variables["params"])
    # Biases that decide choices, as a trained router's do.
    rng = np.random.default_rng(5)
    trunk = dict(params["DecoderTrunk_0"])
    for name in trunk:
        if name.endswith("router_bias"):
            trunk[name] = rng.normal(0.0, 0.05, trunk[name].shape).astype(np.float32)
    params = {**params, "DecoderTrunk_0": trunk}
    net.variables = {"params": jax.tree_util.tree_map(jnp.asarray, params)}
    made = rows.make_rows(
        rows.seed_key(SEED), jnp.arange(0, 512, 32, dtype=jnp.int32), cfg["env"],
        cfg["model"]["OTHER_NN_INPUT_FEATURES_DIM"], cfg["action_dim"], 512,
    )
    made["weights"] = jnp.linspace(0.5, 1.0, 16)
    batch = {
        "grid": made["grid"], "other_features": made["other"],
        "policy_target": made["policy"], "value_target": made["ret"],
        "policy_weight": made["pw"], "weights": made["weights"],
    }
    return {
        "cfg": cfg, "configs": configs, "net": net, "params": params,
        "rows": made, "batch": jax.tree_util.tree_map(np.asarray, batch),
    }


@pytest.fixture(scope="module")
def world():
    return _world()


def _trainer(world):
    from alphatriangle_tpu.rl.trainer import Trainer

    return Trainer(world["net"], world["configs"]["train"])


def _leaf_gaps(got, want):
    """Each leaf's gap against the wanted leaf's norm or the median
    leaf's, whichever is larger; by name."""
    names = [
        jax.tree_util.keystr(path)
        for path, _ in jax.tree_util.tree_flatten_with_path(want)[0]
    ]
    got, want = jax.tree_util.tree_leaves(got), jax.tree_util.tree_leaves(want)
    assert len(got) == len(want) == len(names)
    norms = [float(np.linalg.norm(np.asarray(w, np.float64))) for w in want]
    floor = float(np.median(norms))
    return {
        name: float(np.linalg.norm(np.asarray(g, np.float64) - np.asarray(w, np.float64)))
        / max(norm, floor)
        for name, g, w, norm in zip(names, got, want, norms)
    }


def test_forward_loss_td_errors_and_loads_against_the_reference(world):
    cfg, net, made = world["cfg"], world["net"], world["rows"]
    (policy, value), state = net.model.apply(
        net.variables, made["grid"], made["other"], train=True, mutable=["counters"]
    )
    from alphatriangle_tpu.nn.trunk import counters_of

    counted = counters_of(state)
    want_policy, want_value, want_loads = plain.forward(
        net.variables["params"], cfg, made["grid"], made["other"]
    )
    assert float(jnp.abs(policy - want_policy).max()) < 1e-4
    assert float(jnp.abs(value - want_value).max()) < 1e-4
    assert float(jnp.abs(want_policy).max()) > 0.1
    # All 8 published experts' loads, held here (4-7) or not; exact.
    assert counted["expert_loads"].shape == (2, 8)
    np.testing.assert_array_equal(counted["expert_loads"], want_loads)
    assert int(counted["expert_loads"].sum()) == 2 * 16 * 12 * 2
    np.testing.assert_array_equal(
        counted["expert_tokens"], counted["expert_loads"][:, 4:]
    )
    # An inference forward counts no loads and is the program it was.
    _, state = net.model.apply(
        net.variables, made["grid"], made["other"], train=False, mutable=["counters"]
    )
    assert "expert_loads" not in counters_of(state)

    trainer = _trainer(world)
    total, aux = trainer._loss_fn(
        trainer.state.params, {}, jax.random.PRNGKey(0), world["batch"]
    )
    want_total, (want_td, _) = plain.loss(net.variables["params"], cfg, made)
    assert float(total) == pytest.approx(float(want_total), rel=1e-5)
    np.testing.assert_allclose(aux["td_errors"], want_td, rtol=1e-4)


def test_every_leafs_gradient_against_the_reference(world):
    trainer = _trainer(world)
    grads = jax.grad(
        lambda p: trainer._loss_fn(p, {}, jax.random.PRNGKey(0), world["batch"])[0]
    )(trainer.state.params)
    want, _, _, _ = plain.batch_gradients(
        world["net"].variables["params"], world["cfg"], world["rows"], block=8
    )
    gaps = _leaf_gaps(grads, want)
    assert len(gaps) == 77 and max(gaps.values()) < 1e-4, max(gaps.items(), key=lambda kv: kv[1])
    # No gradient reaches a selection bias, on either side.
    for tree in (grads, want):
        trunk = tree["DecoderTrunk_0"]
        for name in ("l1_router_bias", "l2_router_bias"):
            assert not np.asarray(trunk[name]).any()
        assert np.asarray(trunk["l1_w_router"]).any()  # the weights' path is live
        assert np.asarray(trunk["l2_e_down"]).any() and np.asarray(trunk["l0_wq_b"]).any()


def test_three_steps_against_the_reference(world):
    """Through `Trainer.train_step`, blocks of 4 boards and recomputed
    layers, against the plain step taken whole."""
    cfg = world["cfg"]
    trainer = _trainer(world)
    state = plain.init_state(world["net"].variables["params"])
    before = plain.biases_of(world["params"], cfg)
    for step in range(3):
        metrics, td = trainer.train_step(dict(world["batch"]))
        state, total, norm, want_td, loads = plain.train_step(
            state, cfg, world["rows"], 16
        )
        assert metrics["total_loss"] == pytest.approx(float(total), rel=1e-4), step
        assert metrics["grad_norm"] == pytest.approx(float(norm), rel=1e-3), step
        np.testing.assert_allclose(td, want_td, rtol=2e-3, err_msg=str(step))
        np.testing.assert_array_equal(
            trainer.last_counters["expert_loads"][0], loads, err_msg=str(step)
        )
        assert metrics["learning_rate"] == pytest.approx(
            float(reference.learning_rate(cfg["train"], step + 1)), rel=1e-6
        )
    got = jax.device_get(trainer.state.params)
    change = _leaf_gaps(
        jax.tree_util.tree_map(np.subtract, plain.without_biases(got),
                               plain.without_biases(world["params"])),
        jax.tree_util.tree_map(np.subtract, plain.without_biases(jax.device_get(state[0])),
                               plain.without_biases(world["params"])),
    )
    assert float(np.median(list(change.values()))) < 0.02
    np.testing.assert_array_equal(
        plain.biases_of(got, cfg), plain.biases_of(jax.device_get(state[0]), cfg)
    )
    assert (plain.biases_of(got, cfg) != before).any()
    mu = learner_trunk._mu(trainer.state.opt_state)
    gaps = _leaf_gaps(jax.device_get(mu), jax.device_get(state[1]))
    assert len(gaps) == 75 and float(np.median(list(gaps.values()))) < 0.02


@pytest.mark.parametrize(
    "other", [{"block": None}, {"remat": False}, {"block": None, "remat": False}],
    ids=["one-block-against-four", "remat-on-against-off", "neither"],
)
def test_blocks_and_recomputation_leave_the_step_what_it_is(world, other):
    changed = _world(**other)
    assert (
        jax.tree_util.tree_structure(changed["net"].variables)
        == jax.tree_util.tree_structure(world["net"].variables)
    )
    trainer, there = _trainer(world), _trainer(changed)
    new, metrics, td = jax.jit(trainer._train_step_impl)(trainer.state, world["batch"])
    want, want_metrics, want_td = jax.jit(there._train_step_impl)(
        there.state, world["batch"]
    )
    for name in ("total_loss", "policy_loss", "value_loss", "entropy", "grad_norm"):
        assert float(metrics[name]) == pytest.approx(float(want_metrics[name]), rel=1e-5)
    np.testing.assert_allclose(td, want_td, rtol=1e-5)
    np.testing.assert_array_equal(metrics["expert_loads"], want_metrics["expert_loads"])
    np.testing.assert_array_equal(metrics["expert_tokens"], want_metrics["expert_tokens"])
    # Adam's first moment is (1 - b1) x the clipped gradient: the same
    # gradients to rounding, leaf by leaf.
    gaps = _leaf_gaps(
        jax.device_get(learner_trunk._mu(new.opt_state)),
        jax.device_get(learner_trunk._mu(want.opt_state)),
    )
    assert max(gaps.values()) < 1e-5, max(gaps.items(), key=lambda kv: kv[1])


def test_bfloat16_compute_is_within_a_tolerance_that_fp8_fails():
    """The cell's own driver and comparison at a tiny size with the
    configuration's bfloat16 compute: the program reads within limits
    set at about three times what it reads here, and the reference with
    every product's operands rounded to fp8 reads past at least one."""
    cell = tiny_glm_cell()
    cell["config_file"] = tiny_glm_cfg(config_file(), compute="bfloat16")
    limits = {
        "weight_mismatch": 0, "grad_norm_gap": 0.05, "td_gap": 0.1,
        "td_gap_mean": 0.02, "change_gap": 0.5, "bias_rule_mismatch": 0,
        "load_gap": 0.1,
    }
    driver = learner_trunk.Driver(
        cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
    )
    read = learner_trunk.calibrate(driver, ["program", "control", "bf16"])
    for part in ("program", "bf16"):
        ok, compared = run.compare(
            {k: read[part][k] for k in limits}, limits
        )
        assert ok, (part, compared)
    ok, compared = run.compare({k: read["control"][k] for k in limits}, limits)
    assert not ok, compared
    assert read["control"]["td_gap_mean"] > 3 * read["program"]["td_gap_mean"]
    assert read["program"]["bias_rule_mismatch"] == 0


# --- the share and the whole layer ----------------------------------------------


def test_the_shares_add_up_to_the_uncut_layer_forward_and_backward():
    """One sparse layer, 16 experts top 2: the 8 shares' routed parts
    (2 experts each, the program's sort and grouped products) plus the
    shared expert once are the uncut reference's layer; and for one
    cotangent each share's gradients of its experts' weights are those
    slices of the whole layer's."""
    from alphatriangle_tpu.config import TrunkConfig
    from alphatriangle_tpu.nn import trunk as program

    cfg = tiny_glm_cfg(config_file())
    cfg["published"]["n_routed_experts"] = 16
    cfg["n_routed_experts"] = 2
    t = plain.trunk_settings(cfg)
    rng = np.random.default_rng(11)
    d, im, e = t["hidden_size"], t["moe_intermediate_size"], 16
    whole = {
        "w_router": rng.normal(size=(d, e)) / np.sqrt(d),
        "router_bias": rng.normal(0.0, 0.05, size=(e,)),
        "e_gate": rng.normal(size=(e, d, im)) / np.sqrt(d),
        "e_up": rng.normal(size=(e, d, im)) / np.sqrt(d),
        "e_down": rng.normal(size=(e, im, d)) / np.sqrt(im),
        "s_gate": rng.normal(size=(d, im)) / np.sqrt(d),
        "s_up": rng.normal(size=(d, im)) / np.sqrt(d),
        "s_down": rng.normal(size=(im, d)) / np.sqrt(im),
    }
    whole = {k: jnp.asarray(v, jnp.float32) for k, v in whole.items()}
    x = jnp.asarray(rng.normal(size=(3, 12, d)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(3, 12, d)), jnp.float32)
    experts = ("e_gate", "e_up", "e_down")

    def uncut(p):
        return plain.sparse_mlp(p, x, t, None, held=(0, e))[0]

    want = uncut(whole)
    want_grads = jax.grad(lambda p: (uncut(p) * ct).sum())(whole)
    shared = plain.swiglu(x, whole["s_gate"], whole["s_up"], whole["s_down"], None)

    total = shared
    for chip in range(8):
        held = (2 * chip, 2)
        trunk = TrunkConfig(**{**t, "experts_held": held})
        mine = {
            **whole, **{k: whole[k][held[0] : held[0] + 2] for k in experts}
        }

        def share(p):
            # As the learner calls it: four rounds of the layer's
            # buffers (18 rows each for 72 assignments at most), each
            # computed again in the backward pass.
            return program.sparse_mlp_counted(
                p, x, trunk, jnp.float32, train=True, remat=True
            )[:2]

        out, sizes = share(mine)
        grads = jax.grad(lambda p: (share(p)[0] * ct).sum())(mine)
        total = total + (out - shared)
        assert int(sizes.sum()) > 0
        for name in experts:
            np.testing.assert_allclose(
                grads[name], want_grads[name][held[0] : held[0] + 2],
                rtol=1e-3, atol=1e-5, err_msg=f"{chip} {name}",
            )
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-5)


# --- the cell through its own driver, and the readers ---------------------------


def test_one_run_of_the_tiny_cell_and_the_new_readers():
    result = run.run_cell(tiny_glm_cell(), SEED, 0.3, False, require_chip=False)
    assert result["correct"] and result["failed"] == 0
    assert result["compared"]["window_compiles"]["value"] == 0
    assert result["compared"]["bias_rule_mismatch"]["value"] == 0
    assert set(result["compared"]) == {*learner_trunk.NUMBERS, "window_compiles"}
    assert set(result["metrics"]) == {"learner_steps_per_s", "setup_s"}

    cell = tiny_glm_cell()
    driver = learner_trunk.Driver(
        cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
    )
    driver.setup()
    assert driver.trainer.nn.variables is None  # a learner chip serves nothing
    driver.start_window()
    for _ in range(3):
        assert driver.unit() == 1
    counters = driver.counters()
    tokens = np.asarray(counters["expert_tokens"])
    assert tokens.shape == (2, 4) and counters["routed"] == 3 * 16 * 12 * 2 * 2
    assert counters["trunk_tokens"] == 3 * 16 * 12 * 3
    assert len(counters["load_max_over_mean"]) == 3
    assert counters["step_flops"] == flops_glm_moe.train_step_flops(
        cell["config_file"], 16, tokens.sum() / 3
    )
    ctx = {"counters": counters, "window_s": 2.0, "work": 3, "peak": None}
    read = lambda name: manifest.layer_reader(name)(ctx)  # noqa: E731
    assert read("expert_assignments_per_s.learner") == tokens.sum() / 2.0
    assert read("trunk_tokens_per_s.learner") == counters["trunk_tokens"] / 2.0
    uneven = read("expert_load_max_over_mean.learner")
    assert 1.0 <= uneven < 2.0 and uneven == np.mean(counters["load_max_over_mean"])
    assert read("mfu.learner") is None  # no peak on the CPU
    # A learner cell without routers has no such counters: nothing, not 0.
    bare = {"counters": {"step_flops": 1.0}, "window_s": 2.0}
    for name in (
        "expert_assignments_per_s.learner", "expert_load_max_over_mean.learner",
        "trunk_tokens_per_s.learner",
    ):
        assert manifest.layer_reader(name)(bare) is None
    driver.release()


def test_a_program_that_cannot_describe_the_stack_is_refused_at_once(monkeypatch):
    """What the parent commit does with the cell: its `TrunkConfig` has
    no field for the query's latent, so the driver exits before
    anything is built."""
    from alphatriangle_tpu.config import TrunkConfig

    fields = dict(TrunkConfig.model_fields)
    fields.pop("q_lora_rank")
    monkeypatch.setattr(TrunkConfig, "model_fields", fields, raising=False)
    cell = tiny_glm_cell()
    with pytest.raises(SystemExit, match="refuses the stack"):
        learner_trunk.Driver(
            cell, manifest.program_configs(cell["config_file"]), SEED, Spans()
        )
