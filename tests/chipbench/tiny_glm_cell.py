"""`glm-flash-learner` small enough for a CPU test: the 3x4 test board
(12 tokens), a stack of hidden 64 with 4 heads, three layers (dense,
sparse, sparse), every mixer latent with a query latent of 24, a latent
of 16 and 16 + 8 wide queries, 8 experts of width 32 (top 2, one shared,
this share holding 4 of them as chip 1 of 2), a dense layer of 96;
float32 parameters and compute, so the program and the reference agree
to rounding. Batch 16 in blocks of 4 boards, a ring of 512 rows. The
published widths stay in `chipbench/configs/`; nothing here is ever
timed."""

import copy

from chipbench import manifest


def tiny_glm_cfg(cfg: dict, chip: int = 1, compute: str = "float32") -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(
        hidden_size=64,
        num_attention_heads=4,
        num_key_value_heads=4,
        intermediate_size=96,
        moe_intermediate_size=32,
        n_routed_experts=4,
        num_experts_per_tok=2,
        num_hidden_layers=3,
        q_lora_rank=24,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
    )
    cfg["published"] = {**cfg["published"], "n_routed_experts": 8}
    cfg["deployment"] = {**cfg["deployment"], "expert_parallel": 2, "chip": chip}
    cfg["trunk_choices"] = {
        **cfg["trunk_choices"], "block_boards": 8, "learner_block_boards": 4,
    }
    cfg["env"].update(
        ROWS=3,
        COLS=4,
        PLAYABLE_RANGE_PER_ROW=[[0, 4], [0, 4], [0, 4]],
        NUM_SHAPE_SLOTS=1,
        MAX_SHAPE_TRIANGLES=3,
        LINE_MIN_LENGTH=3,
    )
    cfg["model"].update(
        CONV_FILTERS=[8],
        CONV_KERNEL_SIZES=[3],
        CONV_STRIDES=[1],
        NUM_RESIDUAL_BLOCKS=1,
        RESIDUAL_BLOCK_FILTERS=8,
        FC_DIMS_SHARED=[64],
        POLICY_HEAD_DIMS=[64],
        VALUE_HEAD_DIMS=[64],
        OTHER_NN_INPUT_FEATURES_DIM=14,
        COMPUTE_DTYPE=compute,
        PARAM_DTYPE="float32",
        INFERENCE_PRECISION="float32",
    )
    cfg["train"].update(
        BATCH_SIZE=16,
        BUFFER_CAPACITY=512,
        MIN_BUFFER_SIZE_TO_TRAIN=512,
        SELF_PLAY_BATCH_SIZE=8,
    )
    cfg["action_dim"] = 12
    return cfg


def tiny_glm_cell() -> dict:
    cell = manifest.cell("glm-flash-learner")
    cell["config_file"] = tiny_glm_cfg(cell["config_file"])
    cell["traffic_file"] = {
        **cell["traffic_file"], "fill_block_rows": 256, "reference_block": 8,
        "balance_boards": 64, "trace_units": 2,
    }
    # float32 compute: the program agrees with the reference to
    # rounding; the real cell's limits are for bfloat16.
    cell["limits"] = {
        **cell["limits"],
        "grad_norm_gap": 1e-3, "td_gap": 1e-3, "td_gap_mean": 1e-4,
        "change_gap": 0.05, "load_gap": 0.01,
    }
    return cell
