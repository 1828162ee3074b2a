"""`ling-flash-rollout` small enough for a CPU test: the 3x4 test board
(12 tokens), a stack of hidden 64 with 4 heads of 16, three layers by
the published rule at a period of 3 (linear + dense, linear + sparse,
latent + sparse), a latent of 16 with 16 + 8 wide queries, 8 experts of
width 32 in 2 groups of which 1 stays (top 2, one shared, this share
holding 2 of them as chip 1 of 4), a dense layer of 96; float32
parameters and compute, so the program and the reference agree to
rounding. 8 lanes, 8 and 4 simulations. The published widths stay in
`chipbench/configs/`; nothing here is ever timed."""

import copy

from chipbench import manifest


def tiny_hybrid_cfg(cfg: dict, chip: int = 1) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(
        hidden_size=64,
        num_attention_heads=4,
        num_key_value_heads=4,
        head_dim=16,
        intermediate_size=96,
        moe_intermediate_size=32,
        num_experts=2,
        num_experts_per_tok=2,
        n_group=2,
        topk_group=1,
        num_hidden_layers=3,
        layer_group_size=3,
        kv_lora_rank=16,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
    )
    cfg["published"] = {**cfg["published"], "num_experts": 8}
    cfg["deployment"] = {**cfg["deployment"], "expert_parallel": 4, "chip": chip}
    cfg["trunk_choices"] = {
        **cfg["trunk_choices"], "block_boards": 8, "linear_chunk": 16
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
        COMPUTE_DTYPE="float32",
        PARAM_DTYPE="float32",
        INFERENCE_PRECISION="float32",
    )
    cfg["train"].update(
        BATCH_SIZE=16,
        BUFFER_CAPACITY=512,
        MIN_BUFFER_SIZE_TO_TRAIN=512,
        SELF_PLAY_BATCH_SIZE=8,
    )
    cfg["mcts"].update(
        max_simulations=8, max_depth=4, mcts_batch_size=4,
        fast_simulations=4, gumbel_m=4,
    )
    cfg["action_dim"] = 12
    return cfg


def tiny_hybrid_cell() -> dict:
    cell = manifest.cell("ling-flash-rollout")
    cell["config_file"] = tiny_hybrid_cfg(cell["config_file"])
    cell["traffic_file"] = {
        **cell["traffic_file"], "chunk_moves": 4, "reference_block": 8,
        # 12 actions: a crowd of 3, and no floor on the lanes read.
        "crowd": 3, "min_read_share": 0.0, "unsure_most": 4,
    }
    # float32 compute: the program reads 1e-6 and the fp8 control 0.02
    # and more; the real cell's limits are for bfloat16.
    cell["limits"] = {
        **cell["limits"],
        "root_value_gap_mean": 1e-4,
        "target_value_gap_mean": 1e-4,
    }
    return cell
