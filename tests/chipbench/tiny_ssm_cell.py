"""`nemotron-super-rollout` small enough for a CPU test: the 3x4 test
board (12 tokens), the published pattern's 11 letters `MEMEMEM*EME` at
a hidden size of 32: Mamba-2 mixers of 8 heads of 8 (expand 2) in 2
groups with a state of 8 and chunks of 8 (a board is two chunks, the
second half filled), attention of 4 heads over 2 key/value heads of 8,
8 experts of width 24 in a latent of 16 (top 3, not gated, this share
holding 2 of them as chip 1 of 4), a shared expert of 48; float32
parameters and compute, so the program and the reference agree to
rounding. 8 lanes, 8 and 4 simulations. The published widths stay in
`chipbench/configs/`; nothing here is ever timed."""

import copy

from chipbench import manifest


def tiny_ssm_cfg(cfg: dict, chip: int = 1) -> dict:
    cfg = copy.deepcopy(cfg)
    cfg.update(
        hidden_size=32,
        num_attention_heads=4,
        num_key_value_heads=2,
        head_dim=8,
        mamba_num_heads=8,
        mamba_head_dim=8,
        n_groups=2,
        ssm_state_size=8,
        chunk_size=8,
        moe_latent_size=16,
        moe_intermediate_size=24,
        moe_shared_expert_intermediate_size=48,
        n_routed_experts=2,
        num_experts_per_tok=3,
    )
    cfg["published"] = {**cfg["published"], "n_routed_experts": 8}
    cfg["deployment"] = {**cfg["deployment"], "expert_parallel": 4, "chip": chip}
    cfg["trunk_choices"] = {**cfg["trunk_choices"], "block_boards": 8}
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


def tiny_ssm_cell() -> dict:
    cell = manifest.cell("nemotron-super-rollout")
    cell["config_file"] = tiny_ssm_cfg(cell["config_file"])
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
