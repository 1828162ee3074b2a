"""A cell small enough for a CPU test: the flagship's entry with the
3x4 test board, a net of a few thousand weights (float32 compute, so
the program and the reference agree to rounding), K = 2 steps of 16
rows and a ring of 512; for a rollout cell 8 lanes, 8 and 4 simulations,
chunks of 4 moves. The widths of the real configuration stay in
`chipbench/configs/`; nothing here is ever timed."""

import copy

from chipbench import manifest


def tiny_cell(cell_name: str = "flagship-learner") -> dict:
    cell = manifest.cell(cell_name)
    cfg = copy.deepcopy(cell["config_file"])
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
        TRANSFORMER_DIM=8,
        TRANSFORMER_HEADS=2,
        TRANSFORMER_LAYERS=2,
        TRANSFORMER_FC_DIM=16,
        # 8 channels to a norm group: two to a group is all but
        # singular, and its gradient is rounding.
        FC_DIMS_SHARED=[64],
        POLICY_HEAD_DIMS=[64],
        VALUE_HEAD_DIMS=[64],
        OTHER_NN_INPUT_FEATURES_DIM=14,
        COMPUTE_DTYPE="float32",
    )
    cfg["train"].update(
        BATCH_SIZE=16,
        FUSED_LEARNER_STEPS=2,
        BUFFER_CAPACITY=512,
        MIN_BUFFER_SIZE_TO_TRAIN=512,
    )
    cfg["train"].update(SELF_PLAY_BATCH_SIZE=8)
    cfg["mcts"].update(max_simulations=8, max_depth=4, mcts_batch_size=4)
    if cfg["mcts"]["fast_simulations"]:
        cfg["mcts"].update(fast_simulations=4, gumbel_m=4)
    cfg["action_dim"] = 12
    cell["config_file"] = cfg
    cell["traffic_file"] = {
        **cell["traffic_file"], "fill_block_rows": 128, "chunk_moves": 4,
        # 12 actions: a crowd of 3, and no floor on the lanes read.
        "crowd": 3, "min_read_share": 0.0, "unsure_most": 4,
    }
    if "root_value_gap_mean" in cell["limits"]:
        # float32 compute: the program reads 1e-6 here and the fp8
        # control 0.02 and more; the real cell's limits are for bfloat16.
        cell["limits"] = {
            **cell["limits"],
            "root_value_gap_mean": 1e-4,
            "target_value_gap_mean": 1e-4,
        }
    return cell
