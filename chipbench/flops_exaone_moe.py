"""The operations one evaluation of the net with the K-EXAONE stack
needs, from the configuration's file. Matmul and convolution terms only,
1 MAC = 2 FLOP, as `flops.py` counts.

An evaluation's count has a fixed part (the stem, the 1x1 projection,
every layer's attention, the dense layer, the router, the shared expert,
the heads) and a part that follows the routing: one expert's SwiGLU for
each token-expert assignment that falls on an expert held here. The
driver multiplies that by the assignments the program counted, so
`mfu.rollout` rests on the work really done, not on even routing.

Attention's score products are counted over the keys a query sees
(j <= i, within the window on a sliding layer): what the mathematics
needs; a program that multiplies the masked pairs too does more and is
credited no more.
"""

from .flops import _conv
from .reference_exaone_moe import trunk_settings


def seen_keys(seq: int, window) -> int:
    """Query-key pairs under the causal mask, within `window` if given."""
    return sum(min(i + 1, window or seq) for i in range(seq))


def expert_flops(t: dict) -> int:
    """One expert's SwiGLU on one token: gate, up, down."""
    return 2 * 3 * t["hidden_size"] * t["moe_intermediate_size"]


def trunk_fixed_flops(t: dict, seq: int) -> int:
    """The stack on one board of `seq` tokens, without the routed experts."""
    d, hd = t["hidden_size"], t["head_dim"]
    q_out = t["num_attention_heads"] * hd
    kv_out = t["num_key_value_heads"] * hd
    projections = 2 * (d * (q_out + 2 * kv_out) + q_out * d)
    total = 0
    for kind, mlp in zip(t["layer_types"], t["mlp_layer_types"]):
        total += seq * projections
        window = t["sliding_window"] if kind == "sliding_attention" else None
        total += 2 * 2 * q_out * seen_keys(seq, window)  # q k^T and P v
        if mlp == "dense":
            total += seq * 2 * 3 * d * t["intermediate_size"]
        else:
            total += seq * 2 * d * t["num_experts"]  # the router
            total += seq * t["num_shared_experts"] * expert_flops(t)
    return total


def forward_fixed_flops(cfg: dict) -> int:
    """One evaluation without the routed experts: stem, projection,
    stack, heads."""
    model, env, t = cfg["model"], cfg["env"], trunk_settings(cfg)
    h, w = env["ROWS"], env["COLS"]
    total, cin = 0, model["GRID_INPUT_CHANNELS"]
    for f, k, s in zip(
        model["CONV_FILTERS"], model["CONV_KERNEL_SIZES"], model["CONV_STRIDES"]
    ):
        total += _conv(h, w, cin, f, k, s)
        cin = f
    rf = model["RESIDUAL_BLOCK_FILTERS"]
    total += model["NUM_RESIDUAL_BLOCKS"] * 2 * _conv(h, w, rf, rf, 3, 1)
    total += _conv(h, w, rf, t["hidden_size"], 1, 1)
    total += trunk_fixed_flops(t, h * w)
    dim = h * w * t["hidden_size"] + model["OTHER_NN_INPUT_FEATURES_DIM"]
    for fc in model["FC_DIMS_SHARED"]:
        total += 2 * dim * fc
        dim = fc
    for dims, out in (
        (model["POLICY_HEAD_DIMS"], cfg["action_dim"]),
        (model["VALUE_HEAD_DIMS"], model["NUM_VALUE_ATOMS"]),
    ):
        hd = dim
        for fc in dims:
            total += 2 * hd * fc
            hd = fc
        total += 2 * hd * out
    return total


def even_assignments(cfg: dict) -> float:
    """Assignments an evaluation would bring here if routing were even."""
    t = trunk_settings(cfg)
    sparse = sum(m == "sparse" for m in t["mlp_layer_types"])
    tokens = cfg["env"]["ROWS"] * cfg["env"]["COLS"]
    share = t["experts_held"][1] / t["num_experts"]
    return tokens * sparse * t["num_experts_per_tok"] * share


def forward_flops(cfg: dict, assignments: float) -> float:
    """One evaluation that computed `assignments` token-expert products."""
    return forward_fixed_flops(cfg) + assignments * expert_flops(trunk_settings(cfg))
