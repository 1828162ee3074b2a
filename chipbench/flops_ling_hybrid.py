"""The operations one evaluation of the net with the Ling-3.0-flash
stack needs, from the configuration's file. Matmul and convolution
terms only, 1 MAC = 2 FLOP, as `flops.py` counts.

An evaluation's count has a fixed part (the stem, the 1x1 projection,
every layer's mixer, the dense layer, the router, the shared expert,
the heads) and a part that follows the routing: one expert's SwiGLU for
each token-expert assignment that falls on an expert held here
(`flops_exaone_moe.expert_flops`). The driver multiplies that by the
assignments the program counted, so `mfu.rollout` rests on the work
really done, not on even routing.

A linear layer (KDA) is counted by what the mathematics needs, its
recurrent form: the five projections and the two small ones, the three
convolutions, and a token and head 3 x 2 x 128 x 128 for the state read
by the key, written, and read by the query. The program's chunked form
multiplies more (the pairwise products of a chunk, the triangular
inverse) and is credited no more. A latent layer's score products are
counted over the keys a query sees (j <= i), 192 wide for the scores
and 128 for the values.
"""

from .flops import _conv
from .flops_exaone_moe import expert_flops, seen_keys
from .reference_ling_hybrid import trunk_settings


def linear_mixer_flops(t: dict) -> int:
    """A KDA mixer on one token."""
    d, heads, hd = t["hidden_size"], t["num_attention_heads"], t["head_dim"]
    wide = heads * hd
    projections = 2 * (d * (4 * wide + 2 * heads) + wide * d)  # q k v f, b g, o
    convolutions = 2 * 3 * t["short_conv_kernel_size"] * wide
    recurrence = 3 * 2 * heads * hd * hd
    return projections + convolutions + recurrence


def latent_mixer_flops(t: dict, seq: int) -> int:
    """An MLA mixer on one board of `seq` tokens."""
    d, heads, rank = t["hidden_size"], t["num_attention_heads"], t["kv_lora_rank"]
    nope, rope, vd = t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    projections = 2 * (
        d * (heads * (nope + rope) + rank + rope + heads)
        + rank * heads * (nope + vd)
        + heads * vd * d
    )
    return seq * projections + 2 * heads * (nope + rope + vd) * seen_keys(seq, None)


def trunk_fixed_flops(t: dict, seq: int) -> int:
    """The stack on one board of `seq` tokens, without the routed experts."""
    d = t["hidden_size"]
    total = 0
    for kind, mlp in zip(t["layer_types"], t["mlp_layer_types"]):
        if kind == "linear_attention":
            total += seq * linear_mixer_flops(t)
        else:
            total += latent_mixer_flops(t, seq)
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
