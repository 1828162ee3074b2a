"""The operations one evaluation of the net with the Nemotron-3-Super
stack needs, from the configuration's file. Matmul and convolution
terms only, 1 MAC = 2 FLOP, as `flops.py` counts.

An evaluation's count has a fixed part (the stem, the 1x1 projection,
every Mamba-2 mixer, the attention layer, and of every expert layer the
router, the two latent projections and the shared expert, the heads)
and a part that follows the routing: one expert's two matrices in the
latent for each token-expert assignment that falls on an expert held
here. The driver multiplies that by the assignments the program
counted, so `mfu.rollout` rests on the work really done, not on even
routing.

A Mamba-2 mixer is counted by what the mathematics needs, its recurrent
form: the two projections, the convolution's 4 taps a channel, and a
token and head 2 x 2 x 64 x 128 for the state written (step x B^T) and
read (S C). The program's chunked form multiplies more (a chunk's
pairwise products C_t . B_i and their weighted sum over x) and is
credited no more. The attention layer's score products are counted over
the keys a query sees (j <= i).

`forward_fixed_flops`' stem and heads are `flops_ling_hybrid`'s lines
once more: that function names its stack through its module's globals.
"""

from .flops import _conv
from .flops_exaone_moe import seen_keys
from .reference_nemotron_h import trunk_settings


def expert_flops(t: dict) -> int:
    """One expert on one token: up and down, in the latent."""
    return 2 * 2 * t["moe_latent_size"] * t["moe_intermediate_size"]


def mamba_mixer_flops(t: dict) -> int:
    """A Mamba-2 mixer on one token."""
    d, heads, hd = t["hidden_size"], t["mamba_num_heads"], t["mamba_head_dim"]
    inner = heads * hd
    mixed = inner + 2 * t["n_groups"] * t["ssm_state_size"]
    projections = 2 * (d * (inner + mixed + heads) + inner * d)
    convolution = 2 * t["conv_kernel"] * mixed
    recurrence = 2 * 2 * inner * t["ssm_state_size"]
    return projections + convolution + recurrence


def attention_flops(t: dict, seq: int) -> int:
    """The grouped-query attention layer on one board of `seq` tokens."""
    d, hd = t["hidden_size"], t["head_dim"]
    q_out = t["num_attention_heads"] * hd
    kv_out = t["num_key_value_heads"] * hd
    projections = 2 * (d * (q_out + 2 * kv_out) + q_out * d)
    return seq * projections + 2 * 2 * q_out * seen_keys(seq, None)


def expert_layer_fixed_flops(t: dict) -> int:
    """An expert layer on one token without its routed experts: the
    router, the latent's two projections, the shared expert."""
    d = t["hidden_size"]
    shared = t["num_shared_experts"] * t["moe_shared_expert_intermediate_size"]
    return (
        2 * d * t["num_experts"]
        + 2 * 2 * d * t["moe_latent_size"]
        + 2 * 2 * d * shared
    )


def trunk_fixed_flops(t: dict, seq: int) -> int:
    """The stack on one board of `seq` tokens, without the routed experts."""
    total = 0
    for kind, mlp in zip(t["layer_types"], t["mlp_layer_types"]):
        if kind == "state_space":
            total += seq * mamba_mixer_flops(t)
        elif kind == "full_attention":
            total += attention_flops(t, seq)
        if mlp == "sparse":
            total += seq * expert_layer_fixed_flops(t)
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
