"""The operations one forward pass and one learner step of the net with
the GLM-4.7-Flash stack need, from the configuration's file. Matmul and
convolution terms only, 1 MAC = 2 FLOP, as `flops.py` counts.

A forward pass has a fixed part (the stem, the 1x1 projection, every
layer's latent-attention mixer, the dense layer, the routers, the shared
experts, the heads) and a part that follows the routing: one expert's
SwiGLU for each token-expert assignment that falls on an expert held
here (`flops_exaone_moe.expert_flops`). The driver multiplies that by
the assignments the program counted, so `mfu.learner` rests on the work
really done, not on even routing.

A latent mixer with a compressed query, a token: x Wq_a (2048 x 768),
c_q Wq_b (768 x 20 x 256), x Wkv_a (2048 x 576), c Wkv_b (512 x 20 x
448), Wo (5120 x 2048); its score products are counted over the keys a
query sees (j <= i), 256 wide for the scores and 256 for the values.

A learner step is credited forward + backward = 3 x the forward. The
forward that recomputation by layer (`REMAT`) runs a second time is the
price of fitting the chip, not work of the model's, and is not credited:
a step that recomputes reads a lower `mfu.learner` for it.
"""

from .flops import _conv
from .flops_exaone_moe import expert_flops, seen_keys
from .reference_glm_moe import trunk_settings


def latent_mixer_flops(t: dict, seq: int) -> int:
    """An MLA mixer with a compressed query on one board of `seq` tokens."""
    d, heads, rank = t["hidden_size"], t["num_attention_heads"], t["kv_lora_rank"]
    nope, rope, vd = t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    q_rank = t["q_lora_rank"]
    projections = 2 * (
        d * q_rank
        + q_rank * heads * (nope + rope)
        + d * (rank + rope)
        + rank * heads * (nope + vd)
        + heads * vd * d
    )
    return seq * projections + 2 * heads * (nope + rope + vd) * seen_keys(seq, None)


def trunk_fixed_flops(t: dict, seq: int) -> int:
    """The stack on one board of `seq` tokens, without the routed experts."""
    d = t["hidden_size"]
    total = 0
    for mlp in t["mlp_layer_types"]:
        total += latent_mixer_flops(t, seq)
        if mlp == "dense":
            total += seq * 2 * 3 * d * t["intermediate_size"]
        else:
            total += seq * 2 * d * t["num_experts"]  # the router
            total += seq * t["num_shared_experts"] * expert_flops(t)
    return total


def forward_fixed_flops(cfg: dict) -> int:
    """One board without the routed experts: stem, projection, stack, heads."""
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
    """Assignments a board would bring here if routing were even."""
    t = trunk_settings(cfg)
    sparse = sum(m == "sparse" for m in t["mlp_layer_types"])
    tokens = cfg["env"]["ROWS"] * cfg["env"]["COLS"]
    share = t["experts_held"][1] / t["num_experts"]
    return tokens * sparse * t["num_experts_per_tok"] * share


def forward_flops(cfg: dict, assignments: float) -> float:
    """One board's forward that computed `assignments` token-expert products."""
    return forward_fixed_flops(cfg) + assignments * expert_flops(trunk_settings(cfg))


def train_step_flops(cfg: dict, batch: int, assignments: float) -> float:
    """One learner step on `batch` rows whose forward computed
    `assignments` token-expert products in all: forward + backward."""
    return 3 * (
        batch * forward_fixed_flops(cfg)
        + assignments * expert_flops(trunk_settings(cfg))
    )
