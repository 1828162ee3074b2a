"""The plain reference of the net with a GLM-4.7-Flash decoder stack as
its trunk (`chipbench/configs/glm-flash-ep8.json`, `model_type`
`glm4_moe_lite`), and of one learner step on it: the loss, its
gradients, the clip, the AdamW update and the rule that moves the
routers' selection biases.

Straightforward `jax.numpy` in float32 at `highest` matmul precision: no
kernel, no sorting of tokens by expert, no recomputation. It imports
nothing of the program. The stem, the heads and the small layer
functions (`matmul`, `rms_norm`, `swiglu`) are `reference_exaone_moe`'s,
the interleaved rotary turn and the causal mask
`reference_ling_hybrid`'s; the stack between stem and heads follows the
published `config.json` keys at the top level of the configuration's
file, with what that file cannot fix taken from its `trunk_choices` and
written under its `assumed`. With x (S, 2048), H = 20 heads, pre-norm:

    h = x + mixer(RMSNorm(x)),  y = h + mlp(RMSNorm(h)),  eps 1e-5,

a final RMSNorm before the heads, no biases anywhere.

- the mixer of every layer is latent attention (MLA) in the
  DeepSeek-V2/V3 form with a compressed query: c_q = RMSNorm(x Wq_a)
  (768), q = c_q Wq_b to H x 256 = 192 without position + 64 rotary;
  [c, k_r] = x Wkv_a to 512 + 64; c = RMSNorm(c); [k_n, v] = c Wkv_b to
  H x (192 + 256); rotary positions (`rope_theta` 1e6, neighbouring
  pairs) on q's 64 and on k_r, which all heads share; scores
  (q_n . k_n + q_r . k_r) / sqrt(256) masked to j <= i; softmax; the
  context over v; then Wo. No gate. The expanded form: nothing is
  absorbed, nothing cached;
- layer 0's MLP is dense: Wd(silu(x Wg) * (x Wu)), 10240 wide;
- layers 1..: s = sigmoid(x Wr) over all 64 published experts; the
  choice is of the `num_experts_per_tok` 4 highest s + b (one group:
  `n_group` 1), BY SORTING (a stable argsort: of equals the first);
  w_e = `routed_scaling_factor` 1.8 x s_e / (sum of the chosen s)
  (`norm_topk_prob`); the sum over the chosen experts HELD HERE of
  w_e E_e(x), plus the shared expert: a loop over the held experts,
  each applied to every token and weighted by w_e or nought. What the
  experts held elsewhere would add is left out, as in the program.
  The router's LOADS are counted over all 64: how many tokens chose
  each expert, held here or not.

The step (`train_step`), as `reference.train_step` has it for the
flagship, with two things more. The batch is taken in blocks of boards
and the blocks' gradients added (the loss is a mean over rows, so this
is the batch's gradient; a block bounds the float32 activations, and is
no part of the mathematics). And the selection biases b are no
parameters of the optimizer's: the gradient does not reach them (they
move a choice, which has no derivative), they are left out of the
clipped norm, of AdamW's moments and of its decay, and after the update
each moves by the rule of DeepSeek-V3 (arXiv:2412.19437, section 2.1.2):
b_e += gamma x sign(mean(load) - load_e), the loads those of the step's
whole batch, gamma the file's `router_bias_rate`.

Departures from the published model, as the configuration's file lists
them: the conv stem stands in the embedding's place, the policy and
value heads in the output head's, there is no multi-token prediction
module and no vocabulary; the loss is the system's policy / value loss.

`quant` rounds both operands of every matmul (fp8: the control), as
`reference.py` has it.
"""

import math

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .reference import HIGHEST, _q
from .reference_exaone_moe import heads, layer_weights, matmul, rms_norm, stem, swiglu
from .reference_ling_hybrid import causal, rotary_pairs

PUBLISHED = (
    "hidden_size", "num_attention_heads", "num_key_value_heads",
    "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
    "routed_scaling_factor", "rms_norm_eps", "n_group", "topk_group",
    "rope_theta", "q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim",
)

CHOICES = {
    "norm_position": "pre", "rope_layers": "latent", "router_bias": True,
    "latent_gate": False,
}


def trunk_settings(cfg: dict) -> dict:
    """The stack as it is run, from the configuration's file: the
    published keys (GLM's `n_routed_experts` and `n_shared_experts`
    under the names the program's `TrunkConfig` has for them), every
    mixer latent, dense below `first_k_dense_replace` and sparse from
    there, the router as wide as published, the experts this chip holds
    and the file's `trunk_choices`. The program's `TrunkConfig` takes
    exactly these keys; the reference reads the same dict."""
    for key, value in CHOICES.items():
        if cfg["trunk_choices"][key] != value:
            raise ValueError(f"trunk_choices.{key}: only {value!r} is implemented")
    if cfg["n_group"] != 1 or cfg["topk_group"] != 1:
        raise ValueError("the choice is written for one group")
    depth = cfg["num_hidden_layers"]
    share = cfg["deployment"]
    held = cfg["n_routed_experts"]
    return {
        **{key: cfg[key] for key in PUBLISHED},
        "num_experts": cfg["published"]["n_routed_experts"],
        "num_shared_experts": cfg["n_shared_experts"],
        "layer_types": ["latent_attention"] * depth,
        "mlp_layer_types": [
            "dense" if l < cfg["first_k_dense_replace"] else "sparse"
            for l in range(depth)
        ],
        "experts_held": [share["chip"] * held, held],
        **cfg["trunk_choices"],
    }


def sparse_layers(t: dict) -> list[int]:
    return [i for i, kind in enumerate(t["mlp_layer_types"]) if kind == "sparse"]


# --- layers -----------------------------------------------------------------


def latent_attention(p, x, t, quant):
    b, s, _ = x.shape
    h, rank = t["num_attention_heads"], t["kv_lora_rank"]
    nope, rope, vd = t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    c_q = rms_norm(matmul(x, p["wq_a"], quant), p["q_a_norm"], t["rms_norm_eps"])
    q = matmul(c_q, p["wq_b"], quant).reshape(b, s, h, nope + rope)
    q_n, q_r = q[..., :nope], rotary_pairs(q[..., nope:], t["rope_theta"])
    latent = matmul(x, p["wkv_a"], quant)
    k_r = rotary_pairs(latent[..., rank:], t["rope_theta"])
    c = rms_norm(latent[..., :rank], p["kv_norm"], t["rms_norm_eps"])
    kv = matmul(c, p["wkv_b"], quant).reshape(b, s, h, nope + vd)
    k_n, v = kv[..., :nope], kv[..., nope:]
    scores = (
        jnp.einsum("bqhd,bkhd->bhqk", _q(q_n, quant), _q(k_n, quant), precision=HIGHEST)
        + jnp.einsum("bqhd,bkd->bhqk", _q(q_r, quant), _q(k_r, quant), precision=HIGHEST)
    ) / math.sqrt(nope + rope)
    weights = jax.nn.softmax(jnp.where(causal(s), scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", _q(weights, quant), _q(v, quant), precision=HIGHEST
    )
    # GLM's MLA has no gate on the context (Ling's latent layer has one).
    return matmul(ctx.reshape(b, s, h * vd), p["wo"], quant)


def route(p, x, t, quant):
    """Scores over all experts -> (chosen (..., k), weights (..., k)).
    The bias reaches the choice alone; the choice has no derivative."""
    scores = jax.nn.sigmoid(matmul(x, p["w_router"], quant))
    biased = jax.lax.stop_gradient(scores + p["router_bias"])
    chosen = jnp.argsort(biased, axis=-1, stable=True, descending=True)[
        ..., : t["num_experts_per_tok"]
    ]
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, t["routed_scaling_factor"] * top / top.sum(axis=-1, keepdims=True)


def sparse_mlp(p, x, t, quant, held=None):
    """The held experts' part of the routed sum, plus the shared expert;
    and the loads, (E,) float32: the tokens that chose each of the
    published experts. `held` = (first, count) overrides the
    configuration's share (the test that adds the shares up asks for
    each in turn)."""
    first, count = held or t["experts_held"]
    chosen, weight = route(p, x, t, quant)
    y = jnp.zeros_like(x)
    for e in range(count):
        w_e = jnp.where(chosen == first + e, weight, 0.0).sum(axis=-1)
        y = y + w_e[..., None] * swiglu(
            x, p["e_gate"][e], p["e_up"][e], p["e_down"][e], quant
        )
    if t["num_shared_experts"]:
        y = y + swiglu(x, p["s_gate"], p["s_up"], p["s_down"], quant)
    loads = (
        (chosen[..., None] == jnp.arange(t["num_experts"])).sum(axis=-2)
    ).reshape(-1, t["num_experts"]).sum(axis=0)
    return y, loads.astype(jnp.float32)


def mixer_half(p, x, t, quant):
    """x + MLA(RMSNorm(x)): what the layer's MLP half reads."""
    return x + latent_attention(
        p, rms_norm(x, p["attn_norm"], t["rms_norm_eps"]), t, quant
    )


def mlp_input(p, x, t):
    """RMSNorm(h): what the layer's router, or its dense MLP, reads."""
    return rms_norm(x, p["mlp_norm"], t["rms_norm_eps"])


def mlp_half(p, x, t, i, quant):
    """h + mlp(RMSNorm(h)) and the router's loads (None on a dense layer)."""
    y = mlp_input(p, x, t)
    if t["mlp_layer_types"][i] == "dense":
        return x + swiglu(y, p["w_gate"], p["w_up"], p["w_down"], quant), None
    out, loads = sparse_mlp(p, y, t, quant)
    return x + out, loads


def layer(p, x, t, i, quant):
    """Decoder layer i on x (b, s, d) -> (its output, its router's loads)."""
    return mlp_half(p, mixer_half(p, x, t, quant), t, i, quant)


# --- the net ----------------------------------------------------------------


def forward(params, cfg: dict, grid, other, quant=None):
    """(B, C, H, W) grid + (B, F) other -> policy logits (B, A), value
    logits (B, atoms) and the routers' loads (sparse layers, E), all
    float32. `cfg` is the whole configuration file; `params` the
    program's `params` tree, float32."""
    t = trunk_settings(cfg)
    trunk = params["DecoderTrunk_0"]
    rest = {k: v for k, v in params.items() if k != "DecoderTrunk_0"}
    x = stem(rest, cfg["model"], grid, quant)
    loads = []
    for i in range(len(t["layer_types"])):
        x, counted = layer(layer_weights(trunk, i), x, t, i, quant)
        if counted is not None:
            loads.append(counted)
    policy, value = heads(rest, trunk["norm"], t["rms_norm_eps"], x, other, quant)
    return policy, value, jnp.stack(loads)


def loss_rows(params, cfg, batch, quant=None):
    """A batch's rows of the loss, as `reference.loss` has them: the
    importance-weighted policy and value cross-entropies, and the
    entropy bonus unweighted. -> (their SUM over the rows, (TD errors
    (B,), loads))."""
    model, train = cfg["model"], cfg["train"]
    policy_logits, value_logits, loads = forward(
        params, cfg, batch["grid"], batch["other"], quant
    )
    log_policy = jax.nn.log_softmax(policy_logits, axis=-1)
    pw = batch["pw"]
    policy_ce = pw * -(batch["policy"] * log_policy).sum(axis=-1)
    target = reference.two_hot(
        batch["ret"], model["NUM_VALUE_ATOMS"], model["VALUE_MIN"], model["VALUE_MAX"]
    )
    value_ce = -(target * jax.nn.log_softmax(value_logits, axis=-1)).sum(axis=-1)
    entropy = pw * -(jnp.exp(log_policy) * log_policy).sum(axis=-1)
    rows = (
        batch["weights"]
        * (train["POLICY_LOSS_WEIGHT"] * policy_ce + train["VALUE_LOSS_WEIGHT"] * value_ce)
        - train["ENTROPY_BONUS_WEIGHT"] * entropy
    )
    return rows.sum(), (value_ce, loads)


def loss(params, cfg, batch, quant=None):
    """Total loss (the mean over the rows), TD errors and loads of one
    batch taken whole."""
    total, (td, loads) = loss_rows(params, cfg, batch, quant)
    return total / len(td), (td, loads)


def is_bias(path) -> bool:
    return str(path[-1].key).endswith("router_bias")


def without_biases(tree):
    """`tree` (a params tree) with the routers' selection biases set to
    None, which a tree map passes over: what the optimizer trains."""
    return jax.tree_util.tree_map_with_path(
        lambda path, x: None if is_bias(path) else x, tree
    )


def moved_biases(params, loads, cfg: dict):
    """`params` with every sparse layer's bias one step of the rule on."""
    t = trunk_settings(cfg)
    gamma = jnp.float32(t["router_bias_rate"])
    trunk = dict(params["DecoderTrunk_0"])
    for row, i in enumerate(sparse_layers(t)):
        load = loads[row]
        trunk[f"l{i}_router_bias"] = trunk[f"l{i}_router_bias"] + gamma * jnp.sign(
            load.mean() - load
        )
    return {**params, "DecoderTrunk_0": trunk}


def batch_gradients(params, cfg, batch, block: int, quant=None):
    """The gradient of the batch's loss, a block of `block` rows at a
    time: (gradients, total loss, TD errors (B,), loads (layers, E))."""
    count = len(batch["ret"])
    if count % block:
        raise ValueError(f"{count} rows are not whole blocks of {block}")

    @jax.jit
    def one(params, rows):
        (total, (td, loads)), grads = jax.value_and_grad(loss_rows, has_aux=True)(
            params, cfg, rows, quant
        )
        return grads, total, td, loads

    add = jax.jit(
        lambda a, b: jax.tree_util.tree_map(jnp.add, a, b), donate_argnums=(0,)
    )
    grads = total = loads = None
    td = []
    for at in range(0, count, block):
        g, part, errors, counted = one(
            params, {f: v[at : at + block] for f, v in batch.items()}
        )
        grads = g if grads is None else add(grads, g)
        total = part if total is None else total + part
        loads = counted if loads is None else loads + counted
        td.append(errors)
    grads = jax.jit(
        lambda g: jax.tree_util.tree_map(lambda x: x / count, g), donate_argnums=(0,)
    )(grads)
    return grads, total / count, jnp.concatenate(td), loads


def train_step(state, cfg, batch, block: int, quant=None):
    """One clipped AdamW step and one move of the biases. `state` is
    (params, mu, nu, count), mu and nu None before the first step;
    returns the new state, the loss, the gradient's global norm before
    clipping, the TD errors and the loads. The moments and the norm are
    of the trained leaves: a bias has none."""
    params, mu, nu, count = state
    train, opt = cfg["train"], cfg["optimizer"]
    grads, total, td, loads = batch_gradients(params, cfg, batch, block, quant)
    grads = without_biases(grads)
    norm = reference.global_norm(grads)
    clip = train["GRADIENT_CLIP_VALUE"]
    scale = jnp.where(norm < clip, 1.0, clip / norm)
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    t = count + 1
    lr = reference.learning_rate(train, count)

    @jax.jit
    def leaf(p, m, v, g, scale, lr, t):
        g = g * scale
        m = b1 * m + (1 - b1) * g
        v = b2 * v + (1 - b2) * g * g
        update = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + eps)
        return p - lr * (update + train["WEIGHT_DECAY"] * p), m, v

    if mu is None:
        mu = nu = jax.tree_util.tree_map(jnp.zeros_like, grads)
    # Leaf by leaf, so that no second copy of the whole tree stands
    # beside the state.
    trained = without_biases(params)
    flat, tree = jax.tree_util.tree_flatten(trained)
    out = [
        leaf(p, m, v, g, scale, lr, t)
        for p, m, v, g in zip(
            flat,
            jax.tree_util.tree_leaves(mu),
            jax.tree_util.tree_leaves(nu),
            jax.tree_util.tree_leaves(grads),
        )
    ]
    new, mu, nu = (jax.tree_util.tree_unflatten(tree, part) for part in zip(*out))
    # The biases back in their places, then moved by the rule.
    new = jax.tree_util.tree_map_with_path(
        lambda path, old, x: old if is_bias(path) else x,
        params, new, is_leaf=lambda x: x is None,
    )
    new = moved_biases(new, loads, cfg)
    return (new, mu, nu, t), total, norm, td, loads


def init_state(params):
    return (params, None, None, jnp.float32(0.0))


def biases_of(params, cfg: dict) -> np.ndarray:
    """(sparse layers, E): the selection biases, on the host."""
    t = trunk_settings(cfg)
    trunk = params["DecoderTrunk_0"]
    return np.stack(
        [np.asarray(trunk[f"l{i}_router_bias"], np.float32) for i in sparse_layers(t)]
    )
