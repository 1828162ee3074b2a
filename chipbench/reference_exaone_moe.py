"""The plain reference of the net with a K-EXAONE decoder stack as its
trunk (`chipbench/configs/k-exaone-ep8.json`).

Straightforward `jax.numpy` in float32 at `highest` matmul precision: no
kernel, no sorting of tokens, no blocks inside a layer. It imports
nothing of the program. The stem and the heads are `reference.py`'s (the
configuration's `model` group); the stack between them follows the
published `config.json` keys at the top level of the configuration's
file, with what that file cannot fix taken from its `trunk_choices`:

- attention: q, k, v = x Wq, x Wk, x Wv, no biases; an RMSNorm over the
  head on q and k (`qk_norm` true); rotary positions (default type,
  `rope_theta`, the whole head, halves paired) on the sliding layers
  (`rope_layers` "sliding"); query head h reads key/value head
  h // (heads / kv heads); scores q k^T / sqrt(head_dim) masked to
  j <= i and, on a sliding layer, to i - j < `sliding_window`; softmax;
  (P v) Wo;
- dense layer: Wd(silu(x Wg) * (x Wu));
- sparse layer: s = sigmoid(x Wr) over all the published experts; the
  `num_experts_per_tok` of highest s (of highest s + b where the file's
  `router_bias` gives the router a selection bias b, which moves the
  choice and not the weights); w_e = `routed_scaling_factor` x
  s_e / (sum of the chosen s); the sum over the chosen experts HELD
  HERE of w_e E_e(x), plus the shared expert: a dense loop over the
  held experts, each applied to every token and weighted by w_e or
  nought. What the experts held elsewhere would add is left out, as in
  the program: that partial sum goes on to the next layer;
- `norm_position` "post": x + norm(f(x)); RMSNorm with `rms_norm_eps`; a
  final RMSNorm before the heads. `CHOICES` holds the one value of each
  of these three keys that the reference (and the program) implements.

Departures from the published model, as the configuration's file lists
them: the conv stem stands in the embedding's place, the policy and
value heads in the output head's, and there is no multi-token
prediction module (the board has no next token).

The weights arrive in the type the program holds them in (bfloat16) and
are widened one layer at a time, each layer a jitted call of its own,
so the float32 copies never stand together. `quant` rounds both
operands of every matmul (fp8: the control), as `reference.py` has it.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from . import reference
from .reference import HIGHEST, _q

PUBLISHED = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
    "num_shared_experts", "routed_scaling_factor", "sliding_window",
    "rms_norm_eps",
)


CHOICES = {"norm_position": "post", "qk_norm": True, "rope_layers": "sliding"}


def trunk_settings(cfg: dict) -> dict:
    """The stack as it is run, from the configuration's file: the
    published keys, the first `num_hidden_layers` entries of the layer
    lists, the router as wide as published, the experts this chip holds
    and the file's `trunk_choices`. The program's `TrunkConfig` takes
    exactly these keys; the reference reads the same dict."""
    for key, value in CHOICES.items():
        if cfg["trunk_choices"][key] != value:
            raise ValueError(f"trunk_choices.{key}: only {value!r} is implemented")
    depth = cfg["num_hidden_layers"]
    share = cfg["deployment"]
    held = cfg["num_experts"]
    return {
        **{key: cfg[key] for key in PUBLISHED},
        "num_experts": cfg["published"]["num_experts"],
        "layer_types": cfg["layer_types"][:depth],
        "mlp_layer_types": cfg["mlp_layer_types"][:depth],
        "rope_theta": cfg["rope_parameters"]["rope_theta"],
        "experts_held": [share["chip"] * held, held],
        **cfg["trunk_choices"],
    }


# --- layers -----------------------------------------------------------------


def matmul(x, w, quant):
    return jnp.matmul(_q(x, quant), _q(w, quant), precision=HIGHEST)


def rms_norm(x, weight, eps):
    return x / jnp.sqrt((x * x).mean(axis=-1, keepdims=True) + eps) * weight


def rotary(x, theta):
    """x (b, s, heads, head_dim) turned by its position s."""
    s, d = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, d, 2, dtype=np.float64) / d)
    angle = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    angle = np.concatenate([angle, angle], axis=-1)[None, :, None, :]
    turned = jnp.concatenate([-x[..., d // 2 :], x[..., : d // 2]], axis=-1)
    return x * np.cos(angle).astype(np.float32) + turned * np.sin(angle).astype(
        np.float32
    )


def mask(seq: int, window) -> np.ndarray:
    """(seq, seq): whether query i sees key j."""
    seen = np.zeros((seq, seq), bool)
    for i in range(seq):
        for j in range(i + 1):
            seen[i, j] = window is None or i - j < window
    return seen


def attention(p, x, t, sliding, quant):
    b, s, _ = x.shape
    heads, kv, hd = t["num_attention_heads"], t["num_key_value_heads"], t["head_dim"]
    q = matmul(x, p["wq"], quant).reshape(b, s, heads, hd)
    k = matmul(x, p["wk"], quant).reshape(b, s, kv, hd)
    v = matmul(x, p["wv"], quant).reshape(b, s, kv, hd)
    q = rms_norm(q, p["q_norm"], t["rms_norm_eps"])
    k = rms_norm(k, p["k_norm"], t["rms_norm_eps"])
    if sliding:
        q, k = rotary(q, t["rope_theta"]), rotary(k, t["rope_theta"])
    k = jnp.repeat(k, heads // kv, axis=2)  # head h reads kv head h // rep
    v = jnp.repeat(v, heads // kv, axis=2)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant), precision=HIGHEST
    ) / math.sqrt(hd)
    seen = mask(s, t["sliding_window"] if sliding else None)
    weights = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", _q(weights, quant), _q(v, quant), precision=HIGHEST
    )
    return matmul(ctx.reshape(b, s, heads * hd), p["wo"], quant)


def swiglu(x, gate, up, down, quant):
    return matmul(
        jax.nn.silu(matmul(x, gate, quant)) * matmul(x, up, quant), down, quant
    )


def route(p, x, t, quant):
    """Scores over all experts -> (chosen (..., k), weights (..., k))."""
    scores = jax.nn.sigmoid(matmul(x, p["w_router"], quant))
    biased = scores + p["router_bias"] if t["router_bias"] else scores
    _, chosen = jax.lax.top_k(biased, t["num_experts_per_tok"])
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, t["routed_scaling_factor"] * top / top.sum(axis=-1, keepdims=True)


def sparse_mlp(p, x, t, quant, held=None):
    """The held experts' part of the routed sum, plus the shared expert.
    `held` = (first, count) overrides the configuration's share (the
    test that adds the shares up asks for each in turn)."""
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
    return y


def _f32(p):
    return jax.tree_util.tree_map(lambda w: w.astype(jnp.float32), p)


def attention_half(p, x, t, i, quant):
    """x + norm(attention(x)) of decoder layer i on x (b, s, d): what the
    layer's router, or its dense MLP, reads. `p` holds the layer's
    weights under their names without its prefix, in any float type."""
    p = _f32(p)
    sliding = t["layer_types"][i] == "sliding_attention"
    return x + rms_norm(
        attention(p, x, t, sliding, quant), p["attn_norm"], t["rms_norm_eps"]
    )


def mlp_half(p, x, t, i, quant):
    """x + norm(mlp(x)), the layer's second half."""
    p = _f32(p)
    if t["mlp_layer_types"][i] == "dense":
        y = swiglu(x, p["w_gate"], p["w_up"], p["w_down"], quant)
    else:
        y = sparse_mlp(p, x, t, quant)
    return x + rms_norm(y, p["mlp_norm"], t["rms_norm_eps"])


def layer(p, x, t, i, quant):
    """Decoder layer i on x (b, s, d)."""
    return mlp_half(p, attention_half(p, x, t, i, quant), t, i, quant)


def layer_weights(trunk: dict, i: int) -> dict:
    prefix = f"l{i}_"
    return {k[len(prefix):]: v for k, v in trunk.items() if k.startswith(prefix)}


# --- the net ----------------------------------------------------------------


def stem(params, model, grid, quant):
    """The conv stem and its 1x1 projection: (B, C, H, W) -> (B, H*W, d)."""
    f32 = functools.partial(jax.tree_util.tree_map, lambda w: w.astype(jnp.float32))
    x = jnp.transpose(grid.astype(jnp.float32), (0, 2, 3, 1))
    for i in range(len(model["CONV_FILTERS"])):
        p = f32(params[f"ConvBlock_{i}"])
        x = reference.conv(p["Conv_0"], x, quant)
        x = jax.nn.relu(reference.group_norm(p["_Norm_0"]["GroupNorm_0"], x))
    for i in range(model["NUM_RESIDUAL_BLOCKS"]):
        p = f32(params[f"ResidualBlock_{i}"])
        y = reference.conv(p["Conv_0"], x, quant)
        y = jax.nn.relu(reference.group_norm(p["_Norm_0"]["GroupNorm_0"], y))
        y = reference.conv(p["Conv_1"], y, quant)
        y = reference.group_norm(p["_Norm_1"]["GroupNorm_0"], y)
        x = jax.nn.relu(x + y)
    x = reference.conv(f32(params["Conv_0"]), x, quant)
    return x.reshape(x.shape[0], -1, x.shape[-1])


def heads(params, norm, eps, tokens, other, quant):
    """Final RMSNorm, flatten, other features, shared FC, the two heads."""
    f32 = functools.partial(jax.tree_util.tree_map, lambda w: w.astype(jnp.float32))
    x = rms_norm(tokens, norm.astype(jnp.float32), eps)
    flat = jnp.concatenate(
        [x.reshape(x.shape[0], -1), other.astype(jnp.float32)], axis=-1
    )
    shared = reference.dense(f32(params["Dense_0"]), flat, quant)
    shared = jax.nn.relu(
        reference.group_norm(f32(params["_Norm_0"]["GroupNorm_0"]), shared)
    )
    return (
        reference.head(f32(params["MLPHead_0"]), shared, quant),
        reference.head(f32(params["MLPHead_1"]), shared, quant),
    )


@functools.lru_cache(maxsize=None)
def _pieces(cfg_json: str, quant):
    """The jitted calls of one configuration and precision: the stem,
    one call a layer, the heads. A layer's float32 weights live only
    inside its call."""
    cfg = json.loads(cfg_json)
    t = trunk_settings(cfg)
    return (
        t,
        jax.jit(lambda p, g: stem(p, cfg["model"], g, quant)),
        [
            jax.jit(functools.partial(layer, t=t, i=i, quant=quant))
            for i in range(len(t["layer_types"]))
        ],
        jax.jit(
            lambda p, n, x, o: heads(p, n, t["rms_norm_eps"], x, o, quant)
        ),
    )


def forward(params, cfg: dict, grid, other, quant=None):
    """(B, C, H, W) grid + (B, F) other -> policy logits (B, A) and
    value-distribution logits (B, atoms), float32. `cfg` is the whole
    configuration file; `params` the program's `params` tree."""
    t, stem_fn, layers, heads_fn = _pieces(json.dumps(cfg, sort_keys=True), quant)
    trunk = params["DecoderTrunk_0"]
    rest = {k: v for k, v in params.items() if k != "DecoderTrunk_0"}
    x = stem_fn(rest, grid)
    for i, fn in enumerate(layers):
        x = fn(layer_weights(trunk, i), x)
    return heads_fn(rest, trunk["norm"], x, other)


def loss(params, cfg, batch, quant=None):
    """`reference.loss` with this net in it: total loss and per-row TD
    errors of one batch (no dropout: the stack has none)."""
    model, train = cfg["model"], cfg["train"]
    policy_logits, value_logits = forward(
        params, cfg, batch["grid"], batch["other"], quant
    )
    log_policy = jax.nn.log_softmax(policy_logits, axis=-1)
    pw = batch["pw"]
    policy_ce = pw * -(batch["policy"] * log_policy).sum(axis=-1)
    target = reference.two_hot(
        batch["ret"], model["NUM_VALUE_ATOMS"], model["VALUE_MIN"], model["VALUE_MAX"]
    )
    value_ce = -(target * jax.nn.log_softmax(value_logits, axis=-1)).sum(axis=-1)
    entropy = (pw * -(jnp.exp(log_policy) * log_policy).sum(axis=-1)).mean()
    rows = train["POLICY_LOSS_WEIGHT"] * policy_ce + train["VALUE_LOSS_WEIGHT"] * value_ce
    total = (batch["weights"] * rows).mean() - train["ENTROPY_BONUS_WEIGHT"] * entropy
    return total, value_ce
