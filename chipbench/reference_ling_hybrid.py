"""The plain reference of the net with a Ling-3.0-flash decoder stack as
its trunk (`chipbench/configs/ling-flash-ep4.json`, `model_type`
`bailing_hybrid`).

Straightforward `jax.numpy` in float32 at `highest` matmul precision: no
kernel, no chunks, no sorting of tokens. It imports nothing of the
program. The stem, the heads and the small layer functions (`matmul`,
`rms_norm`, `swiglu`) are `reference_exaone_moe`'s; the stack between
stem and heads follows the published `config.json` keys at the top
level of the configuration's file, with what that file cannot fix taken
from its `trunk_choices` and written under its `assumed`. With x
(S, 2560), H = 32 heads, pre-norm (`norm_position` "pre"):

    h = x + mixer(RMSNorm(x)),  y = h + mlp(RMSNorm(h)),  eps 1e-6,

a final RMSNorm before the heads, no biases anywhere.

- `linear_attention` (KDA), layers with (l + 1) % `layer_group_size`
  != 0: q, k, v = x Wq, x Wk, x Wv, each to H x 128; each channel of
  each through a causal depthwise convolution of
  `short_conv_kernel_size` taps (the last tap on the token itself),
  then SiLU; q and k over their L2 norm per head, q times 128^-0.5;
  g_t = `kda_lower_bound` x sigmoid(exp(A_log_h) x (x Wf + dt_bias)),
  per head and channel; beta_t = sigmoid(x Wb), one a head; the state
  S (128 x 128 a head) from zero, TOKEN BY TOKEN (a `lax.scan` over the
  S tokens; the program takes them a chunk at a time):
      S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T
      o_t = S_t^T q_t;
  the output (RMSNorm over each head's o_t) x sigmoid(x Wg), the gate
  one scalar a head, then Wo;
- `latent_attention` (MLA), layers with (l + 1) % 6 == 0: q = x Wq to
  H x 192 = 128 without position + 64 rotary; [c, k_r] = x Wa to
  512 + 64; c = RMSNorm(c); [k_n, v] = c Wb to H x (128 + 128); rotary
  positions (`rope_theta`, neighbouring pairs: `rope_interleave`) on
  q's 64 and on k_r, which all heads share; scores
  (q_n . k_n + q_r . k_r) / sqrt(192) masked to j <= i; softmax; the
  context over v times sigmoid(x Wg), a head; then Wo. The expanded
  form: nothing is absorbed, nothing cached;
- dense layer (layer 0): Wd(silu(x Wg) * (x Wu)), 6144 wide;
- sparse layers: s = sigmoid(x Wr) over all 512 published experts;
  selection on s + b (the file's `router_bias`): the experts in
  `n_group` 8 groups of 64, a group's score the sum of its two highest
  s + b, the `topk_group` 4 best groups stay, the
  `num_experts_per_tok` 8 highest s + b among them are chosen, BY
  SORTING (a stable argsort: of equals the first); w_e =
  `routed_scaling_factor` x s_e / (sum of the chosen s); the sum over
  the chosen experts HELD HERE of w_e E_e(x), plus the shared expert: a
  loop over the held experts, each applied to every token and weighted
  by w_e or nought. What the experts held elsewhere would add is left
  out, as in the program: that partial sum goes on to the next layer.

`expert_swiglu_limit_list` and `share_expert_swiglu_limit_list` are 0
on every layer kept and are not run. Departures from the published
model, as the configuration's file lists them: the conv stem stands in
the embedding's place, the policy and value heads in the output head's,
there is no multi-token prediction module and no vocabulary.

The weights arrive in the type the program holds them in (bfloat16) and
are widened one layer at a time, each layer a jitted call of its own,
so the float32 copies never stand together. `quant` rounds both
operands of every matmul (fp8: the control), as `reference.py` has it;
the recurrence's products with the state are matmuls too and are
rounded with them.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp
import numpy as np

from .reference import HIGHEST, _q
from .reference_exaone_moe import (
    _f32, heads, layer_weights, matmul, rms_norm, stem, swiglu,
)

PUBLISHED = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
    "num_shared_experts", "routed_scaling_factor", "rms_norm_eps",
    "n_group", "topk_group", "rope_theta", "short_conv_kernel_size",
    "kda_lower_bound", "kv_lora_rank", "qk_nope_head_dim",
    "qk_rope_head_dim", "v_head_dim",
)

CHOICES = {"norm_position": "pre", "qk_norm": "l2", "rope_layers": "latent"}


def layer_kinds(cfg: dict) -> tuple[list[str], list[str]]:
    """(mixers, MLPs) of the layers that are run: layer l is latent
    where (l + 1) % layer_group_size == 0 and linear elsewhere, dense
    below first_k_dense_replace and sparse from there."""
    depth, period = cfg["num_hidden_layers"], cfg["layer_group_size"]
    return (
        [
            "latent_attention" if (l + 1) % period == 0 else "linear_attention"
            for l in range(depth)
        ],
        [
            "dense" if l < cfg["first_k_dense_replace"] else "sparse"
            for l in range(depth)
        ],
    )


def trunk_settings(cfg: dict) -> dict:
    """The stack as it is run, from the configuration's file: the
    published keys, the layers' kinds by the published rule, the router
    as wide as published, the experts this chip holds and the file's
    `trunk_choices`. The program's `TrunkConfig` takes exactly these
    keys; the reference reads the same dict."""
    for key, value in CHOICES.items():
        if cfg["trunk_choices"][key] != value:
            raise ValueError(f"trunk_choices.{key}: only {value!r} is implemented")
    mixers, mlps = layer_kinds(cfg)
    share = cfg["deployment"]
    held = cfg["num_experts"]
    return {
        **{key: cfg[key] for key in PUBLISHED},
        "num_experts": cfg["published"]["num_experts"],
        "layer_types": mixers,
        "mlp_layer_types": mlps,
        "experts_held": [share["chip"] * held, held],
        **cfg["trunk_choices"],
    }


# --- layers -----------------------------------------------------------------


def short_conv(x, taps):
    """y_t = sum_j taps[j] x_{t - (K - 1) + j} along axis 1 of x
    (b, s, c), nought before the first token; then SiLU."""
    count, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (count - 1, 0), (0, 0)))
    y = jnp.zeros_like(x)
    for j in range(count):
        y = y + padded[:, j : j + s] * taps[j]
    return jax.nn.silu(y)


def l2_norm(x):
    return x / jnp.sqrt((x * x).sum(axis=-1, keepdims=True) + 1e-6)


def delta_rule(q, k, v, g, beta, quant):
    """The recurrence, a token at a time: q, k, g (b, s, H, dk), v
    (b, s, H, dv), beta (b, s, H) -> o (b, s, H, dv)."""

    def step(state, xs):
        q_t, k_t, v_t, g_t, b_t = xs  # (b, H, ...)
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.einsum(
            "bhk,bhkv->bhv", _q(k_t, quant), _q(state, quant), precision=HIGHEST
        )
        state = state + jnp.einsum(
            "bhk,bhv->bhkv", _q(k_t, quant),
            _q(b_t[..., None] * (v_t - seen), quant), precision=HIGHEST,
        )
        out = jnp.einsum(
            "bhk,bhkv->bhv", _q(q_t, quant), _q(state, quant), precision=HIGHEST
        )
        return state, out

    b, _, h, dk = q.shape
    start = jnp.zeros((b, h, dk, v.shape[-1]), jnp.float32)
    _, out = jax.lax.scan(
        step, start, tuple(jnp.swapaxes(x, 0, 1) for x in (q, k, v, g, beta))
    )
    return jnp.swapaxes(out, 0, 1)


def linear_attention(p, x, t, quant):
    b, s, _ = x.shape
    h, hd = t["num_attention_heads"], t["head_dim"]
    q = short_conv(matmul(x, p["wq"], quant), p["conv_q"]).reshape(b, s, h, hd)
    k = short_conv(matmul(x, p["wk"], quant), p["conv_k"]).reshape(b, s, h, hd)
    v = short_conv(matmul(x, p["wv"], quant), p["conv_v"]).reshape(b, s, h, hd)
    q, k = l2_norm(q) * hd**-0.5, l2_norm(k)
    f = (matmul(x, p["wf"], quant) + p["dt_bias"]).reshape(b, s, h, hd)
    g = t["kda_lower_bound"] * jax.nn.sigmoid(jnp.exp(p["A_log"])[:, None] * f)
    beta = jax.nn.sigmoid(matmul(x, p["wb"], quant))
    o = delta_rule(q, k, v, g, beta, quant)
    gate = jax.nn.sigmoid(matmul(x, p["wg"], quant))
    o = rms_norm(o, p["o_norm"], t["rms_norm_eps"]) * gate[..., None]
    return matmul(o.reshape(b, s, h * hd), p["wo"], quant)


def rotary_pairs(x, theta):
    """x (b, s, ..., width) turned by its position s, entries 2i and
    2i + 1 a pair."""
    s, width = x.shape[1], x.shape[-1]
    out = []
    for i in range(width // 2):
        inv = theta ** (-2.0 * i / width)
        angle = (np.arange(s, dtype=np.float64) * inv).reshape(
            (1, s) + (1,) * (x.ndim - 3)
        )
        cos, sin = np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)
        a, c = x[..., 2 * i], x[..., 2 * i + 1]
        out += [a * cos - c * sin, c * cos + a * sin]
    return jnp.stack(out, axis=-1)


def causal(seq: int) -> np.ndarray:
    seen = np.zeros((seq, seq), bool)
    for i in range(seq):
        seen[i, : i + 1] = True
    return seen


def latent_attention(p, x, t, quant):
    b, s, _ = x.shape
    h, rank = t["num_attention_heads"], t["kv_lora_rank"]
    nope, rope, vd = t["qk_nope_head_dim"], t["qk_rope_head_dim"], t["v_head_dim"]
    q = matmul(x, p["wq"], quant).reshape(b, s, h, nope + rope)
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
    gate = jax.nn.sigmoid(matmul(x, p["wg"], quant))
    return matmul((ctx * gate[..., None]).reshape(b, s, h * vd), p["wo"], quant)


def choose(biased, t):
    """The grouped choice over `biased` (..., E), by sorting: the
    `topk_group` groups whose two highest entries sum highest stay, and
    the `num_experts_per_tok` highest entries among them are chosen;
    of equals, the first in order. -> chosen (..., k)."""
    groups, stay, k = t["n_group"], t["topk_group"], t["num_experts_per_tok"]
    e = biased.shape[-1]
    by_group = biased.reshape(*biased.shape[:-1], groups, e // groups)
    worth = jnp.sort(by_group, axis=-1)[..., -2:].sum(axis=-1)
    kept = jnp.argsort(worth, axis=-1, stable=True, descending=True)[..., :stay]
    stays = (kept[..., :, None] == jnp.arange(groups)).any(axis=-2)
    among = jnp.where(jnp.repeat(stays, e // groups, axis=-1), biased, -jnp.inf)
    return jnp.argsort(among, axis=-1, stable=True, descending=True)[..., :k]


def route(p, x, t, quant):
    """Scores over all experts -> (chosen (..., k), weights (..., k))."""
    scores = jax.nn.sigmoid(matmul(x, p["w_router"], quant))
    biased = scores + p["router_bias"] if t["router_bias"] else scores
    chosen = choose(biased, t)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, t["routed_scaling_factor"] * top / top.sum(axis=-1, keepdims=True)


def sparse_mlp(p, x, t, quant, held=None):
    """The held experts' part of the routed sum, plus the shared expert.
    `held` = (first, count) overrides the configuration's share (the
    test that adds the shares up asks for each in turn)."""
    first, count = held or t["experts_held"]
    chosen, weight = route(p, x, t, quant)

    def one(y, expert):
        e, gate, up, down = expert
        w_e = jnp.where(chosen == first + e, weight, 0.0).sum(axis=-1)
        return y + w_e[..., None] * swiglu(x, gate, up, down, quant), None

    y, _ = jax.lax.scan(
        one, jnp.zeros_like(x),
        (jnp.arange(count), p["e_gate"][:count], p["e_up"][:count], p["e_down"][:count]),
    )
    if t["num_shared_experts"]:
        y = y + swiglu(x, p["s_gate"], p["s_up"], p["s_down"], quant)
    return y


def mixer_half(p, x, t, i, quant):
    """x + mixer(RMSNorm(x)) of decoder layer i on x (b, s, d): what the
    layer's MLP half reads. `p` holds the layer's weights under their
    names without its prefix, in any float type."""
    p = _f32(p)
    y = rms_norm(x, p["attn_norm"], t["rms_norm_eps"])
    if t["layer_types"][i] == "linear_attention":
        return x + linear_attention(p, y, t, quant)
    return x + latent_attention(p, y, t, quant)


def mlp_input(p, x, t):
    """RMSNorm(h): what the layer's router, or its dense MLP, reads."""
    return rms_norm(x, p["mlp_norm"].astype(jnp.float32), t["rms_norm_eps"])


def mlp_half(p, x, t, i, quant):
    """h + mlp(RMSNorm(h)), the layer's second half."""
    p = _f32(p)
    y = mlp_input(p, x, t)
    if t["mlp_layer_types"][i] == "dense":
        return x + swiglu(y, p["w_gate"], p["w_up"], p["w_down"], quant)
    return x + sparse_mlp(p, y, t, quant)


def layer(p, x, t, i, quant):
    """Decoder layer i on x (b, s, d)."""
    return mlp_half(p, mixer_half(p, x, t, i, quant), t, i, quant)


# --- the net ----------------------------------------------------------------


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
        jax.jit(lambda p, n, x, o: heads(p, n, t["rms_norm_eps"], x, o, quant)),
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
