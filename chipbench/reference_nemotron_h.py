"""The plain reference of the net with a Nemotron-3-Super decoder stack
as its trunk (`chipbench/configs/nemotron-super-ep4.json`, `model_type`
`nemotron_h`).

Straightforward `jax.numpy` in float32 at `highest` matmul precision: no
kernel, no chunks, no sorting of tokens. It imports nothing of the
program. The stem, the heads and the small layer functions (`matmul`,
`rms_norm`) are `reference_exaone_moe`'s; the stack between stem and
heads follows the published `config.json` keys at the top level of the
configuration's file, with what that file cannot fix taken from its
`trunk_choices` and written under its `assumed`. Layer l is the l-th
letter of `hybrid_override_pattern`, and every layer is ONE function
under one norm and one residual, with x (S, 4096):

    x <- x + f(RMSNorm(x)),  eps `layer_norm_epsilon`,

a final RMSNorm before the heads, no bias but the convolution's.

- `M` (Mamba-2): [z | xBC | dt] = u W_in, 8192 | 10240 | 128;
  xBC <- SiLU(conv(xBC) + b), a causal depthwise convolution of
  `conv_kernel` taps from a zero tail (the last tap on the token
  itself); xBC = [x (128 heads x 64) | B (8 groups x 128) | C (8 x
  128)], head h in group h // 16; step_t = softplus(dt_t + dt_bias),
  a_t = exp(-step_t exp(A_log)), one a head; the state S (64 x 128 a
  head) from zero, TOKEN BY TOKEN (a `lax.scan` over the S tokens; the
  program takes them `chunk_size` at a time):
      S_t = a_t S_{t-1} + step_t x_t B_t^T,   y_t = S_t C_t + D x_t;
  y <- RMSNorm(y x SiLU(z)) over each of the 8 groups of 1,024
  channels, one weight of 8,192; then W_out;
- `*` (attention): q to 32 heads, k and v to 2 heads of 128, query
  head h reading key/value head h // 16; no norm on q or k, no
  positions; scores / sqrt(128) masked to j <= i; softmax; W_o;
- `E` (routed experts): s = sigmoid(u W_r) over all 512 published
  experts; selection on s + b (the file's `router_bias`), the
  `num_experts_per_tok` 22 highest, BY SORTING (a stable argsort: of
  equals the first); w_e = `routed_scaling_factor` x s_e / (sum of the
  chosen s); l = u W_dn, the latent of 1,024; expert e is
  W2_e(relu(W1_e l)^2), not gated; r = the sum over the chosen experts
  HELD HERE of w_e E_e(l): a loop over the held experts, each applied
  to every token and weighted by w_e or nought; out = r W_up +
  V2(relu(V1 u)^2), the shared expert of 5,376 on the hidden size.
  What the experts held elsewhere would add is left out, as in the
  program: that partial sum goes on to the next layer.

Departures from the published model, as the configuration's file lists
them: the conv stem stands in the embedding's place, the policy and
value heads in the output head's, there is no multi-token prediction
module, no vocabulary and no decoding.

The weights arrive in the type the program holds them in (bfloat16, a
few float32) and are widened one layer at a time, each layer a jitted
call of its own, so the float32 copies never stand together. `quant`
rounds both operands of every matmul (fp8: the control), as
`reference.py` has it; the recurrence's products with the state are
matmuls too and are rounded with them.
"""

import functools
import json
import math

import jax
import jax.numpy as jnp

from .reference import HIGHEST, _q
from .reference_exaone_moe import _f32, heads, layer_weights, matmul, rms_norm, stem
from .reference_ling_hybrid import causal

PUBLISHED = (
    "hidden_size", "num_attention_heads", "num_key_value_heads", "head_dim",
    "intermediate_size", "moe_intermediate_size", "num_experts_per_tok",
    "routed_scaling_factor", "n_group", "topk_group", "mamba_num_heads",
    "mamba_head_dim", "ssm_state_size", "n_groups", "conv_kernel",
    "chunk_size", "use_conv_bias", "moe_latent_size",
    "moe_shared_expert_intermediate_size", "mlp_hidden_act",
)

CHOICES = {"norm_position": "pre", "qk_norm": "none"}
LETTERS = {
    "M": ("state_space", "none"),
    "E": ("none", "sparse"),
    "*": ("full_attention", "none"),
}


def trunk_settings(cfg: dict) -> dict:
    """The stack as it is run, from the configuration's file: the
    published keys, a layer a letter of the pattern (one half each),
    the router as wide as published, the experts this chip holds and
    the file's `trunk_choices`. The program's `TrunkConfig` takes
    exactly these keys; the reference reads the same dict."""
    for key, value in CHOICES.items():
        if cfg["trunk_choices"][key] != value:
            raise ValueError(f"trunk_choices.{key}: only {value!r} is implemented")
    pattern = cfg["hybrid_override_pattern"]
    if len(pattern) != cfg["num_hidden_layers"]:
        raise ValueError("hybrid_override_pattern names another depth")
    if cfg["mlp_hidden_act"] != "relu2" or cfg["n_group"] != 1:
        raise ValueError("only relu2 experts under an ungrouped choice are implemented")
    mixers, mlps = zip(*(LETTERS[letter] for letter in pattern))
    share = cfg["deployment"]
    held = cfg["n_routed_experts"]
    return {
        **{key: cfg[key] for key in PUBLISHED},
        "num_experts": cfg["published"]["n_routed_experts"],
        "num_shared_experts": cfg["n_shared_experts"],
        "rms_norm_eps": cfg["layer_norm_epsilon"],
        "layer_types": list(mixers),
        "mlp_layer_types": list(mlps),
        "experts_held": [share["chip"] * held, held],
        **cfg["trunk_choices"],
    }


# --- layers -----------------------------------------------------------------


def relu2_mlp(x, up, down, quant):
    return matmul(jnp.square(jax.nn.relu(matmul(x, up, quant))), down, quant)


def conv(x, taps, bias):
    """y_t = sum_j taps[j] x_{t - (K - 1) + j} + bias along axis 1 of x
    (b, s, c), nought before the first token; then SiLU."""
    count, s = taps.shape[0], x.shape[1]
    padded = jnp.pad(x, ((0, 0), (count - 1, 0), (0, 0)))
    y = jnp.zeros_like(x) + bias
    for j in range(count):
        y = y + padded[:, j : j + s] * taps[j]
    return jax.nn.silu(y)


def scan(x, step, a, b, c, skip, quant):
    """The recurrence, a token at a time: x (b, s, H, p), step and a
    (b, s, H), b and c (b, s, H, n) -> y (b, s, H, p)."""

    def one(state, xs):
        x_t, step_t, a_t, b_t, c_t = xs  # (b, H, ...)
        state = a_t[..., None, None] * state + jnp.einsum(
            "bhp,bhn->bhpn", _q(step_t[..., None] * x_t, quant), _q(b_t, quant),
            precision=HIGHEST,
        )
        out = jnp.einsum(
            "bhpn,bhn->bhp", _q(state, quant), _q(c_t, quant), precision=HIGHEST
        )
        return state, out

    batch, _, h, p = x.shape
    start = jnp.zeros((batch, h, p, b.shape[-1]), jnp.float32)
    _, y = jax.lax.scan(
        one, start, tuple(jnp.swapaxes(v, 0, 1) for v in (x, step, a, b, c))
    )
    return jnp.swapaxes(y, 0, 1) + skip[:, None] * x


def mamba(p, u, t, quant):
    b, s, _ = u.shape
    h, hd = t["mamba_num_heads"], t["mamba_head_dim"]
    g, n = t["n_groups"], t["ssm_state_size"]
    inner, per = h * hd, h // g
    zxbcdt = matmul(u, p["w_in"], quant)
    z = zxbcdt[..., :inner]
    xbc = zxbcdt[..., inner : 2 * inner + 2 * g * n]
    dt = zxbcdt[..., 2 * inner + 2 * g * n :]
    xbc = conv(xbc, p["conv"], p["conv_bias"] if t["use_conv_bias"] else 0.0)
    x = xbc[..., :inner].reshape(b, s, h, hd)
    bs = jnp.repeat(xbc[..., inner : inner + g * n].reshape(b, s, g, n), per, axis=2)
    cs = jnp.repeat(xbc[..., inner + g * n :].reshape(b, s, g, n), per, axis=2)
    step = jax.nn.softplus(dt + p["dt_bias"])
    a = jnp.exp(-step * jnp.exp(p["A_log"]))
    y = scan(x, step, a, bs, cs, p["D"], quant).reshape(b, s, inner)
    y = (y * jax.nn.silu(z)).reshape(b, s, g, inner // g)
    y = rms_norm(y, p["gated_norm"].reshape(g, inner // g), t["rms_norm_eps"])
    return matmul(y.reshape(b, s, inner), p["w_out"], quant)


def attention(p, x, t, quant):
    b, s, _ = x.shape
    h, kv, hd = t["num_attention_heads"], t["num_key_value_heads"], t["head_dim"]
    q = matmul(x, p["wq"], quant).reshape(b, s, h, hd)
    k = jnp.repeat(matmul(x, p["wk"], quant).reshape(b, s, kv, hd), h // kv, axis=2)
    v = jnp.repeat(matmul(x, p["wv"], quant).reshape(b, s, kv, hd), h // kv, axis=2)
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant), precision=HIGHEST
    ) / math.sqrt(hd)
    weights = jax.nn.softmax(jnp.where(causal(s), scores, -jnp.inf), axis=-1)
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", _q(weights, quant), _q(v, quant), precision=HIGHEST
    )
    return matmul(ctx.reshape(b, s, h * hd), p["wo"], quant)


def scores_of(p, x, quant):
    return jax.nn.sigmoid(matmul(x, p["w_router"], quant))


def route(p, x, t, quant):
    """Scores over all experts -> (chosen (..., k), weights (..., k))."""
    scores = scores_of(p, x, quant)
    biased = scores + p["router_bias"] if t["router_bias"] else scores
    chosen = jnp.argsort(biased, axis=-1, stable=True, descending=True)[
        ..., : t["num_experts_per_tok"]
    ]
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    return chosen, t["routed_scaling_factor"] * top / top.sum(axis=-1, keepdims=True)


def routed(p, x, t, quant, held=None):
    """The held experts' weighted sum in the latent, (..., latent).
    `held` = (first, count) overrides the configuration's share (the
    test that adds the shares up asks for each in turn)."""
    first, count = held or t["experts_held"]
    chosen, weight = route(p, x, t, quant)
    latent = matmul(x, p["w_latent_down"], quant)

    def one(r, expert):
        e, up, down = expert
        w_e = jnp.where(chosen == first + e, weight, 0.0).sum(axis=-1)
        return r + w_e[..., None] * relu2_mlp(latent, up, down, quant), None

    r, _ = jax.lax.scan(
        one, jnp.zeros_like(latent),
        (jnp.arange(count), p["e_up"][:count], p["e_down"][:count]),
    )
    return r


def experts(p, x, t, quant, held=None):
    """The held experts' part projected up, plus the shared expert."""
    y = matmul(routed(p, x, t, quant, held), p["w_latent_up"], quant)
    if t["num_shared_experts"]:
        y = y + relu2_mlp(x, p["s_up"], p["s_down"], quant)
    return y


def layer_input(p, x, t, i):
    """RMSNorm(x): what layer i's one function, and on an `E` layer its
    router, reads."""
    sparse = t["mlp_layer_types"][i] == "sparse"
    norm = p["mlp_norm" if sparse else "attn_norm"]
    return rms_norm(x, norm.astype(jnp.float32), t["rms_norm_eps"])


def layer(p, x, t, i, quant):
    """Decoder layer i on x (b, s, d). `p` holds the layer's weights
    under their names without its prefix, in any float type."""
    p = _f32(p)
    u = layer_input(p, x, t, i)
    if t["mlp_layer_types"][i] == "sparse":
        return x + experts(p, u, t, quant)
    if t["layer_types"][i] == "state_space":
        return x + mamba(p, u, t, quant)
    return x + attention(p, u, t, quant)


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
