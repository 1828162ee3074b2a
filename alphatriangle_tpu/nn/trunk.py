"""A decoder stack as the net's trunk (`ModelConfig.TRUNK`).

The layers of a routed-expert language model stand where the encoder
layers stand: tokens are the board's cells in row-major order, the
cell's index is its position. A layer is a mixer and an MLP, or where
either list says "none" the other half alone, under one norm and one
residual. Per layer (l = 0..), the mixer `layer_types[l]` names:

- softmax attention (`sliding_attention`, `full_attention`): q, k, v
  without biases, an RMSNorm on q and k per head (none on a full
  layer with `qk_norm` "none"), rotary positions over the whole head
  on the sliding layers, every
  `num_attention_heads / num_key_value_heads` query heads sharing one
  key/value head, scores in float32 over sqrt(head_dim), masked to
  j <= i and on a sliding layer to i - j < `sliding_window`;
- `linear_attention` (KDA): q, k, v to heads x head_dim each, every
  channel through a causal depthwise convolution of
  `short_conv_kernel_size` taps and SiLU, q and k L2-normalised per
  head, q scaled by head_dim^-0.5; a log decay per head and channel
  g = `kda_lower_bound` x sigmoid(exp(A_log) x (x Wf + dt_bias)), a
  beta = sigmoid(x Wb) per head; the gated delta rule over the tokens
  (nn/linear_attention.py, its chunked form); an RMSNorm over each
  head's output, times a gate sigmoid(x Wg) a head, then Wo;
- `latent_attention` (MLA): q to heads x (`qk_nope_head_dim` +
  `qk_rope_head_dim`), with `q_lora_rank` through a latent of its own
  (q = RMSNorm(x Wq_a) Wq_b); x Wa to a latent of `kv_lora_rank` and one
  rotary key of `qk_rope_head_dim` all heads share; the latent under
  an RMSNorm, expanded to each head's keys (`qk_nope_head_dim`) and
  values (`v_head_dim`); rotary positions, neighbouring pairs, on the
  rotary parts; scores over sqrt of the query's whole width, causal
  softmax, the context times a gate sigmoid(x Wg) a head (unless
  `latent_gate` is off), then Wo;
- `state_space` (Mamba-2): [z | xBC | dt] = x W_in; xBC through one
  causal depthwise convolution of `conv_kernel` taps with its bias,
  then SiLU, and cut into the heads' x (`mamba_num_heads` x
  `mamba_head_dim`) and the groups' B and C (`n_groups` x
  `ssm_state_size` each); a step softplus(dt + dt_bias) and a decay
  exp(-step exp(A_log)) a head, float32; the recurrence over the
  tokens with its skip D (nn/state_space.py, its chunked form at
  `chunk_size`; on one TPU chip ops/state_space_scan.py); y x SiLU(z)
  under an RMSNorm over each group's channels, then W_out;
- `gated_deltanet` (Gated DeltaNet): [q | k | v] = x W_qkv, q and k
  `linear_num_key_heads` heads of `linear_key_head_dim`, v
  `linear_num_value_heads` heads of `linear_value_head_dim`, value head
  j reading key head j // (value heads / key heads); all three through
  one causal depthwise convolution of `short_conv_kernel_size` taps and
  SiLU; q and k L2-normalised per head, q scaled by its width^-0.5; a
  log decay g = -exp(A_log) softplus(x W_a + dt_bias) and a beta =
  sigmoid(x W_b), one a value head, float32; the delta rule over the
  tokens (nn/linear_attention.py, `chunked_scalar`; on one TPU chip
  ops/gated_delta_rule.py); each head's output under an RMSNorm times
  s sigmoid(w_o) and the gate s sigmoid(x W_z), one a channel
  (s = `linear_sigmoid_gate_scale`), then W_o;

and the MLP `mlp_layer_types[l]` names:

- a SwiGLU of `intermediate_size` on a dense layer; on a sparse one a
  sigmoid router over all `num_experts`, the `num_experts_per_tok` of
  highest score (with `n_group` > 1 among the `topk_group` groups
  whose two best scores sum highest), weights `routed_scaling_factor`
  x score / (sum of the chosen scores), every expert a SwiGLU of
  `moe_intermediate_size` (with `mlp_hidden_act` "relu2" ungated:
  down(relu(up x)^2), two matrices) on the hidden size, or with
  `moe_latent_size` on a latent of that width (x W_dn before the sort,
  the held experts' weighted sum times W_up after it), and the shared
  expert the same kind of MLP on the hidden size,
  `moe_shared_expert_intermediate_size` wide where that is given;

with x + norm(f(x)) (`norm_position` "post"), x + f(norm(x)) ("pre")
or x + norm'(f(norm(x))) ("pre_post") for mixer and MLP alike, and a
final RMSNorm after the last layer; with `layernorm_gating_weight` g
each of these norms weighs by g sigmoid(w) where it would by w. With
`swiglu_limit` L every SwiGLU is silu(min(x W_gate, L)) x clip(x W_up,
-L, L). A latent layer's `rope_scaling` moves its rotary frequencies
(`yarn_frequencies`), `use_mla_scaling_factor` multiplies its scores by
`yarn_mscale`^2, and `latent_gate_granularity` "element" gates its
context a channel, sigmoid(x Wg) of heads x `v_head_dim`.

One process holds the experts `experts_held` = (first, count). It
routes over all of them, sorts the token-expert assignments that fall
on its own by expert, runs them through the experts' MLP as grouped
products (in an inference forward on one TPU chip the two kernels of
ops/grouped_experts.py, else `jax.lax.ragged_dot` a matrix) and adds
nothing for the experts held elsewhere: no token is dropped, no
capacity is set, nothing stands in for the other holders or their
exchange. The assignments it computed are counted per expert
(`counters` collection, sown only where the caller asks for it).

A training forward (`DecoderTrunk(...)(tokens, train=True)`) also counts
the assignments the router made to every one of `num_experts`, held
here or not (`expert_loads`: what the rule that moves the selection
biases reads, `moved_router_biases`), and with `remat` runs each layer
under `jax.checkpoint`, so that a backward pass holds one layer's
activations at a time. Every function here is differentiated by the
learner (rl/trainer.py), the expert layer's sort, grouped products and
gather among them; the selection bias moves the choice alone and no
gradient reaches it.

The layers are plain functions of a parameter dict; `DecoderTrunk`
declares the parameters, in `param_dtype`, straight from the key. A leaf
batch too large for the device is cut into blocks of `block_boards`
boards by the caller that has it (the search's `_evaluate`): the whole
net runs on a block at a time, so no activation of the full batch at
this width ever exists.
"""

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import Array

from ..config.model_config import TrunkConfig
from ..ops.delta_rule import gated_delta_rule, linear_path
from ..ops.encoder_layer import partitioned
from ..ops.gated_delta_rule import gated_delta_net, gdn_path
from ..ops.grouped_experts import expert_path, grouped_experts
from ..ops.state_space_scan import ssm_path, state_space_scan
from . import linear_attention as delta_rule
from . import state_space

# A share sees num_experts_per_tok x count / num_experts of a block's
# assignments if routing is even. The expert layer's buffers hold this
# many times that; a block that routes more here goes round again.
USUAL_ROOM = 2


def sparse_layers(cfg: TrunkConfig) -> list[int]:
    return [i for i, kind in enumerate(cfg.mlp_layer_types) if kind == "sparse"]


def param_shapes(cfg: TrunkConfig) -> dict[str, tuple[tuple[int, ...], int | str]]:
    """name -> (shape, fan_in); fan_in 0 marks a norm's weight (ones),
    -1 the router's selection bias (noughts), a name a float32
    parameter drawn its own way (`_INITS`): a state-space or
    gated-delta-net layer's, or a zero-centred norm's weight
    ("zero_centred", N(0, 1/4), so that no path that reads it as w or
    1 + w for g sigmoid(w) agrees). Every other parameter is N(0, 1 /
    fan_in): for a linear layer's `A_log` (fan_in 4) and `dt_bias` (1)
    that puts the argument of the decay's sigmoid at order 1. A half a
    layer lacks declares nothing, its norms neither."""
    d, hd = cfg.hidden_size, cfg.head_dim or 0  # no head_dim: none reads it
    heads = cfg.num_attention_heads
    q_out = heads * hd
    kv_out = cfg.num_key_value_heads * hd
    held = cfg.experts_held[1]
    im = cfg.moe_intermediate_size
    gated = cfg.mlp_hidden_act == "silu"
    norm = 0 if cfg.layernorm_gating_weight is None else "zero_centred"
    halves = ("", "_post") if cfg.norm_position == "pre_post" else ("",)
    shapes: dict[str, tuple[tuple[int, ...], int | str]] = {}
    for i, (mixer, kind) in enumerate(zip(cfg.layer_types, cfg.mlp_layer_types)):
        p = f"l{i}_"
        for half in halves:
            if mixer != "none":
                shapes[p + f"attn{half}_norm"] = ((d,), norm)
            if kind != "none":
                shapes[p + f"mlp{half}_norm"] = ((d,), norm)
        if mixer == "none":
            pass
        elif mixer == "gated_deltanet":
            keys, values = gdn_widths(cfg)
            value_heads, taps = cfg.linear_num_value_heads, cfg.short_conv_kernel_size
            shapes[p + "w_qkv"] = ((d, 2 * keys + values), d)
            shapes[p + "w_z"] = ((d, values), d)
            shapes[p + "w_a"] = ((d, value_heads), d)
            shapes[p + "w_b"] = ((d, value_heads), d)
            shapes[p + "conv"] = ((taps, 2 * keys + values), taps)
            shapes[p + "A_log"] = ((value_heads,), "A_log")
            shapes[p + "dt_bias"] = ((value_heads,), "normal")
            shapes[p + "o_norm"] = ((cfg.linear_value_head_dim,), "zero_centred")
            shapes[p + "wo"] = ((values, d), values)
        elif mixer == "state_space":
            inner, mixed = ssm_widths(cfg)
            ssm_heads = cfg.mamba_num_heads
            shapes[p + "w_in"] = ((d, inner + mixed + ssm_heads), d)
            shapes[p + "conv"] = ((cfg.conv_kernel, mixed), cfg.conv_kernel)
            if cfg.use_conv_bias:
                shapes[p + "conv_bias"] = ((mixed,), cfg.conv_kernel)
            shapes[p + "A_log"] = ((ssm_heads,), "A_log")
            shapes[p + "D"] = ((ssm_heads,), "D")
            shapes[p + "dt_bias"] = ((ssm_heads,), "dt_bias")
            shapes[p + "gated_norm"] = ((inner,), 0)
            shapes[p + "w_out"] = ((inner, d), inner)
        elif mixer == "linear_attention":
            taps = cfg.short_conv_kernel_size
            for name in ("wq", "wk", "wv", "wf"):
                shapes[p + name] = ((d, q_out), d)
            for name in ("conv_q", "conv_k", "conv_v"):
                shapes[p + name] = ((taps, q_out), taps)
            shapes[p + "wb"] = ((d, heads), d)
            shapes[p + "wg"] = ((d, heads), d)
            shapes[p + "A_log"] = ((heads,), 4)
            shapes[p + "dt_bias"] = ((q_out,), 1)
            shapes[p + "o_norm"] = ((hd,), 0)
            shapes[p + "wo"] = ((q_out, d), q_out)
        elif mixer == "latent_attention":
            rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            wide = cfg.qk_nope_head_dim + cfg.v_head_dim
            q_wide, q_rank = heads * (cfg.qk_nope_head_dim + rope), cfg.q_lora_rank
            if q_rank is None:
                shapes[p + "wq"] = ((d, q_wide), d)
            else:
                shapes[p + "wq_a"] = ((d, q_rank), d)
                shapes[p + "q_a_norm"] = ((q_rank,), 0)
                shapes[p + "wq_b"] = ((q_rank, q_wide), q_rank)
            shapes[p + "wkv_a"] = ((d, rank + rope), d)
            shapes[p + "kv_norm"] = ((rank,), 0)
            shapes[p + "wkv_b"] = ((rank, heads * wide), rank)
            if cfg.latent_gate:
                shapes[p + "wg"] = ((d, latent_gate_width(cfg)), d)
            shapes[p + "wo"] = ((heads * cfg.v_head_dim, d), heads * cfg.v_head_dim)
        else:
            shapes[p + "wq"] = ((d, q_out), d)
            shapes[p + "wk"] = ((d, kv_out), d)
            shapes[p + "wv"] = ((d, kv_out), d)
            shapes[p + "wo"] = ((q_out, d), q_out)
            if cfg.qk_norm is True:
                shapes[p + "q_norm"] = ((hd,), 0)
                shapes[p + "k_norm"] = ((hd,), 0)
        if kind == "none":
            continue
        if kind == "dense":
            wide = cfg.intermediate_size
            shapes[p + "w_gate"] = ((d, wide), d)
            shapes[p + "w_up"] = ((d, wide), d)
            shapes[p + "w_down"] = ((wide, d), wide)
            continue
        shapes[p + "w_router"] = ((d, cfg.num_experts), d)
        if cfg.router_bias:
            shapes[p + "router_bias"] = ((cfg.num_experts,), -1)
        lat = cfg.moe_latent_size or d  # what an expert reads and writes
        if cfg.moe_latent_size:
            shapes[p + "w_latent_down"] = ((d, lat), d)
            shapes[p + "w_latent_up"] = ((lat, d), lat)
        if gated:
            shapes[p + "e_gate"] = ((held, lat, im), lat)
        shapes[p + "e_up"] = ((held, lat, im), lat)
        shapes[p + "e_down"] = ((held, im, lat), im)
        if cfg.num_shared_experts:
            wide = shared_width(cfg)
            if gated:
                shapes[p + "s_gate"] = ((d, wide), d)
            shapes[p + "s_up"] = ((d, wide), d)
            shapes[p + "s_down"] = ((wide, d), wide)
    shapes["norm"] = ((d,), norm)
    return shapes


def gdn_widths(cfg: TrunkConfig) -> tuple[int, int]:
    """A gated-delta-net layer's key width (q's and k's: key heads x
    their width) and value width (v's, z's and its output's)."""
    return (
        cfg.linear_num_key_heads * cfg.linear_key_head_dim,
        cfg.linear_num_value_heads * cfg.linear_value_head_dim,
    )


def latent_gate_width(cfg: TrunkConfig) -> int:
    """Sigmoids a latent layer's gate has a token: one a head, or one
    a channel of the context."""
    heads = cfg.num_attention_heads
    return heads * cfg.v_head_dim if cfg.latent_gate_granularity == "element" else heads


def ssm_widths(cfg: TrunkConfig) -> tuple[int, int]:
    """A state-space layer's inner width (heads x their width: z's, x's
    and y's) and the convolution's (x, B and C side by side)."""
    inner = cfg.mamba_num_heads * cfg.mamba_head_dim
    return inner, inner + 2 * cfg.n_groups * cfg.ssm_state_size


def shared_width(cfg: TrunkConfig) -> int:
    return cfg.num_shared_experts * (
        cfg.moe_shared_expert_intermediate_size or cfg.moe_intermediate_size
    )


def forward_flops(cfg: TrunkConfig, seq: int) -> int:
    """Matmul FLOP (1 MAC = 2) of the stack on one board of `seq` tokens
    as this share computes it if routing is even: every layer's
    projections, the score products over the pairs the mask keeps (a
    linear layer's convolutions, and its recurrence by its recurrent
    form: the state read by the key, written, and read by the query,
    3 x 2 x head_dim^2 a token and head, whatever the chunked form
    multiplies; a state-space layer's likewise: its two projections,
    its convolution, the state written and read, 2 x 2 x
    `mamba_head_dim` x `ssm_state_size` a token and head; a
    gated-delta-net layer's likewise: its projections, its convolution,
    and 3 x 2 x key width x value width a token and value head for its
    recurrence), the dense layer, the router, the latent's two
    projections, the shared expert, and `num_experts_per_tok` x held /
    `num_experts` experts a token (an MLP's two matrices or, gated,
    three)."""
    d = cfg.hidden_size
    heads = cfg.num_attention_heads
    q_out = heads * (cfg.head_dim or 0)
    kv_out = cfg.num_key_value_heads * (cfg.head_dim or 0)
    matrices = 3 if cfg.mlp_hidden_act == "silu" else 2
    lat = cfg.moe_latent_size or d
    expert = 2 * matrices * lat * cfg.moe_intermediate_size
    shared = 2 * matrices * d * shared_width(cfg)
    here = cfg.num_experts_per_tok * cfg.experts_held[1] / cfg.num_experts
    total = 0.0
    for kind, mlp in zip(cfg.layer_types, cfg.mlp_layer_types):
        if kind == "none":
            pass
        elif kind == "state_space":
            inner, mixed = ssm_widths(cfg)
            total += seq * 2 * (d * (inner + mixed + cfg.mamba_num_heads) + inner * d)
            total += seq * 2 * cfg.conv_kernel * mixed
            total += seq * 2 * 2 * inner * cfg.ssm_state_size
        elif kind == "linear_attention":
            total += seq * 2 * (d * (4 * q_out + 2 * heads) + q_out * d)
            total += seq * 2 * 3 * cfg.short_conv_kernel_size * q_out
            total += seq * 3 * 2 * q_out * cfg.head_dim
        elif kind == "gated_deltanet":
            keys, values = gdn_widths(cfg)
            mixed = 2 * keys + values
            total += seq * 2 * (
                d * (mixed + values + 2 * cfg.linear_num_value_heads) + values * d
            )
            total += seq * 2 * cfg.short_conv_kernel_size * mixed
            total += seq * 3 * 2 * values * cfg.linear_key_head_dim
        elif kind == "latent_attention":
            rank, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
            q_wide = cfg.qk_nope_head_dim + rope
            kv_wide = cfg.qk_nope_head_dim + cfg.v_head_dim
            q_rank = cfg.q_lora_rank
            query = (
                d * heads * q_wide
                if q_rank is None
                else q_rank * (d + heads * q_wide)
            )
            total += seq * 2 * (
                query
                + d * (rank + rope + latent_gate_width(cfg) * cfg.latent_gate)
                + rank * heads * kv_wide
                + heads * cfg.v_head_dim * d
            )
            total += 2 * heads * (q_wide + cfg.v_head_dim) * int(
                causal_mask(seq, None).sum()
            )
        else:
            window = cfg.sliding_window if kind == "sliding_attention" else None
            total += seq * 2 * (d * (q_out + 2 * kv_out) + q_out * d)
            total += 2 * 2 * q_out * int(causal_mask(seq, window).sum())
        if mlp == "dense":
            total += seq * 2 * 3 * d * cfg.intermediate_size
        elif mlp == "sparse":
            total += seq * (2 * d * cfg.num_experts + shared + here * expert)
            if cfg.moe_latent_size:
                total += seq * 2 * 2 * d * lat
    return int(total)


# --- the layers, as functions of their parameters --------------------------


def _dot(x: Array, w: Array, dtype) -> Array:
    """x @ w with operands in `dtype`, accumulated and returned in float32."""
    return jnp.dot(
        x.astype(dtype), w.astype(dtype), preferred_element_type=jnp.float32
    )


def rms_norm(x: Array, weight: Array, eps: float) -> Array:
    xf = x.astype(jnp.float32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (y * weight.astype(jnp.float32)).astype(x.dtype)


def zero_centred(w: Array, scale: float) -> Array:
    """scale x sigmoid(w), float32: a zero-centred gated weight."""
    return scale * jax.nn.sigmoid(w.astype(jnp.float32))


def stack_norm(x: Array, w: Array, cfg: TrunkConfig) -> Array:
    """A norm of a layer's halves, or the final one: RMSNorm(x) times w,
    or with `layernorm_gating_weight` g times g sigmoid(w)."""
    if cfg.layernorm_gating_weight is not None:
        w = zero_centred(w, cfg.layernorm_gating_weight)
    return rms_norm(x, w, cfg.rms_norm_eps)


def rotary_table(seq: int, head_dim: int, theta: float) -> tuple[np.ndarray, np.ndarray]:
    """(cos, sin), each (seq, head_dim): the default rotary type, the
    head's halves paired."""
    inv = theta ** (-np.arange(0, head_dim, 2, dtype=np.float64) / head_dim)
    angle = np.arange(seq, dtype=np.float64)[:, None] * inv[None, :]
    angle = np.concatenate([angle, angle], axis=-1)
    return np.cos(angle).astype(np.float32), np.sin(angle).astype(np.float32)


def rotate(x: Array, cos: np.ndarray, sin: np.ndarray) -> Array:
    """x (b, s, ..., head_dim) turned by its position along axis 1."""
    shape = (1, x.shape[1]) + (1,) * (x.ndim - 3) + (x.shape[-1],)
    xf = x.astype(jnp.float32)
    half = x.shape[-1] // 2
    turned = jnp.concatenate([-xf[..., half:], xf[..., :half]], axis=-1)
    return (xf * cos.reshape(shape) + turned * sin.reshape(shape)).astype(x.dtype)


def causal_mask(seq: int, window: int | None) -> np.ndarray:
    """(seq, seq) bool: query i sees key j <= i, within `window` if given."""
    i, j = np.arange(seq)[:, None], np.arange(seq)[None, :]
    seen = j <= i
    if window is not None:
        seen &= i - j < window
    return seen


def attention(p: dict, x: Array, cfg: TrunkConfig, sliding: bool, dtype) -> Array:
    b, s, _ = x.shape
    hkv, hd = cfg.num_key_value_heads, cfg.head_dim
    rep = cfg.num_attention_heads // hkv
    q = _dot(x, p["wq"], dtype).astype(dtype).reshape(b, s, hkv, rep, hd)
    k = _dot(x, p["wk"], dtype).astype(dtype).reshape(b, s, hkv, hd)
    v = _dot(x, p["wv"], dtype).astype(dtype).reshape(b, s, hkv, hd)
    if cfg.qk_norm is True:
        q = rms_norm(q, p["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_norm_eps)
    if sliding:
        cos, sin = rotary_table(s, hd, cfg.rope_theta)
        q, k = rotate(q, cos, sin), rotate(k, cos, sin)
    scores = jnp.einsum(
        "bqkgd,bskd->bkgqs", q, k, preferred_element_type=jnp.float32
    ) / math.sqrt(hd)
    seen = causal_mask(s, cfg.sliding_window if sliding else None)
    scores = jnp.where(seen[None, None, None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
    ctx = jnp.einsum(
        "bkgqs,bskd->bqkgd", weights, v, preferred_element_type=jnp.float32
    )
    ctx = ctx.astype(dtype).reshape(b, s, hkv * rep * hd)
    return _dot(ctx, p["wo"], dtype).astype(dtype)


def short_conv(x: Array, taps: Array, bias: Array | None = None) -> Array:
    """A causal depthwise convolution along axis 1 of x (b, s, c), then
    SiLU: y_t = sum_j taps[j] x_{t - (K - 1) + j} (+ bias), the last tap
    on the token itself; float32 inside, x's type out."""
    count = taps.shape[0]
    xf = jnp.pad(x.astype(jnp.float32), ((0, 0), (count - 1, 0), (0, 0)))
    s = x.shape[1]
    y = sum(
        xf[:, j : j + s] * taps[j].astype(jnp.float32) for j in range(count)
    )
    if bias is not None:
        y = y + bias.astype(jnp.float32)
    return jax.nn.silu(y).astype(x.dtype)


def _l2(x: Array) -> Array:
    """x over its norm along the last axis, float32."""
    xf = x.astype(jnp.float32)
    return xf * jax.lax.rsqrt(jnp.sum(xf * xf, axis=-1, keepdims=True) + 1e-6)


def log_decay(p: dict, x: Array, cfg: TrunkConfig, dtype) -> Array:
    """g (b, s, heads, head_dim) float32, every entry in
    (`kda_lower_bound`, 0): the lower-bounded gate."""
    b, s, _ = x.shape
    heads, hd = cfg.num_attention_heads, cfg.head_dim
    f = _dot(x, p["wf"], dtype) + p["dt_bias"].astype(jnp.float32)
    rate = jnp.exp(p["A_log"].astype(jnp.float32))[:, None]
    return cfg.kda_lower_bound * jax.nn.sigmoid(rate * f.reshape(b, s, heads, hd))


def recurrence_path(cfg: TrunkConfig, x: Array, dtype) -> str:
    """"kernel" (ops/delta_rule.py) or "chunked" (nn/linear_attention.py)
    for the recurrence of a linear-attention layer on x (b, s, d), from
    what this trace can observe: no field of the config chooses."""
    return linear_path(
        partitioned=partitioned(x),
        backend=jax.default_backend(),
        seq=x.shape[1],
        head_dim=cfg.head_dim,
        chunk=cfg.linear_chunk,
        lower_bound=cfg.kda_lower_bound,
        dtype=dtype,
    )


def linear_attention(p: dict, x: Array, cfg: TrunkConfig, dtype) -> Array:
    b, s, _ = x.shape
    heads, hd = cfg.num_attention_heads, cfg.head_dim
    q, k, v = (
        short_conv(_dot(x, p[w], dtype).astype(dtype), p[c]).reshape(b, s, heads, hd)
        for w, c in (("wq", "conv_q"), ("wk", "conv_k"), ("wv", "conv_v"))
    )
    q, k = _l2(q) * hd**-0.5, _l2(k)
    g = log_decay(p, x, cfg, dtype)
    beta = jax.nn.sigmoid(_dot(x, p["wb"], dtype))  # (b, s, heads)
    gate = jax.nn.sigmoid(_dot(x, p["wg"], dtype))

    def heads_first(y):  # (b, s, heads, ...) -> (b x heads, s, ...)
        y = jnp.moveaxis(y, 2, 1)
        return y.reshape(b * heads, s, *y.shape[3:])

    # A head is a 128-lane block of (b, s, heads x hd): the kernel reads
    # it there, and nothing is moved heads-first or back.
    kernel = recurrence_path(cfg, x, dtype) == "kernel"
    with jax.named_scope("net/trunk/linear_attn/scan"):
        if kernel:
            o = gated_delta_rule(
                *(y.reshape(b, s, heads * hd) for y in (q, k, v, g)), beta,
                heads=heads, chunk=cfg.linear_chunk,
                lower_bound=cfg.kda_lower_bound, dtype=jnp.dtype(dtype),
            )
        else:
            o = delta_rule.chunked(
                *(heads_first(y) for y in (q, k, v, g, beta)),
                cfg.linear_chunk, cfg.kda_lower_bound, dtype,
            )
    if kernel:
        o = o.reshape(b, s, heads, hd)
    else:
        o = jnp.moveaxis(o.reshape(b, heads, s, hd), 1, 2)  # float32
    o = rms_norm(o, p["o_norm"], cfg.rms_norm_eps) * gate[..., None]
    return _dot(o.reshape(b, s, heads * hd), p["wo"], dtype).astype(dtype)


def yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0


def yarn_frequencies(inv: np.ndarray, width: int, theta: float, scaling) -> np.ndarray:
    """The rotary frequencies `inv` (width / 2,) of a `rope_scaling` of
    type yarn, as DeepSeek-V3 computes them: dimension pair i keeps
    inv_i below `low`, takes inv_i / factor above `high`, and a linear
    ramp between, `low` and `high` the pairs that turn `beta_fast` and
    `beta_slow` times over the original context."""

    def pair(turns):  # the pair that turns `turns` times over the context
        length = scaling.original_max_position_embeddings
        return width * math.log(length / (turns * 2 * math.pi)) / (2 * math.log(theta))

    low = max(math.floor(pair(scaling.beta_fast)), 0)
    high = min(math.ceil(pair(scaling.beta_slow)), width - 1)
    ramp = np.clip((np.arange(width // 2) - low) / max(high - low, 1e-3), 0.0, 1.0)
    return inv / scaling.factor * ramp + inv * (1.0 - ramp)


def rotate_pairs(x: Array, theta: float, scaling=None) -> Array:
    """x (b, s, ..., width) turned by its position along axis 1,
    neighbouring entries paired (the interleaved rotary); with a yarn
    `scaling`, at its frequencies and its cos/sin factor."""
    s, width = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, width, 2, dtype=np.float64) / width)
    factor = 1.0
    if scaling is not None:
        inv = yarn_frequencies(inv, width, theta, scaling)
        factor = yarn_mscale(scaling.factor, scaling.mscale) / yarn_mscale(
            scaling.factor, scaling.mscale_all_dim
        )
    angle = np.arange(s, dtype=np.float64)[:, None] * inv[None, :]
    shape = (1, s) + (1,) * (x.ndim - 3) + (width // 2,)
    cos = (factor * np.cos(angle)).astype(np.float32).reshape(shape)
    sin = (factor * np.sin(angle)).astype(np.float32).reshape(shape)
    xf = x.astype(jnp.float32)
    even, odd = xf[..., 0::2], xf[..., 1::2]
    turned = jnp.stack([even * cos - odd * sin, odd * cos + even * sin], axis=-1)
    return turned.reshape(x.shape).astype(x.dtype)


def latent_attention(p: dict, x: Array, cfg: TrunkConfig, dtype) -> Array:
    b, s, _ = x.shape
    heads, rank = cfg.num_attention_heads, cfg.kv_lora_rank
    nope, rope, vd = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
    if cfg.q_lora_rank is None:
        q = _dot(x, p["wq"], dtype)
    else:
        c_q = rms_norm(
            _dot(x, p["wq_a"], dtype).astype(dtype), p["q_a_norm"], cfg.rms_norm_eps
        )
        q = _dot(c_q, p["wq_b"], dtype)
    q = q.astype(dtype).reshape(b, s, heads, nope + rope)
    turn = functools.partial(rotate_pairs, theta=cfg.rope_theta, scaling=cfg.rope_scaling)
    q_n, q_r = q[..., :nope], turn(q[..., nope:])
    latent = _dot(x, p["wkv_a"], dtype).astype(dtype)
    k_r = turn(latent[..., rank:])  # (b, s, rope)
    c = rms_norm(latent[..., :rank], p["kv_norm"], cfg.rms_norm_eps)
    kv = _dot(c, p["wkv_b"], dtype).astype(dtype).reshape(b, s, heads, nope + vd)
    k_n, v = kv[..., :nope], kv[..., nope:]
    scores = (
        jnp.einsum("bqhd,bshd->bhqs", q_n, k_n, preferred_element_type=jnp.float32)
        + jnp.einsum("bqhd,bsd->bhqs", q_r, k_r, preferred_element_type=jnp.float32)
    ) / math.sqrt(nope + rope)
    if cfg.use_mla_scaling_factor:
        scaling = cfg.rope_scaling
        scores = scores * yarn_mscale(scaling.factor, scaling.mscale_all_dim) ** 2
    scores = jnp.where(causal_mask(s, None)[None, None], scores, -jnp.inf)
    weights = jax.nn.softmax(scores, axis=-1).astype(dtype)
    ctx = jnp.einsum("bhqs,bshd->bqhd", weights, v, preferred_element_type=jnp.float32)
    if cfg.latent_gate:
        gate = jax.nn.sigmoid(_dot(x, p["wg"], dtype))
        if cfg.latent_gate_granularity == "element":
            ctx = ctx * gate.reshape(b, s, heads, vd)
        else:
            ctx = ctx * gate[..., None]
    ctx = ctx.astype(dtype).reshape(b, s, heads * vd)
    return _dot(ctx, p["wo"], dtype).astype(dtype)


def scan_path(cfg: TrunkConfig, x: Array, dtype) -> str:
    """"kernel" (ops/state_space_scan.py) or "chunked"
    (nn/state_space.py) for the scan of a state-space layer on x (b, s,
    d), from what this trace can observe: no field of the config
    chooses."""
    return ssm_path(
        partitioned=partitioned(x),
        backend=jax.default_backend(),
        seq=x.shape[1],
        heads=cfg.mamba_num_heads,
        head_dim=cfg.mamba_head_dim,
        groups=cfg.n_groups,
        state_size=cfg.ssm_state_size,
        chunk=cfg.chunk_size,
        dtype=dtype,
    )


def state_space_mixer(p: dict, x: Array, cfg: TrunkConfig, dtype) -> Array:
    b, s, _ = x.shape
    heads, hd = cfg.mamba_num_heads, cfg.mamba_head_dim
    groups, n = cfg.n_groups, cfg.ssm_state_size
    inner, mixed = ssm_widths(cfg)
    # One matrix, [z | xBC | dt]; the step's columns apart, so that it
    # alone stays float32: (b, s, heads).
    zxbc = _dot(x, p["w_in"][:, : inner + mixed], dtype).astype(dtype)
    dt = _dot(x, p["w_in"][:, inner + mixed :], dtype)
    z = zxbc[..., :inner]
    xbc = short_conv(zxbc[..., inner:], p["conv"], p.get("conv_bias"))
    step = jax.nn.softplus(dt + p["dt_bias"].astype(jnp.float32))
    log_a = -step * jnp.exp(p["A_log"].astype(jnp.float32))
    # x, B and C are 128-lane blocks of xbc: the kernel reads them there.
    with jax.named_scope("net/trunk/state_space/scan"):
        if scan_path(cfg, x, dtype) == "kernel":
            y = state_space_scan(
                xbc, step, log_a, p["D"], heads=heads, head_dim=hd,
                groups=groups, chunk=cfg.chunk_size, dtype=jnp.dtype(dtype),
            )
        else:
            y = state_space.chunked(
                xbc[..., :inner].reshape(b, s, heads, hd), step, log_a,
                xbc[..., inner : inner + groups * n].reshape(b, s, groups, n),
                xbc[..., inner + groups * n :].reshape(b, s, groups, n),
                p["D"], cfg.chunk_size, dtype,
            )
    # The gate, then the norm, over each group's channels; one weight
    # as wide as the layer's inner width.
    y = y.reshape(b, s, inner) * jax.nn.silu(z.astype(jnp.float32))
    y = rms_norm(
        y.reshape(b, s, groups, inner // groups),
        p["gated_norm"].reshape(groups, inner // groups), cfg.rms_norm_eps,
    )
    return _dot(y.reshape(b, s, inner), p["w_out"], dtype).astype(dtype)


def delta_net_path(cfg: TrunkConfig, x: Array, dtype) -> str:
    """"kernel" (ops/gated_delta_rule.py) or "chunked"
    (nn/linear_attention.chunked_scalar) for the recurrence of a
    gated-delta-net layer on x (b, s, d), from what this trace can
    observe: no field of the config chooses."""
    return gdn_path(
        partitioned=partitioned(x),
        backend=jax.default_backend(),
        seq=x.shape[1],
        dk=cfg.linear_key_head_dim,
        dv=cfg.linear_value_head_dim,
        group=cfg.linear_num_value_heads // cfg.linear_num_key_heads,
        chunk=cfg.linear_chunk,
        dtype=dtype,
    )


def gated_deltanet(p: dict, x: Array, cfg: TrunkConfig, dtype) -> Array:
    b, s, _ = x.shape
    key_heads, dk = cfg.linear_num_key_heads, cfg.linear_key_head_dim
    group, dv = cfg.linear_num_value_heads // key_heads, cfg.linear_value_head_dim
    keys, values = gdn_widths(cfg)
    qkv = short_conv(_dot(x, p["w_qkv"], dtype).astype(dtype), p["conv"])
    q = _l2(qkv[..., :keys].reshape(b, s, key_heads, dk)) * dk**-0.5
    k = _l2(qkv[..., keys : 2 * keys].reshape(b, s, key_heads, dk))
    v = qkv[..., 2 * keys :].reshape(b, s, key_heads, group, dv)
    beta = jax.nn.sigmoid(_dot(x, p["w_b"], dtype))
    step = jax.nn.softplus(_dot(x, p["w_a"], dtype) + p["dt_bias"].astype(jnp.float32))
    g = -jnp.exp(p["A_log"].astype(jnp.float32)) * step  # (b, s, value heads)

    def heads_first(y):  # (b, s, key heads, ...) -> (b x key heads, s, ...)
        y = jnp.moveaxis(y.reshape(b, s, key_heads, *y.shape[3:]), 2, 1)
        return y.reshape(b * key_heads, s, *y.shape[3:])

    # A key head is a 128-lane block of q and k, a value head one of
    # qkv where the convolution wrote it: the kernel reads them there,
    # and nothing is moved heads-first or back.
    kernel = delta_net_path(cfg, x, dtype) == "kernel"
    with jax.named_scope("net/trunk/gated_deltanet/scan"):
        if kernel:
            # v's heads start on a whole block of qkv unless dv does not
            # divide 2 x keys (no published width): then v is sliced out
            whole = 2 * keys % dv == 0
            o = gated_delta_net(
                q.reshape(b, s, keys), k.reshape(b, s, keys),
                qkv if whole else qkv[..., 2 * keys :], g, beta,
                key_heads=key_heads, value_dim=dv, chunk=cfg.linear_chunk,
                dtype=jnp.dtype(dtype), v_first=2 * keys if whole else 0,
            )
        else:
            o = delta_rule.chunked_scalar(
                heads_first(q), heads_first(k), heads_first(v),
                heads_first(g.reshape(b, s, key_heads, group)),
                heads_first(beta.reshape(b, s, key_heads, group)),
                cfg.linear_chunk, dtype,
            )
    if kernel:
        o = o.reshape(b, s, key_heads, group, dv)
    else:
        o = jnp.moveaxis(o.reshape(b, key_heads, s, group, dv), 1, 2)  # float32
    scale = cfg.linear_sigmoid_gate_scale
    gate = zero_centred(_dot(x, p["w_z"], dtype), scale)
    o = rms_norm(o, zero_centred(p["o_norm"], scale), cfg.rms_norm_eps)
    o = o.reshape(b, s, values) * gate
    return _dot(o, p["wo"], dtype).astype(dtype)


def _limited(h: Array, limit: float | None, both: bool) -> Array:
    """A SwiGLU's product h clamped at `swiglu_limit` where it is set:
    from above (the gate's), or `both` ways (the up projection's)."""
    if limit is None:
        return h
    return jnp.clip(h, -limit if both else None, limit)


def swiglu(x: Array, gate: Array, up: Array, down: Array, dtype,
           limit: float | None = None) -> Array:
    hidden = jax.nn.silu(_limited(_dot(x, gate, dtype), limit, False)) * _limited(
        _dot(x, up, dtype), limit, True
    )
    return _dot(hidden.astype(dtype), down, dtype)


def relu2(x: Array) -> Array:
    return jnp.square(jax.nn.relu(x))


def shared_expert(p: dict, x: Array, cfg: TrunkConfig, dtype) -> Array:
    if cfg.mlp_hidden_act == "silu":
        return swiglu(x, p["s_gate"], p["s_up"], p["s_down"], dtype, cfg.swiglu_limit)
    return _dot(relu2(_dot(x, p["s_up"], dtype)).astype(dtype), p["s_down"], dtype)


def among_groups(biased: Array, groups: int, stay: int) -> Array:
    """`biased` (T, E) with -inf on every expert outside the `stay`
    groups (of `groups`, E / groups experts each, in order) whose two
    highest entries sum highest; of groups that tie the first stays."""
    t, e = biased.shape
    best_two, _ = jax.lax.top_k(biased.reshape(t, groups, e // groups), 2)
    _, kept = jax.lax.top_k(best_two.sum(axis=-1), stay)  # (T, stay)
    stays = jnp.zeros((t, groups), bool).at[jnp.arange(t)[:, None], kept].set(True)
    return jnp.where(jnp.repeat(stays, e // groups, axis=1), biased, -jnp.inf)


def route(p: dict, x: Array, cfg: TrunkConfig, dtype):
    """x (T, d) -> chosen experts (T, k) int32 and their weights (T, k).
    A selection bias (`router_bias`) moves the choice, not the weights;
    with `n_group` > 1 the choice is among the groups that stay."""
    scores = jax.nn.sigmoid(_dot(x, p["w_router"], dtype))
    biased = scores
    if cfg.router_bias:
        biased = scores + p["router_bias"].astype(jnp.float32)
    if cfg.n_group > 1:
        biased = among_groups(biased, cfg.n_group, cfg.topk_group)
    _, chosen = jax.lax.top_k(biased, cfg.num_experts_per_tok)
    top = jnp.take_along_axis(scores, chosen, axis=-1)
    weight = cfg.routed_scaling_factor * top / top.sum(axis=-1, keepdims=True)
    return chosen.astype(jnp.int32), weight


def _ragged(x: Array, w: Array, sizes: Array, dtype) -> Array:
    return jax.lax.ragged_dot(
        x.astype(dtype), w.astype(dtype), sizes,
        preferred_element_type=jnp.float32,
    )


def ragged_experts(x: Array, sizes: Array, up: Array, down: Array,
                   gate: Array | None = None, *, limit: float | None = None,
                   dtype) -> Array:
    """ops/grouped_experts.py's round by `jax.lax.ragged_dot`: the held
    experts' MLP over x (rows, d), sorted by expert with `sizes` rows
    each, (rows, d) in `dtype`; a SwiGLU with `gate`, else relu^2."""
    if gate is not None:
        hidden = jax.nn.silu(
            _limited(_ragged(x, gate, sizes, dtype), limit, False)
        ) * _limited(_ragged(x, up, sizes, dtype), limit, True)
    else:
        hidden = relu2(_ragged(x, up, sizes, dtype))
    out = _ragged(hidden, down, sizes, dtype).astype(dtype)
    # Rows past the round's assignments hold whatever the product left
    # there: nought, so that nothing stray reaches the sum.
    return jnp.where(jnp.arange(x.shape[0])[:, None] < sizes.sum(), out, 0)


def round_rows(cfg: TrunkConfig, tokens: int) -> int:
    """Rows of a round of the expert layer over `tokens` tokens:
    `USUAL_ROOM` times what even routing would bring here, at most
    every assignment that can fall here."""
    k, held = cfg.num_experts_per_tok, cfg.experts_held[1]
    return min(
        tokens * min(k, held), -(-USUAL_ROOM * tokens * k * held // cfg.num_experts)
    )


def routed_path(cfg: TrunkConfig, x: Array, dtype, train: bool) -> str:
    """"kernel" (ops/grouped_experts.py) or "ragged"
    (`jax.lax.ragged_dot`) for the routed experts of a sparse layer on
    the tokens x (..., width), from what this trace can observe: no
    field of the config chooses."""
    return expert_path(
        partitioned=partitioned(x),
        backend=jax.default_backend(),
        train=train,
        rows=round_rows(cfg, math.prod(x.shape[:-1])),
        d=cfg.moe_latent_size or cfg.hidden_size,
        inter=cfg.moe_intermediate_size,
        gated=cfg.mlp_hidden_act == "silu",
        dtype=dtype,
    )


def routed_experts(p: dict, x: Array, chosen: Array, weight: Array,
                   cfg: TrunkConfig, dtype, train: bool = False,
                   remat: bool = False):
    """The held experts' part of the routed sum for tokens x (T, d),
    float32, and how many tokens each held expert computed (count,); d
    is what an expert reads and writes, the hidden size or the latent's.
    `mlp_hidden_act` says which MLP an expert is.

    The assignments that fall on held experts are sorted by expert and
    taken `rows` at a time (`round_rows`: `USUAL_ROOM` times what even
    routing would bring here) through the experts' MLP: one round as a
    rule, as many as the block's routing needs otherwise, each with the
    same buffers. Nothing is dropped. A round is ops/grouped_experts.py's
    two kernels where `routed_path` says so (an inference forward on one
    TPU chip): the products over only the row tiles its assignments
    fill, the activation in the first one's epilogue, hidden and output
    written once in `dtype`; else `jax.lax.ragged_dot`, a grouped
    product a matrix over all `rows`, with the activation and the casts
    as passes of their own.

    `train` says the call will be differentiated: the rows of a round
    past its assignments are then noughts going in as they are coming
    out, because the grouped products' transposes leave in such rows
    whatever they find (on the chip: stray values, which the gather's
    transpose would add into the tokens' gradients). With `remat` (under
    recomputation) a backward pass computes a round again and keeps no
    round's products for it: every round's would stand side by side
    otherwise, taken or not."""
    t, d = x.shape
    k = cfg.num_experts_per_tok
    first, held = cfg.experts_held
    local = chosen - first
    here = (local >= 0) & (local < held)
    group = jnp.where(here, local, held).reshape(-1)  # elsewhere sorts last
    order = jnp.argsort(group, stable=True)
    rank = jnp.argsort(order)  # where each assignment stands in `order`
    sizes = jnp.zeros((held + 1,), jnp.int32).at[group].add(1)[:held]
    ends = jnp.cumsum(sizes)
    share = jnp.where(here, weight, 0.0).reshape(-1)

    most = t * min(k, held)
    rows = round_rows(cfg, t)
    rounds = -(-most // rows)
    kernel = routed_path(cfg, x, dtype, train) == "kernel"
    gated = cfg.mlp_hidden_act == "silu"
    # The rounds read `rounds x rows` entries, the assignments held here
    # first. A share that holds fewer experts than a token chooses has
    # more entries than that (all past the held ones): nothing to pad.
    order = jnp.pad(order, (0, max(0, rounds * rows - order.shape[0])))

    def one_round(i, y):
        low = i * rows
        inside = jnp.clip(ends - low, 0, rows) - jnp.clip(
            ends - sizes - low, 0, rows
        )
        taken = jax.lax.dynamic_slice_in_dim(order, low, rows)
        xs = x[taken // k]
        if train:
            xs = jnp.where(jnp.arange(rows)[:, None] < inside.sum(), xs, 0)
        out = (grouped_experts if kernel else ragged_experts)(
            xs, inside, p["e_up"], p["e_down"], p["e_gate"] if gated else None,
            limit=cfg.swiglu_limit, dtype=jnp.dtype(dtype),
        )
        # Each assignment fetches its row of this round, if it has one.
        at = rank - low
        mine = (at >= 0) & (at < rows)
        picked = out[jnp.clip(at, 0, rows - 1)].reshape(t, k, d)
        return y + jnp.einsum(
            "tkd,tk->td",
            picked,
            jnp.where(mine, share, 0.0).reshape(t, k),
            preferred_element_type=jnp.float32,
        )

    def maybe(i, y):
        return jax.lax.cond(i * rows < ends[-1], one_round, lambda _, y: y, i, y)

    y = jnp.zeros((t, d), jnp.float32)
    if rounds == 1:
        return one_round(0, y), sizes
    if remat:
        maybe = jax.checkpoint(maybe)
    return jax.lax.fori_loop(0, rounds, maybe, y), sizes


def sparse_mlp(p: dict, x: Array, cfg: TrunkConfig, dtype):
    """-> (the layer's output, the assignments each held expert
    computed (count,))."""
    return sparse_mlp_counted(p, x, cfg, dtype)[:2]


def sparse_mlp_counted(p: dict, x: Array, cfg: TrunkConfig, dtype,
                       train=False, remat=False):
    """`sparse_mlp`, and third in a training forward (`train`) the
    assignments the router made to each of `num_experts` (E,) int32,
    else None."""
    b, s, d = x.shape
    flat = x.reshape(b * s, d)
    loads = None
    with jax.named_scope("net/trunk/router"):
        chosen, weight = route(p, flat, cfg, dtype)
        if train:
            loads = jnp.zeros((cfg.num_experts,), jnp.int32).at[
                chosen.reshape(-1)
            ].add(1)
    read = flat
    if cfg.moe_latent_size:
        with jax.named_scope("net/trunk/latent_proj"):
            read = _dot(flat, p["w_latent_down"], dtype).astype(dtype)
    with jax.named_scope("net/trunk/experts"):
        y, sizes = routed_experts(p, read, chosen, weight, cfg, dtype, train, remat)
    if cfg.moe_latent_size:
        with jax.named_scope("net/trunk/latent_proj"):
            y = _dot(y, p["w_latent_up"], dtype)
    if cfg.num_shared_experts:
        with jax.named_scope("net/trunk/shared_expert"):
            y = y + shared_expert(p, flat, cfg, dtype)
    return y.astype(dtype).reshape(b, s, d), sizes, loads


def _residual(f, p: dict, half: str, x: Array, cfg: TrunkConfig) -> Array:
    """x + the `half` ("attn" or "mlp") f under the layer's norms."""
    norm = p[half + "_norm"]
    if cfg.norm_position == "pre":
        return x + f(stack_norm(x, norm, cfg))
    if cfg.norm_position == "pre_post":
        return x + stack_norm(f(stack_norm(x, norm, cfg)), p[half + "_post_norm"], cfg)
    return x + stack_norm(f(x), norm, cfg)


MIXER_SCOPES = {
    "sliding_attention": "net/trunk/attn_window",
    "full_attention": "net/trunk/attn_full",
    "linear_attention": "net/trunk/linear_attn",
    "latent_attention": "net/trunk/latent_attn",
    "state_space": "net/trunk/state_space",
    "gated_deltanet": "net/trunk/gated_deltanet",
}


def attention_block(p: dict, x: Array, cfg: TrunkConfig, i: int, dtype) -> Array:
    """The layer's first half, its mixer, on x (b, s, d)."""
    kind = cfg.layer_types[i]
    if kind == "state_space":
        mixer = lambda y: state_space_mixer(p, y, cfg, dtype)  # noqa: E731
    elif kind == "linear_attention":
        mixer = lambda y: linear_attention(p, y, cfg, dtype)  # noqa: E731
    elif kind == "latent_attention":
        mixer = lambda y: latent_attention(p, y, cfg, dtype)  # noqa: E731
    elif kind == "gated_deltanet":
        mixer = lambda y: gated_deltanet(p, y, cfg, dtype)  # noqa: E731
    else:
        sliding = kind == "sliding_attention"
        mixer = lambda y: attention(p, y, cfg, sliding, dtype)  # noqa: E731
    with jax.named_scope(MIXER_SCOPES[kind]):
        return _residual(mixer, p, "attn", x, cfg)


def mlp_block(p: dict, x: Array, cfg: TrunkConfig, i: int, dtype,
              train=False, remat=False):
    """The layer's second half on x (b, s, d); the second value is a
    sparse layer's per-expert count, None on a dense layer, the third
    `sparse_mlp_counted`'s loads."""
    if cfg.mlp_layer_types[i] == "dense":
        with jax.named_scope("net/trunk/dense_mlp"):
            return _residual(
                lambda y: swiglu(
                    y, p["w_gate"], p["w_up"], p["w_down"], dtype, cfg.swiglu_limit
                ).astype(dtype),
                p,
                "mlp",
                x,
                cfg,
            ), None, None
    sizes = loads = None

    def mlp(y):
        nonlocal sizes, loads
        out, sizes, loads = sparse_mlp_counted(p, y, cfg, dtype, train, remat)
        return out

    return _residual(mlp, p, "mlp", x, cfg), sizes, loads


def decoder_layer(p: dict, x: Array, cfg: TrunkConfig, i: int, dtype,
                  train=False, remat=False):
    """Layer i's halves, those it has, on x (b, s, d); the second and
    third values are `mlp_block`'s (None without a sparse MLP)."""
    if cfg.layer_types[i] != "none":
        x = attention_block(p, x, cfg, i, dtype)
    if cfg.mlp_layer_types[i] == "none":
        return x, None, None
    return mlp_block(p, x, cfg, i, dtype, train, remat)


def layer_params(params: dict, i: int) -> dict:
    prefix = f"l{i}_"
    return {
        name[len(prefix):]: value
        for name, value in params.items()
        if name.startswith(prefix)
    }


def block_size(batch: int, block_boards: int | None) -> int:
    """The largest divisor of `batch` within `block_boards`."""
    if block_boards is None or batch <= block_boards:
        return batch
    size = block_boards
    while batch % size:
        size -= 1
    return size


def apply(params: dict, tokens: Array, cfg: TrunkConfig, dtype,
          train: bool = False, remat: bool = False):
    """tokens (B, S, d) through every layer and the final norm; also the
    assignments each held expert computed, (sparse layers, count) int32
    (a (0, count) array where no layer is sparse), and in a training
    forward (`train`: one that will be differentiated) those the
    routers made to every expert, (sparse layers, `num_experts`) int32,
    else None. `remat` puts each layer under `jax.checkpoint`: a
    backward pass then keeps a layer's input and computes the layer
    again."""
    x, counted, loaded = tokens, [], []
    for i in range(len(cfg.layer_types)):
        layer = functools.partial(
            decoder_layer, cfg=cfg, i=i, dtype=dtype, train=train, remat=remat,
        )
        if remat:
            layer = jax.checkpoint(layer)
        x, sizes, loads = layer(layer_params(params, i), x)
        if sizes is not None:
            counted.append(sizes)
            loaded.append(loads)
    x = stack_norm(x, params["norm"], cfg)
    loads = None
    if train:
        loads = jnp.stack(loaded) if loaded else jnp.zeros((0, cfg.num_experts), jnp.int32)
    if counted:
        return x, jnp.stack(counted), loads
    return x, jnp.zeros((0, cfg.experts_held[1]), jnp.int32), loads


def moved_router_biases(params: dict, loads: Array, cfg: TrunkConfig) -> dict:
    """`params` (the trunk's) with every sparse layer's selection bias
    one step on: bias_e + `router_bias_rate` x sign(mean(load) - load_e),
    `loads` (sparse layers, `num_experts`) being the assignments the
    layer's router made to each expert over the step's batch. The bias
    of an expert chosen less than its share rises, of one chosen more
    falls, by the same step whatever the gap; no gradient, no moment
    and no decay has a part in it."""
    out = dict(params)
    for row, i in enumerate(sparse_layers(cfg)):
        load = loads[row].astype(jnp.float32)
        name = f"l{i}_router_bias"
        out[name] = params[name] + jnp.float32(cfg.router_bias_rate) * jnp.sign(
            load.mean() - load
        )
    return out


# --- the module: parameters, then the functions above ----------------------


def _normal(fan_in: int):
    """N(0, 1/fan_in) drawn in float32 and stored in the parameter's type."""

    def init(key, shape, dtype):
        draw = jax.random.normal(key, shape, jnp.float32)
        return (draw / math.sqrt(fan_in)).astype(dtype)

    return init


_INITS = {
    0: nn.initializers.ones,
    -1: nn.initializers.zeros,
    "A_log": state_space.init_a_log,
    "D": state_space.init_skip,
    "dt_bias": state_space.init_dt_bias,
    "normal": _normal(1),
    "zero_centred": _normal(4),
}


class DecoderTrunk(nn.Module):
    config: TrunkConfig
    dtype: jnp.dtype
    param_dtype: jnp.dtype
    remat: bool = False  # in a training forward, each layer recomputed

    @nn.compact
    def __call__(self, tokens: Array, train: bool = False) -> Array:
        cfg = self.config
        params = {
            name: self.param(
                name,
                _INITS[fan_in] if fan_in in _INITS else _normal(fan_in),
                shape,
                # A selection bias settles ties between scores that
                # differ in the fourth decimal, and a state-space or
                # gated-delta-net head's decay is raised to the power of
                # a board's length: float32 whatever the rest, and so is
                # a zero-centred norm's weight.
                jnp.float32
                if fan_in == -1 or isinstance(fan_in, str)
                else self.param_dtype,
            )
            for name, (shape, fan_in) in param_shapes(cfg).items()
        }
        out, counts, loads = apply(
            params, tokens.astype(self.dtype), cfg, self.dtype,
            train=train, remat=self.remat and train,
        )
        # Read by the search and the learner, which make the collection
        # mutable; no-ops otherwise: the assignments computed here,
        # (sparse layers, held), all the assignments the router made,
        # and in a training forward those by expert (`expert_loads`).
        if not self.is_initializing():
            routed = tokens.shape[0] * tokens.shape[1] * (
                cfg.num_experts_per_tok * len(sparse_layers(cfg))
            )
            sown = [("expert_tokens", counts), ("routed", jnp.int32(routed))]
            if loads is not None:
                sown.append(("expert_loads", loads))
            # tokens x layers of the kind that a recurrence took
            for counter, kind in (
                ("linear_tokens", "linear_attention"), ("ssm_tokens", "state_space"),
                ("gdn_tokens", "gated_deltanet"),
            ):
                layers = cfg.layer_types.count(kind)
                if layers:
                    sown.append(
                        (counter,
                         jnp.int32(tokens.shape[0] * tokens.shape[1] * layers))
                    )
            for name, value in sown:
                self.sow(
                    "counters", name, value,
                    reduce_fn=lambda _, new: new, init_fn=lambda: None,
                )
        return out


def counters_of(state: dict) -> dict:
    """{"expert_tokens", "routed"}, "linear_tokens" where the stack has
    linear layers, "ssm_tokens" where it has state-space layers,
    "gdn_tokens" where it has gated-delta-net layers and
    "expert_loads" after a training forward, out of what
    `apply(..., mutable=["counters"])` returned beside the net's outputs."""
    (sown,) = state["counters"].values()
    return dict(sown)
