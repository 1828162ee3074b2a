"""The plain reference: the net, its loss and its optimizer step.

Straightforward `jax.numpy` in float32 at `highest` matmul precision.
It imports nothing of the program. It reads the sizes from the
configuration's file (`chipbench/configs/<name>.json`) and the weights
the benchmark made from the seed; it makes the rows and the dropout
masks itself.

Follows the architecture `nn/model.py` describes (conv trunk ->
residual blocks -> optional pre-norm transformer over the H*W tokens ->
flatten + other features -> shared FC -> policy head + C51 value head),
and the loss and AdamW step `rl/trainer.py` describes. Departures from
a textbook description, all the program's own and stated in its code:

- GroupNorm with the largest group count <= 8 dividing the width,
  epsilon 1e-6, also on the (B, C) outputs of the head FCs.
- Dropout 0.1 in training on the attention weights (one mask per
  (query, key) pair, shared by batch and heads) and three times per
  encoder layer; the masks come from the step's key by Flax's rule
  (`dropout_key`), which a test holds against Flax itself.
- The entropy bonus is the plain mean over rows, not weighted by the
  importance weights; TD errors are the value cross-entropies.

`quant` rounds both operands of every matmul and convolution to a
narrower type and back: the control of `correct` (fp8 for a bfloat16
configuration). The reference itself passes `None`.
"""

import hashlib
import math

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST


# --- layers ---------------------------------------------------------------


def _q(x, quant):
    return x if quant is None else quant(x)


def fp8(x):
    """Round to float8 e4m3 and back (the control's precision). The
    gradient passes straight through the rounding: a backward pass cast
    to fp8 without scaling underflows to nought, which no one would
    ship and any limit would catch."""
    rounded = x.astype(jnp.float8_e4m3fn).astype(jnp.float32)
    return x + jax.lax.stop_gradient(rounded - x)


def bf16(x):
    """Round to bfloat16 and back: the precision the configurations
    state for the net's compute. Not a control but a second witness: a
    sound computation that differs from float32 by rounding alone."""
    rounded = x.astype(jnp.bfloat16).astype(jnp.float32)
    return x + jax.lax.stop_gradient(rounded - x)


def dense(p, x, quant):
    return (
        jnp.matmul(_q(x, quant), _q(p["kernel"], quant), precision=HIGHEST)
        + p["bias"]
    )


def conv(p, x, quant):
    """SAME-padded stride-1 convolution, NHWC x HWIO."""
    return (
        jax.lax.conv_general_dilated(
            _q(x, quant),
            _q(p["kernel"], quant),
            (1, 1),
            "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=HIGHEST,
        )
        + p["bias"]
    )


def group_count(features: int, preferred: int = 8) -> int:
    g = min(preferred, features)
    while features % g:
        g -= 1
    return g


def group_norm(p, x, eps=1e-6):
    """Per example, over the spatial axes and the channels of a group."""
    c = x.shape[-1]
    g = group_count(c)
    xg = x.reshape(x.shape[0], -1, g, c // g)
    mean = xg.mean(axis=(1, 3), keepdims=True)
    var = ((xg - mean) ** 2).mean(axis=(1, 3), keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    return y * p["scale"] + p["bias"]


def layer_norm(p, x, eps=1e-6):
    mean = x.mean(axis=-1, keepdims=True)
    var = ((x - mean) ** 2).mean(axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["scale"] + p["bias"]


def positional_encoding(seq_len: int, dim: int) -> np.ndarray:
    pos = np.arange(seq_len, dtype=np.float32)[:, None]
    div = np.exp(
        np.arange(0, dim, 2, dtype=np.float32) * (-math.log(10000.0) / dim)
    )
    pe = np.zeros((seq_len, dim), np.float32)
    pe[:, 0::2] = np.sin(pos * div)
    pe[:, 1::2] = np.cos(pos * div[: pe[:, 1::2].shape[1]])
    return pe


def dropout_key(step_key, *path):
    """The key Flax gives the module at `path` for its first draw:
    the step's key folded with the first four bytes of the SHA-1 of the
    path's names and the draw's number (1)."""
    m = hashlib.sha1()
    for part in (*path, 1):
        if isinstance(part, str):
            m.update(part.encode("utf-8"))
        else:
            m.update(part.to_bytes((part.bit_length() + 7) // 8, "big"))
    word = int.from_bytes(m.digest()[:4], "big")
    return jax.random.fold_in(step_key, jnp.uint32(word))


def _dropout(x, key, rate):
    if key is None:
        return x
    keep = jax.random.bernoulli(key, 1.0 - rate, x.shape)
    return jnp.where(keep, x / (1.0 - rate), 0.0)


def encoder_layer(p, x, heads, name, step_key, rate, quant):
    """Pre-norm encoder layer; `step_key` None means no dropout."""

    def key(*sub):
        return None if step_key is None else dropout_key(step_key, name, *sub)

    att = p["MultiHeadDotProductAttention_0"]
    y = layer_norm(p["LayerNorm_0"], x)

    def proj(w):
        return (
            jnp.einsum(
                "bsd,dhk->bshk",
                _q(y, quant),
                _q(w["kernel"], quant),
                precision=HIGHEST,
            )
            + w["bias"]
        )

    q, k, v = proj(att["query"]), proj(att["key"]), proj(att["value"])
    q = q / math.sqrt(q.shape[-1])
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", _q(q, quant), _q(k, quant), precision=HIGHEST
    )
    weights = jax.nn.softmax(scores, axis=-1)
    akey = key("MultiHeadDotProductAttention_0")
    if akey is not None:
        keep = jax.random.bernoulli(
            akey, 1.0 - rate, (1, 1, *weights.shape[-2:])
        )
        weights = weights * (keep.astype(jnp.float32) / (1.0 - rate))
    ctx = jnp.einsum(
        "bhqk,bkhd->bqhd", _q(weights, quant), _q(v, quant), precision=HIGHEST
    )
    y = (
        jnp.einsum(
            "bqhd,hdm->bqm",
            _q(ctx, quant),
            _q(att["out"]["kernel"], quant),
            precision=HIGHEST,
        )
        + att["out"]["bias"]
    )
    x = x + _dropout(y, key("Dropout_0"), rate)
    y = layer_norm(p["LayerNorm_1"], x)
    y = jax.nn.relu(dense(p["Dense_0"], y, quant))
    y = _dropout(y, key("Dropout_1"), rate)
    y = dense(p["Dense_1"], y, quant)
    return x + _dropout(y, key("Dropout_2"), rate)


def head(p, x, quant):
    x = dense(p["Dense_0"], x, quant)
    x = jax.nn.relu(group_norm(p["_Norm_0"]["GroupNorm_0"], x))
    return dense(p["Dense_1"], x, quant)


def forward(params, model, grid, other, step_key=None, quant=None):
    """(B, C, H, W) grid + (B, F) other -> policy logits (B, A) and
    value-distribution logits (B, atoms). `model` is the configuration
    file's `model` group; `step_key` turns training dropout on."""
    x = jnp.transpose(grid.astype(jnp.float32), (0, 2, 3, 1))
    for i in range(len(model["CONV_FILTERS"])):
        p = params[f"ConvBlock_{i}"]
        x = conv(p["Conv_0"], x, quant)
        x = jax.nn.relu(group_norm(p["_Norm_0"]["GroupNorm_0"], x))
    for i in range(model["NUM_RESIDUAL_BLOCKS"]):
        p = params[f"ResidualBlock_{i}"]
        y = conv(p["Conv_0"], x, quant)
        y = jax.nn.relu(group_norm(p["_Norm_0"]["GroupNorm_0"], y))
        y = conv(p["Conv_1"], y, quant)
        y = group_norm(p["_Norm_1"]["GroupNorm_0"], y)
        x = jax.nn.relu(x + y)
    b = x.shape[0]
    if model["USE_TRANSFORMER"] and model["TRANSFORMER_LAYERS"] > 0:
        tokens = x.reshape(b, -1, x.shape[-1])
        tokens = tokens + positional_encoding(*tokens.shape[1:])[None]
        for i in range(model["TRANSFORMER_LAYERS"]):
            name = f"TransformerEncoderLayer_{i}"
            tokens = encoder_layer(
                params[name],
                tokens,
                model["TRANSFORMER_HEADS"],
                name,
                step_key,
                model["DROPOUT_RATE"],
                quant,
            )
        x = layer_norm(params["LayerNorm_0"], tokens)
    flat = jnp.concatenate(
        [x.reshape(b, -1), other.astype(jnp.float32)], axis=-1
    )
    shared = dense(params["Dense_0"], flat, quant)
    shared = jax.nn.relu(group_norm(params["_Norm_0"]["GroupNorm_0"], shared))
    return (
        head(params["MLPHead_0"], shared, quant),
        head(params["MLPHead_1"], shared, quant),
    )


# --- loss and optimizer ---------------------------------------------------


def two_hot(returns, atoms, v_min, v_max):
    """(B,) returns -> (B, atoms): the delta at each return, projected
    on the fixed support."""
    b = (jnp.clip(returns, v_min, v_max) - v_min) / ((v_max - v_min) / (atoms - 1))
    lo, hi = jnp.floor(b), jnp.ceil(b)
    w_lo = jnp.where(lo == hi, 1.0, hi - b)
    w_hi = jnp.where(lo == hi, 0.0, b - lo)
    grid = jnp.arange(atoms)[None, :]
    return (grid == lo[:, None]) * w_lo[:, None] + (grid == hi[:, None]) * w_hi[
        :, None
    ]


def loss(params, cfg, batch, step_key, quant=None):
    """Total loss and the per-row TD errors of one batch."""
    model, train = cfg["model"], cfg["train"]
    policy_logits, value_logits = forward(
        params, model, batch["grid"], batch["other"], step_key, quant
    )
    log_policy = jax.nn.log_softmax(policy_logits, axis=-1)
    pw = batch["pw"]
    policy_ce = pw * -(batch["policy"] * log_policy).sum(axis=-1)
    target = two_hot(
        batch["ret"], model["NUM_VALUE_ATOMS"], model["VALUE_MIN"], model["VALUE_MAX"]
    )
    value_ce = -(target * jax.nn.log_softmax(value_logits, axis=-1)).sum(axis=-1)
    entropy = (pw * -(jnp.exp(log_policy) * log_policy).sum(axis=-1)).mean()
    rows = train["POLICY_LOSS_WEIGHT"] * policy_ce + train["VALUE_LOSS_WEIGHT"] * value_ce
    total = (batch["weights"] * rows).mean() - train["ENTROPY_BONUS_WEIGHT"] * entropy
    return total, value_ce


def leaf_norms(tree) -> np.ndarray:
    """The norm of each leaf, on the host (one fetch of the tree)."""
    return np.asarray(
        [
            np.sqrt((np.asarray(x, np.float64) ** 2).sum())
            for x in jax.tree_util.tree_leaves(jax.device_get(tree))
        ]
    )


def global_norm(tree):
    return jnp.sqrt(
        sum((x**2).sum() for x in jax.tree_util.tree_leaves(tree))
    )


def learning_rate(train, count):
    """Cosine decay from LEARNING_RATE to LR_SCHEDULER_ETA_MIN over
    LR_SCHEDULER_T_MAX steps, read at the count before the step."""
    t_max = train["LR_SCHEDULER_T_MAX"]
    frac = jnp.minimum(count, t_max) / t_max
    cosine = 0.5 * (1.0 + jnp.cos(jnp.pi * frac))
    alpha = train["LR_SCHEDULER_ETA_MIN"] / train["LEARNING_RATE"]
    return train["LEARNING_RATE"] * ((1.0 - alpha) * cosine + alpha)


def train_step(state, cfg, batch, quant=None):
    """One clipped AdamW step. `state` is (params, mu, nu, count, key);
    returns the new state, the loss, the gradient's global norm before
    clipping, and the TD errors."""
    params, mu, nu, count, key = state
    train, opt = cfg["train"], cfg["optimizer"]
    key, step_key = jax.random.split(key)
    if not cfg["model"]["USE_TRANSFORMER"]:
        step_key = None  # nothing draws
    (total, td), grads = jax.value_and_grad(loss, has_aux=True)(
        params, cfg, batch, step_key, quant
    )
    norm = global_norm(grads)
    clip = train["GRADIENT_CLIP_VALUE"]
    grads = jax.tree_util.tree_map(
        lambda g: jnp.where(norm < clip, g, g / norm * clip), grads
    )
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    t = count + 1
    mu = jax.tree_util.tree_map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree_util.tree_map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    lr = learning_rate(train, count)

    def apply(p, m, v):
        update = (m / (1 - b1**t)) / (jnp.sqrt(v / (1 - b2**t)) + eps)
        return p - lr * (update + train["WEIGHT_DECAY"] * p)

    params = jax.tree_util.tree_map(apply, params, mu, nu)
    return (params, mu, nu, t, key), total, norm, td


def init_state(params, cfg):
    zeros = jax.tree_util.tree_map(jnp.zeros_like, params)
    return (
        params,
        zeros,
        zeros,
        jnp.float32(0.0),
        jax.random.PRNGKey(cfg["train"]["RANDOM_SEED"]),
    )
