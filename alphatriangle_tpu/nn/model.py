"""Flax policy/value network (`AlphaTriangleNet` equivalent).

Capability parity with the reference PyTorch architecture
(`alphatriangle/nn/model.py:109-297`): conv trunk -> residual blocks ->
optional pre-norm TransformerEncoder over the flattened spatial sequence
(sinusoidal positional encoding) -> flatten -> concat `other_features`
-> shared FC -> policy-logit head + C51 distributional value head.

TPU-first redesign, not a translation:
- NHWC conv layout (grid arrives (B, C, H, W) for API parity and is
  transposed once on entry) so convs tile onto the MXU.
- bfloat16 compute / float32 params via `ModelConfig.COMPUTE_DTYPE`;
  logits are returned in float32.
- Stateless GroupNorm by default (`NORM_TYPE="group"`): BatchNorm's
  cross-example running statistics are hostile to dp-sharded pjit;
  "batch" is still supported for parity (uses a `batch_stats`
  collection and per-shard statistics).
- Optional `jax.checkpoint` rematerialization of the residual and
  transformer blocks (`ModelConfig.REMAT`) to trade FLOPs for HBM.
- The spatial sequence is H*W tokens; positional encodings are baked as
  a trace-time constant (reference: `nn/model.py:63-106`).
"""

import functools
from collections.abc import Callable

import jax
import jax.numpy as jnp
import numpy as np
from flax import linen as nn
from jax import Array

from ..config.model_config import ModelConfig
from ..ops.encoder_layer import encoder_layer, layer_path, partitioned
from ..telemetry.tracer import default_tracer
from .trunk import DecoderTrunk, recurrence_path, scan_path

_ACTIVATIONS: dict[str, Callable[[Array], Array]] = {
    "ReLU": nn.relu,
    "GELU": nn.gelu,
    "SiLU": nn.silu,
    "Tanh": jnp.tanh,
    "Sigmoid": nn.sigmoid,
}


def _group_count(features: int, preferred: int = 8) -> int:
    """Largest divisor of `features` that is <= preferred."""
    g = min(preferred, features)
    while features % g != 0:
        g -= 1
    return g


def sinusoidal_positional_encoding(seq_len: int, dim: int) -> np.ndarray:
    """(seq_len, dim) float32 sin/cos table (reference `nn/model.py:63-88`)."""
    position = np.arange(seq_len, dtype=np.float32)[:, None]
    div_term = np.exp(
        np.arange(0, dim, 2, dtype=np.float32) * (-np.log(10000.0) / dim)
    )
    pe = np.zeros((seq_len, dim), dtype=np.float32)
    pe[:, 0::2] = np.sin(position * div_term)
    pe[:, 1::2] = np.cos(position * div_term[: pe[:, 1::2].shape[1]])
    return pe


class _Norm(nn.Module):
    """Norm layer selected by `ModelConfig.NORM_TYPE`."""

    norm_type: str
    dtype: jnp.dtype
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        kw = {"dtype": self.dtype, "param_dtype": self.param_dtype}
        if self.norm_type == "group":
            return nn.GroupNorm(num_groups=_group_count(x.shape[-1]), **kw)(x)
        if self.norm_type == "layer":
            return nn.LayerNorm(**kw)(x)
        if self.norm_type == "batch":
            return nn.BatchNorm(
                use_running_average=not train, axis_name=None, **kw
            )(x)
        return x  # "none"


class ConvBlock(nn.Module):
    """Conv -> norm -> activation (reference `conv_block`, model.py:15-38)."""

    features: int
    kernel: int
    stride: int
    norm_type: str
    act: Callable[[Array], Array]
    dtype: jnp.dtype
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        x = nn.Conv(
            self.features,
            (self.kernel, self.kernel),
            strides=(self.stride, self.stride),
            padding="SAME",
            dtype=self.dtype,
            param_dtype=self.param_dtype,
        )(x)
        x = _Norm(self.norm_type, self.dtype, self.param_dtype)(x, train)
        return self.act(x)


class ResidualBlock(nn.Module):
    """Two 3x3 convs with skip connection (reference model.py:41-60)."""

    features: int
    norm_type: str
    act: Callable[[Array], Array]
    dtype: jnp.dtype
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        kw = {"dtype": self.dtype, "param_dtype": self.param_dtype}
        residual = x
        x = nn.Conv(self.features, (3, 3), padding="SAME", **kw)(x)
        x = _Norm(self.norm_type, **kw)(x, train)
        x = self.act(x)
        x = nn.Conv(self.features, (3, 3), padding="SAME", **kw)(x)
        x = _Norm(self.norm_type, **kw)(x, train)
        return self.act(x + residual)


def _in_attention_phase(attention_fn: Callable) -> Callable:
    """`attention_fn` under the phase name `net/encoder/attention`.
    Flax forwards only the keywords the function's signature names:
    `wraps` lends the wrapper `attention_fn`'s."""

    @functools.wraps(attention_fn)
    def attend(*args, **kwargs):
        with jax.named_scope("net/encoder/attention"):
            return attention_fn(*args, **kwargs)

    return attend


class TransformerEncoderLayer(nn.Module):
    """Pre-norm encoder layer (reference model.py:179-202, norm_first=True).

    `attention_fn` swaps the dense attention kernel for a
    sequence-parallel one (`parallel/ring_attention.make_sp_attention`);
    attention-weight dropout is disabled in that case (blockwise
    kernels don't support it) — the residual dropouts still apply.
    Either runs inside the phase `net/encoder/attention`.

    `fused`: the whole layer as `ops/encoder_layer.py`'s kernel, on the
    variables the modules below declare (so `init` runs the modules and
    the tree is one on both paths). `AlphaTriangleNet` sets it where
    `layer_path` says so; it is an inference path, `train` is not read.
    """

    dim: int
    heads: int
    mlp_dim: int
    act: Callable[[Array], Array]
    dtype: jnp.dtype
    dropout_rate: float = 0.1
    attention_fn: Callable | None = None
    param_dtype: jnp.dtype = jnp.float32
    fused: bool = False

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        if self.fused:
            return encoder_layer(
                x, self.variables["params"], heads=self.heads, act=self.act
            )
        kw = {"dtype": self.dtype, "param_dtype": self.param_dtype}
        y = nn.LayerNorm(**kw)(x)
        y = nn.MultiHeadDotProductAttention(
            num_heads=self.heads,
            **kw,
            dropout_rate=(
                0.0 if self.attention_fn is not None else self.dropout_rate
            ),
            deterministic=not train,
            attention_fn=_in_attention_phase(
                self.attention_fn or nn.dot_product_attention
            ),
        )(y, y)
        x = x + nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        y = nn.LayerNorm(**kw)(x)
        y = nn.Dense(self.mlp_dim, **kw)(y)
        y = self.act(y)
        y = nn.Dropout(self.dropout_rate, deterministic=not train)(y)
        y = nn.Dense(self.dim, **kw)(y)
        return x + nn.Dropout(self.dropout_rate, deterministic=not train)(y)


class MLPHead(nn.Module):
    """Dense stack with norm/act, then a linear output layer."""

    hidden_dims: tuple[int, ...]
    out_dim: int
    norm_type: str
    act: Callable[[Array], Array]
    dtype: jnp.dtype
    param_dtype: jnp.dtype = jnp.float32

    @nn.compact
    def __call__(self, x: Array, train: bool) -> Array:
        for h in self.hidden_dims:
            x = nn.Dense(h, dtype=self.dtype, param_dtype=self.param_dtype)(x)
            x = _Norm(self.norm_type, self.dtype, self.param_dtype)(x, train)
            x = self.act(x)
        # Output layer in float32 for stable softmax/loss.
        return nn.Dense(
            self.out_dim, dtype=jnp.float32, param_dtype=self.param_dtype
        )(x)


class AlphaTriangleNet(nn.Module):
    """Policy + C51 value network over (grid, other_features).

    `attention_fn`: optional sequence-parallel attention kernel for the
    transformer stack (see `parallel/ring_attention.make_sp_attention`);
    None = dense single-device attention.
    """

    config: ModelConfig
    action_dim: int
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(
        self, grid: Array, other_features: Array, train: bool = False
    ) -> tuple[Array, Array]:
        """(B, C, H, W) grid + (B, F) extras -> (B, A) policy logits,
        (B, NUM_VALUE_ATOMS) value-distribution logits (both float32)."""
        cfg = self.config
        dtype = jnp.dtype(cfg.COMPUTE_DTYPE)
        pdtype = jnp.dtype(cfg.PARAM_DTYPE)
        act = _ACTIVATIONS[cfg.ACTIVATION_FUNCTION]

        # The four named scopes are the phases of `telemetry/phases.py`:
        # a device trace's time divides by them (flax's own module
        # scopes sit inside).
        with jax.named_scope("net/conv"):
            x = jnp.transpose(grid, (0, 2, 3, 1)).astype(dtype)  # NCHW -> NHWC

            for f, k, s in zip(
                cfg.CONV_FILTERS,
                cfg.CONV_KERNEL_SIZES,
                cfg.CONV_STRIDES,
                strict=True,
            ):
                x = ConvBlock(f, k, s, cfg.NORM_TYPE, act, dtype, pdtype)(x, train)

        if cfg.NUM_RESIDUAL_BLOCKS > 0:
            with jax.named_scope("net/residual"):
                if x.shape[-1] != cfg.RESIDUAL_BLOCK_FILTERS:
                    x = ConvBlock(
                        cfg.RESIDUAL_BLOCK_FILTERS,
                        1,
                        1,
                        cfg.NORM_TYPE,
                        act,
                        dtype,
                        pdtype,
                    )(x, train)
                block = ResidualBlock
                if cfg.REMAT:
                    block = nn.remat(ResidualBlock, static_argnums=(2,))
                for i in range(cfg.NUM_RESIDUAL_BLOCKS):
                    # Recomputed or not, a block's variables keep its name.
                    named = {"name": f"ResidualBlock_{i}"} if cfg.REMAT else {}
                    x = block(
                        cfg.RESIDUAL_BLOCK_FILTERS, cfg.NORM_TYPE, act, dtype, pdtype,
                        **named,
                    )(x, train)

        if cfg.TRUNK is not None:
            # The decoder stack in the encoder's place (nn/trunk.py): the
            # stem's 1x1 projection stands where a language model has its
            # embedding; the cell's row-major index is its position, so
            # no sinusoidal table is added.
            with jax.named_scope("net/trunk"):
                d = cfg.TRUNK.hidden_size
                if x.shape[-1] != d:
                    x = nn.Conv(d, (1, 1), dtype=dtype, param_dtype=pdtype)(x)
                b, h, w, _ = x.shape
                tokens = DecoderTrunk(cfg.TRUNK, dtype, pdtype, remat=cfg.REMAT)(
                    x.reshape(b, h * w, d), train
                )
                flat = tokens.reshape(b, -1)
                # Once each time the net is traced into a program. The
                # linear layers are alike, so they took one path: the one
                # `nn/trunk.py` read from the same tokens; so did the
                # state-space layers.
                kinds = cfg.TRUNK.layer_types
                taken = {"kernel": 0, "chunked": 0}
                if "linear_attention" in kinds:
                    taken[recurrence_path(cfg.TRUNK, tokens, dtype)] = kinds.count(
                        "linear_attention"
                    )
                default_tracer().instant(
                    "net.trunk",
                    # the layers by mixer; one that has none is not one
                    **{
                        kind: kinds.count(kind)
                        for kind in sorted(set(kinds) - {"none"})
                    },
                    linear_path=taken,
                    linear_chunk=cfg.TRUNK.linear_chunk,
                    # tokens a state-space layer's scan takes at a time
                    # and the path the scans took (a stack with such
                    # layers only), the experts' latent (None: the hidden
                    # size) and which MLP they are
                    **(
                        {
                            "ssm_chunk": cfg.TRUNK.chunk_size,
                            "ssm_path": {
                                "kernel": 0, "chunked": 0,
                                scan_path(cfg.TRUNK, tokens, dtype): kinds.count(
                                    "state_space"
                                ),
                            },
                        }
                        if "state_space" in kinds
                        else {}
                    ),
                    moe_latent_size=cfg.TRUNK.moe_latent_size,
                    mlp_hidden_act=cfg.TRUNK.mlp_hidden_act,
                    block_boards=cfg.TRUNK.block_boards,
                    # latent layers whose query comes from its own latent
                    latent_q_compressed=(
                        kinds.count("latent_attention")
                        if cfg.TRUNK.q_lora_rank
                        else 0
                    ),
                    learner_block_boards=cfg.TRUNK.learner_block_boards,
                    remat_layers=len(kinds) if cfg.REMAT and train else 0,
                    batch=b,
                    seq=h * w,
                )
        elif cfg.USE_TRANSFORMER and cfg.TRANSFORMER_LAYERS > 0:
            with jax.named_scope("net/encoder"):
                if x.shape[-1] != cfg.TRANSFORMER_DIM:
                    x = nn.Conv(
                        cfg.TRANSFORMER_DIM, (1, 1), dtype=dtype, param_dtype=pdtype
                    )(x)
                b, h, w, d = x.shape
                tokens = x.reshape(b, h * w, d)
                pe = jnp.asarray(
                    sinusoidal_positional_encoding(h * w, d), dtype=dtype
                )
                tokens = tokens + pe[None, :, :]
                layer = TransformerEncoderLayer
                if cfg.REMAT:
                    layer = nn.remat(TransformerEncoderLayer, static_argnums=(2,))
                # An inference call on a TPU runs each layer as one
                # kernel (its values kept in VMEM), every other call
                # Flax's modules, which also declare the variables: one
                # choice for all layers, from what this trace can observe.
                fused = "fused" == layer_path(
                    initializing=self.is_initializing(),
                    train=train,
                    handed_in=self.attention_fn is not None,
                    masked=False,  # the layer attends to every cell
                    partitioned=partitioned(tokens),
                    backend=jax.default_backend(),
                    dtype=dtype,
                    seq=h * w,
                    heads=cfg.TRANSFORMER_HEADS,
                    head_dim=d // cfg.TRANSFORMER_HEADS,
                    mlp_dim=cfg.TRANSFORMER_FC_DIM,
                )
                for i in range(cfg.TRANSFORMER_LAYERS):
                    named = (
                        {"name": f"TransformerEncoderLayer_{i}"} if cfg.REMAT else {}
                    )
                    tokens = layer(
                        cfg.TRANSFORMER_DIM,
                        cfg.TRANSFORMER_HEADS,
                        cfg.TRANSFORMER_FC_DIM,
                        act,
                        dtype,
                        attention_fn=self.attention_fn,
                        param_dtype=pdtype,
                        fused=fused,
                        **named,
                    )(tokens, train)
                # Once each time the net is traced into a program (a
                # reloaded executable is not traced again).
                default_tracer().instant(
                    "net.encoder",
                    fused_layers=cfg.TRANSFORMER_LAYERS if fused else 0,
                    flax_layers=0 if fused else cfg.TRANSFORMER_LAYERS,
                    batch=b,
                    seq=h * w,
                )
                tokens = nn.LayerNorm(dtype=dtype, param_dtype=pdtype)(tokens)
                flat = tokens.reshape(b, -1)
        else:
            flat = x.reshape(x.shape[0], -1)

        with jax.named_scope("net/heads"):
            combined = jnp.concatenate(
                [flat, other_features.astype(dtype)], axis=-1
            )

            shared = combined
            for hdim in cfg.FC_DIMS_SHARED:
                shared = nn.Dense(hdim, dtype=dtype, param_dtype=pdtype)(shared)
                shared = _Norm(cfg.NORM_TYPE, dtype, pdtype)(shared, train)
                shared = act(shared)

            policy_logits = MLPHead(
                tuple(cfg.POLICY_HEAD_DIMS),
                self.action_dim,
                cfg.NORM_TYPE,
                act,
                dtype,
                pdtype,
            )(shared, train)
            value_logits = MLPHead(
                tuple(cfg.VALUE_HEAD_DIMS),
                cfg.NUM_VALUE_ATOMS,
                cfg.NORM_TYPE,
                act,
                dtype,
                pdtype,
            )(shared, train)
        return policy_logits.astype(jnp.float32), value_logits.astype(jnp.float32)


def value_support(cfg: ModelConfig) -> Array:
    """(NUM_VALUE_ATOMS,) float32 C51 atom support z_i."""
    return jnp.linspace(
        cfg.VALUE_MIN, cfg.VALUE_MAX, cfg.NUM_VALUE_ATOMS, dtype=jnp.float32
    )


def expected_value_from_logits(value_logits: Array, support: Array) -> Array:
    """(..., atoms) logits -> (...,) expected scalar value sum(p_i * z_i)."""
    probs = nn.softmax(value_logits, axis=-1)
    return jnp.sum(probs * support, axis=-1)


def count_parameters(params) -> int:
    """Total scalar parameter count of a params pytree."""
    return sum(int(np.prod(p.shape)) for p in jax.tree_util.tree_leaves(params))
