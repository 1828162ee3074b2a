"""Inference precision policy: bf16 / int8 params for rollout + serve.

`ModelConfig.INFERENCE_PRECISION` selects the representation the
INFERENCE family (self-play chunk programs, `serve/b<B>` dispatch,
arena/eval through the service) reads the network parameters at. The
learner family is excluded by construction: the trainer holds and
updates the f32 `TrainState`, and the fused megastep casts a reduced
copy of the params for its in-program rollout phase while the
learner-step phase keeps consuming the f32 originals.

What the reduced paths cover and what stays f32 (docs/KERNELS.md
"Precision policy"): the cast applies to floating-point
param/batch-stats leaves only. PER priorities, the cumsum the sampler
searches, value targets, IS weights, optimizer state and gradients are
untouched — priority ratios and learner math are precision-sensitive in
ways an Elo-neutral forward pass is not (KataGo, arXiv:1902.10565,
ships reduced-precision *inference* while training full-precision for
exactly this reason). The model's value/policy heads already compute
their final Dense in f32 (nn/model.py MLPHead), so logits keep f32
dynamic range even under a bf16 trunk.

The int8 path is WEIGHT-ONLY quantization with per-channel symmetric
absmax calibration: every floating matrix leaf (ndim >= 2) is replaced
by a `{"q": int8, "scale": f32}` marker dict where `scale` is the
absmax over all axes except the last (the output-channel axis of Dense
kernels and the feature axis of conv kernels) divided by 127, and
`q = round(x / scale)` clipped to [-127, 127]. Vector leaves (biases,
norm gains/offsets) carry negligible bytes and quantization-sensitive
semantics, so they cast to bf16 like the bf16 path. The forward trunk
dequantizes to bf16 at its single evaluation choke point
(`BatchedMCTS._evaluate`, `NeuralNetwork._apply_eval`), so activations
and heads follow the exact bf16 policy and the strength gate for bf16
bounds int8's additional error on top of it.

Caching: callers thread the cast through the AOT compile-cache
signature for free — reduced param avals change leaf dtypes (and, for
int8, the tree structure) in the program signature, and
`config_digest(model_config)` (which includes INFERENCE_PRECISION) is
part of every inference family's `extra` tag, so f32 / bf16 / int8
programs cache as distinct entries with their own `.mem.json` sidecars.
Host-side consumers (`PolicyService._serve_variables`, the rollout
engine's `_inference_variables`) memoize the quantized tree per weights
version, so the program genuinely reads int8 tensors from HBM —
roughly a 4x param-bytes-read reduction against f32 (2x against bf16)
on every leaf-evaluation wave. The megastep calls
`cast_params_for_inference` inside its traced body, where the same
code becomes fake-quant (quantize + dequant fused by XLA) with
bit-identical numerics to the host-side path.
"""

import jax
import jax.numpy as jnp

from ..config.model_config import ModelConfig

# Marker-dict keys for one int8-quantized leaf. The dict is an ordinary
# pytree node, so quantized trees flow through jit/device_put/tree_map
# unchanged and their int8/f32 leaf avals key the compile cache.
_QUANT_KEYS = frozenset({"q", "scale"})

# Symmetric int8 range; scales are clamped so all-zero channels
# round-trip to exact zeros instead of dividing by zero.
_Q_MAX = 127.0
_SCALE_EPS = 1e-12


def inference_dtype(model_config: ModelConfig) -> jnp.dtype:
    """The dtype the inference trunk COMPUTES at: bf16 under both the
    bf16 cast and the int8 weight-only path (which dequantizes to
    bf16), f32 otherwise. `== jnp.float32` is the callers' "identity
    policy, skip the cast memo" test."""
    return jnp.dtype(
        jnp.bfloat16
        if model_config.INFERENCE_PRECISION in ("bfloat16", "int8")
        else jnp.float32
    )


def is_quantized_leaf(x) -> bool:
    """True for one `{"q", "scale"}` marker dict (an int8 leaf)."""
    return isinstance(x, dict) and set(x.keys()) == _QUANT_KEYS


def _quantize_leaf(x):
    """Per-channel symmetric absmax int8 for one matrix leaf.

    The channel axis is the LAST axis (Dense kernels are (in, out),
    conv kernels (kh, kw, in, out) — last is the output-feature axis
    in both), so each output channel gets its own scale and a single
    hot channel cannot crush the resolution of the rest.
    """
    xf = x.astype(jnp.float32)
    reduce_axes = tuple(range(x.ndim - 1))
    absmax = jnp.max(jnp.abs(xf), axis=reduce_axes, keepdims=True)
    scale = jnp.maximum(absmax / _Q_MAX, _SCALE_EPS)
    q = jnp.clip(jnp.round(xf / scale), -_Q_MAX, _Q_MAX).astype(jnp.int8)
    return {"q": q, "scale": scale}


def quantize_params_for_inference(variables):
    """Weight-only int8 quantization of a variables pytree: floating
    matrix leaves (ndim >= 2) become `{"q": int8, "scale": f32}`
    marker dicts; floating vector leaves cast to bf16; everything else
    passes through. `dequantize_params` inverts the representation."""

    def quant(x):
        if not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        if x.ndim >= 2:
            return _quantize_leaf(x)
        return x.astype(jnp.bfloat16)

    return jax.tree_util.tree_map(quant, variables)


def dequantize_params(variables):
    """Reconstitute a (possibly) quantized variables pytree for the
    forward pass: marker dicts dequantize to bf16
    (`q * scale -> bf16`), all other leaves pass through untouched.
    Identity-shaped (and nearly free) on unquantized trees, so the
    evaluation choke points call it unconditionally."""

    def dequant(x):
        if is_quantized_leaf(x):
            return (
                x["q"].astype(jnp.float32) * x["scale"]
            ).astype(jnp.bfloat16)
        return x

    return jax.tree_util.tree_map(
        dequant, variables, is_leaf=is_quantized_leaf
    )


def cast_params_for_inference(variables, model_config: ModelConfig):
    """Apply the inference precision policy to a variables pytree:
    identity (same object, no copy) under f32, bf16 cast of floating
    leaves under bf16, weight-only int8 quantization under int8. A
    tree made in the inference dtype (`PARAM_DTYPE` = the precision) is
    served as it was made, the same object: no second copy, and what its
    module keeps in float32 (a router's selection bias) stays so."""
    if model_config.INFERENCE_PRECISION == "int8":
        return quantize_params_for_inference(variables)
    dtype = inference_dtype(model_config)
    if dtype == jnp.float32 or jnp.dtype(model_config.PARAM_DTYPE) == dtype:
        return variables
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype)
        if jnp.issubdtype(x.dtype, jnp.floating)
        else x,
        variables,
    )


def quantized_param_bytes(variables) -> int:
    """Total bytes of a variables pytree as the serve program reads it
    (marker dicts count their int8 + scale buffers)."""
    total = 0
    for leaf in jax.tree_util.tree_leaves(variables):
        total += int(leaf.size) * int(leaf.dtype.itemsize)
    return total
