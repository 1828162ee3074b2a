"""Analytic FLOP accounting + MFU for the benchmark and profiler.

The reference publishes no utilization numbers at all; BASELINE.md's
throughput rows say nothing about how much of the chip they use. This
module turns ModelConfig/EnvConfig into an analytic forward FLOP count
(matmul/conv terms only — norms, activations and elementwise adds are
bandwidth, not FLOP, bound on TPU) so `bench.py` can report achieved
TFLOP/s and %-of-peak (MFU) next to every games/h row.

Conventions:
- 1 MAC = 2 FLOPs.
- A backward pass costs ~2x the forward matmul FLOPs (grad wrt inputs
  + grad wrt weights), so a train step is ~3x forward; `nn.remat`
  recomputes the forward once more (~4x). `train_step_flops` applies
  the right multiplier from ModelConfig.REMAT.
- Peak table covers the chips this framework targets; the CPU has no
  peak (MFU null) and any other unlisted device kind is an error.
"""

import logging
import os

from ..config.env_config import EnvConfig
from ..config.model_config import ModelConfig

logger = logging.getLogger(__name__)

# Operator-supplied peak override: lets CPU/smoke runs (and chips not
# yet in the table) still produce an MFU ratio instead of null — the
# denominator is then whatever the operator declares, recorded as
# peak_source="env" wherever the number is published.
PEAK_TFLOPS_ENV = "ALPHATRIANGLE_PEAK_TFLOPS"


def _conv2d_flops(h: int, w: int, cin: int, cout: int, k: int, s: int) -> int:
    """SAME-padded k x k conv at stride s over (h, w): 2*HWK^2*Cin*Cout."""
    ho = -(-h // s)
    wo = -(-w // s)
    return 2 * ho * wo * k * k * cin * cout


def forward_flops(model: ModelConfig, env: EnvConfig, action_dim: int) -> int:
    """Matmul/conv FLOPs of ONE forward pass of `AlphaTriangleNet`
    (nn/model.py) for ONE example."""
    h, w = env.ROWS, env.COLS
    total = 0

    # Conv trunk.
    cin = model.GRID_INPUT_CHANNELS
    for f, k, s in zip(
        model.CONV_FILTERS, model.CONV_KERNEL_SIZES, model.CONV_STRIDES
    ):
        total += _conv2d_flops(h, w, cin, f, k, s)
        h, w = -(-h // s), -(-w // s)
        cin = f

    # Residual stack (+ 1x1 adapter when widths differ).
    if model.NUM_RESIDUAL_BLOCKS > 0:
        rf = model.RESIDUAL_BLOCK_FILTERS
        if cin != rf:
            total += _conv2d_flops(h, w, cin, rf, 1, 1)
            cin = rf
        total += model.NUM_RESIDUAL_BLOCKS * 2 * _conv2d_flops(
            h, w, rf, rf, 3, 1
        )

    # A decoder stack in the encoder's place (nn/trunk.py counts it).
    if model.TRUNK is not None:
        from ..nn.trunk import forward_flops as trunk_flops

        d = model.TRUNK.hidden_size
        if cin != d:
            total += _conv2d_flops(h, w, cin, d, 1, 1)
            cin = d
        total += trunk_flops(model.TRUNK, h * w)
    # Transformer over the S = h*w token sequence.
    elif model.USE_TRANSFORMER and model.TRANSFORMER_LAYERS > 0:
        d = model.TRANSFORMER_DIM
        if cin != d:
            total += _conv2d_flops(h, w, cin, d, 1, 1)
            cin = d
        s_len = h * w
        per_layer = (
            4 * 2 * s_len * d * d  # Q, K, V, out projections
            + 2 * 2 * s_len * s_len * d  # QK^T and attn @ V
            + 2 * 2 * s_len * d * model.TRANSFORMER_FC_DIM  # MLP in + out
        )
        total += model.TRANSFORMER_LAYERS * per_layer

    # Heads over the flattened features (+ the auxiliary scalar input).
    flat = h * w * cin + model.OTHER_NN_INPUT_FEATURES_DIM
    dim = flat
    for fc in model.FC_DIMS_SHARED:
        total += 2 * dim * fc
        dim = fc
    for dims, out in (
        (model.POLICY_HEAD_DIMS, action_dim),
        (model.VALUE_HEAD_DIMS, model.NUM_VALUE_ATOMS),
    ):
        hd = dim
        for fc in dims:
            total += 2 * hd * fc
            hd = fc
        total += 2 * hd * out
    return total


def train_step_flops(
    model: ModelConfig, env: EnvConfig, action_dim: int, batch: int
) -> int:
    """Matmul FLOPs of one SGD step on a `batch`: forward + ~2x
    backward (+1x forward recompute under REMAT)."""
    mult = 4 if model.REMAT else 3
    return mult * batch * forward_flops(model, env, action_dim)


def model_step_flops(
    model: ModelConfig, env: EnvConfig, action_dim: int, batch: int
) -> int:
    """What a utilization credits a step with: forward + backward,
    3 x the forward, whatever REMAT makes the chip compute again. The
    recomputed forward is the price of fitting, not work of the model's
    (`train_step_flops` counts it: the work the chip really does)."""
    return 3 * batch * forward_flops(model, env, action_dim)


def gather_einsum_flops(batch: int, wave: int, nodes: int, width: int) -> int:
    """FLOPs of ONE einsum descent row-gather (`ops/gather_rows.py`):
    (B, W, N) one-hot x (B, N, K). The take/pallas lowerings do the
    same row select with zero matmul FLOPs."""
    return 2 * batch * wave * nodes * width


# Peak dense bf16 matmul throughput per chip, TFLOP/s, keyed by the
# `device_kind` JAX reports. Source: Google Cloud TPU documentation,
# the system-architecture page of each generation ("TPU v4", "TPU v5e",
# "TPU v5p", "TPU v6e"). v5e is 197 in bf16; the 393-394 on the same
# page is its int8 figure.
_PEAK_BF16_TFLOPS = {
    "TPU v4": 275.0,
    "TPU v5 lite": 197.0,
    "TPU v5e": 197.0,
    "TPU v5": 459.0,
    "TPU v5p": 459.0,
    "TPU v6 lite": 918.0,
    "TPU v6e": 918.0,
}


def peak_info(
    table: dict[str, float], env_name: str, device_kind: str
) -> tuple[float | None, str]:
    """(peak, source) of one peak table for a `jax.Device.device_kind`.

    Source is "env" (the `env_name` override — wins, so an operator can
    assert a denominator for a chip not yet listed or for a CPU smoke),
    "table" (exact `device_kind` match), or "unknown" with peak None for
    the CPU and for a record that names no device: an explicit marker,
    never a number. Any other unlisted kind raises — a utilization over
    a guessed peak is worse than none.
    """
    override = os.environ.get(env_name, "").strip()
    if override:
        try:
            value = float(override)
            if value > 0:
                return value, "env"
            logger.warning("%s=%r is not positive; ignoring.", env_name, override)
        except ValueError:
            logger.warning("%s=%r is not a number; ignoring.", env_name, override)
    kind = (device_kind or "").strip()
    if kind in table:
        return table[kind], "table"
    if kind.lower() in ("", "cpu"):
        return None, "unknown"
    raise ValueError(
        f"no peak listed for device kind {device_kind!r}: add it, with "
        f"its source, to the table that {env_name} overrides, or set "
        f"{env_name}."
    )


def peak_bf16_tflops_info(device_kind: str) -> tuple[float | None, str]:
    """(peak bf16 TFLOP/s, source) — see `peak_info`."""
    return peak_info(_PEAK_BF16_TFLOPS, PEAK_TFLOPS_ENV, device_kind)


def peak_bf16_tflops(device_kind: str) -> float | None:
    """Peak bf16 TFLOP/s for a `jax.Device.device_kind`; None on the
    CPU (honors the ALPHATRIANGLE_PEAK_TFLOPS override)."""
    return peak_bf16_tflops_info(device_kind)[0]


def mfu(achieved_flops_per_sec: float, device_kind: str) -> float | None:
    """Fraction of the chip's bf16 peak actually achieved, or None on
    the CPU (never guess a denominator)."""
    peak = peak_bf16_tflops(device_kind)
    if peak is None or achieved_flops_per_sec <= 0:
        return None
    return achieved_flops_per_sec / (peak * 1e12)
