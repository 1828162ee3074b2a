"""The operations a forward pass and a learner step need, from shapes.

A copy of the arithmetic of `alphatriangle_tpu/utils/flops.py`, kept
here so that no later PR moves the yardstick. Matmul and convolution
terms only, 1 MAC = 2 FLOP; a learner step is a forward and a backward
of twice the forward (and one more forward under REMAT). Reads the
configuration's file, not the program's config classes.
"""

import json
from pathlib import Path


def _conv(h, w, cin, cout, k, s):
    return 2 * -(-h // s) * -(-w // s) * k * k * cin * cout


def forward_flops(model: dict, env: dict, action_dim: int) -> int:
    """FLOP of one forward pass of the net for one example."""
    h, w = env["ROWS"], env["COLS"]
    total, cin = 0, model["GRID_INPUT_CHANNELS"]
    for f, k, s in zip(
        model["CONV_FILTERS"], model["CONV_KERNEL_SIZES"], model["CONV_STRIDES"]
    ):
        total += _conv(h, w, cin, f, k, s)
        h, w, cin = -(-h // s), -(-w // s), f
    if model["NUM_RESIDUAL_BLOCKS"] > 0:
        rf = model["RESIDUAL_BLOCK_FILTERS"]
        if cin != rf:
            total += _conv(h, w, cin, rf, 1, 1)
            cin = rf
        total += model["NUM_RESIDUAL_BLOCKS"] * 2 * _conv(h, w, rf, rf, 3, 1)
    if model["USE_TRANSFORMER"] and model["TRANSFORMER_LAYERS"] > 0:
        d = model["TRANSFORMER_DIM"]
        if cin != d:
            total += _conv(h, w, cin, d, 1, 1)
            cin = d
        s = h * w
        total += model["TRANSFORMER_LAYERS"] * (
            4 * 2 * s * d * d  # query, key, value, out
            + 2 * 2 * s * s * d  # scores, weights x values
            + 2 * 2 * s * d * model["TRANSFORMER_FC_DIM"]  # MLP in, out
        )
    dim = h * w * cin + model["OTHER_NN_INPUT_FEATURES_DIM"]
    for fc in model["FC_DIMS_SHARED"]:
        total += 2 * dim * fc
        dim = fc
    for dims, out in (
        (model["POLICY_HEAD_DIMS"], action_dim),
        (model["VALUE_HEAD_DIMS"], model["NUM_VALUE_ATOMS"]),
    ):
        hd = dim
        for fc in dims:
            total += 2 * hd * fc
            hd = fc
        total += 2 * hd * out
    return total


def train_step_flops(model: dict, env: dict, action_dim: int, batch: int) -> int:
    """FLOP of one learner step on `batch` rows."""
    return (4 if model.get("REMAT") else 3) * batch * forward_flops(
        model, env, action_dim
    )


def peak(device_kind: str) -> dict:
    """The chip's published peaks; an unlisted device is an error."""
    table = json.loads((Path(__file__).parent / "peaks.json").read_text())
    if device_kind not in table["peaks"]:
        raise ValueError(
            f"no peak listed for device kind {device_kind!r} in "
            "chipbench/peaks.json: add it, with its source"
        )
    return table["peaks"][device_kind]
