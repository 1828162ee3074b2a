"""`chipbench/flops.py` against counts worked by hand, with and without
the transformer, and against the program's own arithmetic, which it
copies."""

import pytest

from chipbench import flops, manifest

CELLS = 8 * 15
# conv trunk 1->32->64->128, 3x3, SAME, on 120 cells; 2 residual blocks
TRUNK = 2 * CELLS * 9 * (1 * 32 + 32 * 64 + 64 * 128) + 2 * 2 * 2 * CELLS * 9 * 128 * 128
# 4 encoder layers: 4 projections, scores + weighted sum, MLP 128-256-128
ENCODER = 4 * (4 * 2 * CELLS * 128 * 128 + 2 * 2 * CELLS * CELLS * 128 + 2 * 2 * CELLS * 128 * 256)
# shared FC from 120*128 + 30 inputs; policy 128-128-360; value 128-128-51
HEADS = 2 * (CELLS * 128 + 30) * 128 + 2 * 128 * 128 + 2 * 128 * 360 + 2 * 128 * 128 + 2 * 128 * 51
HAND = {True: TRUNK + ENCODER + HEADS, False: TRUNK + HEADS}
MFLOP = {True: 323.2, False: 167.9}  # presets 3 and 2


@pytest.mark.parametrize("transformer", [True, False], ids=["flagship", "cnn-only"])
def test_forward_flops_by_hand_and_by_the_program(transformer):
    cfg = manifest.load_json(manifest.HERE / "configs" / "flagship-p3.json")
    cfg["model"]["USE_TRANSFORMER"] = transformer
    got = flops.forward_flops(cfg["model"], cfg["env"], cfg["action_dim"])
    assert got == HAND[transformer]
    assert round(got / 1e6, 1) == MFLOP[transformer]
    assert transformer is False or MFLOP[True] == cfg["forward_mflop"]
    from alphatriangle_tpu.utils import flops as program

    configs = manifest.program_configs(cfg)
    assert got == program.forward_flops(
        configs["model"], configs["env"], configs["env"].action_dim
    )
    assert flops.train_step_flops(
        cfg["model"], cfg["env"], cfg["action_dim"], 256
    ) == 3 * 256 * got


def test_peak_of_an_unlisted_device_is_an_error():
    assert flops.peak("TPU v5 lite")["bf16_tflops"] == 197.0
    with pytest.raises(ValueError):
        flops.peak("TPU v9")
