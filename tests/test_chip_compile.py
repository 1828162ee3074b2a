"""The TPU compiler sees every Pallas kernel, without a chip.

libtpu compiles for a chip that is described and not attached
(`jax.experimental.topologies`). Interpret-mode tests (tests/test_ops.py)
pin what the kernels compute; they cannot see what the compiler refuses
— a block that is not a whole (8, 128) tile, a scalar operand laid out
for VMEM, more VMEM than the scoped limit. All four kernels had passed
every interpret-mode test and none compiled. These cases hold the
compiled-for-v5e property at the widths the chip runs: preset 3 (the
flagship, what `chip_smoke.py` executes on the chip) and preset 4's
400-simulation tree (the largest planes).

A compile that passes here is not a chip run: nothing executes.
"""

import functools
import os

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else libtpu logs to /tmp

import jax  # noqa: E402
import pytest  # noqa: E402

import chip_smoke  # noqa: E402
from alphatriangle_tpu.config.presets import baseline_preset  # noqa: E402

KERNELS = ("gather_rows", "backup_update", "per_sample", "subtree_promote")


@pytest.fixture(scope="module")
def one_v5e_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as exc:  # no libtpu in this image
        pytest.skip(f"cannot describe a v5e topology here: {exc}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(autouse=True)
def no_persistent_cache():
    """A compile for a described chip is written to the persistent
    cache but cannot be read back without one; keep it off here."""
    from jax._src import compilation_cache

    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()


@pytest.fixture(scope="module")
def cases():
    return {
        preset: {
            case["name"]: case
            for case in chip_smoke.kernel_cases(
                chip_smoke.kernel_shapes(baseline_preset(preset))
            )
        }
        for preset in (3, 4)
    }


@pytest.mark.parametrize("preset", [3, 4])
@pytest.mark.parametrize("kernel", KERNELS)
def test_kernel_compiles_for_v5e(
    kernel, preset, cases, one_v5e_chip, monkeypatch
):
    case = cases[preset][kernel]
    # The dispatchers interpret the kernel unless the backend is a TPU;
    # the backend here is the CPU and the target is not.
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    operands = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=one_v5e_chip),
        jax.eval_shape(case["operands"], jax.random.PRNGKey(0)),
    )
    compiled = (
        jax.jit(functools.partial(case["run"], "pallas"))
        .lower(*operands)
        .compile()
    )
    assert "tpu_custom_call" in compiled.as_text()
