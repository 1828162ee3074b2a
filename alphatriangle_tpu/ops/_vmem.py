"""Scoped-VMEM sizing shared by the per-game kernels.

Each of them keeps whole `(N, K)` f32 blocks of one game in VMEM, and
the pipeline double-buffers every block. The compiler's default scoped
limit (16 MiB on v5e) is below what the 400-simulation trees need, so
the wrappers state their own limit from the block shapes; a tree too
large for the chip's VMEM is then refused by the compiler, by name.
"""

from jax.experimental.pallas import tpu as pltpu

_DEFAULT_SCOPED_BYTES = 16 << 20


def f32_block_bytes(rows: int, cols: int) -> int:
    """VMEM bytes of one (rows, cols) f32 block in (8, 128) tiles."""
    return (-(-rows // 8) * 8) * (-(-cols // 128) * 128) * 4


def vmem_params(
    block_bytes: int,
    value_bytes: int = 0,
    dimension_semantics: "tuple[str, ...] | None" = None,
) -> pltpu.CompilerParams:
    """Compiler params whose scoped-VMEM limit holds `block_bytes` of
    blocks double-buffered and `value_bytes` of values the body keeps,
    plus room for the kernel's own."""
    need = 2 * block_bytes + value_bytes + (2 << 20)
    return pltpu.CompilerParams(
        vmem_limit_bytes=max(need, _DEFAULT_SCOPED_BYTES),
        dimension_semantics=dimension_semantics,
    )
