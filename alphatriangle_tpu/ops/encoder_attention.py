"""Fused attention for the encoder's inference forward.

`softmax(q k^T / sqrt(Dh)) v` per board and head, with the `(S, S)`
scores of a board held in VMEM: they never reach HBM. Flax's
`nn.dot_product_attention` writes them once as the first product's
output, reads and writes them in the softmax and reads them in the
second product; at a leaf wave of the flagship (8,192 boards, 4 heads,
120 tokens) that is four trips of 0.94 GB a layer for a tensor that
exists only between two matmuls.

The kernel reads `q`, `k`, `v` as `(B, S, H*Dh)`, a reshape of the
projections' `(B, S, H, Dh)` that moves nothing, and writes the output
the same way. (On a TPU XLA keeps the encoder's activations batch-minor
and copies each operand to the row-major layout a Pallas call takes,
and the output back: four copies a layer, as many as it made around
Flax's two products.) A head is taken by a lane mask on
`k` and `v`, so both products contract over all `H*Dh` lanes and no
narrow slice or concatenate is made: a masked `k` gives head h's scores,
and a masked `v` puts head h's output in head h's lanes of the sum over
heads. Operands of both products stay in the input type (bfloat16 on
the flagship); accumulation and the softmax are float32.

Unlike the other kernels of `ops/` this one sits in a default path and
has no mode argument: `attention_path` picks it, or Flax's function,
from what the code can observe (docs/KERNELS.md).
"""

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import AxisType

from ._vmem import vmem_params

# Boards a grid step takes at most, and what one step may plan to hold
# in VMEM (a v5e core has 128 MiB; the plan leaves the compiler room).
_MAX_BLOCK_BOARDS = 32
_VMEM_PLAN_BYTES = 48 << 20

_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def _step_bytes(boards: int, seq: int, width: int, itemsize: int) -> tuple[int, int]:
    """VMEM bytes of one grid step over `boards` boards: (the four
    pipelined blocks q, k, v, o; the values the body keeps: one head's
    float32 scores, their exponentials and the probabilities, the
    masked k and v, the float32 output)."""
    rows, lanes, keys = _pad(seq, 16), _pad(width, 128), _pad(seq, 128)
    blocks = 4 * boards * rows * lanes * itemsize
    values = boards * (3 * rows * keys * 4 + 3 * rows * lanes * 4)
    return blocks, values


def block_boards(batch: int, seq: int, width: int, itemsize: int) -> int:
    """Boards a grid step takes: as many as the plan holds, at most
    `_MAX_BLOCK_BOARDS` and `batch`; 0 where one board does not fit."""
    blocks, values = _step_bytes(1, seq, width, itemsize)
    fit = _VMEM_PLAN_BYTES // (2 * blocks + values)
    return int(min(fit, _MAX_BLOCK_BOARDS, batch))


def partitioned(x: jax.Array) -> bool:
    """Whether the program `x` is traced into is one the compiler splits
    over the devices of a mesh: its operands were placed on a mesh of
    more than one device, and some axis of it is not under a
    `shard_map`. (A value's type carries the mesh of the program's
    operands; a program of one device carries an empty one.)"""
    mesh = jax.typeof(x).sharding.mesh
    return mesh.size > 1 and any(
        kind != AxisType.Manual for kind in mesh.axis_types
    )


def attention_path(
    *,
    train: bool,
    handed_in: bool,
    masked: bool,
    partitioned: bool,
    backend: str,
    dtype,
    seq: int,
    heads: int,
    head_dim: int,
) -> str:
    """"fused" (this kernel) or "flax" (`nn.dot_product_attention`, or
    the function handed in) for one attention call, from what its site
    can observe. Fused needs: an inference call (no dropout on the
    weights, no backward pass), no `attention_fn` handed in (the
    sequence-parallel hook keeps precedence), no mask and no bias, a
    program the compiler does not partition (it cannot split a Mosaic
    call over a mesh and refuses to lower one; Flax's einsums it
    splits), a TPU backend, a type the kernel takes, all heads filling
    whole 128-lane rows, and a board that fits the VMEM plan."""
    fits = (
        jnp.dtype(dtype) in _DTYPES
        and (heads * head_dim) % 128 == 0
        and block_boards(1, seq, heads * head_dim, jnp.dtype(dtype).itemsize) > 0
    )
    if train or handed_in or masked or partitioned or backend != "tpu" or not fits:
        return "flax"
    return "fused"


def _attention_kernel(q_ref, k_ref, v_ref, o_ref, *, heads: int):
    q, k, v = q_ref[...], k_ref[...], v_ref[...]
    boards, seq, width = q.shape
    head_dim = width // heads
    scale = 1.0 / math.sqrt(head_dim)
    lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, width), 2)
    out = jnp.zeros((boards, seq, width), jnp.float32)
    for h in range(heads):
        own = (lane >= h * head_dim) & (lane < (h + 1) * head_dim)
        # Keys down the sublanes, queries along the lanes: the softmax's
        # two reductions then run over sublanes (elementwise between
        # vregs, and a (1, S) sum to divide by) and not across lanes.
        scores = scale * jnp.einsum(
            "bkd,bqd->bkq",
            jnp.where(own, k, jnp.zeros_like(k)),
            q,
            preferred_element_type=jnp.float32,
        )
        e = jnp.exp(scores - jnp.max(scores, axis=1, keepdims=True))
        p = e * (1.0 / jnp.sum(e, axis=1, keepdims=True))
        out += jnp.einsum(
            "bkq,bkd->bqd",
            p.astype(v.dtype),
            jnp.where(own, v, jnp.zeros_like(v)),
            preferred_element_type=jnp.float32,
        )
    o_ref[...] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def encoder_attention(
    query: jax.Array, key: jax.Array, value: jax.Array, interpret: bool = False
) -> jax.Array:
    """(B, S, H, Dh) x 3 -> (B, S, H, Dh): unmasked self-attention of
    every board, `nn.dot_product_attention`'s answer with the softmax in
    float32. The last grid step is padded where the block does not
    divide B (boards are independent; what a padded board computes is
    never written). `interpret=True` runs the kernel in the Pallas
    interpreter (CPU tests)."""
    b, s, heads, head_dim = query.shape
    width = heads * head_dim
    itemsize = query.dtype.itemsize
    boards = block_boards(b, s, width, itemsize)
    if boards == 0:
        raise ValueError(
            f"one board of {s} tokens x {width} does not fit the kernel's "
            f"VMEM plan ({_VMEM_PLAN_BYTES} bytes)"
        )
    block = pl.BlockSpec(
        (boards, s, width), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    blocks, values = _step_bytes(boards, s, width, itemsize)
    out = pl.pallas_call(
        functools.partial(_attention_kernel, heads=heads),
        grid=(pl.cdiv(b, boards),),
        in_specs=[block, block, block],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, s, width), query.dtype),
        compiler_params=vmem_params(
            blocks, values, dimension_semantics=("parallel",)
        ),
        interpret=interpret,
        name="encoder_attention",
    )(
        query.reshape(b, s, width),
        key.reshape(b, s, width),
        value.reshape(b, s, width),
    )
    return out.reshape(b, s, heads, head_dim)
