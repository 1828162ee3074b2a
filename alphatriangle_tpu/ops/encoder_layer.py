"""One pre-norm encoder layer of the inference forward as one kernel.

`x + Attn(LN(x))`, then `x + MLP(LN(x))`: LayerNorm, the q / k / v
projections, softmax attention per board and head, the output
projection, the residual, LayerNorm, Dense(`mlp_dim`), the activation,
Dense(`dim`) and the second residual, all on values in VMEM. A grid
step reads a block of boards' tokens `(boards, S, D)` once and writes
the layer's output once; the normed tokens, `q`, `k`, `v`, the `(S, S)`
scores, the attention's output and the MLP's hidden `(boards, S,
mlp_dim)` never reach HBM. The layer's parameters enter as whole-array
blocks with a constant index map, so the pipeline fetches them once a
call.

The same mathematics as Flax's modules, in the same stated precision:
operands of every product in the tokens' type (bfloat16 on the
flagship), float32 accumulation, LayerNorm statistics and the softmax
in float32. The residual stream stays float32 inside the layer and is
rounded once, at its output (Flax rounds it at both adds).

A head is a lane mask on `k` and `v`, so both attention products
contract over all `D` lanes and no narrow slice or concatenate is made:
a masked `k` gives head h's scores, and a masked `v` puts head h's
output in head h's lanes of the sum over heads.

Unlike the other kernels of `ops/` this one sits in a default path and
has no mode argument: `layer_path` picks it, or Flax's modules, from
what the call site can observe (docs/KERNELS.md). The parameters are
those Flax's modules declare (`TransformerEncoderLayer` hands over its
own variables), so the tree is one on both paths.
"""

import functools
import math
from collections.abc import Callable

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
# Where S is not whole 8-row sublane tiles (preset 5's 252), a board's
# rows do not sit on tile edges in the block's (boards * S, D) view and
# the compiler unrolls a relayout per board: 16 boards a step took 47 s
# to compile for a v5e, 8 take 10 (sandbox compile, PR 30).
_MAX_RAGGED_BOARDS = 8

_DTYPES = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float32))
_LN_EPSILON = 1e-6  # nn.LayerNorm's default, which the layer takes


def _pad(n: int, to: int) -> int:
    return -(-n // to) * to


def _param_bytes(dim: int, mlp_dim: int, itemsize: int) -> int:
    """VMEM bytes of the layer's parameters as the kernel takes them:
    six matrices in the tokens' type, ten float32 vectors of 8 sublanes."""
    matrices = (4 * dim * dim + 2 * dim * mlp_dim) * itemsize
    return matrices + 10 * 8 * max(dim, mlp_dim) * 4


def _board_bytes(
    seq: int, dim: int, mlp_dim: int, itemsize: int
) -> tuple[int, int]:
    """VMEM bytes one board adds to a grid step: (its pipelined blocks,
    tokens in and out; the values the body keeps at its widest: six
    float32 `(S, D)` (the residual stream, the normed tokens or a
    projection, the attention's sum), `q`, `k`, `v` and the masked `k`,
    `v` in the tokens' type, one head's float32 scores, exponentials and
    probabilities, the MLP's hidden in float32 and in the tokens' type)."""
    rows, rows_t, keys = _pad(seq, 8), _pad(seq, 16), _pad(seq, 128)
    blocks = 2 * rows_t * dim * itemsize
    values = (
        6 * rows * dim * 4
        + 5 * rows_t * dim * itemsize
        + 3 * rows * keys * 4
        + rows * mlp_dim * 4
        + rows_t * mlp_dim * itemsize
    )
    return blocks, values


def block_boards(batch: int, seq: int, dim: int, mlp_dim: int, itemsize: int) -> int:
    """Boards a grid step takes: as many as the plan holds beside the
    parameters, at most `_MAX_BLOCK_BOARDS` (`_MAX_RAGGED_BOARDS` where
    S is not whole sublane tiles) and `batch`; 0 where one board does
    not fit."""
    blocks, values = _board_bytes(seq, dim, mlp_dim, itemsize)
    room = _VMEM_PLAN_BYTES - 2 * _param_bytes(dim, mlp_dim, itemsize)
    most = _MAX_BLOCK_BOARDS if seq % 8 == 0 else _MAX_RAGGED_BOARDS
    return int(min(max(room, 0) // (2 * blocks + values), most, batch))


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


def layer_path(
    *,
    initializing: bool,
    train: bool,
    handed_in: bool,
    masked: bool,
    partitioned: bool,
    backend: str,
    dtype,
    seq: int,
    heads: int,
    head_dim: int,
    mlp_dim: int,
) -> str:
    """"fused" (this kernel) or "flax" (the layer's Flax modules, with
    `nn.dot_product_attention` or the function handed in) for one
    encoder layer, from what its site can observe. Fused needs: variables
    that exist (`init` runs the modules, which declare them), an
    inference call (no dropout, no backward pass), no `attention_fn`
    handed in (the sequence-parallel hook keeps precedence), no mask and
    no bias, a program the compiler does not partition (it cannot split
    a Mosaic call over a mesh and refuses to lower one; Flax's einsums
    it splits), a TPU backend, a type the kernel takes, the heads and
    the MLP's hidden filling whole 128-lane rows, and a board whose
    values fit the VMEM plan."""
    dim = heads * head_dim
    fits = (
        jnp.dtype(dtype) in _DTYPES
        and dim % 128 == 0
        and mlp_dim % 128 == 0
        and block_boards(1, seq, dim, mlp_dim, jnp.dtype(dtype).itemsize) > 0
    )
    flax = initializing or train or handed_in or masked or partitioned
    return "flax" if flax or backend != "tpu" or not fits else "fused"


def _layer_norm(x, scale, bias):
    """`nn.LayerNorm` over the lanes of float32 `x` (rows, D): Flax's
    one-pass statistics, E[x^2] - E[x]^2 floored at 0."""
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(x * x, axis=-1, keepdims=True) - mean * mean, 0.0)
    return (x - mean) * (jax.lax.rsqrt(var + _LN_EPSILON) * scale) + bias


def _attend(q, k, v, heads: int):
    """softmax(q k^T / sqrt(Dh)) v of every board and head: `q`, `k`,
    `v` are (boards, S, H*Dh) in the products' operand type, the answer
    float32."""
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
    return out


def _layer_kernel(
    x_ref,
    ln1_scale, ln1_bias, wq, bq, wk, bk, wv, bv, wo, bo,
    ln2_scale, ln2_bias, w1, b1, w2, b2,
    o_ref,
    *,
    heads: int,
    act: Callable,
):
    boards, seq, dim = x_ref.shape
    dtype = x_ref.dtype

    def dense(y, w, b):
        return (
            jnp.dot(y.astype(dtype), w[...], preferred_element_type=jnp.float32)
            + b[...]
        )

    def boardwise(y):
        return y.reshape(boards, seq, dim).astype(dtype)

    # Rows are tokens of all the block's boards: a view where values are
    # float32 (S a multiple of 8 sublanes), so the cast comes after.
    x = x_ref[...].astype(jnp.float32).reshape(boards * seq, dim)
    y = _layer_norm(x, ln1_scale[...], ln1_bias[...])
    attended = _attend(
        boardwise(dense(y, wq, bq)),
        boardwise(dense(y, wk, bk)),
        boardwise(dense(y, wv, bv)),
        heads,
    )
    x = x + dense(attended.reshape(boards * seq, dim), wo, bo)
    y = _layer_norm(x, ln2_scale[...], ln2_bias[...])
    x = x + dense(act(dense(y, w1, b1)), w2, b2)
    o_ref[...] = x.reshape(boards, seq, dim).astype(o_ref.dtype)


def _operands(params, dim: int, dtype) -> list[jax.Array]:
    """The layer's Flax variables as the kernel takes them: matrices
    `(in, out)` in the tokens' type (Flax casts them so for its own
    products; the `(D, H, Dh)` and `(H, Dh, D)` kernels are views),
    vectors `(1, n)` float32."""
    attention = params["MultiHeadDotProductAttention_0"]

    def vector(x):
        return x.reshape(1, -1).astype(jnp.float32)

    def norm(name):
        return [vector(params[name]["scale"]), vector(params[name]["bias"])]

    def dense(p, shape):
        return [p["kernel"].reshape(shape).astype(dtype), vector(p["bias"])]

    return [
        *norm("LayerNorm_0"),
        *dense(attention["query"], (dim, dim)),
        *dense(attention["key"], (dim, dim)),
        *dense(attention["value"], (dim, dim)),
        *dense(attention["out"], (dim, dim)),
        *norm("LayerNorm_1"),
        *dense(params["Dense_0"], (dim, -1)),
        *dense(params["Dense_1"], (-1, dim)),
    ]


@functools.partial(jax.jit, static_argnames=("heads", "act", "interpret"))
def encoder_layer(
    tokens: jax.Array,
    params,
    *,
    heads: int,
    act: Callable,
    interpret: bool = False,
) -> jax.Array:
    """(B, S, D) -> (B, S, D): `TransformerEncoderLayer`'s inference
    answer on `params`, the variables its Flax modules declare
    (`LayerNorm_0`, `MultiHeadDotProductAttention_0/{query,key,value,
    out}`, `LayerNorm_1`, `Dense_0`, `Dense_1`); `act` is the MLP's
    elementwise activation. The last grid step is padded where the block
    does not divide B (boards are independent; what a padded board
    computes is never written). `interpret=True` runs the kernel in the
    Pallas interpreter (CPU tests)."""
    b, s, dim = tokens.shape
    mlp_dim = params["Dense_0"]["kernel"].shape[-1]
    itemsize = tokens.dtype.itemsize
    boards = block_boards(b, s, dim, mlp_dim, itemsize)
    if boards == 0:
        raise ValueError(
            f"one board of {s} tokens x {dim} (hidden {mlp_dim}) does not "
            f"fit the kernel's VMEM plan ({_VMEM_PLAN_BYTES} bytes)"
        )
    operands = _operands(params, dim, tokens.dtype)
    block = pl.BlockSpec(
        (boards, s, dim), lambda i: (i, 0, 0), memory_space=pltpu.VMEM
    )
    whole = [
        pl.BlockSpec(x.shape, lambda i: (0, 0), memory_space=pltpu.VMEM)
        for x in operands
    ]
    blocks, values = _board_bytes(s, dim, mlp_dim, itemsize)
    return pl.pallas_call(
        functools.partial(_layer_kernel, heads=heads, act=act),
        grid=(pl.cdiv(b, boards),),
        in_specs=[block, *whole],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct((b, s, dim), tokens.dtype),
        compiler_params=vmem_params(
            boards * blocks + _param_bytes(dim, mlp_dim, itemsize),
            boards * values,
            dimension_semantics=("parallel",),
        ),
        interpret=interpret,
        name="encoder_layer",
    )(tokens, *operands)
