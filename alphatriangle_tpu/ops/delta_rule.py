"""The chunked gated delta rule (KDA) as one kernel: a chunk stays in VMEM.

`nn/linear_attention.py` states the recurrence and its chunked form;
this is that chunked form, the same sub-blocks and the same bound on
every exponent, with nothing of a chunk written to HBM. A grid step
takes a block of boards and one head: it reads the head's 128-lane
block of `q`, `k`, `v`, `g` `(b, s, heads x head_dim)` through the
block's index map (no heads-first copy is made), walks the sequence's
chunks in order with the state `S` `(dk, dv)` float32 in VMEM scratch,
and writes `o` once. The running sum of `g`, the sub-block factors, `A`,
`P`, the triangular inverse, `u` never leave the chip.

A chunk, for every board of the block at once (the boards' chains are
independent, so their products fill the matrix units side by side):

- `G`, the running sum of `g` down the chunk's rows: a product with a
  triangle of ones, `g` split into three bfloat16 terms (exact: the
  ones are, and the sum is kept in float32);
- for each sub-block of `SUB` rows the rows' factor `exp(G_t - G_r)`
  about its reference row r and the keys' factor `exp(min(G_r - G_i,
  most))` for the keys of this and the earlier sub-blocks only (the
  later ones are masked: they are not multiplied at all), then one
  product `[k; q] rows x keys` a sub-block: its upper half is `A`'s
  rows, its lower half `P`'s;
- beta scales columns, not rows: with B = Diag(beta),
  `B (I + A B)^-1 = (I + B A)^-1 B`, so `a = A B` below the diagonal,
  `X = (I + a)^-1 (V - K_in S)`, and `u = B X` is never formed: `P B`
  and `K_out^T B` take it. Beta then enters as a row along the lanes,
  the layout it has in memory;
- `(I + a)^-1` by blocks: a = D + E, D the diagonal `SUB x SUB` blocks,
  nilpotent at `SUB`, E the blocks below. `T = (I + D)^-1` by doublings
  (six products at chunk 64), `M = T E` is nilpotent at the number of
  sub-blocks, `(I + a)^-1 = (I + M)^-1 T` (four more): ten products of
  `chunk x chunk` where the doublings of the whole chunk take eleven,
  each in three bfloat16 passes of a two-term split (relative error
  2^-16; the inverse is then rounded to the operands' type, as
  `chunked` rounds its own);
- the state walk: `X = x_v - x_k S`, `o = q_in S + (P B) X`,
  `S <- Diag(exp G_last) S + (K_out^T B) X`.

Operands of the products are `dtype` where `chunked` has them in
`dtype`; decays, `G`, the state and every accumulation are float32.
With float32 operands every product is taken at the highest precision.

No mode argument: `nn/trunk.py` runs the recurrence as this kernel or as
`chunked` by what `linear_path` can observe (docs/KERNELS.md).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vmem import vmem_params

SUB = 16  # rows of a sub-block: SUB x |lower_bound| < 88, float32's exp

# Boards a grid step takes at most (their chains interleave; more only
# lengthens the step's program), and what a step may plan to hold.
_MAX_BLOCK_BOARDS = 8
_VMEM_PLAN_BYTES = 48 << 20
# float32 `(chunk, width)` values a board's chunk body keeps at its
# widest (inputs, factors, operands in both types, the two halves of a
# split), counted generously: the plan refuses, it does not tune.
_BODY_VALUES = 48


def _board_bytes(seq: int, dk: int, dv: int, chunk: int, v_itemsize: int) -> tuple[int, int]:
    """VMEM bytes one board adds to a grid step: (its pipelined blocks:
    q, k, g and o float32, v in its own type, beta a row a chunk; the
    state and the values of a chunk's body)."""
    rows = -(-seq // chunk) * chunk
    blocks = rows * (3 * dk * 4 + dv * (4 + v_itemsize)) + 8 * rows * 4
    values = dk * dv * 4 + _BODY_VALUES * chunk * max(dk, dv, 128) * 4
    return blocks, values


def block_boards(batch: int, seq: int, dk: int, dv: int, chunk: int, v_itemsize: int) -> int:
    """Boards a grid step takes: as many as the plan holds, at most
    `_MAX_BLOCK_BOARDS` and `batch`; 0 where one board does not fit."""
    blocks, values = _board_bytes(seq, dk, dv, chunk, v_itemsize)
    return int(min(_VMEM_PLAN_BYTES // (2 * blocks + values), _MAX_BLOCK_BOARDS, batch))


def _refusal(
    seq: int, dk: int, dv: int, chunk: int, lower_bound: float, v_itemsize: int
) -> "str | None":
    """Why the kernel cannot take these shapes, or None: a chunk of
    whole sub-blocks whose exponents float32 holds (`chunked` refuses
    the others in the same words), heads that fill whole 128-lane
    blocks, a board whose values fit the VMEM plan."""
    if chunk % SUB or SUB * abs(lower_bound) >= 88:
        return (
            f"chunk {chunk} is not whole sub-blocks of {SUB}, or {SUB} steps "
            f"of {lower_bound} pass what float32's exp holds"
        )
    if dk % 128 or dv % 128 or block_boards(1, seq, dk, dv, chunk, v_itemsize) == 0:
        return (
            f"a head of {dk} keys and {dv} values over {seq} tokens is not whole "
            f"128-lane blocks, or does not fit the kernel's VMEM plan "
            f"({_VMEM_PLAN_BYTES} bytes)"
        )
    return None


def linear_path(
    *,
    partitioned: bool,
    backend: str,
    seq: int,
    head_dim: int,
    chunk: int,
    lower_bound: float,
    dtype,
) -> str:
    """"kernel" (this one) or "chunked" (`nn/linear_attention.chunked`)
    for the recurrence of one linear-attention layer, from what its site
    can observe. The kernel needs: a TPU backend, a program the compiler
    does not partition (it refuses to lower a Mosaic call it would have
    to split over a mesh), and shapes it takes (`_refusal`)."""
    fits = _refusal(
        seq, head_dim, head_dim, chunk, lower_bound, jnp.dtype(dtype).itemsize
    ) is None
    return "kernel" if backend == "tpu" and not partitioned and fits else "chunked"


def _products(dtype):
    """(dot, exact): batched products with operands in `dtype` and
    float32 sums, and `bmk,bkn->bmn` between float32 values in three
    bfloat16 passes of a two-term split (x = hi + lo: hi hi + lo hi +
    hi lo, relative error 2^-16); both the one float32 product at the
    highest precision where `dtype` is float32."""

    def einsum(eq, x, y, precision=None):
        return jnp.einsum(
            eq, x, y, preferred_element_type=jnp.float32, precision=precision
        )

    if jnp.dtype(dtype) == jnp.float32:
        highest = functools.partial(einsum, precision=jax.lax.Precision.HIGHEST)
        return highest, lambda x, y: highest("bmk,bkn->bmn", x, y)

    def dot(eq, x, y):
        return einsum(eq, x.astype(dtype), y.astype(dtype))

    def split(x):
        high = x.astype(jnp.bfloat16)
        return high, (x - high.astype(jnp.float32)).astype(jnp.bfloat16)

    def exact(x, y):
        # [xh | xl] [yh; yh] + xh yl: two of the passes as one product
        # of twice the depth, whose sum stays in the matrix unit (at
        # chunk 64 it fills the 128 lanes a product of 64 leaves half
        # empty).
        (xh, xl), (yh, yl) = split(x), split(y)
        eq = "bmk,bkn->bmn"
        return einsum(
            eq, jnp.concatenate([xh, xl], axis=2), jnp.concatenate([yh, yh], axis=1)
        ) + einsum(eq, xh, yl)

    return dot, exact


def _nilpotent_inverse(a, index: int, eye, product):
    """(I + a)^-1 for a (..., c, c) with a^index = 0:
    (I - a)(I + a^2)(I + a^4)... up to the power that is nought."""
    inverse, power, reach = eye - a, a, 2
    while reach < index:
        power = product(power, power)
        inverse = product(inverse, eye + power)
        reach *= 2
    return inverse


def _chunk_kernel(
    q_ref, k_ref, v_ref, g_ref, beta_ref, o_ref, state_ref,
    *, seq: int, chunk: int, most: float, dtype,
):
    boards, rows, _ = q_ref.shape
    dot, exact = _products(dtype)
    blocks = chunk // SUB
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = (t == i).astype(jnp.float32)
    same = t // SUB == i // SUB  # the diagonal sub-blocks
    ones = jnp.broadcast_to((i <= t).astype(jnp.bfloat16), (boards, chunk, chunk))

    def running_sum(g):
        total = None
        for _ in range(3):  # 3 x 8 bits: all of a float32
            part = g.astype(jnp.bfloat16)
            g = g - part.astype(jnp.float32)
            term = jnp.einsum(
                "bti,bid->btd", ones, part, preferred_element_type=jnp.float32
            )
            total = term if total is None else total + term
        return total

    def body(c, _):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        q, k, g = q_ref[:, at, :], k_ref[:, at, :], g_ref[:, at, :]
        v = v_ref[:, at, :]
        if seq < rows:
            # Rows past the sequence hold whatever the buffer held:
            # tokens that decay nothing and write nothing (beta's are 0).
            inside = c * chunk + t[:, :1] < seq
            q, k, g, v = (jnp.where(inside, x, jnp.zeros_like(x)) for x in (q, k, g, v))
        beta = beta_ref[:, 0, pl.ds(c, 1), :]  # (boards, 1, chunk)
        state = state_ref[...]

        cum = running_sum(g)
        a_rows, p_rows = [], []
        for b in range(blocks):
            low, high = b * SUB, (b + 1) * SUB
            middle = cum[:, low + SUB // 2 - 1 : low + SUB // 2, :]
            factor = jnp.exp(cum[:, low:high] - middle)
            left = jnp.concatenate(
                [k[:, low:high] * factor, q[:, low:high] * factor], axis=1
            ).astype(dtype)
            keys = (
                k[:, :high] * jnp.exp(jnp.minimum(middle - cum[:, :high], most))
            ).astype(dtype)
            if high < chunk:  # the later sub-blocks: masked, so nought
                keys = jnp.concatenate(
                    [keys, jnp.zeros((boards, chunk - high, keys.shape[2]), dtype)],
                    axis=1,
                )
            pair = jnp.einsum(
                "bsd,bid->bsi", left, keys, preferred_element_type=jnp.float32
            )
            a_rows.append(pair[:, :SUB])
            p_rows.append(pair[:, SUB:])
        a = jnp.where(i < t, jnp.concatenate(a_rows, axis=1), 0.0) * beta
        p = jnp.where(i <= t, jnp.concatenate(p_rows, axis=1), 0.0) * beta

        diagonal = jnp.where(same, a, 0.0)
        inverse = _nilpotent_inverse(diagonal, SUB, eye, exact)
        inverse = exact(
            _nilpotent_inverse(exact(inverse, a - diagonal), blocks, eye, exact),
            inverse,
        )

        decayed = jnp.exp(cum)
        last = cum[:, chunk - 1 :, :]  # (boards, 1, dk)
        x_v = dot("bti,biv->btv", inverse, v)
        x_k = dot("bti,bik->btk", inverse, k * decayed)
        x = x_v - dot("btk,bkv->btv", x_k, state)
        o_ref[:, at, :] = dot("btk,bkv->btv", q * decayed, state) + dot(
            "bti,biv->btv", p, x
        )
        k_out = jnp.swapaxes(k * jnp.exp(last - cum), 1, 2) * beta  # (boards, dk, chunk)
        kept = jnp.swapaxes(
            jnp.broadcast_to(jnp.exp(last), (boards, 8, last.shape[2])), 1, 2
        )[:, :, :1]  # exp(G_last) down the state's rows
        state_ref[...] = kept * state + dot("bkt,btv->bkv", k_out, x)

    state_ref[...] = jnp.zeros_like(state_ref)
    jax.lax.fori_loop(0, rows // chunk, body, None)


@functools.partial(
    jax.jit, static_argnames=("heads", "chunk", "lower_bound", "dtype", "interpret")
)
def gated_delta_rule(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    heads: int,
    chunk: int,
    lower_bound: float,
    dtype,
    interpret: bool = False,
) -> jax.Array:
    """`nn/linear_attention.recurrent`'s o for every board and head,
    `(b, s, heads x dv)` float32: q, k, g `(b, s, heads x dk)`, v
    `(b, s, heads x dv)`, beta `(b, s, heads)`, g >= `lower_bound`. Any
    s: the last chunk is filled with tokens that decay nothing and write
    nothing. The last grid step is padded where the block does not
    divide b (boards are independent; what a padded board computes is
    never written). `interpret=True` runs the kernel in the Pallas
    interpreter (CPU tests)."""
    b, s, width = q.shape
    dk, dv = width // heads, v.shape[2] // heads
    refusal = _refusal(s, dk, dv, chunk, lower_bound, v.dtype.itemsize)
    if refusal:
        raise ValueError(refusal)
    boards = block_boards(b, s, dk, dv, chunk, v.dtype.itemsize)
    chunks = -(-s // chunk)
    beta = jnp.pad(
        beta.astype(jnp.float32), ((0, 0), (0, chunks * chunk - s), (0, 0))
    )
    beta = jnp.swapaxes(beta, 1, 2).reshape(b, heads, chunks, chunk)

    def head_block(width):
        # Whole chunks of rows over an array of s: the block's last rows
        # lie past the array, are not fetched and not written back.
        return pl.BlockSpec(
            (boards, chunks * chunk, width), lambda n, h: (n, 0, h),
            memory_space=pltpu.VMEM,
        )

    blocks, values = _board_bytes(s, dk, dv, chunk, v.dtype.itemsize)
    return pl.pallas_call(
        functools.partial(
            _chunk_kernel, seq=s, chunk=chunk, most=SUB // 2 * abs(lower_bound),
            dtype=jnp.dtype(dtype),
        ),
        grid=(pl.cdiv(b, boards), heads),
        in_specs=[
            head_block(dk), head_block(dk), head_block(dv), head_block(dk),
            pl.BlockSpec(
                (boards, 1, chunks, chunk), lambda n, h: (n, h, 0, 0),
                memory_space=pltpu.VMEM,
            ),
        ],
        out_specs=head_block(dv),
        out_shape=jax.ShapeDtypeStruct((b, s, heads * dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((boards, dk, dv), jnp.float32)],
        compiler_params=vmem_params(
            boards * blocks, boards * values,
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="delta_rule",
    )(q.astype(jnp.float32), k.astype(jnp.float32), v, g.astype(jnp.float32), beta)
