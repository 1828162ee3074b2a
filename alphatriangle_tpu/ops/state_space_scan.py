"""Mamba-2's chunked scan (SSD) as one kernel: a chunk stays in VMEM.

`nn/state_space.py` states the recurrence and its chunked form; this is
that chunked form, chunk for chunk, with nothing of a chunk written to
HBM. A grid step takes a block of boards and the heads of one 128-lane
block of x (two heads of 64 at `nemotron-super-ep4`'s widths; both lie
in one group). It reads x, and the group's B and C, where the mixer's
convolution wrote them, as 128-lane blocks of `xbc` `(b, s, heads x p +
2 x groups x n)` through the blocks' index maps (no heads-first copy,
no padded float32 copy), walks each board's chunks in order with the
heads' state `S` `(p, n)` float32 carried from chunk to chunk, and
writes `y` `(b, s, heads x p)` float32 once, the skip `D x` in it. A
chunk's running sums of log a, its pairwise decays exp(G_t - G_i), the
products B_i . C_t and the weights never leave the chip.

The chunk is taken transposed: token i down the rows, token t along the
lanes, the heads' channels of x^T down the rows. What belongs to a token
and a head (its step, exp G_t, exp(G_last - G_i)) is then a row a head,
broadcast down that head's rows, and a head's decays need G_i down the
rows once (a transpose of its row). A chunk, for `_TOGETHER` boards at
once (their chains interleave on the units):

- `G`, the running sum of log a along a chunk, for every board and chunk
  of the step before the walk: a product with a triangle of ones, log a
  split into three bfloat16 terms (exact: the ones are; the sum float32);
- `[B; S] C^T`: the pairs B_i . C_t and the carried `S C_t` in one
  product (the step's heads share their group's B and C);
- a head's weights `exp(G_t - G_i) B_i . C_t`, i <= t, nought ahead;
  `y^T = (Delta x)^T W + exp(G_t) (S C^T)`, transposed once into y;
- `S <- exp(G_last) S + (exp(G_last - G_i) Delta x)^T B`.

Operands of the products are `dtype` where `chunked` has them in
`dtype` (B and C, the weights, Delta x, the left-decayed Delta x, the
state in the carried product); steps, `G`, decays, the state and every
sum are float32. With float32 operands every product is taken at the
highest precision.

Forward only, as `ops/delta_rule.py` is: no program differentiates a
state-space layer (`rl/trainer.refuse_untrainable` turns such a stack
away). No mode argument: `nn/trunk.py` runs the scan as this kernel or
as `chunked` by what `ssm_path` can observe (docs/KERNELS.md).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vmem import vmem_params
from .delta_rule import _products

LANES = 128

# Boards a grid step takes at most, and what a step may plan to hold.
_MAX_BLOCK_BOARDS = 16
_VMEM_PLAN_BYTES = 48 << 20
# Boards a chunk's body takes at once (at most; their chains interleave),
# and the chunks a board may have for its walk to be unrolled.
_TOGETHER = 8
_UNROLLED_CHUNKS = 4
# float32 `(chunk, max(chunk, 128))` values a board's chunk body keeps at
# its widest (inputs, [B; S] C^T, a head's decays and weights in both
# types, the heads' rows broadcast), counted generously: the plan
# refuses, it does not tune.
_BODY_VALUES = 32


def _board_bytes(seq: int, n: int, chunk: int, itemsize: int) -> tuple[int, int]:
    """VMEM bytes one board adds to a grid step: (its pipelined blocks:
    x, B, C in their type, y float32, the steps and log decays a row a
    chunk and head; the state and the values of a chunk's body)."""
    rows = -(-seq // chunk) * chunk
    blocks = rows * (LANES + 2 * n) * itemsize + rows * LANES * 4 + 2 * 8 * rows * 4
    values = n * LANES * 4 + _BODY_VALUES * chunk * max(chunk, LANES) * 4
    return blocks, values


def block_boards(batch: int, seq: int, n: int, chunk: int, itemsize: int) -> int:
    """Boards a grid step takes: as many as the plan holds, at most
    `_MAX_BLOCK_BOARDS` and `batch`; 0 where one board does not fit."""
    blocks, values = _board_bytes(seq, n, chunk, itemsize)
    return int(min(_VMEM_PLAN_BYTES // (2 * blocks + values), _MAX_BLOCK_BOARDS, batch))


def _refusal(
    seq: int, heads: int, head_dim: int, groups: int, n: int, chunk: int,
    itemsize: int,
) -> "str | None":
    """Why the kernel cannot take these shapes, or None: heads that fill
    a 128-lane block together and share its group, B and C whole
    128-lane blocks of `xbc`, a chunk of whole lane blocks, a board
    that fits the VMEM plan."""
    if LANES % head_dim or (heads // groups) % (LANES // head_dim) or heads % groups:
        return (
            f"heads of {head_dim} do not fill 128-lane blocks whose heads "
            f"share a group ({heads} heads in {groups} groups)"
        )
    if n % LANES or (heads * head_dim) % n:
        return f"a state of {n} is not whole 128-lane blocks of xbc"
    if chunk % LANES or block_boards(1, seq, n, chunk, itemsize) == 0:
        return (
            f"a chunk of {chunk} over {seq} tokens is not whole 128-lane "
            f"blocks, or does not fit the kernel's VMEM plan "
            f"({_VMEM_PLAN_BYTES} bytes)"
        )
    return None


def ssm_path(
    *,
    partitioned: bool,
    backend: str,
    seq: int,
    heads: int,
    head_dim: int,
    groups: int,
    state_size: int,
    chunk: int,
    dtype,
) -> str:
    """"kernel" (this one) or "chunked" (`nn/state_space.chunked`) for
    the scan of one state-space layer, from what its site can observe.
    The kernel needs: a TPU backend, a program the compiler does not
    partition (it refuses to lower a Mosaic call it would have to split
    over a mesh), and shapes it takes (`_refusal`)."""
    fits = _refusal(
        seq, heads, head_dim, groups, state_size, chunk, jnp.dtype(dtype).itemsize
    ) is None
    return "kernel" if backend == "tpu" and not partitioned and fits else "chunked"


def _scan_kernel(
    x_ref, b_ref, c_ref, log_a_ref, step_ref, skip_ref, y_ref, cum_ref,
    *, seq: int, chunk: int, head_dim: int, dtype, together: int,
):
    boards, rows, _ = x_ref.shape
    n = b_ref.shape[2]
    chunks = rows // chunk
    per_block = LANES // head_dim
    dot, _ = _products(dtype)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    causal = i <= t
    skip = skip_ref[0]  # (1, 128): D down each head's lanes

    # G, the running sum of log a along each chunk, for every board of
    # the step at once: a product with a triangle of ones, log a split
    # into three bfloat16 terms (exact: the ones are; the sum float32).
    ones = causal.astype(jnp.bfloat16)
    rest = log_a_ref[...].reshape(boards * chunks * per_block, chunk)
    cum = None
    for _ in range(3):  # 3 x 8 bits: all of a float32
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        term = jnp.dot(part, ones, preferred_element_type=jnp.float32)
        cum = term if cum is None else cum + term
    cum_ref[...] = cum.reshape(cum_ref.shape)

    def by_head(row_of, width):
        """Head h's row (together, 1, w) down its `head_dim` rows of x^T."""
        return jnp.concatenate(
            [
                jnp.broadcast_to(row_of(h), (together, head_dim, width))
                for h in range(per_block)
            ],
            axis=1,
        )

    def one_chunk(first, c, state):
        on = pl.ds(first, together)
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        x, b, cc = x_ref[on, at, :], b_ref[on, at, :], c_ref[on, at, :]
        if seq < rows:
            # Rows past the sequence hold whatever the buffer held:
            # tokens that decay nothing and write nothing (their steps
            # and log decays are 0; C there reaches only rows that are
            # not written back).
            inside = (c * chunk + i[:, :1] < seq)[None]
            x, b = (jnp.where(inside, v, jnp.zeros_like(v)) for v in (x, b))
        x = x.astype(jnp.float32)
        cum = cum_ref[on, c]  # (together, heads, chunk)
        last = cum[:, :, chunk - 1 :]
        step = step_ref[on, 0, c]
        x_t = jnp.swapaxes(x, 1, 2)  # (together, p, i)
        written = by_head(lambda h: step[:, h : h + 1], chunk) * x_t  # Delta_i x_i

        # [B; S] C^T: the pairs B_i . C_t and S C_t in one product.
        both = dot(
            "bkn,btn->bkt",
            jnp.concatenate([b.astype(dtype), state.astype(dtype)], axis=1), cc,
        )
        pairs, carried = both[:, :chunk], both[:, chunk:]
        within, whole = [], []
        for h in range(per_block):
            gap = cum[:, h : h + 1, :]  # G_t along the lanes
            down = jnp.swapaxes(jnp.broadcast_to(gap, (together, chunk, chunk)), 1, 2)
            # G_last along the lanes, as wide as the state
            whole.append(jnp.tile(down[:, chunk - 1 :, :LANES], (1, 1, n // LANES)))
            # exp(G_t - G_i) B_i . C_t, nought ahead: (i, t)
            weights = jnp.exp(jnp.where(causal, gap - down, -jnp.inf)) * pairs
            rows_h = slice(h * head_dim, (h + 1) * head_dim)
            within.append(dot("bpi,bit->bpt", written[:, rows_h], weights))
        carried = by_head(lambda h: jnp.exp(cum[:, h : h + 1]), chunk) * carried
        y_t = jnp.concatenate(within, axis=1) + carried
        y_ref[on, at, :] = jnp.swapaxes(y_t, 1, 2) + skip * x
        left = by_head(
            lambda h: jnp.exp(last[:, h : h + 1] - cum[:, h : h + 1]), chunk
        ) * written
        kept = by_head(lambda h: jnp.exp(whole[h]), n)
        return kept * state + dot("bpi,bin->bpn", left, b)

    def some_boards(j, _):
        first = pl.multiple_of(j * together, together)
        state = jnp.zeros((together, LANES, n), jnp.float32)
        if chunks <= _UNROLLED_CHUNKS:
            for c in range(chunks):
                state = one_chunk(first, c, state)
        else:
            jax.lax.fori_loop(0, chunks, functools.partial(one_chunk, first), state)

    jax.lax.fori_loop(0, boards // together, some_boards, None)


@functools.partial(
    jax.jit,
    static_argnames=("heads", "head_dim", "groups", "chunk", "dtype", "interpret"),
)
def state_space_scan(
    xbc: jax.Array,
    step: jax.Array,
    log_a: jax.Array,
    skip: jax.Array,
    *,
    heads: int,
    head_dim: int,
    groups: int,
    chunk: int,
    dtype,
    interpret: bool = False,
) -> jax.Array:
    """`nn/state_space.recurrent`'s y for every board and head, `(b, s,
    heads x head_dim)` float32: `xbc` `(b, s, heads x head_dim + 2 x
    groups x n)` the mixer's x, B and C side by side, `step` and `log_a`
    `(b, s, heads)`, `skip` = D `(heads,)`. Any s: the last chunk is
    filled with tokens that decay nothing and write nothing. The last
    grid step is padded where the block does not divide b (boards are
    independent; what a padded board computes is never written).
    `interpret=True` runs the kernel in the Pallas interpreter (CPU
    tests)."""
    b, s, width = xbc.shape
    inner = heads * head_dim
    n = (width - inner) // (2 * groups)
    itemsize = xbc.dtype.itemsize
    refusal = _refusal(s, heads, head_dim, groups, n, chunk, itemsize)
    if refusal:
        raise ValueError(refusal)
    boards = block_boards(b, s, n, chunk, itemsize)
    chunks = -(-s // chunk)
    per_block = LANES // head_dim
    lane_blocks = heads // per_block
    in_group = lane_blocks // groups

    def by_chunk(v):
        # (b, s, heads) -> (b, lane blocks, chunks, heads a block, chunk):
        # a head's row along the lanes a chunk; the fill decays nothing
        # and writes nothing.
        v = jnp.pad(v.astype(jnp.float32), ((0, 0), (0, chunks * chunk - s), (0, 0)))
        v = v.reshape(b, chunks, chunk, lane_blocks, per_block)
        return jnp.transpose(v, (0, 3, 1, 4, 2))

    def rows_of(block):
        # Whole chunks of rows over an array of s: the block's last rows
        # lie past the array, are not fetched and not written back.
        return pl.BlockSpec(
            (boards, chunks * chunk, LANES), block, memory_space=pltpu.VMEM
        )

    first = inner // n  # B's first lane block in xbc; C's `groups` later
    by_head = pl.BlockSpec(
        (boards, 1, chunks, per_block, chunk), lambda m, h: (m, h, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    blocks, values = _board_bytes(s, n, chunk, itemsize)
    return pl.pallas_call(
        functools.partial(
            _scan_kernel, seq=s, chunk=chunk, head_dim=head_dim,
            dtype=jnp.dtype(dtype),
            together=max(d for d in range(1, _TOGETHER + 1) if boards % d == 0),
        ),
        grid=(pl.cdiv(b, boards), lane_blocks),
        in_specs=[
            rows_of(lambda m, h: (m, 0, h)),
            rows_of(lambda m, h: (m, 0, first + h // in_group)),
            rows_of(lambda m, h: (m, 0, first + groups + h // in_group)),
            by_head,
            by_head,
            pl.BlockSpec((1, 1, LANES), lambda m, h: (h, 0, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=rows_of(lambda m, h: (m, 0, h)),
        out_shape=jax.ShapeDtypeStruct((b, s, inner), jnp.float32),
        scratch_shapes=[pltpu.VMEM((boards, chunks, per_block, chunk), jnp.float32)],
        compiler_params=vmem_params(
            boards * blocks, boards * values,
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="state_space_scan",
    )(
        xbc, xbc, xbc, by_chunk(log_a), by_chunk(step),
        jnp.repeat(skip.astype(jnp.float32), head_dim).reshape(lane_blocks, 1, LANES),
    )
