"""The delta rule with one decay a value head (Gated DeltaNet) as one
kernel: a chunk's decays, triangle and state stay in VMEM.

`nn/linear_attention.py` states the recurrence and its chunked form,
`chunked_scalar`; this is that chunked form, chunk for chunk, with
nothing of a chunk written to HBM. A grid step takes a block of boards
and one key head: it reads the head's 128-lane block of `q` and `k`
`(b, s, key heads x dk)`, the `group` value heads that read it from `v`
where the mixer's convolution wrote them (one `dv`-lane block each),
and their log decays and betas laid out a row a chunk and value head.
Nothing is moved heads-first. It walks the sequence's chunks in order
with the value heads' states side by side, `(dk, group x dv)` float32,
and writes `o` `(b, s, value heads x dv)` float32 once.

A chunk, for every board of the block at once (their chains interleave
on the units):

- `G`, the running sum of g along each chunk, for every board and chunk
  of the step before the walk: a product with a triangle of ones, g
  split into three bfloat16 terms (exact: the ones are, and the sum is
  kept in float32);
- `[k; q] k^T` and `[k; q] S` once a key head: the pairs k_t . k_i and
  q_t . k_i, and `K S`, `Q S` for all the value heads at once (one
  product `group x dv` lanes wide);
- a value head's decays `exp(G_t - G_i)` for i <= t, nought ahead (the
  mask before the exp): one decay a head makes every one of them at
  most 1, so there are no sub-blocks and no bound on g (g may pass -88
  in one token), where `ops/delta_rule.py`'s decay a channel needs both;
- beta scales columns, as in `ops/delta_rule.py`: `a = A B` below the
  diagonal, `X = (I + a)^-1 (V - exp(G_t) K S)`, `u = B X` never
  formed; `(I + a)^-1` by that module's blocks of `SUB` (its doublings
  of the diagonal blocks, then of the nilpotent rest, in its three-pass
  products: the doublings of a whole chunk lose every digit where the
  keys of a chunk repeat each other and nothing decays); the value
  heads' triangles go through it side by side;
- `o = exp(G_t) Q S + (P B) X`,
  `S <- exp(G_last) S + (K^T Diag(beta exp(G_last - G_i))) X`.

Operands of the products are `dtype` where `chunked_scalar` has them in
`dtype` (q and k, the state where it is read, the inverse and what it
multiplies, `P`, what a token leaves in the state); g, `G`, the decays,
the state and every sum are float32, the inverse's products three
bfloat16 passes of a two-term split (relative error 2^-16, under the
rounding to `dtype` that follows by 2^7). With float32 operands every
product is taken at the highest precision.

Forward only: no program differentiates a gated-delta-net layer. No
mode argument: `nn/trunk.py` runs the recurrence as this kernel or as
`chunked_scalar` by what `gdn_path` can observe (docs/KERNELS.md).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vmem import vmem_params
from .delta_rule import SUB, _nilpotent_inverse, _products

# Boards a grid step takes at most (their chains interleave; more only
# lengthens the step's program), and what a step may plan to hold.
_MAX_BLOCK_BOARDS = 8
_VMEM_PLAN_BYTES = 48 << 20
# Chunks a board may have for its walk to be unrolled (the next chunk's
# triangle then overlaps this one's state products).
_UNROLLED_CHUNKS = 4
# float32 `(chunk, max(group x dv, 128))` values a board's chunk body
# keeps at its widest (inputs, pairs, reads, a value head's decays,
# triangle, inverse and its split, X, the new state), counted
# generously: the plan refuses, it does not tune.
_BODY_VALUES = 48


def _board_bytes(
    seq: int, dk: int, dv: int, group: int, chunk: int, itemsize: int
) -> tuple[int, int]:
    """VMEM bytes one board adds to a grid step: (its pipelined blocks:
    q, k and the value heads' v in their type, o float32, g and beta a
    row a chunk and value head; the state, G and the values of a chunk's
    body)."""
    rows = -(-seq // chunk) * chunk
    lanes = -(-chunk // 128) * 128
    by_chunk = rows // chunk * 8 * lanes * 4  # a (group, chunk) tile a chunk
    blocks = rows * (2 * dk + group * dv) * itemsize + rows * group * dv * 4
    blocks += 2 * by_chunk
    values = dk * group * dv * 4 + by_chunk
    values += _BODY_VALUES * chunk * max(group * dv, lanes) * 4
    return blocks, values


def block_boards(
    batch: int, seq: int, dk: int, dv: int, group: int, chunk: int, itemsize: int
) -> int:
    """Boards a grid step takes: as many as the plan holds, at most
    `_MAX_BLOCK_BOARDS` and `batch`; 0 where one board does not fit."""
    blocks, values = _board_bytes(seq, dk, dv, group, chunk, itemsize)
    return int(min(_VMEM_PLAN_BYTES // (2 * blocks + values), _MAX_BLOCK_BOARDS, batch))


def _refusal(
    seq: int, dk: int, dv: int, group: int, chunk: int, itemsize: int
) -> "str | None":
    """Why the kernel cannot take these shapes, or None: a chunk of
    whole sub-blocks (the triangle is inverted by them), heads that fill
    whole 128-lane blocks, a board whose values fit the VMEM plan."""
    if chunk % SUB:
        return f"chunk {chunk} is not whole sub-blocks of {SUB}"
    if dk % 128 or dv % 128 or block_boards(1, seq, dk, dv, group, chunk, itemsize) == 0:
        return (
            f"a key head of {dk} and {group} value heads of {dv} over {seq} "
            f"tokens are not whole 128-lane blocks, or do not fit the kernel's "
            f"VMEM plan ({_VMEM_PLAN_BYTES} bytes)"
        )
    return None


def gdn_path(
    *,
    partitioned: bool,
    backend: str,
    seq: int,
    dk: int,
    dv: int,
    group: int,
    chunk: int,
    dtype,
) -> str:
    """"kernel" (this one) or "chunked" (`nn/linear_attention.
    chunked_scalar`) for the recurrence of one gated-delta-net layer,
    from what its site can observe. The kernel needs: a TPU backend, a
    program the compiler does not partition (it refuses to lower a
    Mosaic call it would have to split over a mesh), and shapes it takes
    (`_refusal`)."""
    fits = _refusal(seq, dk, dv, group, chunk, jnp.dtype(dtype).itemsize) is None
    return "kernel" if backend == "tpu" and not partitioned and fits else "chunked"


def _chunk_kernel(
    q_ref, k_ref, *refs, seq: int, chunk: int, group: int, dtype,
):
    v_refs, (g_ref, beta_ref, o_ref, cum_ref) = refs[:group], refs[group:]
    boards, rows, _ = q_ref.shape
    dv = v_refs[0].shape[2]
    chunks = rows // chunk
    wide = max(chunk, dv)
    dot, exact = _products(dtype)
    t = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 0)
    i = jax.lax.broadcasted_iota(jnp.int32, (chunk, chunk), 1)
    eye = (t == i).astype(jnp.float32)
    same = t // SUB == i // SUB  # the diagonal sub-blocks
    causal = i <= t

    # G, the running sum of g along each chunk, for every board, chunk
    # and value head of the step at once: a row times a triangle of ones
    # (ones[j, i] = 1 for j <= i), g split into three bfloat16 terms.
    ones = (t <= i).astype(jnp.bfloat16)
    rest = g_ref[...].reshape(boards * chunks * group, chunk)
    cum = None
    for _ in range(3):  # 3 x 8 bits: all of a float32
        part = rest.astype(jnp.bfloat16)
        rest = rest - part.astype(jnp.float32)
        term = jnp.dot(part, ones, preferred_element_type=jnp.float32)
        cum = term if cum is None else cum + term
    cum_ref[...] = cum.reshape(cum_ref.shape)

    def down(row):
        """A row (boards, 1, chunk) down the rows: (boards, chunk, wide),
        entry t of the row along all of row t."""
        return jnp.swapaxes(jnp.broadcast_to(row, (boards, wide, chunk)), 1, 2)

    def one_chunk(c, state):
        at = pl.ds(pl.multiple_of(c * chunk, chunk), chunk)
        q, k = q_ref[:, at, :], k_ref[:, at, :]
        vs = [v_ref[:, at, :] for v_ref in v_refs]
        if seq < rows:
            # Rows past the sequence hold whatever the buffer held:
            # tokens that decay nothing and write nothing (g and beta
            # are 0 there).
            inside = c * chunk + t[:, :1] < seq
            q, k, *vs = (jnp.where(inside, x, jnp.zeros_like(x)) for x in (q, k, *vs))
        cum = cum_ref[:, c]  # (boards, group, chunk)
        beta = beta_ref[:, 0, c]
        left = jnp.concatenate([k, q], axis=1)
        pairs = dot("bsd,bid->bsi", left, k)  # k_t . k_i, q_t . k_i
        reads = dot("bsd,bdv->bsv", left, state)  # K S, Q S: every head
        k_t = jnp.swapaxes(k.astype(jnp.float32), 1, 2)  # (boards, dk, chunk)
        a, pb, rhs, carried, leaves, kept = [], [], [], [], [], []
        for j in range(group):
            row, scale = cum[:, j : j + 1, :], beta[:, j : j + 1, :]
            g_t = down(row)
            decay = jnp.exp(jnp.where(causal, g_t[:, :, :chunk] - row, -jnp.inf))
            a.append(jnp.where(i < t, pairs[:, :chunk], 0.0) * decay * scale)
            pb.append(pairs[:, chunk:] * decay * scale)
            grown = jnp.exp(g_t[:, :, :dv])  # exp G_t along each row
            lanes = slice(j * dv, (j + 1) * dv)
            rhs.append(vs[j].astype(jnp.float32) - grown * reads[:, :chunk, lanes])
            carried.append(grown * reads[:, chunk:, lanes])
            last = row[:, :, chunk - 1 :]  # (boards, 1, 1)
            leaves.append(k_t * (jnp.exp(last - row) * scale))
            kept.append(jnp.exp(g_t[:, chunk - 1 :, :dv]))  # exp G_last
        # The value heads' triangles side by side: (group x boards, c, c).
        a = jnp.concatenate(a, axis=0)
        diagonal = jnp.where(same, a, 0.0)
        inverse = _nilpotent_inverse(diagonal, SUB, eye, exact)
        inverse = exact(
            _nilpotent_inverse(exact(inverse, a - diagonal), chunk // SUB, eye, exact),
            inverse,
        )
        x = dot("bti,biv->btv", inverse, jnp.concatenate(rhs, axis=0))
        out = dot("bti,biv->btv", jnp.concatenate(pb, axis=0), x)
        heads = [slice(j * boards, (j + 1) * boards) for j in range(group)]
        o_ref[:, at, :] = jnp.concatenate(
            [carried[j] + out[h] for j, h in enumerate(heads)], axis=2
        )
        return jnp.concatenate(
            [
                kept[j] * state[:, :, j * dv : (j + 1) * dv]
                + dot("bkt,btv->bkv", leaves[j], x[h])
                for j, h in enumerate(heads)
            ],
            axis=2,
        )

    state = jnp.zeros((boards, q_ref.shape[2], group * dv), jnp.float32)
    if chunks <= _UNROLLED_CHUNKS:
        for c in range(chunks):
            state = one_chunk(c, state)
    else:
        jax.lax.fori_loop(0, chunks, one_chunk, state)


@functools.partial(
    jax.jit,
    static_argnames=("key_heads", "value_dim", "chunk", "dtype", "v_first", "interpret"),
)
def gated_delta_net(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    g: jax.Array,
    beta: jax.Array,
    *,
    key_heads: int,
    value_dim: int,
    chunk: int,
    dtype,
    v_first: int = 0,
    interpret: bool = False,
) -> jax.Array:
    """`nn/linear_attention.chunked_scalar`'s o for every board and value
    head, `(b, s, value heads x value_dim)` float32: q, k `(b, s,
    key_heads x dk)`, g <= 0 and beta `(b, s, value heads)`, value head
    j (reading key head j // group) in v `(b, s, ...)` at lanes `v_first
    + j x value_dim` (a whole number of `value_dim` blocks: v may be the
    mixer's `[q | k | v]`). Any s: the last chunk is filled with tokens
    that decay nothing and write nothing. The last grid step is padded
    where the block does not divide b (boards are independent; what a
    padded board computes is never written). `interpret=True` runs the
    kernel in the Pallas interpreter (CPU tests)."""
    dtype = jnp.dtype(dtype)
    b, s, width = q.shape
    value_heads = g.shape[2]
    dk, dv, group = width // key_heads, value_dim, value_heads // key_heads
    itemsize = max(dtype.itemsize, v.dtype.itemsize)
    refusal = _refusal(s, dk, dv, group, chunk, itemsize)
    if refusal:
        raise ValueError(refusal)
    if v_first % dv:
        raise ValueError(f"v's heads start at lane {v_first}, not on a block of {dv}")
    boards = block_boards(b, s, dk, dv, group, chunk, itemsize)
    chunks = -(-s // chunk)

    def by_chunk(x):
        # (b, s, value heads) -> (b, key heads, chunks, group, chunk): a
        # value head's row along the lanes a chunk; the fill decays
        # nothing and writes nothing.
        x = jnp.pad(x.astype(jnp.float32), ((0, 0), (0, chunks * chunk - s), (0, 0)))
        x = x.reshape(b, chunks, chunk, key_heads, group)
        return jnp.transpose(x, (0, 3, 1, 4, 2))

    def rows_of(lanes, block):
        # Whole chunks of rows over an array of s: the block's last rows
        # lie past the array, are not fetched and not written back.
        return pl.BlockSpec(
            (boards, chunks * chunk, lanes), block, memory_space=pltpu.VMEM
        )

    def value_head(j):
        return rows_of(dv, lambda n, h: (n, 0, v_first // dv + h * group + j))

    by_head = pl.BlockSpec(
        (boards, 1, chunks, group, chunk), lambda n, h: (n, h, 0, 0, 0),
        memory_space=pltpu.VMEM,
    )
    blocks, values = _board_bytes(s, dk, dv, group, chunk, itemsize)
    return pl.pallas_call(
        functools.partial(
            _chunk_kernel, seq=s, chunk=chunk, group=group, dtype=dtype
        ),
        grid=(pl.cdiv(b, boards), key_heads),
        in_specs=[
            rows_of(dk, lambda n, h: (n, 0, h)),
            rows_of(dk, lambda n, h: (n, 0, h)),
            *(value_head(j) for j in range(group)),
            by_head,
            by_head,
        ],
        out_specs=rows_of(group * dv, lambda n, h: (n, 0, h)),
        out_shape=jax.ShapeDtypeStruct((b, s, value_heads * dv), jnp.float32),
        scratch_shapes=[pltpu.VMEM((boards, chunks, group, chunk), jnp.float32)],
        compiler_params=vmem_params(
            boards * blocks, boards * values,
            dimension_semantics=("parallel", "parallel"),
        ),
        interpret=interpret,
        name="gated_delta_net",
    )(
        q.astype(dtype), k.astype(dtype), *(v,) * group, by_chunk(g), by_chunk(beta)
    )
