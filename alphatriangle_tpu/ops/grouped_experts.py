"""A round of the routed experts' MLP as two kernels: the grouped
products over only the row tiles its assignments fill, the activation
in the first one's epilogue, both outputs in the operands' type.

`nn/trunk.routed_experts` sorts a round's assignments by held expert
into `rows` rows (`USUAL_ROOM` times what even routing brings, so half
of them are padding as a rule) and hands over how many each expert has,
`sizes` (held,). The two calls share one tile schedule built from those
sizes (`schedule`): grid step i computes row tile `tiles[i]` for expert
`experts[i]`, the experts in order and each expert's tiles in order, so
that a held expert's weight block stays put while its row tiles pass
under it and is read once a call (once a column block and call where the
plan cuts the output's columns). A tile two experts share is computed
once for each, its rows masked to the expert's. The grid has at most
`rows / tm + held - 1` steps; the steps past the last expert's tiles do
no product.

- Up: x `(rows, d)` through `gate` and `up` (a SwiGLU: `silu(min(g, L))
  x clip(u, -L, L)` where `swiglu_limit` L is set) or `up` alone
  (relu^2), float32 sums, the activation float32, the hidden written
  once `(rows, inter)` in `dtype`.
- Down: the hidden through `down`, float32 sums, written once `(rows, d)`
  in `dtype`; every row past the round's assignments nought (the steps
  past the last expert's tiles write the tiles past them), so that
  nothing stray reaches the weighted sum that reads the rows back.

Operands are `dtype` where `jax.lax.ragged_dot` has them in the
layer's `_ragged`; the hidden is rounded to `dtype` where that layer
rounds it before the down product, the output where it rounds the
down product's. Only the order of the float32 sums over the reduced
axis may differ.

Forward only: the kernels have no transpose, so a training forward
keeps `ragged_dot`. No mode argument: `nn/trunk.py` runs a round as
these kernels or as `ragged_dot` by what `expert_path` can observe
(docs/KERNELS.md).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ._vmem import vmem_params

LANES = 128
# Rows a tile: a held expert's share of a block is a few hundred rows at
# the cells' widths, so a smaller tile wastes less of the tiles two
# experts share; what a step may plan to hold.
TILE_ROWS = 128
_VMEM_PLAN_BYTES = 48 << 20
# Scoped VMEM Mosaic takes beside the blocks and values counted (the
# clamped SwiGLU's up call at a hidden size of 7,168 took 0.7 MiB past
# a plan without it, on a v5e).
_BODY_SLACK = 4 << 20


def _call_bytes(
    tm: int, k: int, tn: int, weights: int, itemsize: int
) -> tuple[int, int]:
    """VMEM bytes of one call's grid step: (its pipelined blocks: a row
    tile of the input `(tm, k)`, `weights` weight blocks `(k, tn)`, the
    output tile `(tm, tn)`, all in their type; what the body keeps,
    counted generously: the input tile, each product, the epilogue and
    the tile it merges float32, and `_BODY_SLACK` for the compiler's
    own). The plan refuses, it does not tune."""
    blocks = (tm * k + weights * k * tn + tm * tn) * itemsize
    return blocks, (tm * k + (weights + 3) * tm * tn) * 4 + _BODY_SLACK


def column_block(
    tm: int, k: int, n: int, weights: int, itemsize: int
) -> int:
    """The widest block of whole 128-lane columns that divides `n` and
    whose step fits the plan; 0 where a single 128-lane block does not."""
    for tn in range(n - n % LANES, 0, -LANES):
        if n % tn:
            continue
        blocks, values = _call_bytes(tm, k, tn, weights, itemsize)
        if 2 * blocks + values <= _VMEM_PLAN_BYTES:
            return tn
    return 0


def tile_rows(rows: int) -> int:
    """Rows a tile: `TILE_ROWS`, or all of a buffer shorter than that
    (in whole packed bfloat16 tiles of 16)."""
    return min(TILE_ROWS, -(-rows // 16) * 16)


def _refusal(rows: int, d: int, inter: int, gated: bool, dtype) -> "str | None":
    """Why the kernels cannot take these shapes, or None: operands in
    bfloat16 (the hidden and output are written in their type), widths
    of whole 128-lane blocks, a step of each call that fits the plan."""
    if jnp.dtype(dtype) != jnp.bfloat16:
        return f"operands in {jnp.dtype(dtype).name}, not bfloat16"
    tm = tile_rows(rows)
    if (
        d % LANES
        or inter % LANES
        or not column_block(tm, d, inter, 1 + gated, 2)
        or not column_block(tm, inter, d, 1, 2)
    ):
        return (
            f"experts of {d} -> {inter} -> {d} are not whole 128-lane blocks, "
            f"or a step does not fit the kernels' VMEM plan ({_VMEM_PLAN_BYTES} bytes)"
        )
    return None


def expert_path(
    *,
    partitioned: bool,
    backend: str,
    train: bool,
    rows: int,
    d: int,
    inter: int,
    gated: bool,
    dtype,
) -> str:
    """"kernel" (these two) or "ragged" (`jax.lax.ragged_dot`, three
    calls or two) for a round of one sparse layer's routed experts, from
    what its site can observe. The kernels need: a TPU backend, a
    program the compiler does not partition (it refuses to lower a
    Mosaic call it would have to split over a mesh), a forward that is
    not differentiated (they have no transpose), and shapes they take
    (`_refusal`)."""
    fits = _refusal(rows, d, inter, gated, dtype) is None
    on = backend == "tpu" and not partitioned and not train
    return "kernel" if on and fits else "ragged"


def schedule(sizes: jax.Array, rows: int, tm: int):
    """The tile schedule of a round whose held experts have `sizes`
    (held,) rows, in order from row 0: (experts, tiles_in, tiles_out,
    bounds, active). Step i < `active` computes row tile `tiles_in[i]`
    (= `tiles_out[i]`) for expert `experts[i]`, whose rows are
    `bounds[e] <= r < bounds[e + 1]`. A step past them keeps the last
    one's expert and input tile (nothing is fetched again) and points
    `tiles_out` at the tiles past the assignments, one each, then at the
    last tile again."""
    held = sizes.shape[0]
    tiles = -(-rows // tm)
    steps = tiles + held - 1
    ends = jnp.cumsum(sizes)
    starts = ends - sizes
    first = starts // tm
    count = jnp.where(sizes > 0, (ends - 1) // tm - first + 1, 0)
    active = count.sum()
    experts = jnp.repeat(
        jnp.arange(held, dtype=jnp.int32), count, total_repeat_length=steps
    )
    step = jnp.arange(steps, dtype=jnp.int32)
    tile = first[experts] + step - (jnp.cumsum(count) - count)[experts]
    on = step < active
    last = jnp.maximum(active - 1, 0)
    experts = jnp.where(on, experts, experts[last])
    tiles_in = jnp.clip(jnp.where(on, tile, tile[last]), 0, tiles - 1)
    filled = -(-ends[-1] // tm)
    tiles_out = jnp.where(on, tile, jnp.minimum(filled + step - active, tiles - 1))
    bounds = jnp.concatenate([jnp.zeros((1,), jnp.int32), ends.astype(jnp.int32)])
    return (
        experts.astype(jnp.int32), tiles_in.astype(jnp.int32),
        tiles_out.astype(jnp.int32), bounds, active.astype(jnp.int32)[None],
    )


def _first(i, tiles_ref):
    """Whether step i is the first to write its tile."""
    return jnp.logical_or(i == 0, tiles_ref[jnp.maximum(i - 1, 0)] != tiles_ref[i])


def _merge(o_ref, value, i, tiles_ref, experts_ref, bounds_ref, *, tm: int):
    """Write `value` (tm, tn) float32 into the rows of step i's tile
    that belong to its expert: the rest keep what an earlier step of the
    same tile wrote, or nought on the tile's first step."""
    tile, expert = tiles_ref[i], experts_ref[i]
    first = _first(i, tiles_ref)
    row = jax.lax.broadcasted_iota(jnp.int32, (tm, 1), 0)
    at = tile * tm + row
    mine = jnp.logical_and(at >= bounds_ref[expert], at < bounds_ref[expert + 1])
    # every row kept on a later step, none on the first
    kept = row < jnp.where(first, 0, tm)
    old = jnp.where(kept, o_ref[...].astype(jnp.float32), 0.0)
    o_ref[...] = jnp.where(mine, value, old).astype(o_ref.dtype)


def _up_kernel(
    experts_ref, tiles_ref, _, bounds_ref, active_ref, x_ref, *refs,
    tm: int, gated: bool, limit: "float | None",
):
    w_refs, o_ref = refs[:-1], refs[-1]
    i = pl.program_id(1)

    @pl.when(i < active_ref[0])
    def _():
        x = x_ref[...]
        u = jnp.dot(x, w_refs[-1][...], preferred_element_type=jnp.float32)
        if gated:
            g = jnp.dot(x, w_refs[0][...], preferred_element_type=jnp.float32)
            if limit is not None:
                g, u = jnp.minimum(g, limit), jnp.clip(u, -limit, limit)
            h = g * jax.nn.sigmoid(g) * u
        else:
            h = jnp.square(jnp.maximum(u, 0.0))
        _merge(o_ref, h, i, tiles_ref, experts_ref, bounds_ref, tm=tm)


def _down_kernel(
    experts_ref, _, tiles_ref, bounds_ref, active_ref, h_ref, w_ref, o_ref, *,
    tm: int,
):
    i = pl.program_id(1)

    @pl.when(i < active_ref[0])
    def _():
        y = jnp.dot(h_ref[...], w_ref[...], preferred_element_type=jnp.float32)
        _merge(o_ref, y, i, tiles_ref, experts_ref, bounds_ref, tm=tm)

    # a tile past the round's assignments, on its first step: nought
    @pl.when(jnp.logical_and(i >= active_ref[0], _first(i, tiles_ref)))
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)


def _grouped_call(kernel, x, weights, meta, *, tm: int, out_index: int, name: str,
                  interpret: bool):
    """One call over the schedule `meta`: x `(rows, k)`, `weights` each
    `(held, k, n)`, out `(rows, n)` in x's type; the output's tile is
    `meta[out_index]`'s (1: the input tile, 2: `tiles_out`)."""
    rows, k = x.shape
    n = weights[0].shape[2]
    itemsize = x.dtype.itemsize
    tn = column_block(tm, k, n, len(weights), itemsize)
    steps = meta[0].shape[0]
    blocks, values = _call_bytes(tm, k, tn, len(weights), itemsize)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(meta),
            grid=(n // tn, steps),
            in_specs=[
                pl.BlockSpec((tm, k), lambda c, i, e, t, *_: (t[i], 0)),
                *(
                    pl.BlockSpec((None, k, tn), lambda c, i, e, *_: (e[i], 0, c))
                    for _ in weights
                ),
            ],
            out_specs=pl.BlockSpec(
                (tm, tn), lambda c, i, *m: (m[out_index][i], c)
            ),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), x.dtype),
        compiler_params=vmem_params(
            blocks, values, dimension_semantics=("parallel", "arbitrary")
        ),
        interpret=interpret,
        name=name,
    )(*meta, x, *weights)


@functools.partial(jax.jit, static_argnames=("limit", "dtype", "interpret"))
def grouped_experts(
    x: jax.Array,
    sizes: jax.Array,
    up: jax.Array,
    down: jax.Array,
    gate: "jax.Array | None" = None,
    *,
    limit: "float | None" = None,
    dtype,
    interpret: bool = False,
) -> jax.Array:
    """The held experts' MLP over a round's rows x `(rows, d)`, sorted
    by expert with `sizes` (held,) rows each from row 0: `(rows, d)` in
    `dtype`, nought past `sizes.sum()`. `up` and `down` `(held, d,
    inter)`, `(held, inter, d)`; with `gate` each expert is a SwiGLU
    (clamped at `limit` where given), without it relu(x up)^2. Any
    `rows`: a last tile past the buffer is read as rows no expert owns
    and written only where it lies inside. `interpret=True` runs the
    kernels in the Pallas interpreter (CPU tests)."""
    dtype = jnp.dtype(dtype)
    rows, d = x.shape
    inter = up.shape[2]
    gated = gate is not None
    refusal = _refusal(rows, d, inter, gated, dtype)
    if refusal:
        raise ValueError(refusal)
    tm = tile_rows(rows)
    meta = schedule(sizes.astype(jnp.int32), rows, tm)
    weights = [w.astype(dtype) for w in ((gate, up) if gated else (up,))]
    hidden = _grouped_call(
        functools.partial(_up_kernel, tm=tm, gated=gated, limit=limit),
        x.astype(dtype), weights, meta, tm=tm, out_index=1,
        name="grouped_experts_up", interpret=interpret,
    )
    return _grouped_call(
        functools.partial(_down_kernel, tm=tm),
        hidden, [down.astype(dtype)], meta, tm=tm, out_index=2,
        name="grouped_experts_down", interpret=interpret,
    )
