"""NamedSharding helpers for the (dp, mdl, sp) mesh.

The learner's sharding contract (SURVEY.md §2c "TPU-native equivalent"):
- training batches are **sharded on the dp axis** (leading dim);
- model/optimizer state is **replicated** on a 1-wide mdl axis, and
  **tensor-sharded Megatron-style over the mdl axis** when it is wider:
  attention QKV projections and the MLP up-projection split their
  output dimension (column parallel), the attention out-projection and
  MLP down-projection split their input dimension (row parallel), so
  the only cross-shard traffic per layer is the psum after each
  row-parallel matmul — which, like the gradient all-reduce, the code
  never spells: XLA/GSPMD inserts the ICI collectives from the
  shardings alone.

Everything here works identically on a real TPU mesh and on the
virtual 8-CPU-device mesh the tests use.
"""

import logging

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

logger = logging.getLogger(__name__)


def replicated(mesh: Mesh) -> NamedSharding:
    """Fully-replicated sharding (params, opt state, scalars)."""
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh, dp_axis: str = "dp") -> NamedSharding:
    """Leading-dim sharding over the data-parallel axis."""
    return NamedSharding(mesh, P(dp_axis))


# Transformer tensor-parallel layout (Megatron-LM, arXiv:1909.08053):
# per (path-suffix pattern, rank) the PartitionSpec template and which
# dim must divide the mdl axis. Attention kernels are (d, heads, hd)
# for q/k/v and (heads, hd, d) for out — sharding the HEADS dim keeps
# every head intact on one shard, so attention itself needs no
# communication; the out-projection's psum is the layer's only
# collective. MLP: Dense_0 (d, mlp) columns, Dense_1 (mlp, d) rows.
def _tp_spec(path: str, shape: tuple, mdl_axis: str, mdl: int):
    """PartitionSpec for one transformer param leaf, or None (replicate)."""
    if "TransformerEncoderLayer" not in path:
        return None
    if "MultiHeadDotProductAttention" in path:
        for proj in ("query", "key", "value"):
            if f"/{proj}/" in path:
                if path.endswith("kernel") and len(shape) == 3:
                    ok = shape[1] % mdl == 0
                    return P(None, mdl_axis, None) if ok else None
                if path.endswith("bias") and len(shape) == 2:
                    ok = shape[0] % mdl == 0
                    return P(mdl_axis, None) if ok else None
        if "/out/" in path and path.endswith("kernel") and len(shape) == 3:
            ok = shape[0] % mdl == 0
            return P(mdl_axis, None, None) if ok else None
        return None  # out bias, etc.: replicated
    if "/Dense_0/" in path:  # up-projection: column parallel
        if path.endswith("kernel") and len(shape) == 2:
            return P(None, mdl_axis) if shape[1] % mdl == 0 else None
        if path.endswith("bias") and len(shape) == 1:
            return P(mdl_axis) if shape[0] % mdl == 0 else None
    if "/Dense_1/" in path:  # down-projection: row parallel
        if path.endswith("kernel") and len(shape) == 2:
            return P(mdl_axis, None) if shape[0] % mdl == 0 else None
    return None


def state_shardings(
    mesh: Mesh, state, mdl_axis: "str | None" = "mdl"
) -> object:
    """Shardings matching `state`'s structure: tensor-parallel specs
    for transformer params (and their optimizer moments — optax state
    mirrors the params tree, so the same path patterns match) when the
    mesh's mdl axis is wider than 1; replicated otherwise (including
    mdl_axis=None, the no-tensor-parallelism contract)."""
    rep = replicated(mesh)
    mdl = mesh.shape.get(mdl_axis, 1) if mdl_axis is not None else 1
    if mdl <= 1:
        return jax.tree_util.tree_map(lambda _: rep, state)
    logger.info(
        "Tensor parallelism active: transformer params shard over "
        "%s=%d (Megatron layout).",
        mdl_axis,
        mdl,
    )

    def spec_for(path_entries, leaf) -> NamedSharding:
        path = "/".join(
            str(getattr(k, "key", getattr(k, "name", k)))
            for k in path_entries
        )
        spec = _tp_spec(path, tuple(getattr(leaf, "shape", ())), mdl_axis, mdl)
        return NamedSharding(mesh, spec) if spec is not None else rep

    return jax.tree_util.tree_map_with_path(spec_for, state)


def shard_batch(mesh: Mesh, batch, dp_axis: str = "dp"):
    """Place a host batch pytree onto the mesh, sharded on `dp_axis`.

    Single-process: a plain sharded `device_put`; every leaf's leading
    dimension must be divisible by the dp axis size. Multi-process
    (mesh spans hosts): each process passes its LOCAL batch shard and
    the leaves are assembled into global arrays — the global batch is
    the per-process batches concatenated along the leading dim in
    process order.
    """
    sh = batch_sharding(mesh, dp_axis)
    if jax.process_count() > 1:
        return jax.tree_util.tree_map(
            lambda x: jax.make_array_from_process_local_data(sh, x), batch
        )
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), batch)


def local_rows(arr, axis: int = 0) -> "np.ndarray":
    """This process's rows of an `axis`-sharded global array.

    Inverse of `shard_batch` for per-sample outputs (e.g. PER TD
    errors): each host gets back exactly the rows it contributed, in
    order, so host-local bookkeeping (priority updates) needs no
    cross-host traffic. Single-process: the whole array. `axis` is the
    batch-sharded dimension (1 for stacked fused-step outputs (K, B)).
    """
    import numpy as np

    if jax.process_count() == 1:
        return np.asarray(arr)
    shards = sorted(
        arr.addressable_shards, key=lambda s: s.index[axis].start or 0
    )
    return np.concatenate([np.asarray(s.data) for s in shards], axis=axis)
