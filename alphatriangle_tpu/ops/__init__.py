"""Custom TPU ops: Pallas kernels beside the lowerings they replace.

Each kernel of this package (`encoder_layer`, `delta_rule`,
`gated_delta_rule`, `state_space_scan`, `grouped_experts`; docs/KERNELS.md)
sits beside the XLA or Flax lowering it replaces, and the call picks
one by what it can observe: the backend, the mesh of the trace, the
widths, `train`. The four ops exported here are the search's and the
replay programs' own: the descent's row gather, the stratified PER draw
and the subtree promotion are one XLA lowering each; the wave's backup
still has a Pallas lowering beside its XLA chain that a config field
(`MCTSConfig.backup_update`) chooses, until the search picks it by
observation too (ROADMAP S3).
"""

from .gather_rows import gather_rows
from .mcts_backup import backup_update
from .per_sample import per_sample
from .subtree_reuse import subtree_promote

__all__ = ["backup_update", "gather_rows", "per_sample", "subtree_promote"]
