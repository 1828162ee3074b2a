"""Custom TPU ops (Pallas kernels beside their XLA lowerings).

The four ops exported here follow one pattern (docs/KERNELS.md): a
Pallas TPU lowering plus interchangeable XLA lowerings, numerically
pinned against each other by parity tests, with a config knob selecting
the backend. `encoder_layer.py` and `delta_rule.py` have no knob:
`nn/model.py` runs an encoder layer as that kernel or as Flax's modules,
and `nn/trunk.py` a linear-attention layer's recurrence as that kernel
or as `nn/linear_attention.chunked`, by what the call can observe.
"""

from .gather_rows import gather_rows
from .mcts_backup import backup_update
from .per_sample import per_sample
from .subtree_reuse import subtree_promote

__all__ = ["backup_update", "gather_rows", "per_sample", "subtree_promote"]
