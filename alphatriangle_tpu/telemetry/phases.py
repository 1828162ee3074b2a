"""The names `jax.named_scope` gives the phases of the device programs.

The one list: the code sites spell these strings, the phase reader
(`profiling.phase_seconds`) maps a device operation to the innermost of
them in its `op_name`, the tests hold the lowered programs to them, and
docs/OBSERVABILITY.md lists them. `learner/backward` is drawn by no
site: it is what autodiff's transpose of `learner/forward_loss` reads
as (`transpose(jvp(learner/forward_loss))`).
"""

PHASES = (
    # rollout chunk (rl/self_play.py, mcts/search.py, mcts/gumbel.py)
    "rollout/features",
    "search/init",
    "search/descend",
    "search/expand",
    "search/evaluate",
    "search/backup",
    "gumbel/root",
    "rollout/targets",
    "rollout/env_step",
    "rollout/reset",
    "rollout/promote",
    # the net, inside search/evaluate and learner/forward_loss (nn/model.py)
    "net/conv",
    "net/residual",
    "net/encoder",
    # the attention of an encoder layer on the Flax path, from q, k, v
    # to the heads' output (Flax's function or one handed in); a layer
    # fused by ops/encoder_layer.py is one call under net/encoder
    "net/encoder/attention",
    "net/heads",
    # a decoder stack as the trunk (nn/trunk.py), in net/encoder's
    # place; net/trunk itself keeps the projection, the norms and the
    # residual sums
    "net/trunk",
    "net/trunk/attn_window",
    "net/trunk/attn_full",
    # a linear-attention layer's mixer (projections, convolutions,
    # norms, gate), and inside it the recurrence alone
    "net/trunk/linear_attn",
    "net/trunk/linear_attn/scan",
    "net/trunk/latent_attn",
    # a state-space layer's mixer (projections, convolution, gated
    # norm), and inside it the recurrence alone
    "net/trunk/state_space",
    "net/trunk/state_space/scan",
    "net/trunk/dense_mlp",
    "net/trunk/router",
    # the two projections of experts that live in a latent
    "net/trunk/latent_proj",
    "net/trunk/experts",
    "net/trunk/shared_expert",
    # fused learner (rl/trainer.py); a step that takes its batch in
    # blocks runs gather, forward_loss and backward inside learner/block
    # (which keeps only what is left over), adds each block's gradients
    # under learner/accumulate, and moves the routers' selection biases
    # under learner/router_bias
    "learner/block",
    "learner/gather",
    "learner/forward_loss",
    "learner/td",
    "learner/backward",
    "learner/accumulate",
    "learner/optimizer",
    "learner/router_bias",
    # ring ingest (rl/device_buffer.py `ring_scatter`: validation, the
    # gather of the rows that pass, the window write; its host span
    # `replay.ingest_wait` carries `rows` and `windows`)
    "replay/ingest_scatter",
)
