"""Neural-network architecture configuration.

Capability parity with the reference `ModelConfig`
(`alphatriangle/config/model_config.py:17-59`): conv trunk, residual
blocks, optional transformer encoder, shared FC, policy head, C51
distributional value head. TPU-specific additions: compute dtype
(bfloat16 on MXU), rematerialization, and a norm choice that defaults to
GroupNorm — BatchNorm cross-example state is hostile to pjit sharding,
so it is supported but not the default.
"""

from typing import Literal

from pydantic import BaseModel, Field, model_validator


class TrunkConfig(BaseModel):
    """A decoder stack in the encoder's place (nn/trunk.py): RMSNorm,
    routed experts with a shared expert, and by layer one of five
    mixers: grouped-query softmax attention under a causal window
    (`sliding_attention`) or a causal full mask (`full_attention`), the
    gated delta rule with a decay for every channel (`linear_attention`,
    KDA: short causal convolutions, a recurrent state of head_dim x
    head_dim a head, no score matrix), latent attention
    (`latent_attention`, MLA: keys and values expanded from a latent of
    `kv_lora_rank`, a rotary part all heads share; with `q_lora_rank`
    the query too comes from a latent, under an RMSNorm; `latent_gate`
    False leaves the head-wise output gate out) and a selective state
    space (`state_space`, Mamba-2, nn/state_space.py: `mamba_num_heads`
    heads of `mamba_head_dim` with a state of `mamba_head_dim` x
    `ssm_state_size` each and one decay a head, B and C shared by
    `n_groups` groups of heads, one causal convolution of `conv_kernel`
    taps over x, B and C together, a gated RMSNorm over each group's
    channels, the recurrence `chunk_size` tokens at a time). The keys
    are a published `config.json`'s, under its names; layer l is
    `layer_types[l]` with `mlp_layer_types[l]`, and "none" in either
    list says that layer l has no such half: it is then the other half
    alone, under one norm and one residual. `head_dim` is the softmax
    and linear layers' width of a head: a stack that has neither has
    none and reads none.

    `mlp_hidden_act` "silu" makes every MLP a SwiGLU (gate, up, down);
    "relu2" an ungated one, down(relu(up x)^2), in the experts and the
    shared expert. With `moe_latent_size` the routed experts live in a
    latent of that width: one projection down before the tokens are
    sorted, one up after the held experts' sum. The shared expert is
    `moe_shared_expert_intermediate_size` wide where that is given and
    as wide as a routed expert otherwise, and reads the hidden size.

    `experts_held` = (first, count): the router scores all
    `num_experts`; this process computes the experts it holds for the
    tokens routed to them and adds nothing for the others (one chip's
    share under expert parallelism; (0, num_experts) is the whole
    layer). `n_group` > 1 makes the router's choice a grouped one: the
    experts stand in `n_group` groups, a group's score is the sum of its
    two highest, the `topk_group` best groups stay in the choice.

    `norm_position`, `qk_norm` and `rope_layers` are what such a file
    leaves to the family's convention:
    "post" is x + norm(f(x)), "pre" x + f(norm(x)); `qk_norm` True an
    RMSNorm on q and k per head of the softmax layers, "l2" the L2 norm
    on q and k per head of the linear layers (and none on a latent
    layer beyond its latent's), "none" no norm on q or k of a full
    attention layer; `rope_layers` "sliding" the whole head
    turned, halves paired, on the sliding layers only, "latent" the
    `qk_rope_head_dim` part of a latent layer turned, neighbours paired
    (interleaved), and no positions anywhere else. A layer kind whose
    choice is not the one it was written for is refused."""

    hidden_size: int = Field(gt=0)
    num_attention_heads: int = Field(gt=0)
    num_key_value_heads: int = Field(gt=0)
    head_dim: int | None = Field(default=None, gt=0)
    intermediate_size: int = Field(gt=0)
    moe_intermediate_size: int = Field(gt=0)
    num_experts: int = Field(gt=0)
    num_experts_per_tok: int = Field(gt=0)
    num_shared_experts: int = Field(default=1, ge=0)
    routed_scaling_factor: float = Field(default=1.0)
    n_group: int = Field(default=1, gt=0)
    topk_group: int = Field(default=1, gt=0)
    sliding_window: int | None = Field(default=None, gt=0)
    layer_types: list[
        Literal[
            "sliding_attention", "full_attention",
            "linear_attention", "latent_attention", "state_space", "none",
        ]
    ]
    mlp_layer_types: list[Literal["dense", "sparse", "none"]]
    rope_theta: float = Field(default=1e6, gt=0)
    rms_norm_eps: float = Field(default=1e-5, gt=0)
    experts_held: tuple[int, int]
    # A linear layer (KDA): the causal depthwise convolution's taps and
    # the floor of a step's log decay (g in (kda_lower_bound, 0)).
    short_conv_kernel_size: int = Field(default=4, gt=0)
    kda_lower_bound: float = Field(default=-5.0, lt=0)
    # A latent layer (MLA): the latent's width, a head's query/key part
    # without and with positions, a head's value width.
    kv_lora_rank: int | None = Field(default=None, gt=0)
    qk_nope_head_dim: int | None = Field(default=None, gt=0)
    qk_rope_head_dim: int | None = Field(default=None, gt=0)
    v_head_dim: int | None = Field(default=None, gt=0)
    # The query's latent (q = RMSNorm(x Wq_a) Wq_b); None = x Wq. And
    # whether the context is gated a head by sigmoid(x Wg) before Wo.
    q_lora_rank: int | None = Field(default=None, gt=0)
    latent_gate: bool = Field(default=True)
    # A state-space layer (Mamba-2): heads and their width, the state's
    # width a head, the groups that share B and C, the convolution's
    # taps and whether it has a bias, the tokens the recurrence takes
    # at a time (its chunked form, nn/state_space.py).
    mamba_num_heads: int | None = Field(default=None, gt=0)
    mamba_head_dim: int | None = Field(default=None, gt=0)
    ssm_state_size: int | None = Field(default=None, gt=0)
    n_groups: int = Field(default=1, gt=0)
    conv_kernel: int = Field(default=4, gt=0)
    use_conv_bias: bool = Field(default=True)
    chunk_size: int = Field(default=128, gt=0)
    # The experts' latent (None: they read and write the hidden size),
    # the shared expert's own width (None: a routed expert's), and the
    # MLPs' activation.
    moe_latent_size: int | None = Field(default=None, gt=0)
    moe_shared_expert_intermediate_size: int | None = Field(default=None, gt=0)
    mlp_hidden_act: Literal["silu", "relu2"] = Field(default="silu")

    norm_position: Literal["post", "pre"] = Field(default="post")
    qk_norm: Literal[True, "l2", "none"] = Field(default=True)
    rope_layers: Literal["sliding", "latent"] = Field(default="sliding")
    # A per-expert float32 parameter added to the scores for the choice
    # alone (the weights stay the raw scores'): how a router balanced by
    # bias selects. Noughts as initialised; a checkpoint brings its own.
    router_bias: bool = Field(default=False)
    # Boards the net takes at a time where a search evaluates a leaf
    # batch (cut into such blocks inside the program); None = all at once.
    block_boards: int | None = Field(default=None, gt=0)
    # Boards a learner step takes at a time (rl/trainer.py: forward and
    # backward a block, the gradients added in float32, one optimizer
    # update a step); None = the whole batch at once.
    learner_block_boards: int | None = Field(default=None, gt=0)
    # What a training step moves each selection bias by, against the
    # sign of its expert's load error (the bias is reached by no
    # gradient: rl/trainer.py); read only with `router_bias`. Nought:
    # the biases stay what the checkpoint brought.
    router_bias_rate: float = Field(default=0.0, ge=0)
    # Tokens a linear layer's recurrence takes at a time (its chunked
    # form, nn/linear_attention.py): a multiple of that module's
    # sub-block, whose rows x |kda_lower_bound| must stay under what
    # float32's exp holds.
    linear_chunk: int = Field(default=64, gt=0)

    @model_validator(mode="after")
    def _check(self) -> "TrunkConfig":
        if len(self.layer_types) != len(self.mlp_layer_types):
            raise ValueError(
                "layer_types and mlp_layer_types must name the same layers."
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_attention_heads ({self.num_attention_heads}) must be a "
                f"multiple of num_key_value_heads ({self.num_key_value_heads})."
            )
        if self.head_dim is None:
            if set(self.layer_types) - {"latent_attention", "state_space", "none"}:
                raise ValueError(
                    "head_dim may be left out only where no mixer is softmax "
                    "or linear attention."
                )
        elif self.head_dim % 2:
            raise ValueError("head_dim must be even (rotary pairs).")
        if self.num_experts_per_tok > self.num_experts:
            raise ValueError("num_experts_per_tok exceeds num_experts.")
        first, count = self.experts_held
        if first < 0 or count < 1 or first + count > self.num_experts:
            raise ValueError(
                f"experts_held {self.experts_held} lies outside the "
                f"{self.num_experts} experts."
            )
        if self.num_experts % self.n_group or self.topk_group > self.n_group:
            raise ValueError(
                f"{self.num_experts} experts do not stand in {self.n_group} "
                f"groups of which {self.topk_group} stay."
            )
        if self.n_group > 1 and (
            self.num_experts // self.n_group < 2
            or self.topk_group * (self.num_experts // self.n_group)
            < self.num_experts_per_tok
        ):
            raise ValueError(
                "a group holds fewer than 2 experts, or the groups that stay "
                "fewer than num_experts_per_tok."
            )
        halves = list(zip(self.layer_types, self.mlp_layer_types))
        if ("none", "none") in halves:
            raise ValueError(
                f"layer {halves.index(('none', 'none'))} has neither a mixer "
                "nor an MLP."
            )
        kinds = set(self.layer_types)
        softmax = kinds & {"sliding_attention", "full_attention"}
        if self.qk_norm == "none":
            # Written for the full mask alone; a linear layer's "l2"
            # below refuses the rest.
            softmax -= {"full_attention"}
        if softmax and self.qk_norm is not True:
            raise ValueError(f"{sorted(softmax)} layers are written for qk_norm True.")
        if "sliding_attention" in kinds and (
            self.sliding_window is None or self.rope_layers != "sliding"
        ):
            raise ValueError(
                "sliding_attention layers need sliding_window and "
                'rope_layers "sliding".'
            )
        if "linear_attention" in kinds and self.qk_norm != "l2":
            raise ValueError('linear_attention layers are written for qk_norm "l2".')
        if "latent_attention" in kinds:
            widths = (
                self.kv_lora_rank, self.qk_nope_head_dim,
                self.qk_rope_head_dim, self.v_head_dim,
            )
            if None in widths or self.rope_layers != "latent":
                raise ValueError(
                    "latent_attention layers need kv_lora_rank, "
                    "qk_nope_head_dim, qk_rope_head_dim, v_head_dim and "
                    'rope_layers "latent".'
                )
            if self.qk_rope_head_dim % 2:
                raise ValueError("qk_rope_head_dim must be even (rotary pairs).")
        if "state_space" in kinds:
            if None in (self.mamba_num_heads, self.mamba_head_dim, self.ssm_state_size):
                raise ValueError(
                    "state_space layers need mamba_num_heads, mamba_head_dim "
                    "and ssm_state_size."
                )
            if self.mamba_num_heads % self.n_groups:
                raise ValueError(
                    f"{self.mamba_num_heads} state-space heads do not stand in "
                    f"{self.n_groups} groups."
                )
        if self.mlp_hidden_act == "relu2" and "dense" in self.mlp_layer_types:
            raise ValueError("the dense MLP is written for mlp_hidden_act silu.")
        return self


class ModelConfig(BaseModel):
    """Policy/value network hyperparameters (pydantic)."""

    GRID_INPUT_CHANNELS: int = Field(default=1, gt=0)

    # --- CNN trunk ---
    CONV_FILTERS: list[int] = Field(default=[32, 64, 128])
    CONV_KERNEL_SIZES: list[int] = Field(default=[3, 3, 3])
    CONV_STRIDES: list[int] = Field(default=[1, 1, 1])

    # --- Residual blocks ---
    NUM_RESIDUAL_BLOCKS: int = Field(default=2, ge=0)
    RESIDUAL_BLOCK_FILTERS: int = Field(default=128, gt=0)

    # --- Optional transformer encoder over the spatial sequence ---
    USE_TRANSFORMER: bool = Field(default=True)
    TRANSFORMER_DIM: int = Field(default=128, gt=0)
    TRANSFORMER_HEADS: int = Field(default=4, gt=0)
    TRANSFORMER_LAYERS: int = Field(default=2, ge=0)
    TRANSFORMER_FC_DIM: int = Field(default=256, gt=0)
    # A decoder stack in the encoder layers' place; None = the encoder
    # above, key for key.
    TRUNK: TrunkConfig | None = Field(default=None)

    # --- Heads ---
    FC_DIMS_SHARED: list[int] = Field(default=[128])
    POLICY_HEAD_DIMS: list[int] = Field(default=[128])
    VALUE_HEAD_DIMS: list[int] = Field(default=[128])

    # --- Distributional (C51) value head ---
    NUM_VALUE_ATOMS: int = Field(default=51, gt=1)
    VALUE_MIN: float = Field(default=-10.0)
    VALUE_MAX: float = Field(default=10.0)

    # --- Misc ---
    ACTIVATION_FUNCTION: Literal["ReLU", "GELU", "SiLU", "Tanh", "Sigmoid"] = Field(
        default="ReLU"
    )
    # Norm layer. "batch" matches the reference (`model_config.py:54`) but
    # carries running statistics; "group" is stateless and shards cleanly.
    NORM_TYPE: Literal["group", "layer", "batch", "none"] = Field(default="group")

    OTHER_NN_INPUT_FEATURES_DIM: int = Field(default=30, gt=0)

    # --- TPU-specific ---
    COMPUTE_DTYPE: Literal["bfloat16", "float32"] = Field(default="bfloat16")
    # "bfloat16": parameters are made and held in bfloat16 (a trunk
    # published in it, too large to keep a float32 original beside).
    PARAM_DTYPE: Literal["float32", "bfloat16"] = Field(default="float32")
    # jax.checkpoint the residual + transformer blocks, and in a training
    # forward each layer of a TRUNK, to trade FLOPs for HBM.
    REMAT: bool = Field(default=False)
    # Param dtype the INFERENCE family (rollout chunk, serve dispatch,
    # arena/eval) reads the network at; the learner family always
    # trains the f32 originals (nn/precision.py, docs/KERNELS.md).
    # "int8" is weight-only: matrix weights become int8 tensors with
    # per-channel f32 scales, dequantized to bf16 on the forward trunk.
    INFERENCE_PRECISION: Literal["float32", "bfloat16", "int8"] = Field(
        default="float32"
    )

    @property
    def USE_BATCH_NORM(self) -> bool:
        """Parity alias for the reference knob, derived from NORM_TYPE so
        the two can never disagree (`alphatriangle/config/model_config.py:54`)."""
        return self.NORM_TYPE == "batch"

    @model_validator(mode="before")
    @classmethod
    def _map_use_batch_norm(cls, data):
        # Accept the reference's USE_BATCH_NORM kwarg by mapping it onto
        # NORM_TYPE (explicit NORM_TYPE wins if both are given). False
        # means "no normalization" in the reference architecture, not an
        # alternative norm.
        if isinstance(data, dict) and "USE_BATCH_NORM" in data:
            data = {**data}
            use_bn = data.pop("USE_BATCH_NORM")
            if "NORM_TYPE" not in data:
                data["NORM_TYPE"] = "batch" if use_bn else "none"
        return data

    @model_validator(mode="after")
    def _check_conv_consistency(self) -> "ModelConfig":
        n = len(self.CONV_FILTERS)
        if len(self.CONV_KERNEL_SIZES) != n or len(self.CONV_STRIDES) != n:
            raise ValueError(
                "CONV_FILTERS, CONV_KERNEL_SIZES and CONV_STRIDES must have "
                "matching lengths."
            )
        return self

    @model_validator(mode="after")
    def _check_transformer(self) -> "ModelConfig":
        if self.USE_TRANSFORMER and self.TRANSFORMER_LAYERS > 0:
            if self.TRANSFORMER_DIM % self.TRANSFORMER_HEADS != 0:
                raise ValueError(
                    f"TRANSFORMER_DIM ({self.TRANSFORMER_DIM}) must be divisible "
                    f"by TRANSFORMER_HEADS ({self.TRANSFORMER_HEADS})."
                )
        return self

    @model_validator(mode="after")
    def _check_value_support(self) -> "ModelConfig":
        if self.VALUE_MIN >= self.VALUE_MAX:
            raise ValueError("VALUE_MIN must be strictly less than VALUE_MAX.")
        return self


ModelConfig.model_rebuild(force=True)
