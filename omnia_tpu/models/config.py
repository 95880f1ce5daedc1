"""Model configurations for the omnia_tpu model family.

The reference platform (AltairaLabs/Omnia) declares models purely as strings on
Provider CRs (reference api/v1alpha1/provider_types.go:322-412) and never
executes them. Here models run on-device, so the config is a real
architecture description. Presets cover the BASELINE.json staged configs:
Llama-3-8B / 70B and Mixtral-8x7B, plus tiny variants for tests.
"""

from __future__ import annotations

import dataclasses
from typing import Optional


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 8
    head_dim: int = 128
    ffn_hidden_size: int = 14336
    rope_theta: float = 500000.0
    # Llama-3.1 'llama3' rope_type long-context frequency remap, as a
    # hashable tuple (factor, low_freq_factor, high_freq_factor,
    # original_max_position_embeddings); None = plain RoPE.
    rope_scaling: Optional[tuple] = None
    rms_norm_eps: float = 1e-5
    tie_embeddings: bool = False
    # MoE (Mixtral-style). num_experts == 0 means dense MLP.
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Maximum sequence length the serving engine sizes KV caches for.
    max_seq_len: int = 8192
    # Latent attention (MLA, models/mla.py), served when kv_rank > 0 (the
    # sources' kv_lora_rank; q_rank is their q_lora_rank): the cached row
    # of a token is [c | k_rope], kv_rank + qk_rope_head_dim values shared
    # by every head. The query passes through a rank of its own; a head's
    # query and key are qk_nope_head_dim values without position beside
    # qk_rope_head_dim rotated ones, its value v_head_dim.
    # q_rank 0 with a kv_rank: no query rank, q = h·Wq straight to the heads.
    kv_rank: int = 0
    q_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # YaRN rotary scaling as a hashable tuple (factor,
    # original_max_position_embeddings, beta_fast, beta_slow, mscale,
    # mscale_all_dim); None = none. Rotary pairs are dims (2i, 2i+1)
    # when rope_interleave, else the two halves.
    rope_yarn: Optional[tuple] = None
    rope_interleave: bool = False
    # q ← q·(1 + β·ln(1 + ⌊pos / original context⌋)); 0 = none.
    q_scaling_beta: float = 0.0
    # The expert layer of that family (ops/moe.py::moe_dropless): the
    # router scores all num_experts; this chip holds num_experts_held of
    # them (0 = all), those of expert_rank's share, each moe_ffn_hidden_size
    # wide, beside num_shared_experts experts that every token takes.
    moe_ffn_hidden_size: int = 0
    num_shared_experts: int = 0
    num_experts_held: int = 0
    expert_rank: int = 0
    routed_scaling_factor: float = 1.0
    # The router's score of an expert, "softmax" over all of them or
    # "sigmoid" each on its own, and how the k are picked: "greedy" by the
    # score, "noaux_tc" by the score plus a selection bias an expert (the
    # weights are the scores themselves either way).
    router_scoring: str = "softmax"
    router_topk_method: str = "greedy"
    # Of num_layers, the leading ones whose FFN is dense (ffn_hidden_size
    # wide, no router); the rest hold experts. With any, the latent
    # family's params["layers"] is two stacks, (dense, sparse).
    num_dense_layers: int = 0
    # Hyper-connections (ops/hyper_connections.py): a token's residual is
    # residual_copies copies of hidden_size, 1 = the plain residual; the
    # mixing matrix is exp(clip(·, hc_res_clamp_min, hc_res_clamp_max))
    # made doubly stochastic by hc_sinkhorn_iters Sinkhorn iterations, each
    # sum plus hc_eps.
    residual_copies: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp_min: float = -30.0
    hc_res_clamp_max: float = 30.0
    # Attention kinds by layer in the pair family (models/llama.py), the
    # source's ``layer_types``: "sliding_attention" (a layer that sees the
    # query's own row and the sliding_window - 1 before it, cached in a
    # ring) or "full_attention" (every row before it, cached whole). The
    # first num_layers entries count (a model cut in depth keeps its
    # source's list whole); None = every layer full. With any window layer,
    # with experts of moe_ffn_hidden_size or with num_dense_layers,
    # params["layers"] is a sequence of stacks, one for each kind of layer
    # the model has (models/llama.py::stack_kinds).
    layer_types: Optional[tuple] = None
    sliding_window: int = 0
    # The kinds of those stacks where they are not what the layers
    # themselves show: a model cut out of another keeps that one's stacks,
    # some of them with no layer (llama.with_layer_order sets it).
    layer_stacks: Optional[tuple] = None
    # False: a full-attention layer applies no rotary position (window
    # layers always rotate; in the latent family the latent layers are the
    # full ones, and q_rope, k_rope are used as projected). True with no
    # window layer is every model before. True with window layers: a full
    # layer rotates too, by the window layers' table unless rope_full_yarn
    # gives the full layers one of their own.
    rope_on_full_layers: bool = True
    # A rotary table a kind of attention layer in the pair family's stacks
    # (a source whose rope_parameters has a group for each of layer_types'
    # words). Window layers rotate by plain RoPE at rope_theta. Full layers
    # rotate at the same rope_theta by YaRN, where this is its hashable
    # tuple (factor, original_max_position_embeddings, beta_fast,
    # beta_slow, attention_factor): frequencies blended between kept and
    # divided by factor (ops/rope.py::yarn_inv_freq), and cos and sin both
    # times attention_factor, the transformers convention, so a full
    # layer's q.k carries its square (the latent family's rope_yarn folds
    # its magnitude into the softmax scale instead). None = the full
    # layers take the window layers' table.
    rope_full_yarn: Optional[tuple] = None
    # RMSNorm over each query and key head's head_dim values, one gain for
    # all heads, before any rotation.
    qk_norm: bool = False
    # Linear-attention layers in the latent family (models/mla.py;
    # ``layer_types`` "linear_attention"): the gated delta rule with a
    # channel-wise decay (KDA, ops/kda.py) over kda_num_heads heads whose
    # keys and values are both kda_head_dim wide, behind a depthwise causal
    # convolution of kda_conv_kernel taps on q, k and v; the decay and the
    # output gate pass through a rank of kda_gate_rank. Such a layer's slot
    # state is a float32 matrix a head and the convolution's last
    # kda_conv_kernel - 1 input rows: no rows, no positions.
    kda_num_heads: int = 0
    kda_head_dim: int = 0
    kda_conv_kernel: int = 4
    kda_gate_rank: int = 0
    # Linear-attention layers in the pair family (models/stacks.py; the
    # same ``layer_types`` word): the gated delta rule with ONE decay a head
    # (ops/delta.py) over linear_num_heads heads whose keys are
    # linear_key_head_dim wide and whose values linear_value_head_dim,
    # behind a depthwise causal convolution of linear_conv_kernel taps on
    # q, k and v each; the write strength β is a sigmoid, times 2 where
    # linear_allow_neg_eigval (I − β k kᵀ then has eigenvalues down to −1);
    # the output gate is SiLU of a full-rank projection. A slot's state is
    # a float32 [key width, value width] matrix a head and the
    # convolutions' last linear_conv_kernel - 1 input rows.
    linear_num_heads: int = 0
    linear_key_head_dim: int = 0
    linear_value_head_dim: int = 0
    linear_conv_kernel: int = 4
    linear_allow_neg_eigval: bool = False
    # Where a block's RMSNorms stand in the pair family's stacks: "pre", in
    # front of each sublayer (x + f(norm(x)): every model before), or
    # "post", on each sublayer's output with nothing in front
    # (x + norm(f(x)): the OLMo 2/3 block).
    norm_placement: str = "pre"
    # State-space layers in the pair family (models/stacks.py; ``layer_types``
    # "mamba"): the Mamba-1 selective scan (ops/mamba.py) over mamba_expand x
    # hidden_size channels with mamba_d_state state numbers each, behind a
    # depthwise causal convolution of mamba_d_conv taps (with a bias where
    # mamba_conv_bias); the step Δ passes through a rank of mamba_dt_rank;
    # mamba_proj_bias puts a bias on the in and out projections (not built);
    # mamba_inner_norms RMS-norms Δ's rank, B and C before they are used (a
    # gain each). A slot's state is a float32 [mamba_d_state, channels]
    # matrix a layer and the convolution's last mamba_d_conv - 1 input rows.
    mamba_d_state: int = 0
    mamba_d_conv: int = 4
    mamba_dt_rank: int = 0
    mamba_expand: int = 2
    mamba_conv_bias: bool = True
    mamba_proj_bias: bool = False
    mamba_inner_norms: bool = False
    # With qk_norm, the width one RMSNorm spans: False, each head's
    # head_dim values with one gain [head_dim] for all heads; True, the
    # whole projected width before the heads are split, gains [q_dim] and
    # [kv_dim].
    qk_norm_whole: bool = False

    @property
    def q_dim(self) -> int:
        return self.num_heads * self.head_dim

    @property
    def kv_dim(self) -> int:
        return self.num_kv_heads * self.head_dim

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_latent(self) -> bool:
        return self.kv_rank > 0

    @property
    def mamba_channels(self) -> int:
        """E: the channels of a state-space layer, mamba_expand x hidden_size."""
        return self.mamba_expand * self.hidden_size

    @property
    def attn_value_width(self) -> int:
        """Lanes of one head's values in attention: the width the blocked
        prefill kernel's route asks to be whole 128s (a latent head's key
        is padded; ops/attention.py::prefill_kernel_on)."""
        return self.v_head_dim if self.is_latent else self.head_dim

    @property
    def experts_held(self) -> int:
        return self.num_experts_held or self.num_experts

    @property
    def router_bias(self) -> bool:
        return self.router_topk_method == "noaux_tc"

    @property
    def attention_kinds(self) -> tuple:
        """"window", "full", the family's linear kind ("kda" in the latent
        family, "delta" in the pair family) or "mamba" for layer 0 ...
        num_layers - 1."""
        if self.layer_types is None:
            return ("full",) * self.num_layers
        if len(self.layer_types) < self.num_layers:
            raise ValueError(f"layer_types names {len(self.layer_types)} layers "
                             f"of {self.num_layers}")
        kinds = {"sliding_attention": "window", "full_attention": "full",
                 "linear_attention": "kda" if self.is_latent else "delta",
                 "mamba": "mamba"}
        return tuple(kinds[t] for t in self.layer_types[:self.num_layers])

    @property
    def has_window_layers(self) -> bool:
        return "window" in self.attention_kinds or any(
            "window" in kind for kind in self.layer_stacks or ())

    @property
    def has_state_layers(self) -> bool:
        """Whether a slot's cache holds a recurrent state (linear-attention
        or state-space layers, or the stacks of a model cut out of one that
        has them)."""
        return any(kind.endswith(("kda", "delta", "mamba"))
                   for kind in self.attention_kinds + (self.layer_stacks or ()))

    def num_params(self) -> int:
        """Parameters this chip holds (for memory planning): of a model
        with a share of the routed experts (moe_ffn_hidden_size), the held
        ones beside the shared expert and the router, in the layers behind
        the num_dense_layers leading ones; a QK-norm's two gains a layer; a
        linear-attention layer's projections, taps, gates and head norm; a
        state-space layer's projections, taps, A, D and inner norms.
        Exact for the pair family; the latent family's attention and
        hyper-connection maps are not counted (models/mla.py::init_params
        is their word)."""
        d, f, v = self.hidden_size, self.ffn_hidden_size, self.vocab_size
        attn = d * self.q_dim + 2 * d * self.kv_dim + self.q_dim * d
        attn += ((self.q_dim + self.kv_dim if self.qk_norm_whole else 2 * self.head_dim)
                 * self.qk_norm)
        hl, dv = self.linear_num_heads, self.linear_value_head_dim
        width = hl * (2 * self.linear_key_head_dim + dv)       # q | k | v
        linear = ((d + self.linear_conv_kernel) * width + 2 * d * hl + 2 * hl
                  + 2 * d * hl * dv + dv)
        n_linear = self.attention_kinds.count("delta")
        e, n, r = self.mamba_channels, self.mamba_d_state, self.mamba_dt_rank
        mamba = (2 * d * e + (self.mamba_d_conv + self.mamba_conv_bias) * e
                 + e * (r + 2 * n) + (r + 2 * n) * self.mamba_inner_norms
                 + r * e + e + n * e + e + e * d)    # in, conv, x, norms, dt, A, D, out
        n_mamba = self.attention_kinds.count("mamba")
        dense_mlp, dense = 3 * d * f, self.num_layers
        sparse_mlp = 0
        if self.moe_ffn_hidden_size:
            fe = self.moe_ffn_hidden_size
            sparse_mlp = ((self.experts_held + self.num_shared_experts) * 3 * d * fe
                          + d * self.num_experts + self.num_experts * self.router_bias)
            dense = self.num_dense_layers
        elif self.is_moe:
            dense_mlp = self.num_experts * 3 * d * f + d * self.num_experts
        embed = v * d * (1 if self.tie_embeddings else 2)
        return (self.num_layers * (attn + 2 * d) + n_linear * (linear - attn)
                + n_mamba * (mamba - attn)
                + dense * dense_mlp
                + (self.num_layers - dense) * sparse_mlp + embed + d)


PRESETS: dict[str, ModelConfig] = {
    # Flagship serving target (BASELINE config 2/3).
    "llama3-8b": ModelConfig(
        name="llama3-8b",
        vocab_size=128256,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        ffn_hidden_size=14336,
        rope_theta=500000.0,
        max_seq_len=8192,
    ),
    # Batch-eval target (BASELINE config 5).
    "llama3-70b": ModelConfig(
        name="llama3-70b",
        vocab_size=128256,
        hidden_size=8192,
        num_layers=80,
        num_heads=64,
        num_kv_heads=8,
        head_dim=128,
        ffn_hidden_size=28672,
        rope_theta=500000.0,
        max_seq_len=8192,
    ),
    # Tool-calling MoE target (BASELINE config 4).
    "mixtral-8x7b": ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        hidden_size=4096,
        num_layers=32,
        num_heads=32,
        num_kv_heads=8,
        head_dim=128,
        ffn_hidden_size=14336,
        rope_theta=1000000.0,
        num_experts=8,
        num_experts_per_tok=2,
        max_seq_len=8192,
    ),
    # ~1B-class single-chip model (fits one v5e chip in bf16 with KV cache).
    "llama3-1b": ModelConfig(
        name="llama3-1b",
        vocab_size=128256,
        hidden_size=2048,
        num_layers=16,
        num_heads=32,
        num_kv_heads=8,
        head_dim=64,
        ffn_hidden_size=8192,
        rope_theta=500000.0,
        max_seq_len=8192,
    ),
    # Tiny configs for tests (fast compile on CPU).
    "test-tiny": ModelConfig(
        name="test-tiny",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        ffn_hidden_size=128,
        rope_theta=10000.0,
        max_seq_len=128,
    ),
    "test-tiny-gqa8": ModelConfig(
        name="test-tiny-gqa8",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=8,
        num_kv_heads=8,
        head_dim=16,
        ffn_hidden_size=128,
        rope_theta=10000.0,
        max_seq_len=128,
    ),
    # Latent attention + dropless experts at test widths: rank 1 of 2
    # holds experts 4..7 of 8.
    "test-tiny-mla": ModelConfig(
        name="test-tiny-mla",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        ffn_hidden_size=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        num_experts=8,
        num_experts_per_tok=2,
        max_seq_len=512,
        kv_rank=32,
        q_rank=48,
        qk_nope_head_dim=8,
        qk_rope_head_dim=8,
        v_head_dim=16,
        rope_yarn=(16.0, 64, 32.0, 1.0, 1.0, 1.0),
        rope_interleave=True,
        q_scaling_beta=0.1,
        moe_ffn_hidden_size=32,
        num_shared_experts=1,
        num_experts_held=4,
        expert_rank=1,
    ),
    # test-tiny-mla's attention behind a residual of four copies: one leading
    # dense layer, then two sparse ones that hold all 8 experts, a sigmoid
    # router with a selection bias.
    "test-tiny-hc": ModelConfig(
        name="test-tiny-hc",
        vocab_size=256,
        hidden_size=64,
        num_layers=3,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        ffn_hidden_size=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        num_experts=8,
        num_experts_per_tok=2,
        max_seq_len=512,
        kv_rank=32,
        q_rank=48,
        qk_nope_head_dim=8,
        qk_rope_head_dim=8,
        v_head_dim=16,
        rope_yarn=(16.0, 64, 32.0, 1.0, 1.0, 1.0),
        rope_interleave=True,
        moe_ffn_hidden_size=32,
        num_shared_experts=1,
        routed_scaling_factor=2.0,
        router_scoring="sigmoid",
        router_topk_method="noaux_tc",
        num_dense_layers=1,
        residual_copies=4,
    ),
    # The pair family with layers of several kinds: a leading dense layer,
    # window layers (8 rows) around one full layer that applies no rotary
    # position, QK-norm, and behind the dense layer a sigmoid router with a
    # selection bias over 8 experts of which rank 1 of 2 holds 4, beside a
    # shared expert: (dense, window), (sparse, window), (sparse, full).
    "test-tiny-window": ModelConfig(
        name="test-tiny-window",
        vocab_size=256,
        hidden_size=64,
        num_layers=3,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        ffn_hidden_size=128,
        rope_theta=10000.0,
        num_experts=8,
        num_experts_per_tok=2,
        max_seq_len=512,
        moe_ffn_hidden_size=32,
        num_shared_experts=1,
        num_experts_held=4,
        expert_rank=1,
        routed_scaling_factor=2.5,
        router_scoring="sigmoid",
        router_topk_method="noaux_tc",
        num_dense_layers=1,
        layer_types=("sliding_attention", "sliding_attention", "full_attention"),
        sliding_window=8,
        rope_on_full_layers=False,
        qk_norm=True,
    ),
    # The latent family with layers of several kinds: a leading dense layer
    # with linear attention (KDA), then K, M, K behind it with a sigmoid
    # router and a selection bias over 8 experts of which rank 1 of 2 holds
    # 4, beside a shared expert; the latent layer has no query rank and no
    # rotary position: (dense, kda), (sparse, kda), (sparse, mla).
    "test-tiny-kda": ModelConfig(
        name="test-tiny-kda",
        vocab_size=256,
        hidden_size=64,
        num_layers=4,
        num_heads=4,
        num_kv_heads=4,
        head_dim=16,
        ffn_hidden_size=128,
        rope_theta=10000.0,
        num_experts=8,
        num_experts_per_tok=2,
        max_seq_len=512,
        kv_rank=32,
        q_rank=0,
        qk_nope_head_dim=8,
        qk_rope_head_dim=8,
        v_head_dim=16,
        moe_ffn_hidden_size=32,
        num_shared_experts=1,
        num_experts_held=4,
        expert_rank=1,
        routed_scaling_factor=2.446,
        router_scoring="sigmoid",
        router_topk_method="noaux_tc",
        num_dense_layers=1,
        layer_types=("linear_attention", "linear_attention", "full_attention",
                     "linear_attention"),
        rope_on_full_layers=False,
        kda_num_heads=4,
        kda_head_dim=16,
        kda_conv_kernel=4,
        kda_gate_rank=8,
    ),
    # The pair family with sparse stacks only and a rotary table a kind:
    # S S S F, rings of 8 rows, every layer a softmax router's greedy top-2
    # of 8 experts, all held, no shared expert and no dense layer; QK-norm;
    # the full layer rotates by YaRN with cos and sin scaled.
    "test-tiny-yarn": ModelConfig(
        name="test-tiny-yarn",
        vocab_size=256,
        hidden_size=64,
        num_layers=4,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        ffn_hidden_size=128,
        rope_theta=10000.0,
        rms_norm_eps=1e-6,
        num_experts=8,
        num_experts_per_tok=2,
        max_seq_len=512,
        moe_ffn_hidden_size=32,
        layer_types=("sliding_attention", "sliding_attention", "sliding_attention",
                     "full_attention"),
        sliding_window=8,
        qk_norm=True,
        rope_full_yarn=(16.0, 64, 32.0, 1.0, 1.2772588722239782),
    ),
    # The pair family with linear-attention layers: L L L F, six heads whose
    # keys are 8 wide and values 16 (no multiple of a kernel's head block, and
    # dk != dv), one decay a head, β in (0, 2); the full layer has six
    # un-grouped heads, no rotary position and a QK-norm over the whole
    # width; both norms of a block stand on the sublayers' outputs.
    "test-tiny-delta": ModelConfig(
        name="test-tiny-delta",
        vocab_size=256,
        hidden_size=96,
        num_layers=4,
        num_heads=6,
        num_kv_heads=6,
        head_dim=16,
        ffn_hidden_size=128,
        rms_norm_eps=1e-6,
        max_seq_len=512,
        layer_types=("linear_attention", "linear_attention", "linear_attention",
                     "full_attention"),
        rope_on_full_layers=False,
        qk_norm=True,
        qk_norm_whole=True,
        norm_placement="post",
        linear_num_heads=6,
        linear_key_head_dim=8,
        linear_value_head_dim=16,
        linear_conv_kernel=4,
        linear_allow_neg_eigval=True,
    ),
    # The pair family with state-space layers in the published order's shape,
    # M M A M M: 128 channels of 16 state numbers, a step rank of 8, the three
    # inner norms, a convolution bias; the attention layer has four query
    # heads on ONE KV head and no rotary position; the head is the table's.
    "test-tiny-mamba": ModelConfig(
        name="test-tiny-mamba",
        vocab_size=256,
        hidden_size=64,
        num_layers=5,
        num_heads=4,
        num_kv_heads=1,
        head_dim=16,
        ffn_hidden_size=128,
        rms_norm_eps=1e-6,
        max_seq_len=512,
        tie_embeddings=True,
        layer_types=("mamba", "mamba", "full_attention", "mamba", "mamba"),
        rope_on_full_layers=False,
        mamba_d_state=16,
        mamba_d_conv=4,
        mamba_dt_rank=8,
        mamba_expand=2,
        mamba_inner_norms=True,
    ),
    "test-tiny-moe": ModelConfig(
        name="test-tiny-moe",
        vocab_size=256,
        hidden_size=64,
        num_layers=2,
        num_heads=4,
        num_kv_heads=2,
        head_dim=16,
        ffn_hidden_size=128,
        rope_theta=10000.0,
        num_experts=4,
        num_experts_per_tok=2,
        max_seq_len=128,
    ),
}


def get_config(name: str, **overrides) -> ModelConfig:
    cfg = PRESETS[name]
    if overrides:
        cfg = dataclasses.replace(cfg, **overrides)
    return cfg
