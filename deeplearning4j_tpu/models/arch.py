"""LMArch — the one description of a decoder-only LM's architecture.

``ShardedTransformerLM`` is built from this (ROADMAP M0): it can say the
GPT-2 block the repo has always run (pre-LN with bias, learned
positions, full multi-head attention, biased GELU feed-forward) and the
latent-attention / sparse-expert block of DeepSeek-V3's modelling code
(``models/latent_moe.py``): RMSNorm, YaRN-scaled rotary positions on
part of each head, low-rank query and key/value projections whose
cached row is a latent, gated SiLU feed-forwards, a leading dense layer
followed by layers of routed experts with a shared expert beside them;
and the grouped-query block with a learned sparse selection
(``models/sparse_gqa.py``): query heads over fewer key/value heads of a
free ``head_dim``, RMSNorm on each head's query and key, plain rotary
over three position streams, an indexer that scores the context and
keeps ``index_topk`` rows of it, every layer an expert layer behind a
softmax router; and the block whose layers are of TWO kinds
(``models/linear_gqa.py``): gated grouped-query attention without
positions at the ``gqa_layers`` indices, a gated delta-rule linear
attention with a per-slot recurrent state everywhere else, every layer an
expert layer with a shared expert; and the block of Mamba-2 state-space
layers beside ungated NoPE grouped-query layers (``models/ssm_gqa.py``):
one scalar decay a head from an input-dependent step, a per-slot state
``[heads, d_head, d_state]``, every layer a softmax-routed expert layer
with a shared MLP, scalars on embedding, residual, attention and logits,
a tied head; and the block of gated short-convolution layers beside
rotary grouped-query layers (``models/conv_gqa.py``): a mixer whose whole
memory is the last ``conv_L_cache - 1`` rows of its convolution's input,
RMSNorm on each head's query and key BEFORE plain rotary, leading dense
layers followed by expert layers behind the ``noaux_tc`` router with the
file's own epsilon, no shared expert, a tied head.

``from_config`` reads a configuration file's keys (the published
``config.json`` names of each family), so a model is a data file and
not a constructor call.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Dict

BLOCKS = ("gpt2", "latent_moe", "sparse_gqa", "linear_gqa", "ssm_gqa",
          "conv_gqa")
#: the blocks of the expert family (``models/<block>.py``): served through
#: one decode-program builder, not trained yet
EXPERT_BLOCKS = ("latent_moe", "sparse_gqa", "linear_gqa", "ssm_gqa",
                 "conv_gqa")
#: what a layer remembers (``LMArch.layer_types``): rows in the paged pools
#: a token, or a per-slot recurrent state (the delta rule's, or a
#: state-space layer's), or only the tail of a short convolution's input
LAYER_TYPES = ("gqa", "linear", "mamba", "conv")
#: which of them each block of two layer kinds may name
BLOCK_LAYER_TYPES = {"linear_gqa": ("gqa", "linear"),
                     "ssm_gqa": ("gqa", "mamba"),
                     "conv_gqa": ("gqa", "conv")}
ROUTERS = ("noaux_tc", "softmax_topk")


@dataclasses.dataclass(frozen=True)
class LMArch:
    vocab_size: int
    n_layers: int
    d_model: int
    n_heads: int
    d_ff: int = 0                  # 0 -> 4 * d_model (GPT-2's convention)
    max_len: int = 512             # learned table's rows / rotary table's rows
    block: str = "gpt2"
    # -- latent attention (block == "latent_moe") --------------------------
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rms_eps: float = 1e-6
    rope_theta: float = 10000.0
    rope_factor: float = 1.0       # YaRN; 1.0 = plain rotary
    rope_original_max_len: int = 4096
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    # -- grouped-query attention over a learned selection (sparse_gqa) ----
    n_kv_heads: int = 0            # query head a reads KV head a // (H / KV)
    head_dim: int = 0              # free: n_heads * head_dim need not be d_model
    mrope_section: tuple = ()      # rotary frequencies per position stream
    index_n_heads: int = 0         # the indexer's query heads ...
    index_head_dim: int = 0        # ... over ONE index key a token
    index_topk: int = 0            # context rows a query keeps
    # -- layers of two kinds (linear_gqa) ---------------------------------
    layer_types: tuple = ()        # per layer, of LAYER_TYPES
    linear_n_heads: int = 0        # the linear layers' heads (q, k and v) ...
    linear_head_dim: int = 0       # ... of this width: state [dim, dim] a head
    conv_kernel: int = 0           # taps of their causal depthwise convolution
    # -- state-space layers beside grouped-query layers (ssm_gqa) ---------
    mamba_n_heads: int = 0         # heads of the state-space layers ...
    mamba_d_head: int = 0          # ... of this width (heads * width = inner)
    mamba_d_state: int = 0         # state [d_head, d_state] a head
    mamba_d_conv: int = 0          # taps of the convolution over x | B | C
    mamba_expand: int = 0          # inner width / d_model
    mamba_n_groups: int = 1        # groups that share B and C
    mamba_chunk_size: int = 256    # rows of a chunk of the scan
    mamba_conv_bias: bool = True   # the convolution's; the projections have none
    embedding_multiplier: float = 1.0   # on the embedding's rows
    residual_multiplier: float = 1.0    # on what a layer adds to the stream
    logits_scaling: float = 1.0         # the logits are DIVIDED by it
    attention_multiplier: float = 0.0   # the softmax scale; 0 = head_dim^-0.5
    tie_embeddings: bool = False        # the head is the embedding, transposed
    # -- gated short convolutions beside rotary grouped-query (conv_gqa) --
    conv_L_cache: int = 0          # taps of the short convolution
    conv_bias: bool = False        # on the convolution and its projections
    # -- experts ------------------------------------------------------------
    n_dense_layers: int = 0        # leading layers with a dense feed-forward
    moe_d_ff: int = 0
    n_experts: int = 0             # the router's width (all experts)
    experts_held: int = 0          # how many of them live here ...
    first_expert: int = 0          # ... the range [first, first + held)
    experts_per_token: int = 0
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    router: str = "noaux_tc"       # parallel/moe.py ROUTERS
    router_eps: float = 1e-20      # added to the chosen scores' sum (noaux_tc)
    init_std: float = 0.02
    param_dtype: str = "float32"

    def __post_init__(self):
        if self.block not in BLOCKS:
            raise ValueError(f"block must be one of {BLOCKS}, got "
                             f"{self.block!r}")
        if not self.d_ff:
            object.__setattr__(self, "d_ff", 4 * self.d_model)
        if self.block == "latent_moe":
            for k in ("q_lora_rank", "kv_lora_rank", "qk_nope_head_dim",
                      "qk_rope_head_dim", "v_head_dim"):
                if getattr(self, k) < 1:
                    raise ValueError(f"latent_moe needs {k} >= 1")
            if self.qk_rope_head_dim % 2:
                raise ValueError("qk_rope_head_dim must be even")
            if not 0 <= self.n_dense_layers <= self.n_layers:
                raise ValueError("n_dense_layers must lie in [0, n_layers]")
        if self.block == "sparse_gqa":
            for k in ("n_kv_heads", "head_dim", "index_n_heads",
                      "index_head_dim", "index_topk"):
                if getattr(self, k) < 1:
                    raise ValueError(f"sparse_gqa needs {k} >= 1")
            if self.n_heads % self.n_kv_heads:
                raise ValueError("n_heads must be a multiple of n_kv_heads")
            if self.head_dim % 2 or self.index_head_dim % 2:
                raise ValueError("head_dim and index_head_dim must be even")
            if sum(self.mrope_section) != self.head_dim // 2:
                raise ValueError(
                    f"mrope_section {self.mrope_section} must sum to "
                    f"head_dim / 2 = {self.head_dim // 2}")
            if self.n_dense_layers:
                raise ValueError("every sparse_gqa layer is an expert layer")
        if self.block in BLOCK_LAYER_TYPES:
            needs = {"linear_gqa": ("linear_n_heads", "linear_head_dim"),
                     "ssm_gqa": ("mamba_n_heads", "mamba_d_head",
                                 "mamba_d_state", "mamba_chunk_size"),
                     "conv_gqa": ()}
            for k in ("n_kv_heads", "head_dim") + needs[self.block]:
                if getattr(self, k) < 1:
                    raise ValueError(f"{self.block} needs {k} >= 1")
            taps = {"linear_gqa": "conv_kernel", "ssm_gqa": "mamba_d_conv",
                    "conv_gqa": "conv_L_cache"}[self.block]
            if getattr(self, taps) < 2:
                raise ValueError(f"{self.block} needs {taps} >= 2")
            if self.n_heads % self.n_kv_heads:
                raise ValueError("n_heads must be a multiple of n_kv_heads")
            kinds = BLOCK_LAYER_TYPES[self.block]
            if (len(self.layer_types) != self.n_layers
                    or any(t not in kinds for t in self.layer_types)):
                raise ValueError(
                    f"layer_types must name one of {kinds} for each of "
                    f"the {self.n_layers} layers, got {self.layer_types!r}")
            if self.block == "conv_gqa":
                if self.head_dim % 2:
                    raise ValueError("head_dim must be even")
                if self.conv_bias:
                    raise ValueError("conv_gqa has no bias anywhere "
                                     "(conv_bias)")
                if not 0 <= self.n_dense_layers <= self.n_layers:
                    raise ValueError("n_dense_layers must lie in "
                                     "[0, n_layers]")
            elif self.n_dense_layers:
                raise ValueError(f"every {self.block} layer is an expert "
                                 "layer")
        if self.block == "ssm_gqa":
            if self.mamba_n_groups != 1:
                raise ValueError("ssm_gqa needs mamba_n_groups == 1 (B and C "
                                 "shared by all heads)")
            if self.mamba_n_heads * self.mamba_d_head \
                    != self.mamba_expand * self.d_model:
                raise ValueError(
                    f"mamba_n_heads * mamba_d_head = "
                    f"{self.mamba_n_heads * self.mamba_d_head} must be "
                    f"mamba_expand * d_model = "
                    f"{self.mamba_expand * self.d_model}")
        if self.router not in ROUTERS:
            raise ValueError(f"router must be one of {ROUTERS}, got "
                             f"{self.router!r}")
        if self.n_moe_layers:
            if not (0 < self.experts_per_token <= self.n_experts):
                raise ValueError("experts_per_token must lie in "
                                 "(0, n_experts]")
            if not (0 < self.experts_held
                    and 0 <= self.first_expert
                    and self.first_expert + self.experts_held
                    <= self.n_experts):
                raise ValueError(
                    f"held experts [{self.first_expert}, "
                    f"{self.first_expert + self.experts_held}) must be "
                    f"a non-empty range inside [0, {self.n_experts})")
            if self.moe_d_ff < 1:
                raise ValueError("moe_d_ff must be >= 1")

    # -- derived ------------------------------------------------------------

    @property
    def n_moe_layers(self) -> int:
        return self.n_layers - self.n_dense_layers \
            if self.block in EXPERT_BLOCKS else 0

    @property
    def latent_width(self) -> int:
        """Values one token leaves in one layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_lanes(self) -> int:
        """Lanes one cached row takes in the pool: ``latent_width``
        rounded up to the chip's 128-lane tile (models/latent_moe.py
        says why)."""
        return -(-self.latent_width // 128) * 128

    @property
    def qk_head_dim(self) -> int:
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """Channels the state-space layers' convolution runs over:
        ``x | B | C``."""
        return self.mamba_d_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def gqa_scale(self) -> float:
        """The grouped-query layers' softmax scale: ``attention_multiplier``
        where the file states one, else ``head_dim^-0.5``."""
        return self.attention_multiplier or self.head_dim ** -0.5

    @property
    def softmax_scale(self) -> float:
        s = self.qk_head_dim ** -0.5
        if self.rope_factor > 1.0:
            m = yarn_mscale(self.rope_factor, self.rope_mscale_all_dim)
            s *= m * m
        return s

    # -- construction ---------------------------------------------------------

    @classmethod
    def gpt2(cls, vocab_size: int, n_layers: int, d_model: int, n_heads: int,
             d_ff: int = 0, max_len: int = 512) -> "LMArch":
        return cls(vocab_size=vocab_size, n_layers=n_layers, d_model=d_model,
                   n_heads=n_heads, d_ff=d_ff, max_len=max_len)

    @classmethod
    def from_config(cls, cfg: Dict[str, Any], **over) -> "LMArch":
        """From a configuration file's keys.  A file with ``n_embd`` is
        GPT-2's; one with ``kv_lora_rank`` is the latent/expert family
        (DeepSeek-V3's names, which ``model_type: kimi_k2`` reuses).
        ``n_routed_experts`` there counts the experts HELD (the chip's
        share); the router's width is ``n_routed_experts_published``
        when the file states one.  One with ``sa_config`` beside
        ``num_key_value_heads`` is the grouped-query block over a
        learned selection (Qwen3-MoE's names plus the indexer's):
        ``num_experts`` is the router's width, and ``n_routed_experts``,
        when stated, the count held from ``first_expert`` on.  One with
        ``linear_attn_config`` beside ``gqa_layers`` is the block of two
        layer kinds (DeepSeek-V3's expert names, as the first family):
        ``gqa_layers`` is kept as published and read up to
        ``num_hidden_layers``.  One whose ``layer_types`` name ``mamba`` and
        ``attention`` beside the ``mamba_*`` sizes is the block of
        state-space and grouped-query layers (GraniteMoeHybrid's names):
        ``layer_types`` is kept as published and read up to
        ``num_hidden_layers``; ``num_local_experts`` counts the experts
        HELD and ``num_local_experts_published``, when stated, is the
        router's width; the shared MLP of ``shared_intermediate_size`` is
        that many experts' width in one.  One with ``conv_L_cache`` beside
        ``layer_types`` naming ``conv`` and ``full_attention`` is the
        block of gated short-convolution and rotary grouped-query layers
        (Lfm2Moe's names): ``layer_types`` is kept as published and read
        up to ``num_hidden_layers``; ``num_experts`` is the router's
        width and ``n_routed_experts``, when stated, the count held from
        ``first_expert`` on; the head is tied unless ``tie_embedding``
        (or ``tie_word_embeddings``) says otherwise.  ``over`` replaces
        any field."""
        if "n_embd" in cfg:
            kw = dict(vocab_size=cfg["vocab_size"], n_layers=cfg["n_layer"],
                      d_model=cfg["n_embd"], n_heads=cfg["n_head"],
                      d_ff=cfg.get("n_inner") or 0,
                      max_len=cfg["n_positions"])
        elif "kv_lora_rank" in cfg:
            rs = cfg.get("rope_scaling") or {}
            held = int(cfg["n_routed_experts"])
            kw = dict(
                block="latent_moe", vocab_size=cfg["vocab_size"],
                n_layers=cfg["num_hidden_layers"],
                d_model=cfg["hidden_size"],
                n_heads=cfg["num_attention_heads"],
                d_ff=cfg["intermediate_size"],
                max_len=cfg["max_position_embeddings"],
                q_lora_rank=cfg["q_lora_rank"],
                kv_lora_rank=cfg["kv_lora_rank"],
                qk_nope_head_dim=cfg["qk_nope_head_dim"],
                qk_rope_head_dim=cfg["qk_rope_head_dim"],
                v_head_dim=cfg["v_head_dim"],
                rms_eps=cfg.get("rms_norm_eps", 1e-6),
                rope_theta=cfg.get("rope_theta", 10000.0),
                rope_factor=float(rs.get("factor", 1.0)),
                rope_original_max_len=rs.get(
                    "original_max_position_embeddings", 4096),
                rope_beta_fast=rs.get("beta_fast", 32.0),
                rope_beta_slow=rs.get("beta_slow", 1.0),
                rope_mscale=rs.get("mscale", 1.0),
                rope_mscale_all_dim=rs.get("mscale_all_dim", 0.0),
                n_dense_layers=min(cfg.get("first_k_dense_replace", 0),
                                   cfg["num_hidden_layers"]),
                moe_d_ff=cfg["moe_intermediate_size"],
                n_experts=int(cfg.get("n_routed_experts_published", held)),
                experts_held=held,
                first_expert=int(cfg.get("first_expert", 0)),
                experts_per_token=cfg["num_experts_per_tok"],
                n_shared_experts=cfg.get("n_shared_experts", 0),
                routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
                init_std=cfg.get("initializer_range", 0.02))
            unsupported = {
                "attention_bias": (False,), "hidden_act": ("silu",),
                "scoring_func": ("sigmoid",), "topk_method": ("noaux_tc",),
                "n_group": (1,), "topk_group": (1,),
                "norm_topk_prob": (True,), "moe_layer_freq": (1,),
                "tie_word_embeddings": (False,)}
            for k, ok in unsupported.items():
                if k in cfg and cfg[k] not in ok:
                    raise ValueError(
                        f"config key {k}={cfg[k]!r} is not expressible by "
                        f"the latent_moe block (supported: {ok})")
        elif "sa_config" in cfg and "num_key_value_heads" in cfg:
            sa = cfg["sa_config"]
            rs = cfg.get("rope_scaling") or {}
            head_dim = int(cfg.get("head_dim") or cfg["hidden_size"]
                           // cfg["num_attention_heads"])
            kw = dict(
                block="sparse_gqa", vocab_size=cfg["vocab_size"],
                n_layers=cfg["num_hidden_layers"],
                d_model=cfg["hidden_size"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"], head_dim=head_dim,
                d_ff=cfg.get("intermediate_size") or 0,
                max_len=cfg["max_position_embeddings"],
                rms_eps=cfg.get("rms_norm_eps", 1e-6),
                rope_theta=cfg.get("rope_theta", 10000.0),
                mrope_section=tuple(rs.get("mrope_section")
                                    or (head_dim // 2,)),
                index_n_heads=sa["indexer_num_heads"],
                index_head_dim=sa["indexer_head_dim"],
                index_topk=sa["topk"],
                moe_d_ff=cfg["moe_intermediate_size"],
                n_experts=int(cfg["num_experts"]),
                experts_held=int(cfg.get("n_routed_experts",
                                         cfg["num_experts"])),
                first_expert=int(cfg.get("first_expert", 0)),
                experts_per_token=cfg["num_experts_per_tok"],
                router="softmax_topk",
                init_std=cfg.get("initializer_range", 0.02))
            unsupported = {
                "attention_bias": (False,), "hidden_act": ("silu",),
                "norm_topk_prob": (True,), "tie_word_embeddings": (False,),
                "decoder_sparse_step": (1,), "mlp_only_layers": ([],),
                "use_sliding_window": (False,), "sliding_window": (None,)}
            for k, ok in unsupported.items():
                if k in cfg and cfg[k] not in ok:
                    raise ValueError(
                        f"config key {k}={cfg[k]!r} is not expressible by "
                        f"the sparse_gqa block (supported: {ok})")
            if sa.get("indexer_num_kv_heads", 1) != 1:
                raise ValueError(
                    "config key sa_config.indexer_num_kv_heads="
                    f"{sa['indexer_num_kv_heads']!r} is not expressible by "
                    "the sparse_gqa block (supported: one index key a token)")
            if rs.get("rope_type", rs.get("type", "default")) != "default":
                raise ValueError(
                    f"config key rope_scaling={rs!r} is not expressible by "
                    "the sparse_gqa block (supported: plain rotary, "
                    "rope_type default)")
        elif "linear_attn_config" in cfg and "gqa_layers" in cfg:
            la = cfg["linear_attn_config"]
            n_layers = int(cfg["num_hidden_layers"])
            held = int(cfg["n_routed_experts"])
            gqa = {int(i) for i in cfg["gqa_layers"]}
            kw = dict(
                block="linear_gqa", vocab_size=cfg["vocab_size"],
                n_layers=n_layers, d_model=cfg["hidden_size"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=int(cfg.get("head_dim") or cfg["hidden_size"]
                             // cfg["num_attention_heads"]),
                d_ff=cfg.get("intermediate_size") or 0,
                max_len=cfg["max_position_embeddings"],
                rms_eps=cfg.get("rms_norm_eps", 1e-6),
                # the published list, read up to the depth held
                layer_types=tuple("gqa" if i in gqa else "linear"
                                  for i in range(n_layers)),
                linear_n_heads=la["num_heads"],
                linear_head_dim=la["head_dim"],
                conv_kernel=la["short_conv_kernel_size"],
                moe_d_ff=cfg["moe_intermediate_size"],
                n_experts=int(cfg.get("n_routed_experts_published", held)),
                experts_held=held,
                first_expert=int(cfg.get("first_expert", 0)),
                experts_per_token=cfg["num_experts_per_tok"],
                n_shared_experts=cfg.get("n_shared_experts", 0),
                routed_scaling_factor=cfg.get("routed_scaling_factor", 1.0),
                init_std=cfg.get("initializer_range", 0.02))
            unsupported = {
                "use_rope": (False,), "use_gqa_gate": (True,),
                "kda_use_full_proj": (False,),
                "kda_allow_neg_eigval": (True,),
                "first_k_dense_replace": (0,), "norm_topk_prob": (True,),
                "tie_word_embeddings": (False,)}
            for k, ok in unsupported.items():
                if k in cfg and cfg[k] not in ok:
                    raise ValueError(
                        f"config key {k}={cfg[k]!r} is not expressible by "
                        f"the linear_gqa block (supported: {ok})")
            if la.get("num_kv_heads") not in (None, la["num_heads"]):
                raise ValueError(
                    "config key linear_attn_config.num_kv_heads="
                    f"{la['num_kv_heads']!r} is not expressible by the "
                    "linear_gqa block (supported: as many key and value "
                    "heads as query heads)")
        elif "layer_types" in cfg and "mamba_n_heads" in cfg:
            n_layers = int(cfg["num_hidden_layers"])
            held = int(cfg["num_local_experts"])
            kinds = {"mamba": "mamba", "attention": "gqa"}
            named = list(cfg["layer_types"])[:n_layers]
            if len(named) != n_layers or any(t not in kinds for t in named):
                raise ValueError(
                    f"config key layer_types={cfg['layer_types']!r} is not "
                    "expressible by the ssm_gqa block (supported: one of "
                    f"{tuple(kinds)} for each of the {n_layers} layers)")
            moe_d_ff = int(cfg["intermediate_size"])
            shared = int(cfg.get("shared_intermediate_size", 0))
            if shared % moe_d_ff:
                raise ValueError(
                    f"config key shared_intermediate_size={shared!r} is not "
                    "expressible by the ssm_gqa block (supported: a whole "
                    f"multiple of intermediate_size {moe_d_ff})")
            kw = dict(
                block="ssm_gqa", vocab_size=cfg["vocab_size"],
                n_layers=n_layers, d_model=cfg["hidden_size"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=int(cfg.get("head_dim") or cfg["hidden_size"]
                             // cfg["num_attention_heads"]),
                max_len=cfg["max_position_embeddings"],
                rms_eps=cfg.get("rms_norm_eps", 1e-6),
                # the published list, read up to the depth held
                layer_types=tuple(kinds[t] for t in named),
                mamba_n_heads=cfg["mamba_n_heads"],
                mamba_d_head=cfg["mamba_d_head"],
                mamba_d_state=cfg["mamba_d_state"],
                mamba_d_conv=cfg["mamba_d_conv"],
                mamba_expand=cfg["mamba_expand"],
                mamba_n_groups=cfg.get("mamba_n_groups", 1),
                mamba_chunk_size=cfg.get("mamba_chunk_size", 256),
                mamba_conv_bias=bool(cfg.get("mamba_conv_bias", True)),
                embedding_multiplier=float(cfg.get("embedding_multiplier", 1)),
                residual_multiplier=float(cfg.get("residual_multiplier", 1)),
                logits_scaling=float(cfg.get("logits_scaling", 1)),
                attention_multiplier=float(
                    cfg.get("attention_multiplier") or 0.0),
                tie_embeddings=bool(cfg.get("tie_word_embeddings", False)),
                moe_d_ff=moe_d_ff,
                n_experts=int(cfg.get("num_local_experts_published", held)),
                experts_held=held,
                first_expert=int(cfg.get("first_expert", 0)),
                experts_per_token=cfg["num_experts_per_tok"],
                n_shared_experts=shared // moe_d_ff,
                router="softmax_topk",
                init_std=cfg.get("initializer_range", 0.02))
            unsupported = {
                "position_embedding_type": ("nope",), "mamba_n_groups": (1,),
                "attention_bias": (False,), "mamba_proj_bias": (False,),
                "hidden_act": ("silu",), "rope_scaling": (None,),
                "normalization_function": ("rmsnorm",)}
            for k, ok in unsupported.items():
                if k in cfg and cfg[k] not in ok:
                    raise ValueError(
                        f"config key {k}={cfg[k]!r} is not expressible by "
                        f"the ssm_gqa block (supported: {ok})")
        elif "layer_types" in cfg and "conv_L_cache" in cfg:
            n_layers = int(cfg["num_hidden_layers"])
            kinds = {"conv": "conv", "full_attention": "gqa"}
            named = list(cfg["layer_types"])[:n_layers]
            if len(named) != n_layers or any(t not in kinds for t in named):
                raise ValueError(
                    f"config key layer_types={cfg['layer_types']!r} is not "
                    "expressible by the conv_gqa block (supported: one of "
                    f"{tuple(kinds)} for each of the {n_layers} layers)")
            rp = cfg.get("rope_parameters") or {}
            n_experts = int(cfg["num_experts"])
            kw = dict(
                block="conv_gqa", vocab_size=cfg["vocab_size"],
                n_layers=n_layers, d_model=cfg["hidden_size"],
                n_heads=cfg["num_attention_heads"],
                n_kv_heads=cfg["num_key_value_heads"],
                head_dim=int(cfg.get("head_dim") or cfg["hidden_size"]
                             // cfg["num_attention_heads"]),
                d_ff=cfg["intermediate_size"],
                max_len=cfg["max_position_embeddings"],
                rms_eps=cfg.get("norm_eps", 1e-5),
                rope_theta=float(rp.get("rope_theta",
                                        cfg.get("rope_theta", 1000000.0))),
                # the published list, read up to the depth held
                layer_types=tuple(kinds[t] for t in named),
                conv_L_cache=int(cfg["conv_L_cache"]),
                conv_bias=bool(cfg.get("conv_bias", False)),
                tie_embeddings=bool(cfg.get(
                    "tie_embedding", cfg.get("tie_word_embeddings", True))),
                n_dense_layers=min(int(cfg.get("num_dense_layers", 0)),
                                   n_layers),
                moe_d_ff=cfg["moe_intermediate_size"],
                n_experts=n_experts,
                experts_held=int(cfg.get("n_routed_experts", n_experts)),
                first_expert=int(cfg.get("first_expert", 0)),
                experts_per_token=cfg["num_experts_per_tok"],
                routed_scaling_factor=float(
                    cfg.get("routed_scaling_factor", 1.0)),
                router="noaux_tc",
                router_eps=float(cfg.get("router_eps", 1e-6)),
                init_std=cfg.get("initializer_range", 0.02))
            unsupported = {
                "conv_bias": (False,), "use_expert_bias": (True,),
                "norm_topk_prob": (True,)}
            for k, ok in unsupported.items():
                if k in cfg and cfg[k] not in ok:
                    raise ValueError(
                        f"config key {k}={cfg[k]!r} is not expressible by "
                        f"the conv_gqa block (supported: {ok})")
            if rp.get("rope_type", rp.get("type", "default")) != "default":
                raise ValueError(
                    f"config key rope_parameters={rp!r} is not expressible "
                    "by the conv_gqa block (supported: plain rotary, "
                    "rope_type default)")
        else:
            raise ValueError(
                "configuration names neither a GPT-2 block (n_embd), a "
                "latent/expert block (kv_lora_rank), a grouped-query "
                "block over a learned selection (sa_config with "
                "num_key_value_heads), a block of linear-attention and "
                "grouped-query layers (linear_attn_config with gqa_layers), "
                "a block of state-space and grouped-query layers "
                "(layer_types naming mamba / attention with mamba_n_heads) "
                "nor a block of short-convolution and grouped-query layers "
                "(layer_types naming conv / full_attention with "
                "conv_L_cache)")
        kw.update(over)
        return cls(**kw)


def yarn_mscale(scale: float, mscale: float) -> float:
    """``yarn_get_mscale`` of the published modelling code."""
    if scale <= 1.0:
        return 1.0
    return 0.1 * mscale * math.log(scale) + 1.0
