"""Transformer model configs + registry.

Sizes follow the public Llama-2/-3 architecture descriptions (RMSNorm, RoPE, GQA,
SwiGLU, untied or tied embeddings).
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Dict, Tuple

import jax.numpy as jnp

# One character of a layer pattern: the stack of params its layers lie in, its mixer and its
# feed-forward part (rows of models/llama.py's MIXERS and FEED_FORWARD; either or none), and
# what a message calls it; `windowed`: attention inside cfg.attn_window, always rotated.
# Pure data: this module imports no model code.
LayerKind = collections.namedtuple("LayerKind", "stack mixer ff says windowed", defaults=(False,))
LAYER_KINDS = {
    "M": LayerKind("ssm_layers", "ssm", None, "Mamba-2"),
    "K": LayerKind("kda_layers", "kda", None, "Kimi Delta Attention"),
    "C": LayerKind("sconv_layers", "sconv", None, "gated short convolution"),
    "E": LayerKind("layers", None, "experts", "experts"),
    "*": LayerKind("attn_layers", "attn", None, "attention"),
    "W": LayerKind("window_layers", "attn", None, "attention inside attn_window, rotated", True),
    "-": LayerKind("mlp_layers", None, "dense", "MLP"),
}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    vocab_size: int
    d_model: int
    n_layers: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    max_seq_len: int = 8192
    rope_theta: float = 500000.0
    norm_eps: float = 1e-5
    tie_embeddings: bool = False
    dtype: str = "bfloat16"  # activation dtype; params kept f32, cast in forward
    remat: bool = True  # jax.checkpoint each layer (HBM <-> FLOPs trade)
    # full: recompute everything in backward (min HBM). dots: save matmul outputs
    # and recompute only cheap elementwise ops (more HBM, fewer recomputed FLOPs —
    # higher MFU when activations fit). none == remat=False.
    remat_policy: str = "full"  # full | dots | dots_no_batch | none
    # Attention backend: auto|pallas|reference|ring|ulysses. ring/ulysses are the
    # sequence-parallel collectives (ops/ring_attention.py) — use with an sp>1 mesh.
    attention_impl: str = "auto"
    # Pipeline parallelism: >1 splits the layer stack into this many stages over the
    # "pp" mesh axis (parallel/pipeline.py); requires n_layers % pipeline_stages == 0.
    pipeline_stages: int = 1
    pipeline_microbatches: int = 0  # 0 -> = pipeline_stages
    # Mixture-of-experts (0 = dense). Experts shard over the "ep" mesh axis; dispatch
    # is static capacity-based einsum (models/moe.py) so shapes stay XLA-friendly.
    n_experts: int = 0
    moe_top_k: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_coef: float = 0.01
    # top-1 gate convention: False = raw top-1 softmax prob (Switch; keeps the
    # router differentiable through the task loss), True = renormalize to 1.0
    # (Mixtral inference semantics — what HF MixtralForCausalLM computes for
    # num_experts_per_tok=1). checkpoint.config_from_hf sets True for
    # model_type=mixtral; irrelevant when moe_top_k > 1 (both renormalize).
    moe_top1_renorm: bool = False
    # --- what a published config.json of another family states (0 / default = absent) ---
    # Latent attention (MLA): q through a rank-`q_lora_rank` latent (0 beside kv_lora_rank > 0:
    # by one product, no latent and no norm), k and v through one of rank `kv_lora_rank` beside
    # a rotated key of `qk_rope_head_dim` shared by all heads; a head's q and k are
    # `qk_nope_head_dim` un-rotated + `qk_rope_head_dim` rotated wide (attention_rotation false:
    # neither part is rotated), its v `v_head_dim`, which need not be as wide as q and k.
    q_lora_rank: int = 0
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # Expert layers: `n_dense_layers` leading layers keep the dense MLP (width d_ff), the
    # rest route over `n_experts` experts of width `d_ff_expert` (0 = d_ff) beside
    # `n_shared_experts` that every token meets. moe_capacity_factor <= 0 is no capacity:
    # the dropless layer (moe.py:expert_layer), which is told which experts it holds
    # (`experts_held` = (index, of): the index-th of `of` equal contiguous shares).
    d_ff_expert: int = 0
    n_shared_experts: int = 0
    n_dense_layers: int = 0
    moe_scoring: str = "softmax"  # softmax | sigmoid
    moe_route_scale: float = 1.0  # the gates' factor (routed_scaling_factor)
    # selection by score + a bias that no gradient reaches; it moves by the balance rule
    # after a step (train/step.py) at this rate
    moe_select_bias: bool = False
    moe_bias_update_rate: float = 0.001
    experts_held: Tuple[int, int] = (0, 1)
    # Multi-token prediction: modules after the last block, each one more expert layer
    # that predicts one token further; their loss is added with this weight.
    mtp_depth: int = 0
    mtp_loss_weight: float = 0.3
    # --- a stack of single-part layers (nemotron_h's hybrid_override_pattern) ---
    # One character a layer, each layer a mixer OR a feed-forward part alone behind its
    # own norm and residual: the characters are LAYER_KINDS' (M a Mamba-2 mixer, models/ssm.py;
    # K a Kimi-Delta-Attention mixer, models/kda.py; C a gated short convolution,
    # models/sconv.py; * attention; W attention inside attn_window; E an expert layer; - the
    # dense MLP). A published layer of two parts (solar_open2, lfm2_moe, afmoe:
    # a mixer, then a feed-forward part) is two characters. "" is
    # the block every other family has: attention followed
    # by a feed-forward part, n_layers times (a prefix of n_dense_layers dense).
    # mtp_layer_pattern is an MTP module's own layers (only "*E": the block, in two).
    layer_pattern: str = ""
    mtp_layer_pattern: str = ""
    # Mamba-2: ssm_n_heads heads ssm_head_dim wide (d_inner their product), B and C a
    # group of ssm_n_groups, ssm_state wide; a causal depthwise convolution of
    # ssm_conv_taps taps; the scan runs in chunks of ssm_chunk positions. The counts are
    # what is HELD: a tensor-parallel share of a layer is fewer heads and groups.
    ssm_n_heads: int = 0
    ssm_head_dim: int = 64
    ssm_n_groups: int = 1
    ssm_state: int = 128
    ssm_conv_taps: int = 4
    ssm_chunk: int = 128
    # the range the seeded dt_bias is drawn from (time_step_min / _max / _floor)
    ssm_dt_min: float = 0.001
    ssm_dt_max: float = 0.1
    ssm_dt_floor: float = 1e-4
    # A head's width where it is not d_model / n_heads (0 = derived): a published
    # `head_dim`, which a share of the heads needs too (4 heads of 128 beside d_model 4096)
    attn_head_dim: int = 0
    # (query heads, key/value heads) an attention layer HOLDS of n_heads / n_kv_heads,
    # 0 = all: a tensor-parallel share of its heads, as experts_held is of the experts
    attn_heads_held: Tuple[int, int] = (0, 0)
    attention_rotation: bool = True  # False: q and k are not rotated (position comes from elsewhere)
    # attention's output times sigmoid(u W_gate) before W_o, a channel ([d_model -> heads x
    # head_dim], from the layer's normed input: solar_open2's use_gqa_gate)
    attn_output_gate: bool = False
    # Kimi Delta Attention (arXiv:2510.26692; models/kda.py, ops/kda.py): kda_n_heads heads
    # HELD (a tensor-parallel share is fewer heads), keys and values kda_head_dim wide, a
    # causal depthwise convolution of kda_conv_taps taps over q, k and v, the decay and
    # the output gate through low-rank projections of kda_proj_rank (0 = kda_head_dim;
    # kda_use_full_proj false), beta in (0, 2) where kda_neg_eigval (kda_allow_neg_eigval)
    # and (0, 1) else; the scan runs in chunks of kda_chunk positions. The seeded dt_bias
    # is drawn from ssm_dt_min / _max / _floor, as a Mamba-2 layer's.
    kda_n_heads: int = 0
    kda_head_dim: int = 128
    kda_conv_taps: int = 4
    kda_proj_rank: int = 0
    kda_neg_eigval: bool = True
    kda_chunk: int = 64
    # Experts in a latent: the routed experts work at this width, between a projection
    # down before the dispatch and one up after the combine (0 = at d_model); router and
    # shared expert see d_model. d_ff_shared: the shared expert's width (0 =
    # n_shared_experts x d_ff_expert).
    moe_latent_dim: int = 0
    d_ff_shared: int = 0
    mlp_activation: str = "silu_gated"  # silu_gated | relu2 (non-gated: relu(x W_up)^2 W_down)
    # what moe.route cannot do and refuses by name: a group limit on the choice
    # (n_group > 1) and gates that are not normalised over the chosen (norm_topk_prob false)
    moe_n_group: int = 1
    moe_norm_topk: bool = True
    # what is added to the chosen scores' sum before the gates are divided by it (1e-20 is
    # every family's here but lfm2_moe's, which states 1e-6)
    moe_gate_eps: float = 1e-20
    # The gated short convolution (lfm2's Lfm2ShortConv; models/sconv.py): [B | C | x] by one
    # projection, a causal depthwise convolution of conv_taps taps (conv_L_cache) over B * x,
    # times C, an output projection; all d_model wide, no bias
    conv_taps: int = 3
    # RMSNorm over a head's width of q and of k, one [head_dim] weight each, before the
    # rotation (lfm2's q_layernorm / k_layernorm; eps is norm_eps)
    attn_qk_norm: bool = False
    # A sliding window (afmoe's sliding_window; 0 = none): in a `W` part of the pattern key j
    # is kept for query i where 0 <= i - j < attn_window, and q and k are rotated whatever
    # attention_rotation says, which is the `*` parts' (afmoe's full layers are not rotated)
    attn_window: int = 0
    # every part's OUTPUT goes through an RMSNorm of its own before the residual, mixers and
    # feed-forward parts alike (afmoe's sandwich norms): x + RMSNorm(part(RMSNorm(x)))
    part_post_norm: bool = False
    embed_scale: float = 0.0  # the embedding's output times this (0 = none; afmoe's mup_enabled: sqrt(d_model))
    # The training objective: 0 is next-token prediction. > 0 is block diffusion (sdar_moe; BD3-LMs,
    # arXiv:2503.09573) in blocks of this many positions (a power of two): llama.loss_fn noises a
    # sequence to `diffusion_mask_token` where the batch's `masked` says, runs [noised ; clean] as
    # one row of twice the length with the positions repeated, under the block-diffusion mask
    # (ops/attention.py:block_diffusion_keep), and reads the noised half's logits at a token's
    # OWN position, weighted by 1 / p_mask. A forward pass of such a configuration takes the
    # doubled row.
    diffusion_block: int = 0
    diffusion_mask_token: int = 0
    # A looped stack (ouro's total_ut_steps; arXiv:2510.25741): the layers run `loop_steps` times over
    # the SAME weights, the final norm's output of one recurrence the input of the next, a head and
    # an exit gate (one sigmoid a position, the leaf `exit_gate`: d_model weights and a bias) behind each; 1 is
    # every other family's stack, run once. With more than one the objective is the expected-exit loss
    # (llama.loss_fn -> `expected_exit_loss`): each recurrence's next-token cross entropy weighted by
    # the probability of leaving there, less `exit_entropy_weight` times that distribution's entropy.
    loop_steps: int = 1
    exit_entropy_weight: float = 0.0
    # Hyper-connections (xing4_0's hc_mult; arXiv:2409.19606, manifold-constrained: mHC, arXiv:2512.24880): the
    # residual stream is `hc_mult` copies of d_model channels a token, carried flat, [B, T, hc_mult x d_model]. A
    # part reads a learned, input-dependent mixture of the copies and writes its output back through a second one,
    # and the copies are mixed by a matrix a token that `hc_sinkhorn_iters` rounds of Sinkhorn-Knopp (column sums,
    # then row sums, `hc_eps` in the denominators) make doubly stochastic; its logits are clipped to
    # +-`hc_res_clamp` before the exponential (models/hyper.py). 1 is every other family's stream: x + F(x).
    hc_mult: int = 1
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: float = 30.0
    # YaRN (arXiv:2309.00071, as DeepSeek-V3's latent attention applies it; `rope_scaling` of type yarn): the rotation's
    # frequencies are blended between theta's own and theirs over `rope_factor` (1 = no scaling), by where a pair's
    # wavelength lies between `rope_beta_fast` and `rope_beta_slow` turns over `rope_original_len` positions; cos and
    # sin times mscale(rope_mscale) / mscale(rope_mscale_all_dim) and the softmax scale times mscale(rope_mscale_all_dim)^2
    # (models/attn.py:yarn_mscale; 0 = none of the two).
    rope_factor: float = 1.0
    rope_original_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 0.0
    rope_mscale_all_dim: float = 0.0

    def __post_init__(self):
        # JSON hands a list; the dataclass is a static (hashed) argument of jitted programs
        object.__setattr__(self, "experts_held", tuple(self.experts_held))
        object.__setattr__(self, "attn_heads_held", tuple(self.attn_heads_held))
        if self.layer_pattern:
            unknown = set(self.layer_pattern) - set(LAYER_KINDS)
            if unknown or len(self.layer_pattern) != self.n_layers:
                raise ValueError(
                    f"layer_pattern {self.layer_pattern!r}: one of "
                    + " ".join(f"{c} ({kind.says})" for c, kind in LAYER_KINDS.items())
                    + f" a layer, n_layers ({self.n_layers}) of them")
            if "K" in self.layer_pattern and not self.kda_n_heads:
                raise ValueError("layer_pattern has K layers: kda_n_heads says how many heads one holds")
            if any(LAYER_KINDS[c].windowed for c in self.layer_pattern) and self.attn_window < 1:
                raise ValueError("layer_pattern has W layers: attn_window says how many keys a query keeps")
            if self.n_dense_layers:
                raise ValueError("layer_pattern says which layers are dense ('-'); n_dense_layers is the block's")
        if self.mtp_layer_pattern not in ("", "*E"):
            raise NotImplementedError(
                f"mtp_layer_pattern {self.mtp_layer_pattern!r}: an MTP module is attention then "
                "an expert layer ('*E')")
        if self.diffusion_block and (self.diffusion_block & (self.diffusion_block - 1) or self.mtp_depth
                                     or self.layer_pattern or self.pipeline_stages > 1
                                     or not 0 <= self.diffusion_mask_token < self.vocab_size):
            raise NotImplementedError(
                f"the block-diffusion objective (diffusion_block {self.diffusion_block}) with blocks that are no "
                "power of two, MTP modules, a pattern of single-part layers, pipeline stages, or a mask token "
                f"({self.diffusion_mask_token}) outside the vocabulary")
        if self.loop_steps < 1 or self.loop_steps > 1 and (
                self.layer_pattern or self.pipeline_stages > 1 or self.n_dense_layers or self.mtp_depth
                or self.diffusion_block or self.n_experts):
            raise NotImplementedError(
                f"a looped stack (loop_steps {self.loop_steps}) of fewer than one recurrence, or around a pattern of "
                "single-part layers (_pattern_layers), pipeline stages (_pipeline_layers), a leading dense stack, "
                "MTP modules, the block-diffusion objective or expert layers (their counters are a row a layer, "
                "not a row a layer and recurrence)")
        if self.hc_mult < 1 or self.hc_mult > 1 and (
                self.layer_pattern or self.pipeline_stages > 1 or self.loop_steps > 1 or self.diffusion_block
                or self.part_post_norm or self.hc_sinkhorn_iters < 1):
            raise NotImplementedError(
                f"a residual stream of hc_mult {self.hc_mult} copies: fewer than one, no round of the projection "
                "(hc_sinkhorn_iters), or n streams over a pattern of single-part layers (_pattern_layers hands a part's "
                "projection error out of no loop), across pipeline stages (_pipeline_layers: a stage hands on "
                "[B, T, d_model]), under a looped stack (looped_outputs norms d_model channels between recurrences), "
                "the block-diffusion objective or a norm behind each part")
        if self.rope_factor != 1.0 and (
                self.rope_factor < 1.0 or self.rope_original_len < 1 or not self.latent_attention
                or not self.attention_rotation or self.attention_impl in ("ring", "ulysses")):
            raise NotImplementedError(
                f"YaRN (rope_factor {self.rope_factor}) under 1, without rope_original_len, on the ring / Ulysses paths "
                "(ops/ring_attention.py takes no softmax scale) or outside rotated latent attention: the rotate kernel in "
                "front of the flash kernels (ops/attention.py:Rotation) and the serving programs take theta alone")
        if self.mlp_activation not in ("silu_gated", "relu2"):
            raise ValueError(f"unknown mlp_activation {self.mlp_activation!r} (silu_gated | relu2)")

    @property
    def latent_attention(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def head_dim(self) -> int:
        """Width of a head's q and k (v's is `v_dim`)."""
        if self.latent_attention:
            return self.qk_nope_head_dim + self.qk_rope_head_dim
        return self.attn_head_dim or self.d_model // self.n_heads

    @property
    def v_dim(self) -> int:
        """Width of a head's v and of attention's output a head: latent attention's published
        v_head_dim (Kimi Linear: 128 beside q and k of 192), else q's and k's."""
        return self.v_head_dim if self.latent_attention else self.head_dim

    @property
    def heads_held(self) -> int:
        return self.attn_heads_held[0] or self.n_heads

    @property
    def kv_heads_held(self) -> int:
        return self.attn_heads_held[1] or self.n_kv_heads

    @property
    def ssm_d_inner(self) -> int:
        return self.ssm_n_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the convolution runs over: x beside every group's B and C."""
        return self.ssm_d_inner + 2 * self.ssm_n_groups * self.ssm_state

    @property
    def kda_d_inner(self) -> int:
        return self.kda_n_heads * self.kda_head_dim

    @property
    def kda_rank(self) -> int:
        return self.kda_proj_rank or self.kda_head_dim

    @property
    def moe_dropless(self) -> bool:
        return self.n_experts > 0 and self.moe_capacity_factor <= 0

    @property
    def n_experts_held(self) -> int:
        return self.n_experts // self.experts_held[1]

    @property
    def shared_width(self) -> int:
        """Width of the shared experts, as the one MLP they are run as."""
        return self.d_ff_shared or self.n_shared_experts * (self.d_ff_expert or self.d_ff)

    @property
    def activation_dtype(self):
        return jnp.dtype(self.dtype)

    @property
    def n_params(self) -> int:
        """Approximate parameter count (embeddings + blocks + norms), of what is held: the sum
        of what each layer kind's parts count beside their `init` (llama.n_params)."""
        from . import llama  # at the call: this module imports no model code

        return llama.n_params(self)


_REGISTRY: Dict[str, ModelConfig] = {}


def register_config(cfg: ModelConfig) -> ModelConfig:
    _REGISTRY[cfg.name] = cfg
    return cfg


def get_config(name: str, **overrides) -> ModelConfig:
    cfg = _REGISTRY[name]
    return dataclasses.replace(cfg, **overrides) if overrides else cfg


register_config(
    ModelConfig(
        name="test-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=128,
        dtype="float32",
    )
)
register_config(
    # Tiny serving-test model whose vocab covers the byte-level tokenizer (259 ids).
    ModelConfig(
        name="byte-tiny",
        vocab_size=512,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=128,
        max_seq_len=256,
        dtype="float32",
    )
)
register_config(
    # Single-chip bench model (~0.4B): same architecture family as llama3, sized so that
    # f32 params + Adam state + remat activations fit one v5e chip's 16 GiB HBM.
    ModelConfig(
        name="llama-500m",
        vocab_size=32000,
        d_model=1536,
        n_layers=12,
        n_heads=12,
        n_kv_heads=6,
        d_ff=4096,
        max_seq_len=2048,
        rope_theta=500000.0,
    )
)
register_config(
    ModelConfig(
        name="llama-1b",
        vocab_size=32000,
        d_model=2048,
        n_layers=16,
        n_heads=16,
        n_kv_heads=8,
        d_ff=5632,
        max_seq_len=2048,
        rope_theta=500000.0,
    )
)
register_config(
    # llama3-8b LAYER GEOMETRY at single-chip depth: the realistic
    # arithmetic-intensity regime (d_model 4096, GQA 32/8, d_ff 14336) for
    # one-chip MFU benchmarking without 8B-scale optimizer state. 2 layers +
    # the 32k vocab keep f32 Adam + remat activations inside one v5e's HBM.
    ModelConfig(
        name="llama8b-geom2",
        vocab_size=32000,
        d_model=4096,
        n_layers=2,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        max_seq_len=2048,
        rope_theta=500000.0,
    )
)
register_config(
    ModelConfig(
        name="llama3-8b",
        vocab_size=128256,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        max_seq_len=8192,
        rope_theta=500000.0,
    )
)
register_config(
    ModelConfig(
        name="llama3-70b",
        vocab_size=128256,
        d_model=8192,
        n_layers=80,
        n_heads=64,
        n_kv_heads=8,
        d_ff=28672,
        max_seq_len=8192,
    )
)
register_config(
    ModelConfig(
        name="moe-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=2,
        n_heads=4,
        n_kv_heads=2,
        d_ff=96,
        max_seq_len=128,
        dtype="float32",
        n_experts=4,
        moe_top_k=2,
    )
)
register_config(
    # Toy of the GLM-4.7-Flash family (glm4_moe_lite) for the CPU tests: latent attention,
    # a leading dense layer, sigmoid-routed experts beside a shared one, one MTP module.
    # All 8 experts held; tests cut shares with experts_held=(i, n).
    ModelConfig(
        name="glm-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=4,
        d_ff=160,
        max_seq_len=128,
        rope_theta=1e6,
        dtype="float32",
        q_lora_rank=48,
        kv_lora_rank=32,
        qk_nope_head_dim=24,
        qk_rope_head_dim=8,
        v_head_dim=32,
        n_experts=8,
        moe_top_k=2,
        moe_capacity_factor=0.0,
        moe_aux_loss_coef=0.0,
        d_ff_expert=48,
        n_shared_experts=1,
        n_dense_layers=1,
        moe_scoring="sigmoid",
        moe_route_scale=1.8,
        moe_select_bias=True,
        mtp_depth=1,
    )
)
register_config(
    # Toy of the nemotron_h family (Nemotron-3-Super) for the CPU tests: a pattern of
    # single-part layers (Mamba-2, latent relu2 experts beside a shared one, attention
    # without rotation at a head width of its own), one MTP module. Everything held;
    # tests cut shares of heads, groups and experts.
    ModelConfig(
        name="nemotron-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=6,
        n_heads=4,
        n_kv_heads=2,
        d_ff=96,
        max_seq_len=128,
        dtype="float32",
        layer_pattern="MEM*E-",
        mtp_layer_pattern="*E",
        ssm_n_heads=8,
        ssm_head_dim=8,
        ssm_n_groups=2,
        ssm_state=16,
        ssm_chunk=8,
        attn_head_dim=24,
        attention_rotation=False,
        n_experts=16,
        moe_top_k=3,
        moe_capacity_factor=0.0,
        moe_aux_loss_coef=0.0,
        d_ff_expert=40,
        n_shared_experts=1,
        d_ff_shared=80,
        moe_latent_dim=32,
        mlp_activation="relu2",
        moe_scoring="sigmoid",
        moe_route_scale=5.0,
        moe_select_bias=True,
        mtp_depth=1,
    )
)
register_config(
    # Toy of the solar_open2 family (Solar-Open2) for the CPU tests: every published layer
    # two parts of the pattern (a mixer, then experts); Kimi-Delta-Attention mixers three
    # to one with softmax attention that has an output gate and no rotation, at a head
    # width of its own; sigmoid-routed SwiGLU experts beside a shared one. Everything
    # held; tests cut shares of heads and experts.
    ModelConfig(
        name="solar-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=8,
        n_heads=4,
        n_kv_heads=2,
        d_ff=96,
        max_seq_len=128,
        dtype="float32",
        layer_pattern="*EKEKEKE",
        kda_n_heads=4,
        kda_head_dim=16,
        kda_chunk=8,
        attn_head_dim=24,
        attention_rotation=False,
        attn_output_gate=True,
        n_experts=20,
        moe_top_k=3,
        moe_capacity_factor=0.0,
        moe_aux_loss_coef=0.0,
        d_ff_expert=40,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        moe_select_bias=True,
    )
)
register_config(
    # Toy of the lfm2_moe family (LFM2-24B-A2B) for the CPU tests: every published layer two
    # parts of the pattern (a mixer, then a feed-forward part); gated short convolutions
    # three to one with rotated GQA whose q and k are normed a head; a leading dense layer,
    # then sigmoid-routed SwiGLU experts with NO shared expert; a tied head. Everything
    # held; tests cut shares of the experts.
    ModelConfig(
        name="lfm2-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=10,
        n_heads=4,
        n_kv_heads=2,
        d_ff=96,
        max_seq_len=128,
        rope_theta=1e6,
        tie_embeddings=True,
        dtype="float32",
        layer_pattern="C-*ECECECE",
        attn_qk_norm=True,
        n_experts=16,
        moe_top_k=4,
        moe_capacity_factor=0.0,
        moe_aux_loss_coef=0.0,
        d_ff_expert=40,
        moe_scoring="sigmoid",
        moe_select_bias=True,
        moe_gate_eps=1e-6,
    )
)
register_config(
    # Toy of the afmoe family (Trinity-Mini) for the CPU tests: every published layer two parts
    # of the pattern (a mixer, then a feed-forward part), each between two norms; attention
    # inside a window (11 keys: shorter than any test's sequence, a multiple of no tile) and
    # rotated, three to one with full attention that is not; both gated and normed a head, at a
    # head width of its own (4 x 24 is wider than d_model, as 32 x 128 is than 2048); a leading
    # dense layer, then sigmoid-routed SwiGLU experts beside a shared one; the embedding
    # scaled by sqrt(d_model). Everything held; tests cut shares of the experts.
    ModelConfig(
        name="trinity-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=10,
        n_heads=4,
        n_kv_heads=2,
        d_ff=96,
        max_seq_len=128,
        rope_theta=1e4,
        dtype="float32",
        layer_pattern="W-WEWEWE*E",
        attn_head_dim=24,
        attention_rotation=False,
        attn_output_gate=True,
        attn_qk_norm=True,
        attn_window=11,
        part_post_norm=True,
        embed_scale=8.0,
        n_experts=16,
        moe_top_k=3,
        moe_capacity_factor=0.0,
        moe_aux_loss_coef=0.0,
        d_ff_expert=40,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        moe_route_scale=2.826,
        moe_select_bias=True,
    )
)
register_config(
    # Toy of the sdar_moe family (SDAR-30B-A3B) for the CPU tests: the block every layer is
    # (rotated GQA whose q and k are normed a head, then SOFTMAX-routed SwiGLU experts with
    # no shared expert, no selection bias and no dense layer), an untied head, and the
    # block-diffusion objective in blocks of 4 with the vocabulary's last row as the mask
    # token. Everything held; tests cut shares of the experts.
    ModelConfig(
        name="sdar-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=2,
        d_ff=96,
        max_seq_len=128,
        rope_theta=1e6,
        norm_eps=1e-6,
        dtype="float32",
        attn_head_dim=24,
        attn_qk_norm=True,
        n_experts=16,
        moe_top_k=4,
        moe_capacity_factor=0.0,
        moe_aux_loss_coef=0.0,
        d_ff_expert=40,
        moe_scoring="softmax",
        diffusion_block=4,
        diffusion_mask_token=255,
    )
)
register_config(
    # Toy of the kimi_linear family (Kimi-Linear-48B-A3B) for the CPU tests: every published layer
    # two parts of the pattern (a mixer, then a feed-forward part); Kimi-Delta-Attention mixers with
    # beta in (0, 1) three to one with latent attention that has NO q latent, NO rotation and v
    # heads (16) narrower than q's and k's (16 + 8); a leading dense layer, then sigmoid-routed
    # SwiGLU experts beside a shared one, the gates scaled. Everything held; tests cut shares of
    # the experts.
    ModelConfig(
        name="kimi-linear-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=10,
        n_heads=4,
        n_kv_heads=4,
        d_ff=96,
        max_seq_len=128,
        rope_theta=1e4,
        dtype="float32",
        layer_pattern="K-KEKE*EKE",
        kda_n_heads=4,
        kda_head_dim=16,
        kda_chunk=8,
        kda_neg_eigval=False,
        attention_rotation=False,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        n_experts=16,
        moe_top_k=3,
        moe_capacity_factor=0.0,
        moe_aux_loss_coef=0.0,
        d_ff_expert=40,
        n_shared_experts=1,
        moe_scoring="sigmoid",
        moe_route_scale=2.446,
        moe_select_bias=True,
    )
)
register_config(
    # Toy of the ouro family (Ouro-2.6B) for the CPU tests: the block every layer is (rotated
    # attention with as many key/value heads as query heads, then the dense SwiGLU MLP), each part
    # between a norm on its input and one on its output, an untied head; the three layers run FOUR
    # times over the same weights, an exit gate and a head behind each recurrence, under the
    # expected-exit loss with its entropy term.
    ModelConfig(
        name="ouro-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=4,
        d_ff=96,
        max_seq_len=128,
        rope_theta=1e6,
        norm_eps=1e-6,
        dtype="float32",
        part_post_norm=True,
        loop_steps=4,
        exit_entropy_weight=0.05,
    )
)
register_config(
    # Toy of the xing4_0 family (Xing4.0-29B-A4B) for the CPU tests: glm-tiny's block (rotated latent attention with a q
    # latent, a leading dense layer, sigmoid-routed experts beside a shared one) with v heads narrower than q's and k's,
    # under YaRN (a factor of 8 over 32 positions: the tests' sequences run past them) and around it a residual stream
    # of FOUR copies mixed by manifold-constrained hyper-connections; no MTP module. Everything held; tests cut shares
    # of the heads and of the experts.
    ModelConfig(
        name="xing-tiny",
        vocab_size=256,
        d_model=64,
        n_layers=3,
        n_heads=4,
        n_kv_heads=4,
        d_ff=160,
        max_seq_len=128,
        rope_theta=1e4,
        norm_eps=1e-6,
        dtype="float32",
        q_lora_rank=48,
        kv_lora_rank=32,
        qk_nope_head_dim=16,
        qk_rope_head_dim=8,
        v_head_dim=16,
        n_experts=8,
        moe_top_k=2,
        moe_capacity_factor=0.0,
        moe_aux_loss_coef=0.0,
        d_ff_expert=48,
        n_shared_experts=1,
        n_dense_layers=1,
        moe_scoring="sigmoid",
        moe_route_scale=2.0,
        moe_select_bias=True,
        hc_mult=4,
        rope_factor=8.0,
        rope_original_len=32,
        rope_beta_fast=4.0,
        rope_beta_slow=0.5,
        rope_mscale=1.0,
        rope_mscale_all_dim=1.0,
    )
)
register_config(
    # Mixtral-8x7B architecture description (public): 8 experts, top-2 routing.
    ModelConfig(
        name="mixtral-8x7b",
        vocab_size=32000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=8,
        d_ff=14336,
        max_seq_len=32768,
        rope_theta=1e6,
        n_experts=8,
        moe_top_k=2,
    )
)
register_config(
    ModelConfig(
        name="llama2-7b",
        vocab_size=32000,
        d_model=4096,
        n_layers=32,
        n_heads=32,
        n_kv_heads=32,
        d_ff=11008,
        max_seq_len=4096,
        rope_theta=10000.0,
    )
)
