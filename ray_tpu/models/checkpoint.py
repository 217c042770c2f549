"""HF-format checkpoint IO: safetensors ⇄ the ray_tpu llama parameter pytree.

Loading real weights is table stakes of the serving-engine contract (reference:
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:180 — the
engine constructor is handed a model id and must materialize it). The reference
delegates to vLLM/HF loaders; here the loader is native:

- reads HF transformers Llama layout (config.json + *.safetensors, sharded
  index supported), torch ``Linear`` weight convention (out_features, in_features);
- streams ONE target leaf at a time: gather the per-layer tensors, transform
  (transpose/reshape/stack for the scanned layout), cast, and ``jax.device_put``
  with the leaf's NamedSharding before touching the next leaf — peak host memory
  is one stacked leaf, not the whole model;
- the writer emits the same layout so checkpoints round-trip (and tests can
  fabricate tiny "HF" checkpoints without the hub).
"""
from __future__ import annotations

import json
import os
from typing import Any, Callable, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ray_tpu.parallel.sharding import INFER_RULES, AxisRules, named_sharding

from .config import ModelConfig

Params = Dict[str, Any]


# ----------------------------------------------------------------- config.json

def config_from_hf(source_dir: str, **overrides) -> ModelConfig:
    """Map an HF transformers LlamaConfig (config.json) onto ModelConfig."""
    with open(os.path.join(source_dir, "config.json")) as f:
        hf = json.load(f)
    fields = dict(
        name=hf.get("_name_or_path") or os.path.basename(os.path.normpath(source_dir)),
        vocab_size=hf["vocab_size"],
        d_model=hf["hidden_size"],
        n_layers=hf["num_hidden_layers"],
        n_heads=hf["num_attention_heads"],
        n_kv_heads=hf.get("num_key_value_heads", hf["num_attention_heads"]),
        d_ff=hf["intermediate_size"],
        # missing keys take the HF transformers LlamaConfig defaults, NOT ours —
        # a Llama-2-era config.json omits rope_theta and means 10000.0
        max_seq_len=hf.get("max_position_embeddings", 2048),
        rope_theta=float(hf.get("rope_theta", 10000.0)),
        norm_eps=float(hf.get("rms_norm_eps", 1e-6)),
        tie_embeddings=bool(hf.get("tie_word_embeddings", False)),
    )
    # Mixtral-style sparse MoE (num_local_experts in MixtralConfig). Our top-k
    # gating (softmax over all E, keep top-k, renormalize to sum 1) is
    # mathematically identical to Mixtral's softmax-over-the-top-k-logits: the
    # full-softmax normalizer cancels in the renormalization; top-1 needs the
    # explicit renorm flag (Switch convention differs there). Mixtral has no
    # capacity concept (dropless), so the faithful default is capacity_factor
    # = E/k, which makes expert capacity cover the worst-case routing (every
    # token to one expert) — moe.py then drops nothing. Our own round-tripped
    # checkpoints carry the trained factor in config.json instead.
    if hf.get("num_local_experts", 0):
        # Only Mixtral's layout/gating is wired: other HF MoE families that
        # also carry num_local_experts (e.g. Phi-MoE) have different tensor
        # layouts and routing conventions — accepting them here would fail
        # much later at weight load with an opaque missing-tensor error.
        model_type = hf.get("model_type", "")
        if model_type != "mixtral":
            raise ValueError(
                f"unsupported MoE checkpoint: model_type {model_type!r} with "
                f"num_local_experts={hf['num_local_experts']}; only "
                "Mixtral-style sparse MoE (model_type 'mixtral') is supported"
            )
        e = int(hf["num_local_experts"])
        k = int(hf.get("num_experts_per_tok", 2))
        fields["n_experts"] = e
        fields["moe_top_k"] = k
        fields["moe_top1_renorm"] = bool(hf.get("moe_top1_renorm", True))
        fields["moe_capacity_factor"] = float(
            hf.get("moe_capacity_factor", e / k))
    if hf.get("model_type") in _FAMILY_FIELDS:
        fields.update(_FAMILY_FIELDS[hf["model_type"]](hf))
    fields.update(overrides)
    return ModelConfig(**fields)


def _lfm2_moe_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `lfm2_moe` keys (LFM2-24B-A2B) as ModelConfig fields. A published layer is a
    token mixer then a feed-forward part, each behind its own norm and residual: two
    characters of the layer pattern, the mixer `*` where `layer_types` says
    `full_attention` (GQA whose q and k are normed a head before the rotation) and `C`
    where it says `conv` (the gated short convolution), the feed-forward part `-` in the
    `num_dense_layers` leading layers and `E` after them (sigmoid-routed SwiGLU experts,
    no shared one), so n_layers counts parts. Every expert is held; a share is an override
    (`experts_held`). The family writes `norm_eps`, `num_experts` and
    `rope_parameters.rope_theta` where Llama's writes `rms_norm_eps`, `num_local_experts`
    and `rope_theta`, and ties its head unless told otherwise. What the program does not
    run is refused by name; what config.json does not state is
    benchmarks/configs/lfm2-24b-a2b-train-ep8.json's `assumed`. Weights' names are not
    mapped."""
    kinds = hf.get("layer_types") or []
    rope = hf.get("rope_parameters") or {}
    refused = [what for has, what in (
        (len(kinds) != hf["num_hidden_layers"] or set(kinds) - {"full_attention", "conv"},
         f"layer_types that are not num_hidden_layers of full_attention | conv ({sorted(set(kinds))})"),
        (hf.get("conv_bias", False), "a bias on the convolution's projections (conv_bias true)"),
        (not hf.get("norm_topk_prob", True), "gates that are not normalised (norm_topk_prob false)"),
        (not hf.get("use_expert_bias", True), "a router without its selection bias (use_expert_bias false)"),
        (rope.get("rope_type", "default") != "default", f"rope_type {rope.get('rope_type')!r}"),
        (hf.get("sliding_window") is not None, "window attention (sliding_window)"),
        (not hf.get("num_experts", 0), "layers without routed experts (the dense family is lfm2)"),
    ) if has]
    if refused:
        raise ValueError("lfm2_moe as this config.json states it is not supported: " + "; ".join(refused))
    dense = hf.get("num_dense_layers", 0)
    pattern = "".join(("*" if kind == "full_attention" else "C") + ("-" if i < dense else "E")
                      for i, kind in enumerate(kinds))
    return dict(
        n_layers=len(pattern), layer_pattern=pattern,
        norm_eps=float(hf.get("norm_eps", 1e-5)),
        rope_theta=float(rope.get("rope_theta", hf.get("rope_theta", 1e6))),
        tie_embeddings=bool(hf.get("tie_word_embeddings", hf.get("tie_embedding", True))),
        attn_qk_norm=True, conv_taps=hf.get("conv_L_cache", 3),
        n_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_tok"],
        d_ff_expert=hf["moe_intermediate_size"], n_shared_experts=0,
        moe_capacity_factor=0.0, moe_aux_loss_coef=0.0, moe_scoring="sigmoid", moe_select_bias=True,
        moe_route_scale=float(hf.get("routed_scaling_factor", 1.0)), moe_gate_eps=1e-6,
    )


# What sdar_moe's config.json does not state and the released model fixes: the chat model's
# default block length, and the id of `<|MASK|>` in its tokenizer (the first id behind
# Qwen3's own special tokens); keys of these names override them
_SDAR_BLOCK_LENGTH = 4
_SDAR_MASK_TOKEN = 151669


def _sdar_moe_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `sdar_moe` keys (SDAR-30B-A3B) as ModelConfig fields: qwen3_moe's block (rotated
    GQA at a published head width whose q and k are normed a head, then SOFTMAX-routed
    SwiGLU experts: scores over all experts, the k largest, gates normalised over them; no
    shared expert, no selection bias) in every layer, and the block-diffusion objective
    (`diffusion_block`, `diffusion_mask_token`: config.json states neither; `block_length`
    and `mask_token_id` are read where a file has them). No auxiliary router loss
    (config.json states no coefficient). Every expert is held; a share is an override
    (`experts_held`). What the program does not run is refused by name; what config.json
    does not state is benchmarks/configs/sdar-30b-a3b-train-ep8.json's `assumed`. Weights'
    names are not mapped."""
    refused = [what for has, what in (
        (hf.get("attention_bias", False), "biases on the attention projections (attention_bias true)"),
        (hf.get("decoder_sparse_step", 1) != 1 or hf.get("mlp_only_layers"),
         "dense layers among the expert layers (decoder_sparse_step, mlp_only_layers)"),
        (not hf.get("norm_topk_prob", True), "gates that are not normalised (norm_topk_prob false)"),
        (hf.get("use_sliding_window", False), "window attention (use_sliding_window)"),
        (hf.get("rope_scaling") is not None, f"rope_scaling {hf.get('rope_scaling')!r}"),
        (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
        (not hf.get("num_experts", 0), "layers without routed experts (the dense family is sdar)"),
    ) if has]
    if refused:
        raise ValueError("sdar_moe as this config.json states it is not supported: " + "; ".join(refused))
    return dict(
        attn_head_dim=hf.get("head_dim", 0), attn_qk_norm=True,
        n_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_tok"],
        d_ff_expert=hf["moe_intermediate_size"], n_shared_experts=0,
        moe_capacity_factor=0.0, moe_aux_loss_coef=0.0, moe_scoring="softmax", moe_select_bias=False,
        diffusion_block=hf.get("block_length", _SDAR_BLOCK_LENGTH),
        diffusion_mask_token=hf.get("mask_token_id", min(_SDAR_MASK_TOKEN, hf["vocab_size"] - 1)),
    )


def _afmoe_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `afmoe` keys (Trinity-Mini) as ModelConfig fields. A published layer is attention
    then a feed-forward part, each between a norm on its input and one on its output
    (`part_post_norm`): two characters of the layer pattern, the mixer `W` where
    `layer_types` says `sliding_attention` (inside `sliding_window`, rotated) and `*` where it
    says `full_attention` (not rotated: `attention_rotation` false is the `*` parts'), both
    gated a channel and normed a head; the feed-forward part `-` in the `num_dense_layers`
    leading layers and `E` after them (sigmoid-routed SwiGLU experts beside shared ones), so
    n_layers counts parts. `mup_enabled` is the embedding's output times sqrt(hidden_size);
    `load_balance_coeff` the rate at which the selection bias moves. Every expert is held; a
    share is an override (`experts_held`). What the program does not run is refused by name;
    what config.json does not state is benchmarks/configs/trinity-mini-train-ep16.json's
    `assumed`. Weights' names are not mapped."""
    kinds = hf.get("layer_types") or []
    refused = [what for has, what in (
        (len(kinds) != hf["num_hidden_layers"] or set(kinds) - {"full_attention", "sliding_attention"},
         f"layer_types that are not num_hidden_layers of full_attention | sliding_attention ({sorted(set(kinds))})"),
        ("sliding_attention" in kinds and not hf.get("sliding_window"), "sliding_attention layers without a sliding_window"),
        (hf.get("score_func", "sigmoid") != "sigmoid", f"score_func {hf.get('score_func')!r}"),
        (not hf.get("route_norm", True), "gates that are not normalised (route_norm false)"),
        (any(hf.get(key, 1) != 1 for key in ("n_group", "topk_group", "num_expert_groups", "num_limited_groups")),
         "group-limited routing (n_group / topk_group / num_expert_groups / num_limited_groups > 1)"),
        (hf.get("rope_scaling") is not None, f"rope_scaling {hf.get('rope_scaling')!r}"),
        (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
        (not hf.get("num_experts", 0), "layers without routed experts"),
    ) if has]
    if refused:
        raise ValueError("afmoe as this config.json states it is not supported: " + "; ".join(refused))
    dense = hf.get("num_dense_layers", 0)
    pattern = "".join(("W" if kind == "sliding_attention" else "*") + ("-" if i < dense else "E")
                      for i, kind in enumerate(kinds))
    return dict(
        n_layers=len(pattern), layer_pattern=pattern,
        attn_head_dim=hf.get("head_dim", 0), attn_window=hf.get("sliding_window") or 0,
        attention_rotation=False, attn_output_gate=True, attn_qk_norm=True, part_post_norm=True,
        embed_scale=float(hf["hidden_size"]) ** 0.5 if hf.get("mup_enabled", False) else 0.0,
        n_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_tok"],
        d_ff_expert=hf["moe_intermediate_size"], n_shared_experts=hf.get("num_shared_experts", 0),
        moe_capacity_factor=0.0, moe_aux_loss_coef=0.0, moe_scoring="sigmoid", moe_select_bias=True,
        moe_route_scale=float(hf.get("route_scale", 1.0)),
        moe_bias_update_rate=float(hf.get("load_balance_coeff", 0.001)),
    )


def _nemotron_h_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `nemotron_h` keys (Nemotron-3-Super) as ModelConfig fields: a pattern of
    single-part layers (Mamba-2 mixers, attention, latent relu2 experts beside a shared
    one, the dense relu2 MLP) and the MTP modules. Everything is held; a share is an
    override (fewer ssm heads and groups, `attn_heads_held`, `experts_held`). What the
    program does not run is refused by name. The attention layers apply NO rotation (the
    published implementation's attention is Jamba's, which has none; rope_theta and
    partial_rotary_factor stand in the config unread): one field, attention_rotation, for
    a reader who finds otherwise. Weights' names are not mapped."""
    pattern = hf["hybrid_override_pattern"]
    refused = [what for has, what in (
        (hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1,
         "group-limited routing (n_group / topk_group > 1)"),
        (not hf.get("norm_topk_prob", True), "gates that are not normalised (norm_topk_prob false)"),
        (hf.get("mlp_hidden_act", "relu2") != "relu2", f"mlp_hidden_act {hf.get('mlp_hidden_act')!r}"),
        (hf.get("mamba_hidden_act", "silu") != "silu", f"mamba_hidden_act {hf.get('mamba_hidden_act')!r}"),
        (any(hf.get(k) for k in ("attention_bias", "mlp_bias", "mamba_proj_bias", "use_bias")),
         "biases on the projections"),
        (not hf.get("use_conv_bias", True), "a convolution without its bias"),
        (hf.get("time_step_limit") is not None, "a clamp on dt (time_step_limit)"),
        (hf.get("sliding_window") is not None, "window attention (sliding_window)"),
        (hf.get("moe_shared_expert_overlap", False), "moe_shared_expert_overlap"),
        (hf.get("num_nextn_predict_layers", 0) and hf.get("mtp_hybrid_override_pattern", "*E") != "*E",
         f"an MTP module of layers {hf.get('mtp_hybrid_override_pattern')!r} (only '*E')"),
        (hf.get("n_routed_experts", 0) and not hf.get("moe_latent_size"),
         "experts at the full width (no moe_latent_size)"),
    ) if has]
    if refused:
        raise ValueError("nemotron_h as this config.json states it is not supported: " + "; ".join(refused))
    d_inner = hf["mamba_num_heads"] * hf["mamba_head_dim"]
    if d_inner != hf.get("expand", 2) * hf["hidden_size"]:
        raise ValueError(f"mamba_num_heads x mamba_head_dim ({d_inner}) is not expand x hidden_size")
    experts = hf.get("n_routed_experts", 0)
    fields = dict(
        n_layers=len(pattern), layer_pattern=pattern,
        norm_eps=float(hf.get("norm_eps", hf.get("layer_norm_epsilon", 1e-5))),
        attn_head_dim=hf.get("head_dim", 0), attention_rotation=False, mlp_activation="relu2",
        ssm_n_heads=hf["mamba_num_heads"], ssm_head_dim=hf["mamba_head_dim"],
        ssm_n_groups=hf["n_groups"], ssm_state=hf["ssm_state_size"],
        ssm_conv_taps=hf["conv_kernel"], ssm_chunk=hf["chunk_size"],
        ssm_dt_min=hf.get("time_step_min", 0.001), ssm_dt_max=hf.get("time_step_max", 0.1),
        ssm_dt_floor=hf.get("time_step_floor", 1e-4),
        mtp_depth=hf.get("num_nextn_predict_layers", 0),
    )
    if fields["mtp_depth"]:
        fields["mtp_layer_pattern"] = hf.get("mtp_hybrid_override_pattern", "*E")
    if experts:
        fields.update(
            n_experts=experts, moe_top_k=hf["num_experts_per_tok"],
            d_ff_expert=hf["moe_intermediate_size"], n_shared_experts=hf.get("n_shared_experts", 0),
            d_ff_shared=hf.get("moe_shared_expert_intermediate_size", 0),
            moe_latent_dim=hf["moe_latent_size"], moe_capacity_factor=0.0, moe_aux_loss_coef=0.0,
            moe_scoring="sigmoid", moe_select_bias=True,
            moe_route_scale=float(hf.get("routed_scaling_factor", 1.0)))
    return fields


def _solar_open2_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `solar_open2` keys (Solar-Open2) as ModelConfig fields. A published layer is a
    token mixer then an expert part, each behind its own norm and residual: two characters
    of the layer pattern, `*E` in `gqa_layers` (softmax attention without positions, with
    an output gate) and `KE` elsewhere (Kimi Delta Attention), so n_layers counts parts.
    Everything is held; a share is an override (`kda_n_heads`, `attn_heads_held`,
    `experts_held`). What the program does not run is refused by name. What config.json
    does not state (the low-rank projections' rank, the gate's form, the router's family)
    is benchmarks/configs/solar-open2-train-tp8-ep40.json's `assumed`, one field each.
    Weights' names are not mapped."""
    linear = hf.get("linear_attn_config") or {}
    refused = [what for has, what in (
        (hf.get("use_rope", False), "rotated attention layers (use_rope true)"),
        (hf.get("kda_use_full_proj", False), "full-rank decay and gate projections (kda_use_full_proj true)"),
        (hf.get("first_k_dense_replace", 0), "leading dense layers (first_k_dense_replace > 0)"),
        (not hf.get("norm_topk_prob", True), "gates that are not normalised (norm_topk_prob false)"),
        (hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1,
         "group-limited routing (n_group / topk_group > 1)"),
        (linear.get("num_kv_heads") not in (None, linear.get("num_heads")),
         "Kimi-Delta-Attention layers whose keys have fewer heads than their values (num_kv_heads)"),
        (linear.get("head_dim") is None or linear.get("num_heads") is None,
         "no linear_attn_config (head_dim, num_heads)"),
        (hf.get("sliding_window") is not None, "window attention (sliding_window)"),
        (hf.get("num_nextn_predict_layers", 0), "MTP modules (num_nextn_predict_layers)"),
        (not hf.get("n_routed_experts", 0), "layers without routed experts"),
    ) if has]
    if refused:
        raise ValueError("solar_open2 as this config.json states it is not supported: " + "; ".join(refused))
    gqa = set(hf.get("gqa_layers", ()))
    pattern = "".join("*E" if i in gqa else "KE" for i in range(hf["num_hidden_layers"]))
    return dict(
        n_layers=len(pattern), layer_pattern=pattern,
        attn_head_dim=hf.get("head_dim", 0), attention_rotation=False,
        attn_output_gate=bool(hf.get("use_gqa_gate", False)),
        kda_n_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv_taps=linear.get("short_conv_kernel_size", 4),
        kda_neg_eigval=bool(hf.get("kda_allow_neg_eigval", False)),
        n_experts=hf["n_routed_experts"], moe_top_k=hf["num_experts_per_tok"],
        d_ff_expert=hf["moe_intermediate_size"], n_shared_experts=hf.get("n_shared_experts", 0),
        moe_capacity_factor=0.0, moe_aux_loss_coef=0.0, moe_scoring="sigmoid", moe_select_bias=True,
        moe_route_scale=float(hf.get("routed_scaling_factor", 1.0)),
    )


def _glm4_moe_lite_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `glm4_moe_lite` keys (GLM-4.7-Flash) as ModelConfig fields: latent attention,
    leading dense layers, sigmoid-routed experts chosen by score + bias (`noaux_tc`)
    beside shared ones, served without drops, and the MTP modules. Every expert is held;
    a share is an override (`experts_held=(index, of)`). Weights' names are not mapped:
    load_llama_params does not read this family's tensors yet."""
    if hf.get("n_group", 1) != 1 or hf.get("topk_group", 1) != 1:
        raise ValueError("group-limited routing (n_group / topk_group > 1) is not supported")
    if hf.get("rope_scaling") is not None:
        raise ValueError(f"rope_scaling {hf['rope_scaling']!r} is not supported")
    if not hf.get("norm_topk_prob", True):
        raise ValueError("gates that are not normalised over the chosen experts "
                         "(norm_topk_prob false) are not supported")
    return dict(
        q_lora_rank=hf["q_lora_rank"], kv_lora_rank=hf["kv_lora_rank"],
        qk_nope_head_dim=hf["qk_nope_head_dim"], qk_rope_head_dim=hf["qk_rope_head_dim"],
        v_head_dim=hf["v_head_dim"],
        n_experts=hf["n_routed_experts"], moe_top_k=hf["num_experts_per_tok"],
        d_ff_expert=hf["moe_intermediate_size"], n_shared_experts=hf.get("n_shared_experts", 0),
        n_dense_layers=hf.get("first_k_dense_replace", 0),
        moe_capacity_factor=0.0, moe_aux_loss_coef=0.0,
        moe_scoring="sigmoid", moe_select_bias=hf.get("topk_method") == "noaux_tc",
        moe_route_scale=float(hf.get("routed_scaling_factor", 1.0)),
        mtp_depth=hf.get("num_nextn_predict_layers", 0),
    )


def _kimi_linear_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `kimi_linear` keys (Kimi-Linear-48B-A3B) as ModelConfig fields. A published layer is
    a token mixer then a feed-forward part, each behind its own norm and residual: two
    characters of the layer pattern, the mixer `K` where `linear_attn_config.kda_layers` (1-based)
    lists the layer (Kimi Delta Attention, beta in (0, 1) unless a `kda_allow_neg_eigval` key
    says otherwise) and `*` where `full_attn_layers` does (latent attention: `q_lora_rank` null is
    q by one product, `mla_use_nope` true is no rotation anywhere, `v_head_dim` its own width),
    the feed-forward part `-` in the `first_k_dense_replace` leading layers and `E` after them
    (sigmoid scores, the choice by score + bias, gates renormalised and scaled, beside shared
    experts), so n_layers counts parts. `use_grouped_topk` with one group is the plain top-k.
    Everything is held; a share is an override (`kda_n_heads`, `experts_held`). What the program
    does not run is refused by name; what config.json does not state is
    benchmarks/configs/kimi-linear-48b-a3b-train-ep32.json's `assumed`. Weights' names are not
    mapped."""
    linear = hf.get("linear_attn_config") or {}
    n = hf["num_hidden_layers"]
    kda_at, full_at = (list(linear.get(key) or ()) for key in ("kda_layers", "full_attn_layers"))
    refused = [what for has, what in (
        (linear.get("head_dim") is None or linear.get("num_heads") is None,
         "no linear_attn_config (head_dim, num_heads)"),
        (sorted(kda_at + full_at) != list(range(1, n + 1)),
         "linear_attn_config's kda_layers and full_attn_layers do not name every layer 1..num_hidden_layers once"),
        (full_at and not hf.get("kv_lora_rank"), "full attention layers without a latent (kv_lora_rank)"),
        (hf.get("num_key_value_heads", hf["num_attention_heads"]) != hf["num_attention_heads"],
         "latent attention with fewer key/value heads than query heads (num_key_value_heads)"),
        (hf.get("num_expert_group", 1) != 1 or hf.get("topk_group", 1) != 1,
         "group-limited routing (num_expert_group / topk_group > 1)"),
        (hf.get("num_nextn_predict_layers", 0), "MTP modules (num_nextn_predict_layers)"),
        (not hf.get("moe_renormalize", True), "gates that are not normalised (moe_renormalize false)"),
        (hf.get("moe_router_activation_func", "sigmoid") != "sigmoid",
         f"moe_router_activation_func {hf.get('moe_router_activation_func')!r}"),
        (hf.get("moe_layer_freq", 1) != 1, "dense layers among the expert layers (moe_layer_freq)"),
        (hf.get("rope_scaling") is not None, f"rope_scaling {hf.get('rope_scaling')!r}"),
        (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
        (not hf.get("num_experts", 0), "layers without routed experts"),
    ) if has]
    if refused:
        raise ValueError("kimi_linear as this config.json states it is not supported: " + "; ".join(refused))
    dense = hf.get("first_k_dense_replace", 0)
    pattern = "".join(("K" if i + 1 in kda_at else "*") + ("-" if i < dense else "E") for i in range(n))
    return dict(
        n_layers=len(pattern), layer_pattern=pattern,
        max_seq_len=hf.get("model_max_length", hf.get("max_position_embeddings", 2048)),
        q_lora_rank=hf.get("q_lora_rank") or 0, kv_lora_rank=hf.get("kv_lora_rank") or 0,
        qk_nope_head_dim=hf.get("qk_nope_head_dim", 0), qk_rope_head_dim=hf.get("qk_rope_head_dim", 0),
        v_head_dim=hf.get("v_head_dim", 0), attention_rotation=not hf.get("mla_use_nope", False),
        kda_n_heads=linear["num_heads"], kda_head_dim=linear["head_dim"],
        kda_conv_taps=linear.get("short_conv_kernel_size", 4),
        kda_neg_eigval=bool(hf.get("kda_allow_neg_eigval", False)),
        n_experts=hf["num_experts"], moe_top_k=hf["num_experts_per_token"],
        d_ff_expert=hf["moe_intermediate_size"], n_shared_experts=hf.get("num_shared_experts", 0),
        moe_capacity_factor=0.0, moe_aux_loss_coef=0.0, moe_scoring="sigmoid", moe_select_bias=True,
        moe_route_scale=float(hf.get("routed_scaling_factor", 1.0)),
    )


# What ouro's config.json does not state: the weight of the entropy term in the first training
# stage's objective (arXiv:2510.25741); a key of this name overrides it
_OURO_EXIT_ENTROPY_WEIGHT = 0.05


def _ouro_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `ouro` keys (Ouro-2.6B) as ModelConfig fields: Llama's keys map as they do (the block is
    rotated attention then the SwiGLU MLP), every part between a norm on its input and one on its
    output (`part_post_norm`), an untied head, and the stack run `total_ut_steps` times over the same
    weights with an exit gate and a head behind each recurrence (`loop_steps`; llama.loss_fn's
    expected-exit loss). What the program does not run is refused by name; what config.json does not
    state is benchmarks/configs/ouro-2.6b-train-loop4.json's `assumed`. Weights' names are not mapped."""
    kinds = hf.get("layer_types") or ["full_attention"] * hf["num_hidden_layers"]
    head = hf.get("head_dim") or hf["hidden_size"] // hf["num_attention_heads"]
    refused = [what for has, what in (
        (len(kinds) != hf["num_hidden_layers"] or set(kinds) - {"full_attention"},
         f"layer_types that are not num_hidden_layers of full_attention ({sorted(set(kinds))})"),
        (hf.get("use_sliding_window", False), "window attention (use_sliding_window true)"),
        (hf.get("rope_scaling") is not None, f"rope_scaling {hf.get('rope_scaling')!r}"),
        (hf.get("early_exit_threshold", 1) < 1,
         f"exit by the gate's threshold (early_exit_threshold {hf.get('early_exit_threshold')} under 1: every "
         "recurrence runs, in training and in `forward`)"),
        (hf.get("total_ut_steps", 1) < 1, f"total_ut_steps {hf.get('total_ut_steps')}"),
        (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
    ) if has]
    if refused:
        raise ValueError("ouro as this config.json states it is not supported: " + "; ".join(refused))
    steps = hf.get("total_ut_steps", 1)
    return dict(
        part_post_norm=True, loop_steps=steps,
        exit_entropy_weight=float(hf.get("exit_entropy_weight", _OURO_EXIT_ENTROPY_WEIGHT)) if steps > 1 else 0.0,
        attn_head_dim=0 if head * hf["num_attention_heads"] == hf["hidden_size"] else head,
    )


def _xing4_0_fields(hf: Dict[str, Any]) -> Dict[str, Any]:
    """The `xing4_0` keys (Xing4.0-29B-A4B) as ModelConfig fields: glm4_moe_lite's block (latent attention with a q
    latent, leading dense layers, sigmoid-routed experts chosen by score + bias beside shared ones, served without
    drops), its rotation under YaRN (`rope_scaling` of type yarn: this family's alone; every other family refuses it
    as before), and around every part a manifold-constrained hyper-connection over `hc_mult` streams (the `hc_*` /
    `mhc_*` keys: models/hyper.py). The MTP modules map (`mtp_depth`) and llama.mtp_logits refuses them over n streams.
    What config.json does not state is benchmarks/configs/xing4.0-29b-a4b-train-tp8-ep8.json's `assumed`. Weights'
    names are not mapped: the catalog gives keys, not tensor names."""
    scaling = hf.get("rope_scaling")
    clamp = (hf.get("mhc_h_res_clamp_min", -30), hf.get("mhc_h_res_clamp_max", 30))
    refused = [what for has, what in (
        (scaling is not None and (scaling.get("type", scaling.get("rope_type")) != "yarn"
                                  or "original_max_position_embeddings" not in scaling),
         f"rope_scaling {scaling!r} (yarn over original_max_position_embeddings is what maps)"),
        (clamp[0] != -clamp[1] or clamp[1] <= 0,
         f"a clamp of Hres' logits that is not symmetric (mhc_h_res_clamp_min / _max {clamp})"),
        (hf.get("moe_layer_freq", 1) != 1, f"moe_layer_freq {hf.get('moe_layer_freq')}"),
        (hf.get("scoring_func", "sigmoid") != "sigmoid", f"scoring_func {hf.get('scoring_func')!r}"),
        (hf.get("hidden_act", "silu") != "silu", f"hidden_act {hf.get('hidden_act')!r}"),
    ) if has]
    if refused:
        raise ValueError("xing4_0 as this config.json states it is not supported: " + "; ".join(refused))
    yarn = {} if scaling is None else dict(
        rope_factor=float(scaling["factor"]), rope_original_len=int(scaling["original_max_position_embeddings"]),
        rope_beta_fast=float(scaling.get("beta_fast", 32)), rope_beta_slow=float(scaling.get("beta_slow", 1)),
        rope_mscale=float(scaling.get("mscale", 1)), rope_mscale_all_dim=float(scaling.get("mscale_all_dim", 0)))
    return dict(
        _glm4_moe_lite_fields({**hf, "rope_scaling": None}), **yarn,
        hc_mult=hf.get("hc_mult", 1), hc_sinkhorn_iters=hf.get("hc_sinkhorn_iters", 20),
        hc_eps=float(hf.get("hc_eps", 1e-6)), hc_res_clamp=float(clamp[1]))


# a family's keys as ModelConfig fields, by its `model_type` (config_from_hf)
_FAMILY_FIELDS = {"xing4_0": _xing4_0_fields, "ouro": _ouro_fields, "glm4_moe_lite": _glm4_moe_lite_fields,
                  "nemotron_h": _nemotron_h_fields, "solar_open2": _solar_open2_fields, "lfm2_moe": _lfm2_moe_fields,
                  "afmoe": _afmoe_fields, "sdar_moe": _sdar_moe_fields, "kimi_linear": _kimi_linear_fields}


def config_to_hf(cfg: ModelConfig) -> Dict[str, Any]:
    moe = cfg.n_experts > 0
    extra = (
        # moe_capacity_factor/moe_top1_renorm are our extension keys (ignored by
        # HF): they persist the trained dispatch semantics through a round-trip
        # instead of resetting to the dropless Mixtral defaults on reload.
        {"num_local_experts": cfg.n_experts, "num_experts_per_tok": cfg.moe_top_k,
         "moe_capacity_factor": cfg.moe_capacity_factor,
         "moe_top1_renorm": cfg.moe_top1_renorm}
        if moe else {}
    )
    return {
        "architectures": ["MixtralForCausalLM" if moe else "LlamaForCausalLM"],
        "model_type": "mixtral" if moe else "llama",
        **extra,
        "vocab_size": cfg.vocab_size,
        "hidden_size": cfg.d_model,
        "num_hidden_layers": cfg.n_layers,
        "num_attention_heads": cfg.n_heads,
        "num_key_value_heads": cfg.n_kv_heads,
        "intermediate_size": cfg.d_ff,
        "max_position_embeddings": cfg.max_seq_len,
        "rope_theta": cfg.rope_theta,
        "rms_norm_eps": cfg.norm_eps,
        "tie_word_embeddings": cfg.tie_embeddings,
    }


# ---------------------------------------------------------------- tensor index

class _ShardedReader:
    """name -> tensor across one or many .safetensors files (lazy handles)."""

    def __init__(self, source_dir: str):
        from safetensors import safe_open

        self._safe_open = safe_open
        index_path = os.path.join(source_dir, "model.safetensors.index.json")
        self._key_to_file: Dict[str, str] = {}
        if os.path.exists(index_path):
            with open(index_path) as f:
                weight_map = json.load(f)["weight_map"]
            for key, fname in weight_map.items():
                self._key_to_file[key] = os.path.join(source_dir, fname)
        else:
            files = sorted(
                os.path.join(source_dir, f) for f in os.listdir(source_dir)
                if f.endswith(".safetensors"))
            if not files:
                raise FileNotFoundError(f"no .safetensors files in {source_dir}")
            for path in files:
                with safe_open(path, framework="numpy") as h:
                    for key in h.keys():
                        self._key_to_file[key] = path
        self._handles: Dict[str, Any] = {}

    def keys(self):
        return self._key_to_file.keys()

    def get(self, name: str) -> np.ndarray:
        path = self._key_to_file[name]
        h = self._handles.get(path)
        if h is None:
            h = self._handles[path] = self._safe_open(path, framework="numpy")
        return h.get_tensor(name)


# -------------------------------------------------------------------- mapping
# HF torch Linear stores weight as (out_features, in_features); ours contract
# inputs on the leading axis, so every projection transposes.

def _leaf_readers(cfg: ModelConfig, rd: _ShardedReader) -> Dict[str, Any]:
    d, hd = cfg.d_model, cfg.head_dim
    nh, nkv, ff = cfg.n_heads, cfg.n_kv_heads, cfg.d_ff

    def layer_leaf(field: str) -> Callable[[int], np.ndarray]:
        pre = "model.layers.{}."

        def q(i):
            return rd.get(f"model.layers.{i}.self_attn.q_proj.weight").T.reshape(d, nh, hd)

        def k(i):
            return rd.get(f"model.layers.{i}.self_attn.k_proj.weight").T.reshape(d, nkv, hd)

        def v(i):
            return rd.get(f"model.layers.{i}.self_attn.v_proj.weight").T.reshape(d, nkv, hd)

        def o(i):
            return rd.get(f"model.layers.{i}.self_attn.o_proj.weight").T.reshape(nh, hd, d)

        if cfg.n_experts > 0:
            # Mixtral layout: block_sparse_moe.gate (router, [E, D]) +
            # experts.{e}.{w1,w3,w2} (gate/up/down, torch Linear orientation).
            e_ = cfg.n_experts
            moe_pre = "model.layers.{}.block_sparse_moe."

            def expert_stack(i: int, w: str) -> np.ndarray:
                return np.stack([
                    rd.get(moe_pre.format(i) + f"experts.{j}.{w}.weight").T
                    for j in range(e_)
                ])

            mlp_readers = {
                "router": lambda i: rd.get(moe_pre.format(i) + "gate.weight").T,
                "w_gate": lambda i: expert_stack(i, "w1"),  # [E, D, F]
                "w_up": lambda i: expert_stack(i, "w3"),    # [E, D, F]
                "w_down": lambda i: expert_stack(i, "w2"),  # [E, F, D]
            }
        else:
            mlp_readers = {
                "w_gate": lambda i: rd.get(pre.format(i) + "mlp.gate_proj.weight").T,
                "w_up": lambda i: rd.get(pre.format(i) + "mlp.up_proj.weight").T,
                "w_down": lambda i: rd.get(pre.format(i) + "mlp.down_proj.weight").T,
            }
        return {
            "attn_norm": lambda i: rd.get(pre.format(i) + "input_layernorm.weight"),
            "mlp_norm": lambda i: rd.get(pre.format(i) + "post_attention_layernorm.weight"),
            "wq": q, "wk": k, "wv": v, "wo": o,
            **mlp_readers,
        }[field]

    return {
        "embed": lambda: rd.get("model.embed_tokens.weight"),
        "final_norm": lambda: rd.get("model.norm.weight"),
        "lm_head": lambda: rd.get("lm_head.weight").T,
        "layer": layer_leaf,
    }


_LAYER_FIELDS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                 "w_gate", "w_up", "w_down")
_MOE_LAYER_FIELDS = ("attn_norm", "wq", "wk", "wv", "wo", "mlp_norm",
                     "router", "w_gate", "w_up", "w_down")


def _layer_fields(cfg: ModelConfig):
    return _MOE_LAYER_FIELDS if cfg.n_experts > 0 else _LAYER_FIELDS


def load_llama_params(
    source_dir: str,
    cfg: Optional[ModelConfig] = None,
    mesh=None,
    rules: AxisRules = INFER_RULES,
    param_dtype=jnp.bfloat16,
) -> Params:
    """Stream an HF Llama safetensors checkpoint into a (sharded) pytree.

    cfg defaults to config.json in source_dir. With a mesh, every leaf is
    device_put with its NamedSharding as soon as it is assembled (reference
    engine contract: vllm_engine.py:180). Without a mesh, leaves stay host-local
    jnp arrays (single-process tests / single chip)."""
    if cfg is None:
        cfg = config_from_hf(source_dir)
    from . import llama

    rd = _ShardedReader(source_dir)
    readers = _leaf_readers(cfg, rd)
    axes = llama.param_axes(cfg)

    def put(arr: np.ndarray, leaf_axes) -> jax.Array:
        arr = arr.astype(param_dtype) if param_dtype is not None else arr
        if mesh is None:
            return jnp.asarray(arr)
        return jax.device_put(arr, named_sharding(mesh, *leaf_axes, rules=rules))

    params: Params = {
        "embed": put(readers["embed"](), axes["embed"]),
        "final_norm": put(readers["final_norm"](), axes["final_norm"]),
    }
    fields = _layer_fields(cfg)
    layers = {}
    for field in fields:
        read = readers["layer"](field)
        stacked = np.stack([np.asarray(read(i)) for i in range(cfg.n_layers)])
        layers[field] = put(stacked, axes["layers"][field])
        del stacked  # one leaf resident at a time
    params["layers"] = layers
    if not cfg.tie_embeddings:
        params["lm_head"] = put(readers["lm_head"](), axes["lm_head"])
    return params


def save_llama_params(params: Params, cfg: ModelConfig, out_dir: str) -> str:
    """Write the pytree as an HF-layout safetensors checkpoint + config.json."""
    from safetensors.numpy import save_file

    if cfg.n_experts > 0 and not cfg.moe_top1_renorm and cfg.moe_top_k == 1:
        import warnings

        # HF ignores our extension keys: MixtralForCausalLM renormalizes the
        # single gate to 1.0 while this model was trained gating by the raw
        # top-1 prob — a transformers consumer of this export gets different
        # forward math. Our own loader reads the keys back faithfully.
        warnings.warn(
            "exporting a Switch-gated MoE (moe_top_k=1, moe_top1_renorm=False) "
            "in Mixtral layout: transformers will renormalize the gate to 1.0 "
            "and produce different logits; only ray_tpu's loader reproduces "
            "the trained semantics", stacklevel=2)
    os.makedirs(out_dir, exist_ok=True)
    d = cfg.d_model

    def host(x) -> np.ndarray:
        arr = np.asarray(jax.device_get(x))
        # numpy can't persist ml_dtypes bfloat16 through every consumer; f32 is
        # the interchange dtype for these (typically tiny/test) exports
        return arr.astype(np.float32) if arr.dtype not in (np.float32, np.float16) else arr

    def layer(i):
        return {f: jax.tree.map(lambda x: x[i], params["layers"][f])
                for f in _layer_fields(cfg)}

    tensors: Dict[str, np.ndarray] = {
        "model.embed_tokens.weight": host(params["embed"]),
        "model.norm.weight": host(params["final_norm"]),
    }
    if not cfg.tie_embeddings:
        tensors["lm_head.weight"] = host(params["lm_head"]).T
    for i in range(cfg.n_layers):
        ly = layer(i)
        pre = f"model.layers.{i}."
        tensors[pre + "input_layernorm.weight"] = host(ly["attn_norm"])
        tensors[pre + "post_attention_layernorm.weight"] = host(ly["mlp_norm"])
        tensors[pre + "self_attn.q_proj.weight"] = host(ly["wq"]).reshape(d, -1).T
        tensors[pre + "self_attn.k_proj.weight"] = host(ly["wk"]).reshape(d, -1).T
        tensors[pre + "self_attn.v_proj.weight"] = host(ly["wv"]).reshape(d, -1).T
        tensors[pre + "self_attn.o_proj.weight"] = host(ly["wo"]).reshape(-1, d).T
        if cfg.n_experts > 0:
            moe_pre = pre + "block_sparse_moe."
            tensors[moe_pre + "gate.weight"] = host(ly["router"]).T
            wg, wu, wd = host(ly["w_gate"]), host(ly["w_up"]), host(ly["w_down"])
            for j in range(cfg.n_experts):
                ex = moe_pre + f"experts.{j}."
                tensors[ex + "w1.weight"] = wg[j].T
                tensors[ex + "w3.weight"] = wu[j].T
                tensors[ex + "w2.weight"] = wd[j].T
        else:
            tensors[pre + "mlp.gate_proj.weight"] = host(ly["w_gate"]).T
            tensors[pre + "mlp.up_proj.weight"] = host(ly["w_up"]).T
            tensors[pre + "mlp.down_proj.weight"] = host(ly["w_down"]).T
    tensors = {k: np.ascontiguousarray(v) for k, v in tensors.items()}
    save_file(tensors, os.path.join(out_dir, "model.safetensors"))
    with open(os.path.join(out_dir, "config.json"), "w") as f:
        json.dump(config_to_hf(cfg), f, indent=2)
    return out_dir


def looks_like_checkpoint_dir(path: Any) -> bool:
    return (isinstance(path, str) and os.path.isdir(path)
            and os.path.exists(os.path.join(path, "config.json")))
