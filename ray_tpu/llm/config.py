"""LLM engine/server configuration.

Capability parity: reference python/ray/llm/_internal/serve/deployments/llm/vllm/
vllm_models.py:40 (``VLLMEngineConfig`` — model id, engine_kwargs, TP/PP degrees
:125-139 mapped to resource bundles). Here the engine is JAX, so parallelism
degrees map to mesh axes instead of placement-group bundles.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Union


def _flag(name: str):
    from ray_tpu.config import flag

    return flag(name)


@dataclasses.dataclass
class SamplingParams:
    """Per-request sampling controls (reference vLLM SamplingParams surface)."""

    max_tokens: int = 64
    temperature: float = 1.0
    top_p: float = 1.0
    top_k: int = 0  # 0 = disabled
    stop_token_ids: Optional[List[int]] = None
    seed: Optional[int] = None

    def __post_init__(self):
        if self.max_tokens < 1:
            raise ValueError("max_tokens must be >= 1")
        if not 0.0 < self.top_p <= 1.0:
            raise ValueError("top_p must be in (0, 1]")


@dataclasses.dataclass
class SpecConfig:
    """First-class speculative-decoding mode (reference vLLM SpeculativeConfig).

    Composes with fused multi-step decode: each fused window proposes/verifies
    ``num_tokens`` drafts on device, so per host sync the engine emits between
    K and K*(num_tokens+1) tokens. ``method`` is the proposer; only "ngram"
    (prompt lookup) is implemented."""

    num_tokens: int = 4
    method: str = "ngram"
    ngram_max: int = 3  # longest trailing n-gram the proposer matches

    def __post_init__(self):
        if self.num_tokens < 1:
            raise ValueError("SpecConfig.num_tokens must be >= 1")
        if self.ngram_max < 1:
            raise ValueError("SpecConfig.ngram_max must be >= 1")


@dataclasses.dataclass
class LLMConfig:
    """Model + engine knobs for ``JaxLLMEngine`` / ``LLMServer``.

    ``model_id`` is the served-model name (OpenAI ``model`` field); ``model_source``
    picks the ray_tpu.models config (e.g. "byte-tiny", "llama3-8b") or is a
    ModelConfig instance directly.
    """

    model_id: str = "llama"
    model_source: Union[str, Any] = "byte-tiny"
    # engine (defaults env-overridable via the config registry)
    max_num_seqs: int = dataclasses.field(  # decode slots (batching width)
        default_factory=lambda: _flag("llm_max_num_seqs"))
    max_model_len: int = dataclasses.field(  # KV capacity per slot
        default_factory=lambda: _flag("llm_max_model_len"))
    prefill_buckets: Optional[List[int]] = None  # pad-to lengths; default powers of 2
    dtype: str = "bfloat16"
    # KV layout (reference: vLLM PagedAttention block tables):
    #   "slot"  — max_model_len tokens reserved per slot up front
    #   "paged" — one shared block pool; per-slot block tables; allocation per
    #             kv_block_size tokens, so HBM caps TOTAL tokens, not slots
    kv_layout: str = "slot"
    kv_block_size: int = 16
    # total pool blocks; None = same token capacity as the slot layout
    num_kv_blocks: Optional[int] = None
    # share full prompt blocks across requests (vLLM automatic prefix caching)
    enable_prefix_caching: bool = True
    # prompts longer than this prefill in chunks of this many tokens (peak
    # activation memory = one chunk); None = whole-prompt prefill
    prefill_chunk: Optional[int] = None
    # fused decode burst: run this many decode+sample iterations on-device per
    # host sync (lax.scan; vLLM multi-step scheduling). >1 amortizes the
    # per-step host round trip (about a millisecond on a local chip) at the
    # cost of K-token streaming granularity and up to
    # K-1 wasted steps after a mid-burst EOS. None (the default) resolves
    # RAY_TPU_LLM_FUSED_STEPS, whose 0 default auto-tunes K from the measured
    # host round trip vs device step time — fused decode is the standard
    # engine mode, not an opt-in
    num_decode_steps: Optional[int] = None
    # speculative decoding (reference: vLLM ngram / prompt-lookup): propose up
    # to this many draft tokens per step by matching the trailing n-gram
    # against earlier context, verify all of them in ONE forward pass, accept
    # the longest matching prefix + a bonus token. Greedy (temperature=0)
    # requests only; slot KV layout; dense models. 0 = off
    num_speculative_tokens: int = 0
    speculative_method: str = "ngram"
    ngram_prompt_lookup_max: int = 3
    # first-class speculation mode: a SpecConfig (or its dict form) here
    # overrides the three scalar knobs above, which remain as the resolved
    # values engine code reads
    speculative: Optional[Union["SpecConfig", Dict[str, Any]]] = None
    # weight-only quantization (reference: vLLM quantization engine_kwargs):
    #   None   — serve in `dtype` as loaded
    #   "int8" — per-output-channel int8 weights, bf16 activations (W8A16):
    #            halves the weight bytes every decode step streams from HBM
    quantization: Optional[str] = None
    # parallelism: mesh axes for the in-process device mesh
    tensor_parallel_size: int = 1
    data_parallel_size: int = 1
    expert_parallel_size: int = 1  # MoE models: experts shard over "ep"
    # layer stack split across pp stages with microbatched decode (reference
    # passes pipeline_parallel_size to vLLM, vllm_models.py:125-139)
    pipeline_parallel_size: int = 1
    # serving
    tokenizer: str = "byte"  # "byte" | "hf:<name-or-path>"
    deployment_config: Dict[str, Any] = dataclasses.field(default_factory=dict)
    engine_kwargs: Dict[str, Any] = dataclasses.field(default_factory=dict)

    def __post_init__(self):
        if self.speculative is not None:
            sp = self.speculative
            if isinstance(sp, dict):
                sp = SpecConfig(**sp)
                self.speculative = sp
            self.num_speculative_tokens = sp.num_tokens
            self.speculative_method = sp.method
            self.ngram_prompt_lookup_max = sp.ngram_max

    def resolve_decode_steps(self) -> int:
        """Configured fused burst width: explicit value, else the
        RAY_TPU_LLM_FUSED_STEPS flag. 0 means auto-tune (engine-side)."""
        if self.num_decode_steps is not None:
            return max(0, int(self.num_decode_steps))
        return max(0, int(_flag("llm_fused_steps")))

    def resolve_model_config(self):
        from ray_tpu.models.config import ModelConfig, get_config

        if isinstance(self.model_source, ModelConfig):
            return _served(self.model_source)
        from ray_tpu.models import checkpoint as ckpt_io

        if ckpt_io.looks_like_checkpoint_dir(self.model_source):
            # a local HF-layout checkpoint dir: architecture from its config.json,
            # weights loaded by the engine at start() (vllm_engine.py:180 contract)
            return _served(ckpt_io.config_from_hf(self.model_source, **self.engine_kwargs))
        return _served(get_config(self.model_source, **self.engine_kwargs))

    def resolve_tokenizer_name(self) -> str:
        """Default the tokenizer to the checkpoint's own HF tokenizer when the
        model is a checkpoint dir that ships one."""
        if self.tokenizer != "byte":
            return self.tokenizer
        import os

        from ray_tpu.models import checkpoint as ckpt_io

        if ckpt_io.looks_like_checkpoint_dir(self.model_source) and any(
            os.path.exists(os.path.join(self.model_source, f))
            for f in ("tokenizer.json", "tokenizer_config.json")
        ):
            return f"hf:{self.model_source}"
        return self.tokenizer

    def buckets(self) -> List[int]:
        if self.prefill_buckets:
            return sorted(self.prefill_buckets)
        out, b = [], 16
        while b < self.max_model_len:
            out.append(b)
            b *= 2
        out.append(self.max_model_len)
        return out


def _served(cfg):
    """The model configuration, if the engine can serve it; else what it lacks, by name
    (models/llama.py trains these; ROADMAP.md B4-B6 has the serving side)."""
    missing = [what for has, what in (
        (cfg.latent_attention, "a paged cache of latents (llm/paged.py holds K and V a head, both head_dim wide: "
                               "none of a latent and a shared key, nor of v heads of another width than k's)"),
        (cfg.n_dense_layers, "a layer loop over stacks of more than one kind (llm/model_runner.py)"),
        (cfg.moe_dropless, "the dropless expert layer under decode (inactive slots are not masked)"),
        (cfg.mtp_depth, "multi-token-prediction modules as drafts of the verify window"),
        (cfg.layer_pattern, "a pattern of single-part layers under decode: recurrent state and "
                            "convolution tails a slot beside the KV cache (cache manager, snapshots, prefix cache)"),
        (cfg.kda_n_heads, "a delta-rule state [heads, 128, 128] a slot and layer (models/kda.py keeps none)"),
        (cfg.attn_output_gate, "the attention output gate in the decode window (llm/model_runner.py)"),
        (cfg.attn_window, "a window of the KV cache: the cache keeps every position and the decode "
                          "window masks none (llm/model_runner.py, llm/paged.py)"),
        (cfg.part_post_norm, "a norm behind each part in the decode window (llm/model_runner.py adds "
                             "attention's output itself)"),
        (cfg.diffusion_block, "generation by diffusion over blocks: the engine's step yields one token a sequence, "
                              "where a block of diffusion_block tokens takes several denoising passes and the "
                              "cache commits a block (llm/engine.py, llm/model_runner.py)"),
        (cfg.loop_steps > 1, "a KV cache of loop_steps x n_layers entries for a looped stack (every recurrence "
                             "attends over keys and values of its own: llama.forward(cache=) and llm/paged.py hold "
                             "n_layers) and exit by the gate's threshold"),
        ("C" in cfg.layer_pattern, "a gated short convolution's tail of conv_taps - 1 positions a slot "
                                   "and layer (models/sconv.py keeps none)"),
        (cfg.hc_mult > 1, "a residual stream of hc_mult copies in the decode window (llm/model_runner.py adds a part's "
                          "output to [B, S, d_model] itself; models/hyper.py's reading and writing are llama._block's)"),
    ) if has]
    if missing:
        raise NotImplementedError(
            f"llm cannot serve {cfg.name!r} yet; it lacks: " + "; ".join(missing))
    return cfg
