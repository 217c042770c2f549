"""Operations a call of the `glm4_moe_lite` family needs (GLM-4.7-Flash: latent
attention, a leading dense layer, sigmoid-routed experts beside shared ones, MTP
modules), from its shapes alone. `flops.py`'s rules: only matrix work is counted (a
multiply-add is two operations); norms, rotary embeddings, softmax, the gather and what
remat recomputes are left out. `model` is the `model` group of the configuration (the
program's ModelConfig fields).

What ONE CHIP of the deployment needs is counted: `experts_held = (index, of)` of the
routed experts live here, so of a token's `moe_top_k` assignments `1 / of` fall on this
chip in expectation; everything else (attention, shared experts, router, dense layer,
the sliced head) every token meets here.
"""


def _attention_params(model: dict) -> int:
    d, h = model["d_model"], model["n_heads"]
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (d * model["q_lora_rank"] + model["q_lora_rank"] * h * qk
            + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * h * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d)


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["d_ff_expert"]


def layer_matmul_params(model: dict) -> dict:
    """Weights one token multiplies against in one layer of each kind, by part."""
    d = model["d_model"]
    routed = model["moe_top_k"] / model["experts_held"][1] * expert_params(model)
    return {
        "attention_projections": _attention_params(model),
        "dense_mlp": 3 * d * model["d_ff"],
        "shared_experts": model["n_shared_experts"] * expert_params(model),
        "router": d * model["n_experts"],
        "routed_experts_expected": routed,
    }


def _attention_context_flops(model: dict, context: float) -> float:
    # scores over nope + rope, the weighted sum over v_head_dim
    h = model["n_heads"]
    return 2 * h * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
                    + model["v_head_dim"]) * context


def forward_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token that attends to `context` positions, by part:
    the main stack, its head, and the MTP modules (a block, eh_proj and the head again)."""
    p = layer_matmul_params(model)
    d, depth = model["d_model"], model.get("mtp_depth", 0)
    attn = 2 * p["attention_projections"] + _attention_context_flops(model, context)
    dense = attn + 2 * p["dense_mlp"]
    expert = attn + 2 * (p["shared_experts"] + p["router"] + p["routed_experts_expected"])
    head = 2 * d * model["vocab_size"]
    n_dense = model["n_dense_layers"]
    return {
        "dense_layers": n_dense * dense,
        "expert_layers": (model["n_layers"] - n_dense) * expert,
        "head": head,
        "mtp": depth * (expert + 2 * 2 * d * d + head),
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward for one token of a causal sequence of `seq` tokens: the
    backward costs twice the forward; recomputation is not counted. (An MTP module runs
    one position short of the sequence: counted as the whole of it, 1 / seq too much.)"""
    return 3 * sum(forward_flops_per_token(model, (seq + 1) / 2).values())


def grouped_products_flops(model: dict, held_rows: float) -> float:
    """Forward and backward operations of the grouped products (gate, up, down and their
    two transposes each) over `held_rows` rows that fell on held experts: the rows the
    step's counter gives, so no padding of a tile or a buffer is counted."""
    return 3 * 2 * held_rows * expert_params(model)


def causal_attention_flops(model: dict, seq: int, batch: int) -> float:
    """Forward operations of causal attention's two products over one layer's call."""
    return batch * seq * _attention_context_flops(model, (seq + 1) / 2)
