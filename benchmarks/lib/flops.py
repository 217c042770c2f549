"""Operations and bytes a model call needs, from its shapes alone.

The yardstick: kept with the benchmark so that no PR that claims a gain can
change what "one token's work" means. Only matrix work is counted (a
multiply-add is two operations); norms, rotary embeddings, softmax and the
embedding gather are left out, as is anything recomputed by remat. `model` is
the `model` group of a configuration file (the program's ModelConfig keys).
"""
import json
import os


def _head_dim(model: dict) -> int:
    return model["d_model"] // model["n_heads"]


def layer_matmul_params(model: dict) -> dict:
    """Weights one token multiplies against in one layer, by part. For a
    mixture of experts a token meets its top-k experts and the router."""
    d, hd = model["d_model"], _head_dim(model)
    attn = d * hd * (model["n_heads"] + 2 * model["n_kv_heads"]) + model["n_heads"] * hd * d
    mlp = 3 * d * model["d_ff"]
    experts = model.get("n_experts", 0)
    if experts:
        mlp = model["moe_top_k"] * mlp + d * experts
    return {"attention_projections": attn, "mlp": mlp}


def forward_flops_per_token(model: dict, context: float) -> float:
    """Forward operations for one token that attends to `context` positions
    (for a causal sequence of length s, the mean context is (s + 1) / 2)."""
    per_layer = 2 * sum(layer_matmul_params(model).values())
    # scores and the weighted sum of values: 2 * heads * head_dim each per position
    attn = 4 * model["n_heads"] * _head_dim(model) * context
    head = 2 * model["d_model"] * model["vocab_size"]
    return model["n_layers"] * (per_layer + attn) + head


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward for one token of a causal sequence of `seq`
    tokens: the backward pass costs twice the forward. Recomputation (remat)
    is work the schedule adds, not work the model needs: not counted."""
    return 3 * forward_flops_per_token(model, (seq + 1) / 2)


def weight_bytes(model: dict, bytes_per_param: int) -> float:
    """Bytes of all weights as held on the device (every expert counted)."""
    d, hd = model["d_model"], _head_dim(model)
    attn = d * hd * (model["n_heads"] + 2 * model["n_kv_heads"]) + model["n_heads"] * hd * d
    mlp = 3 * d * model["d_ff"]
    experts = model.get("n_experts", 0)
    if experts:
        mlp = experts * mlp + d * experts
    emb = model["vocab_size"] * d * (1 if model.get("tie_embeddings") else 2)
    return bytes_per_param * (model["n_layers"] * (attn + mlp + 2 * d) + emb + d)


def kv_bytes_per_token(model: dict, bytes_per_value: int = 2) -> float:
    return 2 * model["n_layers"] * model["n_kv_heads"] * _head_dim(model) * bytes_per_value


def peaks_for(device_kind: str) -> dict:
    """Published peaks of the chip JAX reports. An unknown kind is an error."""
    with open(os.path.join(os.path.dirname(os.path.abspath(__file__)), "peaks.json")) as f:
        table = json.load(f)["device_kinds"]
    if device_kind not in table:
        raise KeyError(f"no published peaks on record for device kind {device_kind!r} "
                       f"(lib/peaks.json has {sorted(table)})")
    return table[device_kind]
