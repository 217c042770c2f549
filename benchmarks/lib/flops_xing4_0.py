"""Operations a call of the `xing4_0` family needs (Xing4.0-29B-A4B: glm4_moe_lite's block, latent
attention with v heads of their own width, a leading dense layer, sigmoid-routed experts beside a shared
one, around every part a manifold-constrained hyper-connection over n streams), from its shapes alone.
`flops.py`'s rules: only matrix work is counted (a multiply-add is two operations); norms, rotary
embeddings, softmax, sigmoids, the projection's rounds, the gather and what remat recomputes are left out.
`model` is the `model` group of the configuration (the program's ModelConfig fields).

What ONE CHIP of the deployment needs is counted: `attn_heads_held` of the heads and `experts_held =
(index, of)` of the routed experts live here, so of a token's `moe_top_k` assignments `1 / of` fall on
this chip in expectation; everything else (the latents' down-projections, the hyper-connections, shared
expert, router, dense layer, the sliced head) every token meets here whole.
"""


def _heads(model: dict) -> int:
    return (model.get("attn_heads_held") or (0, 0))[0] or model["n_heads"]


def _attention_params(model: dict) -> int:
    d, h = model["d_model"], _heads(model)
    qk = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    return (d * model["q_lora_rank"] + model["q_lora_rank"] * h * qk
            + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * h * (model["qk_nope_head_dim"] + model["v_head_dim"])
            + h * model["v_head_dim"] * d)


def expert_params(model: dict) -> int:
    return 3 * model["d_model"] * model["d_ff_expert"]


def hyper_connection_flops(model: dict) -> dict:
    """Forward operations of ONE part's hyper-connection for one token, by pass: the coefficient product
    [n C -> 2n + n^2], the reading (n C multiply-adds), the writing (n^2 C for Hres X, n C for Hpost o)."""
    n, c = model["hc_mult"], model["d_model"]
    return {"mix": 2 * n * c * (2 * n + n * n), "pre": 2 * n * c, "post": 2 * (n * n + n) * c}


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
    return 2 * _heads(model) * (model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
                                + model["v_head_dim"]) * context


def forward_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token that attends to `context` positions, by part: the layers' own
    parts, the hyper-connections beside them (two a layer), the head."""
    p = layer_matmul_params(model)
    attn = 2 * p["attention_projections"] + _attention_context_flops(model, context)
    n_dense = model["n_dense_layers"]
    return {
        "dense_layers": n_dense * (attn + 2 * p["dense_mlp"]),
        "expert_layers": (model["n_layers"] - n_dense) * (
            attn + 2 * (p["shared_experts"] + p["router"] + p["routed_experts_expected"])),
        "hyper_connections": 2 * model["n_layers"] * sum(hyper_connection_flops(model).values()),
        "head": 2 * model["d_model"] * model["vocab_size"],
    }


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward for one token of a causal sequence of `seq` tokens: the
    backward costs twice the forward; recomputation is not counted."""
    return 3 * sum(forward_flops_per_token(model, (seq + 1) / 2).values())


def grouped_products_flops(model: dict, held_rows: float) -> float:
    """Forward and backward operations of the grouped products (gate, up, down and their
    two transposes each) over `held_rows` rows that fell on held experts: the rows the
    step's counter gives, so no padding of a tile or a buffer is counted."""
    return 3 * 2 * held_rows * expert_params(model)


def causal_attention_flops(model: dict, seq: int, batch: int) -> float:
    """Forward operations of causal attention's two products over one layer's call."""
    return batch * seq * _attention_context_flops(model, (seq + 1) / 2)


def attention_step_work(model: dict, tokens: int, seq: int) -> dict:
    """What the attention cores of one train step need (every layer's; the projections are outside), at the
    heads HELD, q and k at their width and v at its own: {"flops": the causal triangle's products a head,
    forward the scores and the weighted values, backward the scores again, dP, dV, dK and dQ: 3 x qk + 2 x v a
    score where the forward is qk + v, each 2 x seen a query; "bytes": q, k, v read and o written forward;
    q, k, v, o, dO read and dq, dk, dv written backward, two bytes a number}. The same whatever implements
    it: lanes a kernel pads (192 to 256), the diagonal's masked halves and a forward run again under remat
    are the program's, not the need's."""
    qk, v = model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["v_head_dim"]
    heads, layers, seen = _heads(model), model["n_layers"], (seq + 1) / 2
    q_like, v_like = 2 * tokens * heads * qk, 2 * tokens * heads * v
    return {"flops": layers * 2 * seen * ((qk + v) + (3 * qk + 2 * v)) * heads * tokens,
            "bytes": layers * ((2 * q_like + 2 * v_like) + (4 * q_like + 4 * v_like))}


def scan_step_work(model: dict, tokens: int) -> dict:
    """What the hyper-connections of one train step NEED under remat `full` (`train_hc_roofline_pct`, scope
    `hc`): the LEAST traffic the equations allow, whatever implements them, with a token's row of the stream
    held in fast memory through a pass. S = the stream's bytes (tokens x n C x 2), A = an activation's (tokens
    x C x 2); coefficients and phi are a thousandth of either and left out.
      forward, a part:   the coefficients and the reading in one pass over x (read S, write A); the writing
                         (read S and the part's output A, write S): 3 S + 2 A
      again, a layer:    both parts' coefficients and readings and the FIRST part's writing (the second's
                         result nothing in the backward pass reads): 4 S + 3 A
      backward, a part:  the writing's transpose (read dX' S, X S and o A; write d_o A and the coefficients'
                         cotangents), then, once the part's own backward pass has given dy, the stream's
                         cotangent in one pass (read dX' S, X S, dy A; write dX S): 5 S + 3 A
    and the ends: the embedding's repeat (write S; backward read S) and the sum in front of the head (read S;
    backward write S). Operations: `hyper_connection_flops`, forward, again and twice backward. The bytes bound
    it (~28 GB = 35 ms a step at the cell's size beside ~1 ms of operations)."""
    n, c, layers = model["hc_mult"], model["d_model"], model["n_layers"]
    s, a = 2 * tokens * n * c, 2 * tokens * c
    f = hyper_connection_flops(model)
    part = sum(f.values())
    again = 2 * (f["mix"] + f["pre"]) + f["post"]
    return {"bytes": layers * (2 * (3 * s + 2 * a) + (4 * s + 3 * a) + 2 * (5 * s + 3 * a)) + 4 * s,
            "flops": tokens * layers * (2 * part + again + 2 * 2 * part)}
