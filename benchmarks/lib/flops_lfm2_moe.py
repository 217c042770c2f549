"""Operations a call of the `lfm2_moe` family needs (LFM2-24B-A2B: every published layer a
token mixer then a feed-forward part, run as two characters of a pattern: gated short
convolutions `C`, rotated GQA whose q and k are normed a head `*`, the dense SwiGLU `-`,
SwiGLU experts with no shared one `E`), from its shapes alone. `flops.py`'s rules: only
matrix work is counted (a multiply-add is two operations); norms, the convolution's taps,
the gates' products, softmax, the rotation, the gathers and what remat recomputes are left
out. `model` is the `model` group of the configuration (the program's ModelConfig fields).

What ONE CHIP of the deployment needs is counted. The vocabulary rows in `model` are the
chip's own; `experts_held = (index, of)` of the routed experts live here, so of a token's
`moe_top_k` assignments `1 / of` fall on this chip in expectation; mixers, router and the
dense layer every token of the chip's own sequences meets here.
"""


def expert_params(model: dict) -> int:
    """A routed expert's three matrices."""
    return 3 * model["d_model"] * model["d_ff_expert"]


def layer_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token in one part of each character of the pattern."""
    d = model["d_model"]
    hd = model.get("attn_head_dim") or d // model["n_heads"]
    heads, kv_heads = model["n_heads"], model["n_kv_heads"]
    routed = model["moe_top_k"] / model["experts_held"][1] * expert_params(model)
    return {
        "C": 2 * (d * 3 * d + d * d),  # [B | C | x] and the output
        # q, k, v, o; the scores and the weighted values over the positions a token sees
        "*": 2 * d * hd * (2 * heads + 2 * kv_heads) + 2 * heads * 2 * hd * context,
        "-": 2 * 3 * d * model["d_ff"],
        "E": 2 * (d * model["n_experts"] + routed),
    }


def forward_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token that attends to `context` positions, by part: the
    pattern's parts by character, and the head."""
    layer = layer_flops_per_token(model, context)
    out = {c: model["layer_pattern"].count(c) * layer[c] for c in layer}
    out["head"] = 2 * model["d_model"] * model["vocab_size"]
    return out


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward for one token of a causal sequence of `seq` tokens: the
    backward costs twice the forward; recomputation is not counted."""
    return 3 * sum(forward_flops_per_token(model, (seq + 1) / 2).values())


def grouped_products_flops(model: dict, held_rows: float) -> float:
    """Forward and backward operations of the grouped products (gate, up, down and their
    two transposes each) over `held_rows` rows that fell on held experts: the rows the
    step's counter gives, so no padding of a tile or a buffer is counted."""
    return 3 * 2 * held_rows * expert_params(model)


def scan_step_work(model: dict, tokens: int) -> dict:
    """What the gated short convolutions of one train step over `tokens` tokens need, ALL
    of each mixer (the name is `readers/train_scan_roofline.py`'s; the family has no scan):
    {"flops": forward and backward (twice the forward) of the two products, "bytes": W_in
    and W_out in the activation's two bytes (the program's copies of the float32 leaves
    are its own), the normed input u, the three thirds, the gated convolution's output and
    the mixer's output in two bytes a number, each read or written once forward and twice
    backward (the same read beside the output's cotangent, a gradient written for
    each)}. The norm, the float32 sum inside the convolution and what remat runs again
    are the implementation's, not the need's."""
    d = model["d_model"]
    layers = model["layer_pattern"].count("C")
    weights = 2 * (d * 3 * d + d * d)
    activations = 2 * tokens * (d + 3 * d + d + d)  # u, [B | C | x], (C * c), the output
    return {"flops": layers * 3 * tokens * 2 * (d * 3 * d + d * d),
            "bytes": layers * 3 * (weights + activations)}


def attention_step_work(model: dict, tokens: int, seq: int) -> dict:
    """What the attention cores of one train step need (every `*` part; the projections,
    the norms a head and the rotation are outside): {"flops": the causal half of six
    products a head (forward: scores, weighted values; backward: scores again, dP, dQ,
    dK and dV count as four: 2 + 4 = six of 2 x seen x head_dim a query), "bytes": q, k,
    v read and o written forward; q, k, v, o, dO read and dq, dk, dv written backward, two
    bytes a number at the head's own width}. The same whatever implements it: lanes a
    kernel pads, the masked halves of the diagonal's tiles and the forward's second run
    under remat are the program's, not the need's."""
    hd = model.get("attn_head_dim") or model["d_model"] // model["n_heads"]
    heads, kv_heads = model["n_heads"], model["n_kv_heads"]
    layers = model["layer_pattern"].count("*")
    seen = (seq + 1) / 2
    q_like, kv_like = 2 * tokens * heads * hd, 2 * tokens * kv_heads * hd
    forward = 2 * q_like + 2 * kv_like  # q, o; k, v
    backward = 4 * q_like + 4 * kv_like  # q, o, dO, dq; k, v, dk, dv
    return {"flops": layers * 6 * 2 * seen * hd * heads * tokens,
            "bytes": layers * (forward + backward)}
