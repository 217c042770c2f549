"""Operations a call of the `afmoe` family needs (Trinity-Mini: every published layer
attention then a feed-forward part, run as two characters of a pattern: attention inside a
window `W`, full attention `*`, both gated a channel and normed a head, the dense SwiGLU
`-`, SwiGLU experts beside a shared one `E`), from its shapes alone. `flops.py`'s rules:
only matrix work is counted (a multiply-add is two operations); norms (the two a part and
the two a head), the gate's sigmoid and product, softmax, the rotation, the gathers and
what remat recomputes are left out. `model` is the `model` group of the configuration (the
program's ModelConfig fields).

What ONE CHIP of the deployment needs is counted. The vocabulary rows in `model` are the
chip's own; `experts_held = (index, of)` of the routed experts live here, so of a token's
`moe_top_k` assignments `1 / of` fall on this chip in expectation; mixers (every head:
`attn_heads_held`), router, shared expert and the dense layer every token of the chip's own
sequences meets here.

A query of a windowed part keeps `min(i + 1, attn_window)` keys, of a full part `i + 1`:
the means over a sequence are `band_context` and `(seq + 1) / 2`.
"""


def expert_params(model: dict) -> int:
    """A routed expert's three matrices (the shared expert is as wide)."""
    return 3 * model["d_model"] * model["d_ff_expert"]


def band_context(model: dict, seq: int) -> float:
    """Keys a query of a windowed part keeps, on average over a causal sequence of `seq`:
    sum_i min(i + 1, window) / seq."""
    w = min(model["attn_window"], seq)
    return (w * (w + 1) / 2 + (seq - w) * w) / seq


def _heads(model: dict):
    held = model.get("attn_heads_held") or (0, 0)
    return held[0] or model["n_heads"], held[1] or model["n_kv_heads"], model["attn_head_dim"]


def layer_flops_per_token(model: dict, seq: int) -> dict:
    """Forward operations for one token in one part of each character of the pattern, on
    average over a causal sequence of `seq` tokens."""
    d = model["d_model"]
    heads, kv_heads, hd = _heads(model)
    routed = model["moe_top_k"] / model["experts_held"][1] * expert_params(model)
    shared = model.get("n_shared_experts", 0) * expert_params(model)
    projections = 2 * d * hd * (3 * heads + 2 * kv_heads)  # q, the gate, o; k, v
    core = 2 * heads * 2 * hd  # the scores and the weighted values, a key the query keeps
    return {
        "W": projections + core * band_context(model, seq),
        "*": projections + core * (seq + 1) / 2,
        "-": 2 * 3 * d * model["d_ff"],
        "E": 2 * (d * model["n_experts"] + shared + routed),
    }


def forward_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token by part: the pattern's parts by character, and the
    head. `context` is what a query of a FULL part attends to on average, (seq + 1) / 2, as
    every family's file takes it; the windowed parts' follows from the same sequence."""
    layer = layer_flops_per_token(model, round(2 * context - 1))
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


def window_attention_step_work(model: dict, tokens: int, seq: int) -> dict:
    """What the windowed attention cores of one train step need (every `W` part; the
    projections, the norms a head, the rotation and the gate are outside): {"flops": the
    BAND's share of six products a head (forward: scores, weighted values; backward: scores
    again, dP, dQ, dK and dV count as four: 2 + 4 = six of 2 x kept x head_dim a query),
    "bytes": q, k, v read and o written forward; q, k, v, o, dO read and dq, dk, dv written
    backward, two bytes a number}. The same whatever implements it: the tiles a kernel
    computes at the band's two edges beyond what the mask keeps, K and V rows fetched outside
    the band and the forward's second run under remat are the program's, not the need's."""
    heads, kv_heads, hd = _heads(model)
    layers = model["layer_pattern"].count("W")
    q_like, kv_like = 2 * tokens * heads * hd, 2 * tokens * kv_heads * hd
    forward = 2 * q_like + 2 * kv_like  # q, o; k, v
    backward = 4 * q_like + 4 * kv_like  # q, o, dO, dq; k, v, dk, dv
    return {"flops": layers * 6 * 2 * band_context(model, seq) * hd * heads * tokens,
            "bytes": layers * (forward + backward)}
