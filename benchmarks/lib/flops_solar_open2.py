"""Operations a call of the `solar_open2` family needs (Solar-Open2: every published layer
a token mixer then an expert part, run as two characters of a pattern: Kimi-Delta-Attention
mixers `K`, softmax attention without rotation and with an output gate `*`, SwiGLU experts
beside a shared one `E`), from its shapes alone. `flops.py`'s rules: only matrix work is
counted (a multiply-add is two operations); norms, the convolution, softmax, gates, the
decays' exponentials, the gathers and what remat recomputes are left out. `model` is the
`model` group of the configuration (the program's ModelConfig fields).

What ONE CHIP of the deployment needs is counted. The heads and vocabulary rows in `model`
are the chip's own; `experts_held = (index, of)` of the routed experts live here, so of a
token's `moe_top_k` assignments `1 / of` fall on this chip in expectation; router and
shared expert every token meets here.
"""

SCAN_CHUNK = 64  # the yardstick's, whatever chunk the program's scan runs at


def expert_params(model: dict) -> int:
    """A routed expert's three matrices."""
    return 3 * model["d_model"] * model["d_ff_expert"]


def scan_flops_per_token(model: dict) -> float:
    """Forward operations of the chunked delta rule's products for one token, all heads
    held (the WY form at a chunk of SCAN_CHUNK, keys and values `kda_head_dim` wide):
    inside a chunk, over the (chunk + 1) / 2 positions a token sees there, the keys'
    overlaps, the queries' with the keys, the triangular system's substitution over keys
    and values, and the weighted sum of the solved values; with the state, three
    [width, width] products a token (what the state holds under the keys and under the
    queries, and the chunk's addition to it)."""
    width = model.get("kda_head_dim", 128)
    seen = (SCAN_CHUNK + 1) / 2
    inside = 2 * seen * (width + width + 2 * width + width)
    return model["kda_n_heads"] * (inside + 3 * 2 * width * width)


def layer_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token in one part of each character of the pattern."""
    d = model["d_model"]
    hd = model.get("attn_head_dim") or d // model["n_heads"]
    held = model.get("attn_heads_held", (0, 0))  # the query and key/value heads held here
    heads, kv_heads = held[0] or model["n_heads"], held[1] or model["n_kv_heads"]
    gated = 1 if model.get("attn_output_gate") else 0
    inner = model["kda_n_heads"] * model.get("kda_head_dim", 128)
    rank = model.get("kda_proj_rank") or model.get("kda_head_dim", 128)
    routed = model["moe_top_k"] / model["experts_held"][1] * expert_params(model)
    shared = 3 * d * (model.get("d_ff_shared") or model["n_shared_experts"] * model["d_ff_expert"])
    return {
        # q k v, the decay's and the gate's low-rank pairs, beta, the output
        "K": 2 * (d * 3 * inner + 2 * (d + inner) * rank + d * model["kda_n_heads"] + inner * d)
        + scan_flops_per_token(model),
        "*": 2 * d * hd * ((2 + gated) * heads + 2 * kv_heads) + 2 * heads * 2 * hd * context,
        "E": 2 * (d * model["n_experts"] + shared + routed),
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
    """What the delta-rule scans of one train step over `tokens` tokens need, all
    Kimi-Delta-Attention parts: {"flops": forward and backward (twice the forward) of the
    products at SCAN_CHUNK, "bytes": q, k and v read in the activation's two bytes, g (a
    channel) and beta (a head) read and o written in float32's four, once forward and
    twice backward (the same read beside o's cotangent, a gradient written for each
    input)}. The [chunk, chunk, width] differences of the decays, the chunks' own
    matrices and the states between chunks are the implementation's, not the need's, and
    are not counted."""
    h, width = model["kda_n_heads"], model.get("kda_head_dim", 128)
    layers = model["layer_pattern"].count("K")
    forward_bytes = tokens * h * (3 * 2 * width + 4 * width + 4 + 4 * width)
    return {"flops": layers * 3 * tokens * scan_flops_per_token(model),
            "bytes": layers * 3 * forward_bytes}
