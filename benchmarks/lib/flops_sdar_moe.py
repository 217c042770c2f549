"""Operations a call of the `sdar_moe` family needs (SDAR-30B-A3B: every layer rotated,
QK-normed GQA then softmax-routed SwiGLU experts, no shared expert) under its training
objective, block diffusion, from its shapes alone. `flops.py`'s rules: only matrix work is
counted (a multiply-add is two operations); norms, softmax, the rotation, the gathers and
what remat recomputes are left out. `model` is the `model` group of the configuration (the
program's ModelConfig fields).

The unit is the TRAINING token: a sequence of `seq` tokens goes through the layers as one
row of 2 x seq positions, noised and clean, because the objective needs both (a noised
block sees the clean blocks before it), so every product of a layer runs over two rows a
training token; the head reads the noised half alone, once. That doubling is what the
objective costs, not recomputation, and is counted. Attention keeps, over a sequence,
seq x (seq + block) scores: a noised row the clean keys before its block and its own block,
a clean row the clean keys up to its block's end.

What ONE CHIP of the deployment needs is counted. The vocabulary rows in `model` are the
chip's own; `experts_held = (index, of)` of the routed experts live here, so of a row's
`moe_top_k` assignments `1 / of` fall on this chip in expectation; attention (every head:
`attn_heads_held`) and the router every row of the chip's own sequences meets here.
"""


def expert_params(model: dict) -> int:
    """A routed expert's three matrices."""
    return 3 * model["d_model"] * model["d_ff_expert"]


def kept_keys(model: dict, seq: int) -> float:
    """Keys the two rows of a training token keep together, on average over a sequence of
    `seq` tokens: seq x (seq + block) scores a sequence."""
    return seq + model["diffusion_block"]


def _heads(model: dict):
    held = model.get("attn_heads_held") or (0, 0)
    return held[0] or model["n_heads"], held[1] or model["n_kv_heads"], model["attn_head_dim"]


def layer_flops_per_token(model: dict, seq: int) -> dict:
    """Forward operations for one TRAINING token in one layer, by part: both its rows."""
    d = model["d_model"]
    heads, kv_heads, hd = _heads(model)
    routed = model["moe_top_k"] / model["experts_held"][1] * expert_params(model)
    projections = 2 * d * hd * (2 * heads + 2 * kv_heads)  # q, o; k, v
    core = 2 * heads * 2 * hd  # the scores and the weighted values, a key a query keeps
    return {"attention": 2 * projections + core * kept_keys(model, seq),
            "experts": 2 * 2 * (d * model["n_experts"] + routed)}


def forward_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one training token by part: the layers' two parts and the
    head. `context` is (seq + 1) / 2, as every family's file takes it."""
    layer = layer_flops_per_token(model, round(2 * context - 1))
    out = {part: model["n_layers"] * ops for part, ops in layer.items()}
    out["head"] = 2 * model["d_model"] * model["vocab_size"]
    return out


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward for one training token of a sequence of `seq` tokens: the
    backward costs twice the forward; recomputation is not counted."""
    return 3 * sum(forward_flops_per_token(model, (seq + 1) / 2).values())


def grouped_products_flops(model: dict, held_rows: float) -> float:
    """Forward and backward operations of the grouped products (gate, up, down and their
    two transposes each) over `held_rows` rows that fell on held experts: the rows the
    step's counter gives (over both halves of the doubled row), so no padding of a tile or a
    buffer is counted."""
    return 3 * 2 * held_rows * expert_params(model)


def block_diffusion_attention_step_work(model: dict, tokens: int, seq: int) -> dict:
    """What the attention cores of one train step need (every layer; the projections, the
    norms a head and the rotation are outside): {"flops": six products a head (forward:
    scores, weighted values; backward: scores again, dP, dQ, dK and dV count as four: 2 + 4 =
    six of 2 x kept x head_dim) over the seq x (seq + block) scores a sequence that the
    block-diffusion mask keeps, `tokens` = sequences x seq TRAINING tokens a step; "bytes":
    q, k, v read and o written forward; q, k, v, o, dO read and dq, dk, dv written backward,
    two bytes a number, over the 2 x tokens rows of the doubled row}. The same whatever
    implements it: the tiles a kernel computes beyond what the mask keeps and the forward's
    second run under remat are the program's, not the need's."""
    heads, kv_heads, hd = _heads(model)
    rows = 2 * tokens
    q_like, kv_like = 2 * rows * heads * hd, 2 * rows * kv_heads * hd
    forward = 2 * q_like + 2 * kv_like  # q, o; k, v
    backward = 4 * q_like + 4 * kv_like  # q, o, dO, dq; k, v, dk, dv
    return {"flops": model["n_layers"] * 6 * 2 * kept_keys(model, seq) * hd * heads * tokens,
            "bytes": model["n_layers"] * (forward + backward)}
