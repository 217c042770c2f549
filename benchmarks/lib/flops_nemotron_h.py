"""Operations a call of the `nemotron_h` family needs (Nemotron-3-Super: a pattern of
single-part layers: Mamba-2 mixers, attention without rotation, latent relu2 experts
beside a shared one, the dense relu2 MLP; MTP modules), from its shapes alone. `flops.py`'s
rules: only matrix work is counted (a multiply-add is two operations); norms, the
convolution, softmax, gates, the gathers and what remat recomputes are left out. `model`
is the `model` group of the configuration (the program's ModelConfig fields).

What ONE CHIP of the deployment needs is counted. The heads, groups and vocabulary rows
in `model` are the chip's own; `experts_held = (index, of)` of the routed experts live
here, so of a token's `moe_top_k` assignments `1 / of` fall on this chip in expectation;
router, latent projections and shared expert every token meets here.
"""


def _d_inner(model: dict) -> int:
    return model["ssm_n_heads"] * model["ssm_head_dim"]


def expert_params(model: dict) -> int:
    """A routed expert's two matrices, in the latent."""
    return 2 * (model.get("moe_latent_dim") or model["d_model"]) * model["d_ff_expert"]


def scan_flops_per_token(model: dict) -> float:
    """Forward operations of the chunked scan's four products for one token (ops/ssd.py):
    C.B and the weighted sum inside a chunk over the (chunk + 1) / 2 positions a token
    sees there, the chunk's state and the product with the state carried in."""
    h, p, n = model["ssm_n_heads"], model["ssm_head_dim"], model["ssm_state"]
    seen = (model["ssm_chunk"] + 1) / 2
    return 2 * seen * (model["ssm_n_groups"] * n + h * p) + 2 * 2 * h * p * n


def layer_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token in one layer of each character of the pattern."""
    d = model["d_model"]
    hd = model.get("attn_head_dim") or d // model["n_heads"]
    held = model.get("attn_heads_held", (0, 0))  # the query and key/value heads held here
    heads, kv_heads = held[0] or model["n_heads"], held[1] or model["n_kv_heads"]
    d_in = _d_inner(model)
    in_width = 2 * d_in + 2 * model["ssm_n_groups"] * model["ssm_state"] + model["ssm_n_heads"]
    latent = 2 * d * model.get("moe_latent_dim", 0)
    routed = model["moe_top_k"] / model["experts_held"][1] * expert_params(model)
    shared = 2 * d * (model.get("d_ff_shared") or model["n_shared_experts"] * model["d_ff_expert"])
    return {
        "M": 2 * d * (in_width + d_in) + scan_flops_per_token(model),
        "*": 2 * d * hd * (2 * heads + 2 * kv_heads) + 2 * heads * 2 * hd * context,
        "E": 2 * (d * model["n_experts"] + latent + shared + routed),
        "-": 2 * 2 * d * model["d_ff"],
    }


def forward_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token that attends to `context` positions, by part: the
    pattern's layers by character, the head, the MTP modules (a * and an E layer, eh_proj
    and the head again)."""
    layer, d = layer_flops_per_token(model, context), model["d_model"]
    head = 2 * d * model["vocab_size"]
    out = {c: model["layer_pattern"].count(c) * layer[c] for c in layer}
    out["head"] = head
    out["mtp"] = model.get("mtp_depth", 0) * (layer["*"] + layer["E"] + 2 * 2 * d * d + head)
    return out


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward for one token of a causal sequence of `seq` tokens: the
    backward costs twice the forward; recomputation is not counted."""
    return 3 * sum(forward_flops_per_token(model, (seq + 1) / 2).values())


def grouped_products_flops(model: dict, held_rows: float) -> float:
    """Forward and backward operations of the grouped products (up, down and their two
    transposes each) over `held_rows` rows that fell on held experts: the rows the step's
    counter gives, so no padding of a tile or a buffer is counted."""
    return 3 * 2 * held_rows * expert_params(model)


def scan_step_work(model: dict, tokens: int) -> dict:
    """What the scans of one train step over `tokens` tokens need, all Mamba-2 layers:
    {"flops": forward and backward (twice the forward) of the four products, "bytes": x, B
    and C read in the activation's two bytes, dt read and y written in float32's four,
    forward, and twice that backward (the same read beside y's cotangent, a gradient
    written for each input)}. The [chunk, chunk] decays and scores between the products
    are the implementation's, not the need's, and are not counted."""
    h, p = model["ssm_n_heads"], model["ssm_head_dim"]
    layers = model["layer_pattern"].count("M")
    forward_bytes = tokens * (2 * (h * p + 2 * model["ssm_n_groups"] * model["ssm_state"]) + 4 * h + 4 * h * p)
    return {"flops": layers * 3 * tokens * scan_flops_per_token(model),
            "bytes": layers * 3 * forward_bytes}
