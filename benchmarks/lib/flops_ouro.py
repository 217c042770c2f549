"""Operations a call of the `ouro` family needs (Ouro-2.6B: a stack of layers, each rotated
attention then the dense SwiGLU MLP between sandwich norms, run `loop_steps` times over the SAME
weights, a head and an exit gate behind every recurrence) under its training objective, the
expected-exit loss, from its shapes alone. `flops.py`'s rules: only matrix work is counted (a
multiply-add is two operations); norms, softmax, the rotation, the gathers and what remat
recomputes are left out. `model` is the `model` group of the configuration (the program's
ModelConfig fields).

What a looped model needs is NOT 6 N a token: a parameter of a layer is held once and used
`loop_steps` times, and so is the head's (every recurrence's logits enter the loss), so every
layer and the head are counted `loop_steps` times and nothing is counted by the parameter. The
exit gate is one product of width 1 a recurrence but the last. The second head product that the
rematerialised head-and-loss unit runs in the backward pass is recomputation and not counted.
"""


def attention_projections(model: dict) -> int:
    """Weights of one attention part's four products: q and o over the query heads, k and v over
    the key/value heads."""
    head = model.get("attn_head_dim") or model["d_model"] // model["n_heads"]
    return model["d_model"] * head * (2 * model["n_heads"] + 2 * model["n_kv_heads"])


def layer_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token in ONE application of one layer, by part. `context`:
    the keys a query sees on average, (seq + 1) / 2 under a causal mask."""
    head = model.get("attn_head_dim") or model["d_model"] // model["n_heads"]
    core = 2 * model["n_heads"] * 2 * head  # the scores and the weighted values, a key
    return {"attention": 2 * attention_projections(model) + core * context,
            "mlp": 2 * 3 * model["d_model"] * model["d_ff"]}


def forward_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token by part: every layer application (`loop_steps` x
    `n_layers` of them), the head behind every recurrence, the exit gate behind all but the last."""
    steps = model["loop_steps"]
    out = {part: steps * model["n_layers"] * ops for part, ops in layer_flops_per_token(model, context).items()}
    out["head"] = steps * 2 * model["d_model"] * model["vocab_size"]
    out["exit_gate"] = (steps - 1) * 2 * model["d_model"]
    return out


def train_flops_per_token(model: dict, seq: int) -> float:
    """Forward and backward for one token of a sequence of `seq` tokens: the backward costs
    twice the forward; recomputation is not counted."""
    return 3 * sum(forward_flops_per_token(model, (seq + 1) / 2).values())
