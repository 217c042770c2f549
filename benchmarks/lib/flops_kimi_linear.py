"""Operations a call of the `kimi_linear` family needs (Kimi-Linear-48B-A3B: every published
layer a token mixer then a feed-forward part, run as two characters of a pattern:
Kimi-Delta-Attention mixers `K`, latent attention without a q latent and without rotation
`*` whose q and k heads are wider than its v heads, the dense SwiGLU `-`, SwiGLU experts beside
a shared one `E`), from its shapes alone. `flops.py`'s rules: only matrix work is counted (a
multiply-add is two operations); norms, the convolution, softmax, gates, the decays'
exponentials, the gathers and what remat recomputes are left out. `model` is the `model`
group of the configuration (the program's ModelConfig fields).

What ONE CHIP of the deployment needs is counted. The heads and vocabulary rows in `model`
are the chip's own; `experts_held = (index, of)` of the routed experts live here, so of a
token's `moe_top_k` assignments `1 / of` fall on this chip in expectation; mixers, router,
shared expert and the dense part every token of the chip's own sequence meets here.
"""

# the delta rule's yardstick and the grouped products' count are the Solar-Open2 file's own (the same mixer and
# expert layer: `model` says how many heads and experts are held, here all 32 and 8 of 256)
from benchmarks.lib.flops_solar_open2 import (  # noqa: F401  (the readers and the tests ask this module for them)
    SCAN_CHUNK, expert_params, grouped_products_flops, scan_flops_per_token, scan_step_work)


def _latent_widths(model: dict):
    """(q's and k's width a head, v's)."""
    return model["qk_nope_head_dim"] + model["qk_rope_head_dim"], model["v_head_dim"]


def attention_projections(model: dict) -> int:
    """Weights a token multiplies against in a latent attention part: q by one product where
    there is no q latent, the latent and the shared key, its expansion, the output."""
    d, h = model["d_model"], model["n_heads"]
    qk, v = _latent_widths(model)
    rank = model.get("q_lora_rank", 0)
    q = d * rank + rank * h * qk if rank else d * h * qk
    return (q + d * (model["kv_lora_rank"] + model["qk_rope_head_dim"])
            + model["kv_lora_rank"] * h * (model["qk_nope_head_dim"] + v) + h * v * d)


def layer_flops_per_token(model: dict, context: float) -> dict:
    """Forward operations for one token in one part of each character of the pattern."""
    d = model["d_model"]
    qk, v = _latent_widths(model)
    inner = model["kda_n_heads"] * model.get("kda_head_dim", 128)
    rank = model.get("kda_proj_rank") or model.get("kda_head_dim", 128)
    routed = model["moe_top_k"] / model["experts_held"][1] * expert_params(model)
    shared = 3 * d * (model.get("d_ff_shared") or model["n_shared_experts"] * model["d_ff_expert"])
    return {
        # q k v, the decay's and the gate's low-rank pairs, beta, the output
        "K": 2 * (d * 3 * inner + 2 * (d + inner) * rank + d * model["kda_n_heads"] + inner * d)
        + scan_flops_per_token(model),
        # the projections; the scores over q's and k's width and the weighted values over v's,
        # over the positions a token sees
        "*": 2 * attention_projections(model) + 2 * model["n_heads"] * (qk + v) * context,
        "-": 2 * 3 * d * model["d_ff"],
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


def attention_step_work(model: dict, tokens: int, seq: int) -> dict:
    """What the attention cores of one train step need (every `*` part; the projections are
    outside), q and k at their width and v at its own: {"flops": the causal triangle's
    products a head, forward the scores (q's width a score) and the weighted values (v's),
    backward the scores again, dP (v's), dV (v's), dK and dQ (q's): 3 x qk + 2 x v a score
    where the forward is qk + v, each 2 x seen a query; "bytes": q, k, v read and o written
    forward; q, k, v, o, dO read and dq, dk, dv written backward, two bytes a number, each at
    its own width}. The same whatever implements it: lanes a kernel pads (192 to 256), the
    masked halves of the diagonal's tiles and the forward's second run under remat are the
    program's, not the need's, so a padded kernel reads low and none reads over 100."""
    qk, v = _latent_widths(model)
    heads = model["n_heads"]
    layers = model["layer_pattern"].count("*")
    seen = (seq + 1) / 2
    q_like, v_like = 2 * tokens * heads * qk, 2 * tokens * heads * v
    forward = 2 * q_like + 2 * v_like  # q, k; v, o
    backward = 4 * q_like + 4 * v_like  # q, k, dq, dk; v, o, dO, dv
    return {"flops": layers * 2 * seen * ((qk + v) + (3 * qk + 2 * v)) * heads * tokens,
            "bytes": layers * (forward + backward)}
