"""The plain reference of the `afmoe` family (Trinity-Mini): forward pass, loss, gradients
by `jax.grad(loss)`. Straightforward jax.numpy, float32, matrix products at the highest
precision; no kernel, no cache: attention is a masked softmax a block of queries at a time,
experts run one at a time, the head a block of positions at a time, and the batch a
sequence at a time (`lax.map`). `jax.checkpoint` around a sequence, a part, a block of
queries, an expert and a block of the head says what the backward pass keeps (their
inputs) and changes no number.

A stack is `model["layer_pattern"]`, one character a part; a published layer is two parts
(a mixer, then a feed-forward part). Every part lies between TWO norms, one on its input
and one on its OUTPUT, before the residual: x <- x + RMSNorm(part(RMSNorm(x))), each norm
with a weight of its own (`attn_norm` / `attn_post_norm`, `mlp_norm` / `mlp_post_norm`; the
norm behind is there where the tree has the leaf). The embedding's output is multiplied by
`embed_scale` (sqrt(hidden): mup_enabled); a final RMSNorm, an untied head. The tree holds
a stack a character, in the pattern's order (W `window_layers`, * `attn_layers`, E
`layers`, - `mlp_layers`). With u = RMSNorm(x), D wide:

  W, *  attention  q, k, v by three products (H / KV / KV heads); q and k normed a head
               (RMSNorm over the head's width, one weight each) BEFORE any rotation.
               W (sliding): q and k rotated (halves, theta `rope_theta`); key j is kept for
               query i where 0 <= i - j < `attn_window` (the query's own position counts).
               * (full): q and k are NOT rotated (`attention_rotation` false); key j is
               kept where j <= i.
               softmax(q k^T / sqrt(head)) v with H / KV query heads a key/value head; the
               output times sigmoid(u W_gate), a channel; heads joined through W_o.
  -  dense     (silu(u W_1) * (u W_3)) W_2.
  E  experts   s = sigmoid(u W_r) in float32; the k experts with the largest s + b; gates
               g = route_scale * s_sel / (sum s_sel + moe_gate_eps);
               y = SwiGLU_shared(u) + sum g_e SwiGLU_e(u).

Departures from the family's published code (AfmoeForCausalLM), each of which changes no
number: the mask is built from positions a block of queries at a time where the published
code hands a [S, S] mask to one softmax; a sliding block reads only the `attn_window` + block
keys that its mask can keep (the others' probabilities are exactly zero); grouped-query
heads are repeated, not grouped; the experts run on every token, weighted by a gate that is
zero where they were not chosen, where the published code gathers; the loss is computed a
block of positions at a time. The selection bias (`expert_bias`) moves by the balance rule
outside the model (train/step.py), as the published training code moves it outside the
forward pass.

The share: the tree holds the experts and vocabulary rows of one chip; the counts are read
off the leaves. `model["experts_held"] = (index, of)` says which contiguous share of the
experts `w_gate` holds; the router scores all `n_experts`, the shared expert is whole, and
what the experts held elsewhere would add is left out.

`dtype=float32` is the reference. `dtype=bfloat16` is the same code with parameters and
activations rounded to bfloat16 and default matrix precision (norms' statistics, the
router's products, the softmax and the gate's sigmoid stay float32): the yardstick of what
bfloat16 costs at this depth, in whose multiples a tolerance is stated. `selection` (a
list, one [B, S, k] int array an expert layer) makes the layers use those experts in place
of their own top-k: a near tie between the k-th and the next score is decided by rounding,
and a comparison of losses holds the arithmetic to account only where both sides use the
same experts; what was chosen, and by what margin, comes back for a comparison of its own
(`forward`'s third).
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
HEAD_BLOCK = 2048
STACKS = {"W": "window_layers", "*": "attn_layers", "E": "layers", "-": "mlp_layers"}


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1: the pairs (i, i + D/2) rotated, float32."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _attention(q, k, v, window=None):
    """Causal attention, q [B, S, H, D], k and v [B, S, KV, D], QUERY_BLOCK queries at a
    time: key j is kept for query i where j <= i and, under a window, i - j < window. A
    block then reads the keys from `window` before its first query to its last (zeros
    stand before the sequence, at negative positions that no mask keeps)."""
    b, s, h, d = q.shape
    k, v = (jnp.repeat(m, h // m.shape[2], axis=2) for m in (k, v))
    size = min(QUERY_BLOCK, s)
    blocks = -(-s // size)
    back = 0 if window is None else min(window, s)  # keys in front of a block's first query
    span = s if window is None else back + size
    if window is not None:  # (and behind the last block's, where it is not full)
        k, v = (jnp.pad(m, ((0, 0), (back, blocks * size - s), (0, 0), (0, 0))) for m in (k, v))

    @jax.checkpoint
    def block(start, qb):
        kb, vb, first = k, v, 0
        if window is not None:
            kb, vb = (jax.lax.dynamic_slice_in_dim(m, start, span, axis=1) for m in (k, v))
            first = start - back
        scores = jnp.einsum("bqhd,bphd->bhqp", qb, kb,
                            preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(d))
        i, j = (start + jnp.arange(size))[:, None], (first + jnp.arange(span))[None, :]
        seen = (j <= i) & (j >= 0)
        if window is not None:
            seen = seen & (i - j < window)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqp,bphd->bqhd", probs.astype(vb.dtype), vb)

    # (queries past the end, where the last block is not full, see their keys and are cut)
    padded = jnp.pad(q, ((0, 0), (0, blocks * size - s), (0, 0), (0, 0)))
    out = jax.lax.map(lambda a: block(*a), (jnp.arange(blocks) * size,
                                            padded.reshape(b, blocks, size, h, d).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, blocks * size, h, d)[:, :s]


def attention_part(x, lp, model, windowed):
    """x [B, S, D] -> the mixer's output (before the norm behind it and the residual)."""
    u = _rms_norm(x, lp["attn_norm"], model["norm_eps"])
    q, k, v = (jnp.einsum("bsd,dhk->bshk", u, lp[name]) for name in ("wq", "wk", "wv"))
    if "q_head_norm" in lp:
        q = _rms_norm(q, lp["q_head_norm"], model["norm_eps"])
        k = _rms_norm(k, lp["k_head_norm"], model["norm_eps"])
    if windowed or model.get("attention_rotation", True):
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    out = _attention(q, k, v, model["attn_window"] if windowed else None)
    if "wo_gate" in lp:
        gate = jnp.einsum("bsd,dhk->bshk", u, lp["wo_gate"])
        out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(x.dtype)
    return jnp.einsum("bshk,hkd->bsd", out, lp["wo"])


def _mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_layer(x, lp, model, chosen=None):
    """x [B, S, D] (normed) -> (the shared expert's and the held routed experts' part of
    the layer, {"chosen": [B, S, k] as used, "own": the layer's own top-k, "margin":
    [B, S] how far its k-th score + bias lies above the next})."""
    k, n = model["moe_top_k"], model["n_experts"]
    with jax.default_matmul_precision("highest"):  # the router is float32 in every dtype
        logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    scores = jax.nn.sigmoid(logits)
    biased = scores + lp["router_bias"].astype(jnp.float32) if "router_bias" in lp else scores
    top, own = jax.lax.top_k(biased, k + 1)
    own, margin = own[..., :k], top[..., k - 1] - top[..., k]
    chosen = own if chosen is None else chosen
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = model.get("moe_route_scale", 1.0) * gates / (
        gates.sum(-1, keepdims=True) + model.get("moe_gate_eps", 1e-20))
    index, of = model["experts_held"]
    held = n // of

    @jax.checkpoint
    def one(out, e):  # one expert at a time, on every token, weighted (0 where not chosen)
        w_gate, w_up, w_down, number = e
        weight = jnp.sum(jnp.where(chosen == number, gates, 0.0), axis=-1)
        return out + weight[..., None].astype(x.dtype) * _mlp(
            x, w_gate.astype(x.dtype), w_up.astype(x.dtype), w_down.astype(x.dtype)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lp["w_gate"], lp["w_up"], lp["w_down"], index * held + jnp.arange(held)))
    if model.get("n_shared_experts"):
        out = out + _mlp(x, lp["shared_gate"], lp["shared_up"], lp["shared_down"])
    return out, {"chosen": chosen, "own": own, "margin": margin}


def _layer(x, lp, model, dtype, windowed, chosen=None):
    """One part, whichever its leaves are, between its two norms. lp: the leaves as held
    (float32); everything but the routed experts, which are cast one at a time, is rounded
    to `dtype` here."""
    keep = {name: a for name, a in lp.items() if "router" in lp and name in ("w_gate", "w_up", "w_down")}
    lp = {**jax.tree.map(lambda a: a.astype(dtype), {n: a for n, a in lp.items() if n not in keep}),
          **keep}
    eps, routing = model["norm_eps"], None

    def behind(out, leaf):  # the norm behind a part, where the tree has one
        return _rms_norm(out, lp[leaf], eps) if leaf in lp else out

    if "attn_norm" in lp:
        x = x + behind(attention_part(x, lp, model, windowed), "attn_post_norm")
    if "mlp_norm" in lp:
        u = _rms_norm(x, lp["mlp_norm"], eps)
        if "router" in lp:
            y, routing = expert_layer(u, lp, model, chosen)
        else:
            y = _mlp(u, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = x + behind(y, "mlp_post_norm")
    return x, routing


def _sequences(params, tokens, model, dtype, selection):
    """tokens [B, S] -> (the last part's output behind the final norm [B, S, D] in `dtype`,
    [routing an expert layer]), one sequence at a time; of each only its tokens and its
    selection are kept for the backward pass."""
    cast = lambda a: a.astype(dtype)  # noqa: E731

    @jax.checkpoint
    def one(row):
        tokens, selection = row
        routings = []
        x = cast(params["embed"])[tokens[None]]
        if model.get("embed_scale"):
            x = (x.astype(jnp.float32) * model["embed_scale"]).astype(dtype)
        at = dict.fromkeys(STACKS.values(), 0)
        for character in model["layer_pattern"]:
            name = STACKS[character]
            lp = jax.tree.map(lambda a: a[at[name]], params[name])  # noqa: B023
            at[name] += 1
            chosen = None
            if selection is not None and "router" in lp:
                chosen = selection[len(routings)][None]
            x, routed = jax.checkpoint(
                lambda x, lp, c, w=character == "W": _layer(x, lp, model, dtype, w, c))(x, lp, chosen)
            if routed is not None:
                routings.append(jax.tree.map(lambda a: a[0], routed))
        return _rms_norm(x, cast(params["final_norm"]), model["norm_eps"])[0], routings

    return jax.lax.map(one, (tokens, selection))


def _hidden(params, tokens, model, dtype, selection):
    if selection is not None:
        selection = [chosen[:, :tokens.shape[1]] for chosen in selection]
    return _sequences(params, tokens, model, dtype, selection)


def forward(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, [] (the family has no MTP module),
    [routing an expert layer])."""
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        hidden, routings = _hidden(params, tokens, model, dtype, selection)
        # logits are rounded to `dtype` before they are widened, as a decoder that
        # computes in `dtype` hands them over
        return (hidden @ params["lm_head"].astype(dtype)).astype(jnp.float32), [], routings


def _losses(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def position_losses(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, T] -> (next-token losses [B, T - 1], [], routings): one number a position,
    the head and its softmax HEAD_BLOCK positions at a time (the logits of a sequence of
    16,384 over 25,024 rows are 1.6 GB)."""
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        hidden, routings = _hidden(params, tokens[:, :-1], model, dtype, selection)
        head, targets = params["lm_head"].astype(dtype), tokens[:, 1:]
        b, s, d = hidden.shape
        size = min(HEAD_BLOCK, s)
        blocks = -(-s // size)
        pad = blocks * size - s  # (positions past the end are cut)

        @jax.checkpoint
        def block(xs):
            h, t = xs
            return _losses((h @ head).astype(jnp.float32), t)

        losses = jax.lax.map(block, (
            jnp.pad(hidden, ((0, 0), (0, pad), (0, 0))).reshape(b, blocks, size, d).swapaxes(0, 1),
            jnp.pad(targets, ((0, 0), (0, pad))).reshape(b, blocks, size).swapaxes(0, 1)))
        return losses.swapaxes(0, 1).reshape(b, blocks * size)[:, :s], [], routings


def next_token_losses(params, tokens, model: dict, dtype=jnp.float32):
    return position_losses(params, tokens, model, dtype)[0]


def loss(params, tokens, model: dict, dtype=jnp.float32, selection=None, parts=False):
    """The training loss of tokens [B, T]: mean next-token cross entropy (no auxiliary
    loss: the selection bias balances). parts=True: (loss, {"ce_loss", "mtp_loss" (0),
    "position_losses", "routings"}), as `jax.value_and_grad(..., has_aux=True)` takes it."""
    main, _, routings = position_losses(params, tokens, model, dtype, selection)
    total = main.mean()
    if not parts:
        return total
    return total, {"ce_loss": total, "mtp_loss": jnp.zeros(()), "routings": routings,
                   "position_losses": main}
