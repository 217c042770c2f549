"""The plain reference of the `lfm2_moe` family (LFM2-24B-A2B): forward pass, loss,
gradients by `jax.grad(loss)`. Straightforward jax.numpy, float32, matrix products at the
highest precision; no kernel, no cache: the convolution is three shifted products, experts
run one at a time, attention a block of queries at a time against every key, and the
batch a sequence at a time (`lax.map`). `jax.checkpoint` around a sequence, a part, a
block of queries and an expert says what the backward pass keeps (their inputs) and
changes no number.

A stack is `model["layer_pattern"]`, one character a part, each part behind its own
RMSNorm and residual, x <- x + part(RMSNorm(x)); a published layer is two parts (a mixer,
then a feed-forward part); a final RMSNorm, the head tied to the embedding where the tree
has no `lm_head`. The tree holds a stack a character, in the pattern's order (C
`sconv_layers`, * `attn_layers`, E `layers`, - `mlp_layers`). With u = RMSNorm(x), D wide:

  C  the gated short convolution (Lfm2ShortConv): [B | C | x] = u W_in (W_in [D, 3, D], the
               thirds in this order), z = B * x, c_t = sum_k w_k z_{t - (taps-1) + k} (a
               channel at a time, zeros before the sequence, the last tap the current
               position's; float32), out = (C * c) W_out. No bias, no activation.
  *  attention q, k, v by three products; q and k normed a head (RMSNorm over the head's
               width, one weight each, where the tree has `q_head_norm`) and THEN rotated
               (halves, theta `rope_theta`); causal softmax(q k^T / sqrt(head)) v with
               H / KV query heads a key/value head; heads joined through W_o.
  -  dense     (silu(u W_1) * (u W_3)) W_2.
  E  experts   s = sigmoid(u W_r) in float32; the k experts with the largest s + b; gates
               g = route_scale * s_sel / (sum s_sel + moe_gate_eps);
               y = sum g_e SwiGLU_e(u). Nothing beside the routed experts.

The share: the tree holds the experts and vocabulary rows of one chip; the counts are read
off the leaves. `model["experts_held"] = (index, of)` says which contiguous share of the
experts `w_gate` holds; the router scores all `n_experts`, and what the experts held
elsewhere would add is left out.

`dtype=float32` is the reference. `dtype=bfloat16` is the same code with parameters and
activations rounded to bfloat16 and default matrix precision (norms' statistics, the
router's products and the convolution's sum stay float32): the yardstick of what bfloat16
costs at this depth, in whose multiples a tolerance is stated. `selection` (a list, one
[B, S, k] int array an expert layer) makes the layers use those experts in place of their
own top-k: a near tie between the k-th and the next score is decided by rounding, and a
comparison of losses holds the arithmetic to account only where both sides use the same
experts; what was chosen, and by what margin, comes back for a comparison of its own
(`forward`'s third).
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
STACKS = {"C": "sconv_layers", "*": "attn_layers", "E": "layers", "-": "mlp_layers"}


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def short_conv(z, w):
    """z [B, T, D], w [taps, D] -> float32 [B, T, D]: c_t = sum_k w_k z_{t - (taps-1) + k},
    a product a tap with z shifted, zeros before the sequence."""
    taps, t = w.shape[0], z.shape[1]
    z, w = z.astype(jnp.float32), w.astype(jnp.float32)
    out = jnp.zeros_like(z)
    for k in range(taps):
        back = taps - 1 - k  # positions this tap looks back
        out = out + w[k] * jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :t]
    return out


def conv_layer(x, lp, model):
    """x [B, T, D] -> x + the gated short convolution's output."""
    u = _rms_norm(x, lp["sconv_norm"], model["norm_eps"])
    b, c, v = jnp.moveaxis(jnp.einsum("btd,dpe->btpe", u, lp["sconv_in"]), 2, 0)
    y = (c.astype(jnp.float32) * short_conv(b * v, lp["sconv_w"])).astype(x.dtype)
    return x + y @ lp["sconv_out"]


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1: the pairs (i, i + D/2) rotated, float32."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _attention(q, k, v):
    """Causal attention, q [B, S, H, D], k and v [B, S, KV, D], QUERY_BLOCK queries at a time."""
    b, s, h, d = q.shape
    k, v = (jnp.repeat(m, h // m.shape[2], axis=2) for m in (k, v))
    size = min(QUERY_BLOCK, s)
    blocks = -(-s // size)

    @jax.checkpoint
    def block(start, qb):
        scores = jnp.einsum("bqhd,bphd->bhqp", qb, k,
                            preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(s)[None, :] <= (start + jnp.arange(size))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqp,bphd->bqhd", probs.astype(v.dtype), v)

    # (queries past the end, where the last block is not full, see every key and are cut)
    padded = jnp.pad(q, ((0, 0), (0, blocks * size - s), (0, 0), (0, 0)))
    out = jax.lax.map(lambda a: block(*a), (jnp.arange(blocks) * size,
                                            padded.reshape(b, blocks, size, h, d).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, blocks * size, h, d)[:, :s]


def attention_layer(x, lp, model):
    u = _rms_norm(x, lp["attn_norm"], model["norm_eps"])
    q, k, v = (jnp.einsum("bsd,dhk->bshk", u, lp[name]) for name in ("wq", "wk", "wv"))
    if "q_head_norm" in lp:
        q = _rms_norm(q, lp["q_head_norm"], model["norm_eps"])
        k = _rms_norm(k, lp["k_head_norm"], model["norm_eps"])
    if model.get("attention_rotation", True):
        q, k = _rope(q, model["rope_theta"]), _rope(k, model["rope_theta"])
    return x + jnp.einsum("bshk,hkd->bsd", _attention(q, k, v), lp["wo"])


def _mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_layer(x, lp, model, chosen=None):
    """x [B, S, D] (normed) -> (the held routed experts' part of the layer, {"chosen":
    [B, S, k] as used, "own": the layer's own top-k, "margin": [B, S] how far its k-th
    score + bias lies above the next})."""
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
    return out, {"chosen": chosen, "own": own, "margin": margin}


def _layer(x, lp, model, dtype, chosen=None):
    """One part, whichever its leaves are. lp: the leaves as held (float32); everything but
    the routed experts, which are cast one at a time, is rounded to `dtype` here."""
    keep = {name: a for name, a in lp.items() if "router" in lp and name in ("w_gate", "w_up", "w_down")}
    lp = {**jax.tree.map(lambda a: a.astype(dtype), {n: a for n, a in lp.items() if n not in keep}),
          **keep}
    routing = None
    if "sconv_in" in lp:
        x = conv_layer(x, lp, model)
    if "attn_norm" in lp:
        x = attention_layer(x, lp, model)
    if "mlp_norm" in lp:
        u = _rms_norm(x, lp["mlp_norm"], model["norm_eps"])
        if "router" in lp:
            y, routing = expert_layer(u, lp, model, chosen)
        else:
            y = _mlp(u, lp["w_gate"], lp["w_up"], lp["w_down"])
        x = x + y
    return x, routing


def _sequences(params, tokens, model, dtype, selection):
    """tokens [B, S] -> (logits [B, S, vocab] float32, [routing an expert layer]), one
    sequence at a time; of each only its tokens and its selection are kept for the
    backward pass."""
    cast = lambda a: a.astype(dtype)  # noqa: E731
    head = cast(params["lm_head"]) if "lm_head" in params else cast(params["embed"]).T

    @jax.checkpoint
    def one(row):
        tokens, selection = row
        routings = []
        x = cast(params["embed"])[tokens[None]]
        at = dict.fromkeys(STACKS.values(), 0)
        for character in model["layer_pattern"]:
            name = STACKS[character]
            lp = jax.tree.map(lambda a: a[at[name]], params[name])  # noqa: B023
            at[name] += 1
            chosen = None
            if selection is not None and "router" in lp:
                chosen = selection[len(routings)][None]
            x, routed = jax.checkpoint(lambda x, lp, c: _layer(x, lp, model, dtype, c))(x, lp, chosen)
            if routed is not None:
                routings.append(jax.tree.map(lambda a: a[0], routed))
        # logits are rounded to `dtype` before they are widened, as a decoder that
        # computes in `dtype` hands them over
        logits = _rms_norm(x, cast(params["final_norm"]), model["norm_eps"]) @ head
        return logits[0].astype(jnp.float32), routings

    return jax.lax.map(one, (tokens, selection))


def forward(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, S] -> (logits [B, S, vocab] float32, [] (the family has no MTP module),
    [routing an expert layer])."""
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        if selection is not None:
            selection = [chosen[:, :tokens.shape[1]] for chosen in selection]
        logits, routings = _sequences(params, tokens, model, dtype, selection)
        return logits, [], routings


def _losses(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def position_losses(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, T] -> (next-token losses [B, T - 1], [], routings): one number a position."""
    logits, mtp, routings = forward(params, tokens[:, :-1], model, dtype, selection)
    return _losses(logits, tokens[:, 1:]), mtp, routings


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
