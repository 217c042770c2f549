"""The plain reference of the `nemotron_h` family (Nemotron-3-Super): forward pass, loss
with the multi-token-prediction term, gradients by `jax.grad(loss)`. Straightforward
jax.numpy, float32, matrix products at the highest precision; no kernel, no cache, no
chunks: the state-space layer is the recurrence a position at a time, experts one at a
time, attention a block of queries at a time. `jax.checkpoint` around a layer, a segment
of the recurrence, a block of queries and an expert says what the backward pass keeps
(their inputs; of the recurrence the state at every SEGMENT-th position, 0.5 MB each at
16 heads of 64 by 128 where every position's would be 4.3 GB a layer at 8,192) and
changes no number.

A stack is `model["layer_pattern"]`, one character a layer, each layer ONE part behind
its own RMSNorm and residual, x <- x + part(RMSNorm(x)); a final RMSNorm, an untied head.
The tree holds a stack a character, in the pattern's order (M `ssm_layers`, * `attn_layers`,
E `layers`, - `mlp_layers`).

  M  Mamba-2   [z | xBC | dt] = u W_in; xBC = silu(conv(xBC) + b): causal, depthwise, 4
               taps; x [T, H, P], B and C [T, G, N] = split(xBC), head h reads group
               h // (H / G); dt = softplus(dt + dt_bias), A = -exp(A_log); a head, in
               float32:  S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T,  y_t = S_t C_t + D x_t;
               y = RMSNorm_group(y * silu(z)) (gate first, a group's d_inner / G channels,
               a weight a channel); out = y W_out.
  *  attention q, k, v by three products, NO rotation, causal softmax(q k^T / sqrt(head))
               v with H / KV query heads a key/value head, heads joined through W_o.
  E  experts   s = sigmoid(u W_r) in float32; the k experts with the largest s + b; gates
               g = route_scale * s_sel / (sum s_sel + 1e-20); l = u W_latent_down;
               y = (sum g_e relu(l W_up,e)^2 W_down,e) W_latent_up + relu(u W_sup)^2 W_sdown.
  -  MLP       relu(u W_up)^2 W_down.
  MTP module   h' = [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)] W_eh, a * and an E layer, its
               own final RMSNorm, the model's head; its loss is the cross entropy of
               t_{i+2}, added with weight mtp_loss_weight. h_i is the last layer's output
               before the final norm.

The share: the tree holds the heads, groups, experts and vocabulary rows of one chip;
the counts are read off the leaves. `model["experts_held"] = (index, of)` says which
contiguous share of the experts `w_up` holds; the router scores all `n_experts`, and what
the experts held elsewhere would add is left out.

`dtype=float32` is the reference. `dtype=bfloat16` is the same code with parameters and
activations rounded to bfloat16 and default matrix precision (norms' statistics, the
router's products, dt, the decays and the recurrent state stay float32): the yardstick of what
bfloat16 costs at this depth, in whose multiples a tolerance is stated. `selection` (a
list, one [B, S, k] int array an expert layer, MTP modules last) makes the layers use
those experts in place of their own top-k: a near tie between the k-th and the next score
is decided by rounding, and a comparison of losses holds the arithmetic to account only
where both sides use the same experts; what was chosen, and by what margin, comes back
for a comparison of its own (`forward`'s third).
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
SEGMENT = 128  # positions of the recurrence between two kept states
STACKS = {"M": "ssm_layers", "*": "attn_layers", "E": "layers", "-": "mlp_layers"}
FLOAT32_LEAVES = ("A_log", "dt_bias", "D")  # a head each: the decays' own, float32 in every dtype


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def recurrence(x, dt, a, b, c):
    """x [B, T, H, P], dt [B, T, H] (after its softplus), a [H] (< 0), b and c [B, T, G, N]
    -> y [B, T, H, P] float32, y_t = S_t C_t from a zero state: T steps, one after the other."""
    bsz, t, h, p = x.shape
    n = b.shape[-1]
    f32 = jnp.float32
    per_head = lambda m: jnp.repeat(m.astype(f32), h // m.shape[2], axis=2)  # noqa: E731

    def step(state, at):
        x_t, dt_t, b_t, c_t = at  # [B, H, P], [B, H], [B, H, N] twice
        state = (jnp.exp(dt_t * a)[..., None, None] * state
                 + (dt_t[..., None] * x_t)[..., None] * b_t[..., None, :])
        return state, jnp.sum(state * c_t[..., None, :], axis=-1)

    @jax.checkpoint
    def segment(state, ats):
        return jax.lax.scan(step, state, ats)

    size = SEGMENT if t % SEGMENT == 0 else t
    seq = lambda m: jnp.moveaxis(m, 1, 0).reshape(t // size, size, *m.shape[:1], *m.shape[2:])  # noqa: E731
    _, y = jax.lax.scan(segment, jnp.zeros((bsz, h, p, n), f32),
                        (seq(x.astype(f32)), seq(dt.astype(f32)), seq(per_head(b)), seq(per_head(c))))
    return jnp.moveaxis(y.reshape(t, bsz, h, p), 0, 1)


def mamba_layer(x, lp, model):
    """x [B, T, D] -> x + the Mamba-2 layer's output. Heads and groups are the tree's."""
    eps, n = model["norm_eps"], model["ssm_state"]
    h = lp["A_log"].shape[0]
    d_in = lp["out_proj"].shape[0]
    g = (lp["conv_w"].shape[1] - d_in) // (2 * n)
    bsz, t, _ = x.shape
    joined = _rms_norm(x, lp["ssm_norm"], eps) @ lp["in_proj"]
    z, xbc, dt = jnp.split(joined, [d_in, 2 * d_in + 2 * g * n], axis=-1)
    taps = lp["conv_w"].shape[0]
    conv = jax.lax.conv_general_dilated(  # a channel at a time; zeros before the sequence
        xbc.astype(jnp.float32), lp["conv_w"].astype(jnp.float32)[:, None, :], window_strides=(1,),
        padding=[(taps - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=xbc.shape[-1], precision=jax.lax.Precision.HIGHEST)
    xbc = jax.nn.silu(conv + lp["conv_b"].astype(jnp.float32)).astype(x.dtype)
    xs, b, c = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
    xs = xs.reshape(bsz, t, h, d_in // h)
    dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
    y = recurrence(xs, dt, -jnp.exp(lp["A_log"]), b.reshape(bsz, t, g, n), c.reshape(bsz, t, g, n))
    y = y + lp["D"][:, None] * xs.astype(jnp.float32)
    y = (y.reshape(bsz, t, d_in) * jax.nn.silu(z.astype(jnp.float32))).reshape(bsz, t, g, d_in // g)
    y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + eps)
    y = (y.reshape(bsz, t, d_in) * lp["gate_norm"].astype(jnp.float32)).astype(x.dtype)
    return x + y @ lp["out_proj"]


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
    h = _rms_norm(x, lp["attn_norm"], model["norm_eps"])
    q, k, v = (jnp.einsum("bsd,dhk->bshk", h, lp[name]) for name in ("wq", "wk", "wv"))
    return x + jnp.einsum("bshk,hkd->bsd", _attention(q, k, v), lp["wo"])


def _relu2_mlp(x, w_up, w_down):
    return jnp.square(jax.nn.relu(x @ w_up)) @ w_down


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
    gates = model["moe_route_scale"] * gates / (gates.sum(-1, keepdims=True) + 1e-20)
    index, of = model["experts_held"]
    held = n // of
    latent = x @ lp["latent_down"]

    @jax.checkpoint
    def one(out, e):  # one expert at a time, on every token, weighted (0 where not chosen)
        w_up, w_down, number = e
        weight = jnp.sum(jnp.where(chosen == number, gates, 0.0), axis=-1)
        return out + weight[..., None].astype(x.dtype) * _relu2_mlp(
            latent, w_up.astype(x.dtype), w_down.astype(x.dtype)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(latent),
                          (lp["w_up"], lp["w_down"], index * held + jnp.arange(held)))
    out = out @ lp["latent_up"]
    if model.get("n_shared_experts"):
        out = out + _relu2_mlp(x, lp["shared_up"], lp["shared_down"])
    return out, {"chosen": chosen, "own": own, "margin": margin}


def _layer(x, lp, model, dtype, chosen=None):
    """One layer of whichever parts its leaves are (an MTP module's holds * and E). lp: the
    leaves as held (float32); everything but the routed experts, which are cast one at a
    time, and what stays float32 in every dtype is rounded to `dtype` here."""
    keep = {name: a for name, a in lp.items()
            if name in FLOAT32_LEAVES or ("router" in lp and name in ("w_up", "w_down"))}
    lp = {**jax.tree.map(lambda a: a.astype(dtype), {n: a for n, a in lp.items() if n not in keep}),
          **keep}
    routing = None
    if "in_proj" in lp:
        x = mamba_layer(x, lp, model)
    if "attn_norm" in lp:
        x = attention_layer(x, lp, model)
    if "mlp_norm" in lp:
        h = _rms_norm(x, lp["mlp_norm"], model["norm_eps"])
        if "router" in lp:
            y, routing = expert_layer(h, lp, model, chosen)
        else:
            y = _relu2_mlp(h, lp["w_up"], lp["w_down"])
        x = x + y
    return x, routing


def forward(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, S] -> (logits [B, S, vocab], [MTP module m's logits [B, S - m, vocab]:
    at position i, of token i + m + 1], [routing an expert layer, the MTP modules' last]),
    float32."""
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        eps, depth = model["norm_eps"], model.get("mtp_depth", 0)
        cast = lambda a: a.astype(dtype)  # noqa: E731
        embed, head = cast(params["embed"]), cast(params["lm_head"])
        selection = list(selection) if selection is not None else None
        routings = []

        def layer(x, lp):
            """x through one layer; an expert layer's routing is appended, and it is given
            its own selection (made over at least this many positions)."""
            chosen = None
            if selection is not None and "router" in lp:
                chosen = selection[len(routings)][:, :x.shape[1]]
            x, routed = jax.checkpoint(lambda x, lp, c: _layer(x, lp, model, dtype, c))(x, lp, chosen)
            if routed is not None:
                routings.append(routed)
            return x

        x = embed[tokens]
        at = dict.fromkeys(STACKS.values(), 0)
        for character in model["layer_pattern"]:
            name = STACKS[character]
            x = layer(x, jax.tree.map(lambda a: a[at[name]], params[name]))  # noqa: B023
            at[name] += 1
        # logits are rounded to `dtype` before they are widened, as a decoder that
        # computes in `dtype` hands them over
        logits = (_rms_norm(x, cast(params["final_norm"]), eps) @ head).astype(jnp.float32)
        mtp = []
        for m in range(1, depth + 1):
            mp = jax.tree.map(lambda a: a[m - 1], params["mtp"])
            n = x.shape[1] - 1
            joined = jnp.concatenate([_rms_norm(embed[tokens[:, m:m + n]], cast(mp["embed_norm"]), eps),
                                      _rms_norm(x[:, :n], cast(mp["hidden_norm"]), eps)], -1)
            x = layer(joined @ cast(mp["eh_proj"]), {
                name: a for name, a in mp.items()
                if name not in ("embed_norm", "hidden_norm", "eh_proj", "final_norm")})
            mtp.append((_rms_norm(x, cast(mp["final_norm"]), eps) @ head).astype(jnp.float32))
        return logits, mtp, routings


def _losses(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def position_losses(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, T] -> (next-token losses [B, T - 1], [module m's losses [B, T - 1 - m],
    of the token m + 1 ahead], routings): one number a position and head."""
    logits, mtp, routings = forward(params, tokens[:, :-1], model, dtype, selection)
    return (_losses(logits, tokens[:, 1:]),
            [_losses(lg, tokens[:, m + 1:]) for m, lg in enumerate(mtp, 1)], routings)


def next_token_losses(params, tokens, model: dict, dtype=jnp.float32):
    return position_losses(params, tokens, model, dtype)[0]


def loss(params, tokens, model: dict, dtype=jnp.float32, selection=None, parts=False):
    """The training loss of tokens [B, T]: mean next-token cross entropy plus
    mtp_loss_weight times the mean of the MTP modules' (no auxiliary loss: the selection
    bias balances). parts=True: (loss, {"ce_loss", "mtp_loss", "position_losses": every
    head's joined along the positions, "routings"}), as `jax.value_and_grad(...,
    has_aux=True)` takes it."""
    main, mtp, routings = position_losses(params, tokens, model, dtype, selection)
    ce = total = main.mean()
    mtp_loss = sum(m.mean() for m in mtp) / len(mtp) if mtp else jnp.zeros(())
    if mtp:
        total = total + model["mtp_loss_weight"] * mtp_loss
    if not parts:
        return total
    return total, {"ce_loss": ce, "mtp_loss": mtp_loss, "routings": routings,
                   "position_losses": jnp.concatenate([main, *mtp], axis=1)}
