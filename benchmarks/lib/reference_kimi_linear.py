"""The plain reference of the `kimi_linear` family (Kimi-Linear-48B-A3B): forward pass, loss,
gradients by `jax.grad(loss)`. Straightforward jax.numpy, float32, matrix products at the
highest precision; no kernel, no cache, no chunks: the delta rule is the recurrence a
position at a time, attention a block of queries at a time with q and k at their width and v
at its own, experts one at a time, the head and its softmax HEAD_BLOCK positions at a time.
`jax.checkpoint` around a part, a segment of the recurrence, a block of queries, an expert
and a block of the head says what the backward pass keeps (their inputs; of the recurrence
the state at every SEGMENT-th position: at 32 heads of 128 by 128 a state is 2 MB, so 8,192
positions keep 128 of them, 0.27 GB, and the backward pass of one segment holds its 64
states and their cotangents, 0.3 GB, where every position's would be 17 GB a layer) and
changes no number.

Written from the published description (config.json of moonshotai/Kimi-Linear-48B-A3B-Instruct;
Kimi Linear, arXiv:2510.26692, sections 3 and 4: Kimi Delta Attention three to one with
NoPE multi-head latent attention, DeepSeek-V3's form un-absorbed; the expert layer is
DeepSeek-V3's, arXiv:2412.19437 section 2.1.2). A stack is `model["layer_pattern"]`, one
character a part, each part behind its own RMSNorm and residual, x <- x + part(RMSNorm(x));
a published layer is two parts (a mixer, then a feed-forward part); a final RMSNorm, an
untied head. The tree holds a stack a character, in the pattern's order (K `kda_layers`,
* `attn_layers`, E `layers`, - `mlp_layers`). With u = RMSNorm(x):

  K  Kimi Delta Attention, per head, keys and values 128 wide:
               [q~ | k~ | v~] = conv(u W_qkv): causal, depthwise, 4 taps, no bias;
               q = l2norm(silu(q~)) * 128^-1/2, k = l2norm(silu(k~)), v = silu(v~);
               g_t = -exp(A_log) * softplus(W_f_up (W_f_down u) + dt_bias), a channel, <= 0;
               beta_t = sigmoid(u W_beta), a head, in (0, 1) (times 2 only where
               `kda_neg_eigval`, which this family's config does not set); in float32
               S_t = (I - beta_t k_t k_t^T) Diag(exp g_t) S_{t-1} + beta_t k_t v_t^T,  o_t = S_t^T q_t
               from S = 0; out = (RMSNorm_head(o) * sigmoid(W_g_up (W_g_down u))) W_o, one
               [128] norm weight for all heads.
  *  latent attention WITHOUT a q latent (q_lora_rank null) and WITHOUT rotation
               (mla_use_nope): q = u W_q, heads of nope + rope = 192; c = u W_kva, 512 + 64 wide;
               [k_nope | v] = RMSNorm(c[:512]) W_kvb, heads of 128 + 128; k = [k_nope | c[512:]], the
               last 64 columns ONE key for all heads, used as they come; causal
               softmax(q k^T / sqrt(192)) v; heads (128 wide) joined through W_o.
  E  experts   s = sigmoid(u W_r) in float32; the k experts with the largest s + b; gates
               g = route_scale * s_sel / (sum s_sel + 1e-20);
               y = sum g_e SwiGLU_e(u) + SwiGLU_shared(u).
  -  the dense SwiGLU of width d_ff (the published first layer's feed-forward part).

Departures: none in the mathematics. The tree is the program's (a leaf a matrix, named as
models/attn.py, kda.py and moe.py name them; W_qkv as [D, 3, H, K], the convolution as
[taps, 3, H, K] with the last tap the current position's), so that one seeded tree feeds
both; a family member WITH a q latent or rotation is GLM-4.7-Flash's form and is not written
here (a tree with `wq_a` is refused by its missing `wq`).

The share: the tree holds the heads, experts and vocabulary rows of one chip; the counts
are read off the leaves. `model["experts_held"] = (index, of)` says which contiguous share
of the experts `w_gate` holds; the router scores all `n_experts`, and what the experts held
elsewhere would add is left out.

`dtype=float32` is the reference. `dtype=bfloat16` is the same code with parameters and
activations rounded to bfloat16 and default matrix precision (norms' statistics, the
router's products, the decays, beta and the recurrent state stay float32): the yardstick of
what bfloat16 costs at this depth, in whose multiples a tolerance is stated. `selection` (a
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
SEGMENT = 64  # positions of the recurrence between two kept states
STACKS = {"K": "kda_layers", "*": "attn_layers", "E": "layers", "-": "mlp_layers"}
FLOAT32_LEAVES = ("kda_A_log", "kda_dt_bias")  # the decays' own: float32 in every dtype


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def recurrence(q, k, v, g, beta):
    """q, k, v, g [B, T, H, K] (g <= 0: the log of a channel's decay), beta [B, T, H] -> o
    [B, T, H, K] float32, o_t = S_t^T q_t from a zero state: T steps, one after the other."""
    bsz, t, h, width = q.shape
    f32 = jnp.float32

    def step(state, at):
        q_t, k_t, v_t, g_t, beta_t = at  # [B, H, K] four times, [B, H]
        state = jnp.exp(g_t)[..., None] * state
        seen = jnp.sum(state * k_t[..., None], axis=-2)  # what the state holds under k_t: [B, H, V]
        state = state + k_t[..., None] * (beta_t[..., None] * (v_t - seen))[..., None, :]
        return state, jnp.sum(state * q_t[..., None], axis=-2)

    @jax.checkpoint
    def segment(state, ats):
        return jax.lax.scan(step, state, ats)

    size = SEGMENT if t % SEGMENT == 0 else t
    seq = lambda m: jnp.moveaxis(m.astype(f32), 1, 0).reshape(t // size, size, *m.shape[:1], *m.shape[2:])  # noqa: E731
    _, o = jax.lax.scan(segment, jnp.zeros((bsz, h, width, width), f32),
                        (seq(q), seq(k), seq(v), seq(g), seq(beta)))
    return jnp.moveaxis(o.reshape(t, bsz, h, width), 0, 1)


def _l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def kda_layer(x, lp, model):
    """x [B, T, D] -> x + the Kimi-Delta-Attention layer's output. Heads are the tree's."""
    f32 = jnp.float32
    bsz, t, _ = x.shape
    taps, _, h, width = lp["kda_conv"].shape
    u = _rms_norm(x, lp["kda_norm"], model["norm_eps"])
    qkv = jnp.einsum("btd,dphk->btphk", u, lp["kda_qkv"]).reshape(bsz, t, 3 * h * width)
    conv = jax.lax.conv_general_dilated(  # a channel at a time; zeros before the sequence
        qkv.astype(f32), lp["kda_conv"].astype(f32).reshape(taps, 1, 3 * h * width), window_strides=(1,),
        padding=[(taps - 1, 0)], dimension_numbers=("NWC", "WIO", "NWC"),
        feature_group_count=qkv.shape[-1], precision=jax.lax.Precision.HIGHEST)
    q, k, v = jnp.moveaxis(jax.nn.silu(conv).reshape(bsz, t, 3, h, width), 2, 0)
    q, k, v = ((_l2norm(q) * width**-0.5).astype(x.dtype), _l2norm(k).astype(x.dtype), v.astype(x.dtype))
    decay = jnp.einsum("btr,rhk->bthk", u @ lp["kda_f_down"], lp["kda_f_up"])
    g = -jnp.exp(lp["kda_A_log"])[:, None] * jax.nn.softplus(decay.astype(f32) + lp["kda_dt_bias"])
    beta = jax.nn.sigmoid((u @ lp["kda_beta"]).astype(f32)) * (2.0 if model.get("kda_neg_eigval", False) else 1.0)
    o = recurrence(q, k, v, g, beta)
    o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + model["norm_eps"])
    gate = jnp.einsum("btr,rhk->bthk", u @ lp["kda_g_down"], lp["kda_g_up"])
    o = (o * lp["kda_o_norm"].astype(f32) * jax.nn.sigmoid(gate.astype(f32))).astype(x.dtype)
    return x + jnp.einsum("bthk,hkd->btd", o, lp["kda_out"])


def _attention(q, k, v):
    """Causal multi-head attention, q and k [B, S, H, D], v [B, S, H, Dv] -> [B, S, H, Dv],
    QUERY_BLOCK queries at a time; the scores are scaled by D^-1/2, q's and k's width."""
    b, s, h, d = q.shape
    size = min(QUERY_BLOCK, s)
    blocks = -(-s // size)

    @jax.checkpoint
    def block(start, qb):
        scores = jnp.einsum("bqhd,bphd->bhqp", qb, k,
                            preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(d))
        seen = jnp.arange(s)[None, :] <= (start + jnp.arange(size))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqp,bphv->bqhv", probs.astype(v.dtype), v)

    # (queries past the end, where the last block is not full, see every key and are cut)
    padded = jnp.pad(q, ((0, 0), (0, blocks * size - s), (0, 0), (0, 0)))
    out = jax.lax.map(lambda a: block(*a), (jnp.arange(blocks) * size,
                                            padded.reshape(b, blocks, size, h, d).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, blocks * size, h, v.shape[-1])[:, :s]


def attention_layer(x, lp, model):
    """x [B, S, D] -> x + the latent attention layer's output: no q latent, no rotation."""
    nope, rank = model["qk_nope_head_dim"], model["kv_lora_rank"]
    u = _rms_norm(x, lp["attn_norm"], model["norm_eps"])
    q = jnp.einsum("bsd,dhk->bshk", u, lp["wq"])
    c = u @ lp["wkv_a"]  # the latent and, behind it, the key every head shares
    kv = jnp.einsum("bsr,rhk->bshk", _rms_norm(c[..., :rank], lp["kv_norm"], model["norm_eps"]), lp["wkv_b"])
    shared = jnp.broadcast_to(c[:, :, None, rank:], (*kv.shape[:3], c.shape[-1] - rank))
    k = jnp.concatenate([kv[..., :nope], shared], axis=-1)
    return x + jnp.einsum("bshv,hvd->bsd", _attention(q, k, kv[..., nope:]), lp["wo"])


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
    gates = model.get("moe_route_scale", 1.0) * gates / (gates.sum(-1, keepdims=True) + 1e-20)
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


def _layer(x, lp, model, dtype, chosen=None):
    """One part, whichever its leaves are. lp: the leaves as held (float32); everything but
    the routed experts, which are cast one at a time, and what stays float32 in every
    dtype is rounded to `dtype` here."""
    keep = {name: a for name, a in lp.items()
            if name in FLOAT32_LEAVES or ("router" in lp and name in ("w_gate", "w_up", "w_down"))}
    lp = {**jax.tree.map(lambda a: a.astype(dtype), {n: a for n, a in lp.items() if n not in keep}),
          **keep}
    routing = None
    if "kda_qkv" in lp:
        x = kda_layer(x, lp, model)
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


def _hidden(params, tokens, model, dtype, selection):
    """tokens [B, S] -> (the last part's output behind the final norm [B, S, D] in `dtype`,
    [routing an expert layer])."""
    cast = lambda a: a.astype(dtype)  # noqa: E731
    selection = list(selection) if selection is not None else None
    routings = []
    x = cast(params["embed"])[tokens]
    at = dict.fromkeys(STACKS.values(), 0)
    for character in model["layer_pattern"]:
        name = STACKS[character]
        lp = jax.tree.map(lambda a: a[at[name]], params[name])  # noqa: B023
        at[name] += 1
        chosen = None
        if selection is not None and "router" in lp:
            chosen = selection[len(routings)][:, :x.shape[1]]
        x, routed = jax.checkpoint(lambda x, lp, c: _layer(x, lp, model, dtype, c))(x, lp, chosen)
        if routed is not None:
            routings.append(routed)
    return _rms_norm(x, cast(params["final_norm"]), model["norm_eps"]), routings


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
    the head and its softmax HEAD_BLOCK positions at a time (the logits of 8,192 positions
    over 20,480 rows are 0.67 GB, and their softmax's cotangent as much again)."""
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
