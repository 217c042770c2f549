"""The plain reference of the `glm4_moe_lite` family (GLM-4.7-Flash): forward pass, loss
with the multi-token-prediction term, gradients by `jax.grad(loss)`. Straightforward
jax.numpy, float32, matrix products at the highest precision; no kernel, no cache;
experts one at a time and attention a block of queries at a time, so that 8,192 positions
fit beside a training state. The loops over a stack's layers, the blocks of queries and
the experts held are `lax.scan` / `lax.map` (one body compiled, not one a turn), and
`jax.checkpoint` around a layer, a block of queries and an expert says what the backward
pass keeps (their inputs) and changes no number: the gradients of 706 M parameters at
8,192 positions fit on the chip that way.

Pre-norm residual blocks, RMSNorm, SiLU-gated MLPs, no biases, untied embedding and head.

  attention   c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads of [nope ; rope];
              [c_kv ; k_r] = x W_kva, c_kv = RMSNorm(c_kv); [k_nope ; v] = c_kv W_kvb;
              q = [q_nope ; RoPE(q_rope)], k = [k_nope ; RoPE(k_r)] with k_r shared by all
              heads; causal softmax(q k^T / sqrt(nope + rope)) v; heads joined through W_o.
              RoPE turns the pairs (2i, 2i + 1) (DeepSeek-V3's, which the family inherits).
  dense layer the first `n_dense_layers`: an MLP of width d_ff.
  expert layer s = sigmoid(x W_r) in float32; the k experts with the largest s + b; gates
              g = route_scale * s_sel / (sum s_sel + 1e-20); y = E_shared(x) + sum g_e E_e(x).
  MTP module  h' = [RMSNorm(Emb(t_{i+1})) ; RMSNorm(h_i)] W_eh, one expert layer, its own
              final RMSNorm, the model's head; its loss is the cross entropy of t_{i+2},
              added with weight mtp_loss_weight. h_i is the last block's output before
              the final norm.

The share: `model["experts_held"] = (index, of)` says which contiguous share of the
experts the tree holds (`w_gate` has that many); the router scores all `n_experts`, and what
the experts held elsewhere would add is left out. A sliced vocabulary is a smaller one.

Departure for reading the program's tree: the program keeps the rotated columns in the
order its rotation pairs them, (i, i + d/2); `_published_order` puts them back before the
pairs (2i, 2i + 1) are turned.

`dtype=float32` is the reference. `dtype=bfloat16` is the same code with parameters and
activations rounded to bfloat16 and default matrix precision (statistics and the router
stay float32): the yardstick of what bfloat16 costs at this depth, in whose multiples a
tolerance is stated. `selection` (a list, one [B, S, k] int array an expert layer, MTP
modules last) makes the layers use those experts in place of their own top-k: a near tie
between the k-th and the next score is decided by rounding, and a comparison of losses
holds the arithmetic to account only where both sides use the same experts; what was
chosen, and by what margin, comes back for a comparison of its own (`forward`'s third).
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _published_order(x):
    """Columns kept as (i, i + d/2) pairs -> the checkpoint's (2i, 2i + 1) order."""
    d = x.shape[-1]
    return jnp.stack([x[..., :d // 2], x[..., d // 2:]], axis=-1).reshape(x.shape)


def _rope_pairs(x, theta):
    """x [B, S, H, D]: the pairs (2i, 2i + 1) turned by position * theta^(-2i/D)."""
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape).astype(x.dtype)


def _attention(q, k, v):
    """Causal multi-head attention, q k v [B, S, H, D], QUERY_BLOCK queries at a time."""
    b, s, h, d = q.shape
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
    return out.swapaxes(0, 1).reshape(b, blocks * size, h, v.shape[-1])[:, :s]


def _mla(x, lp, model):
    eps, theta = model["norm_eps"], model["rope_theta"]
    nope, kvr = model["qk_nope_head_dim"], model["kv_lora_rank"]
    h = _rms_norm(x, lp["attn_norm"], eps)
    q = jnp.einsum("bsr,rhk->bshk", _rms_norm(h @ lp["wq_a"], lp["q_norm"], eps), lp["wq_b"])
    q = jnp.concatenate([q[..., :nope], _rope_pairs(_published_order(q[..., nope:]), theta)], -1)
    ckv = h @ lp["wkv_a"]
    k_rot = _rope_pairs(_published_order(ckv[:, :, None, kvr:]), theta)
    kv = jnp.einsum("bsr,rhk->bshk", _rms_norm(ckv[..., :kvr], lp["kv_norm"], eps), lp["wkv_b"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rot, (*kv.shape[:3], k_rot.shape[-1]))], -1)
    return x + jnp.einsum("bshk,hkd->bsd", _attention(q, k, kv[..., nope:]), lp["wo"])


def _mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_layer(x, lp, model, chosen=None):
    """x [B, S, D] (normed) -> (the shared experts' and the held routed experts' part of
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
    weight = jnp.sum(jax.nn.one_hot(chosen, n) * gates[..., None], axis=-2)  # [B, S, E]
    index, of = model["experts_held"]
    held = n // of

    @jax.checkpoint
    def one(out, e):  # one expert at a time, on every token, weighted (0 where not chosen)
        w_gate, w_up, w_down, wt = e
        return out + wt[..., None].astype(x.dtype) * _mlp(
            x, w_gate.astype(x.dtype), w_up.astype(x.dtype), w_down.astype(x.dtype)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lp["w_gate"], lp["w_up"], lp["w_down"],
        jnp.moveaxis(weight[..., index * held:(index + 1) * held], -1, 0)))
    if model.get("n_shared_experts"):
        out = out + _mlp(x, *(lp[name].astype(x.dtype)
                              for name in ("shared_gate", "shared_up", "shared_down")))
    return out, {"chosen": chosen, "own": own, "margin": margin}


def _layer(x, lp, model, dtype, chosen=None):
    """One block. lp: the layer's leaves as held (float32); everything but the routed
    experts, which are cast one at a time, is rounded to `dtype` here."""
    routed = {name: lp[name] for name in ("w_gate", "w_up", "w_down") if "router" in lp}
    lp = {**jax.tree.map(lambda a: a.astype(dtype), {n: a for n, a in lp.items() if n not in routed}),
          **routed}
    x = _mla(x, lp, model)
    h = _rms_norm(x, lp["mlp_norm"], model["norm_eps"])
    if "router" not in lp:
        return x + _mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None
    y, routing = expert_layer(h, lp, model, chosen)
    return x + y, routing


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

        def stack(x, layers):
            """x through a stack of like layers [n, ...]; the expert layers' routings are
            appended, each given its own selection (made over at least this many positions)."""
            n = layers["attn_norm"].shape[0]
            chosen = None
            if selection is not None and "router" in layers:
                chosen = jnp.stack([c[:, :x.shape[1]] for c in selection[len(routings):len(routings) + n]])
            x, routed = jax.lax.scan(
                jax.checkpoint(lambda x, xs: _layer(x, xs[0], model, dtype, xs[1])), x, (layers, chosen))
            if routed is not None:
                routings.extend(jax.tree.map(lambda a: a[i], routed) for i in range(n))
            return x

        x = embed[tokens]
        for name in ("dense_layers", "layers"):
            if name in params:
                x = stack(x, params[name])
        # logits are rounded to `dtype` before they are widened, as a decoder that
        # computes in `dtype` hands them over
        logits = (_rms_norm(x, cast(params["final_norm"]), eps) @ head).astype(jnp.float32)
        mtp = []
        for m in range(1, depth + 1):
            mp = jax.tree.map(lambda a: a[m - 1], params["mtp"])
            n = x.shape[1] - 1
            joined = jnp.concatenate([_rms_norm(embed[tokens[:, m:m + n]], cast(mp["embed_norm"]), eps),
                                      _rms_norm(x[:, :n], cast(mp["hidden_norm"]), eps)], -1)
            x = stack(joined @ cast(mp["eh_proj"]), jax.tree.map(lambda a: a[None], mp))
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
    mtp_loss_weight times the mean of the MTP modules' (no auxiliary loss: `noaux_tc`).
    parts=True: (loss, {"ce_loss", "mtp_loss", "position_losses": every head's joined
    along the positions, "routings"}), as `jax.value_and_grad(..., has_aux=True)` takes it."""
    main, mtp, routings = position_losses(params, tokens, model, dtype, selection)
    ce = total = main.mean()
    mtp_loss = sum(m.mean() for m in mtp) / len(mtp) if mtp else jnp.zeros(())
    if mtp:
        total = total + model["mtp_loss_weight"] * mtp_loss
    if not parts:
        return total
    return total, {"ce_loss": ce, "mtp_loss": mtp_loss, "routings": routings,
                   "position_losses": jnp.concatenate([main, *mtp], axis=1)}
