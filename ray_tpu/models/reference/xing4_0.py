"""The plain reference of the `xing4_0` family (Xing4.0-29B-A4B): forward pass, loss, gradients by
`jax.grad(loss)`. Straightforward jax.numpy, float32, matrix products at the highest precision; no kernel,
no cache; experts one at a time and attention a block of queries at a time, so that 8,192 positions fit
beside a training state. The loops over a stack's layers, the blocks of queries and the experts held are
`lax.scan` / `lax.map` (one body compiled, not one a turn), and `jax.checkpoint` around a layer, a
hyper-connection's coefficients, a block of queries and an expert says what the backward pass keeps (their
inputs) and changes no number: the gradients of 656 M parameters at 8,192 positions fit on the chip that way.

The block is glm4_moe_lite's (pre-norm parts, RMSNorm, SiLU-gated MLPs, no biases, untied embedding and head):

  attention   c_q = RMSNorm(x W_qa); q = c_q W_qb -> heads of [nope ; rope];
              [c_kv ; k_r] = x W_kva, c_kv = RMSNorm(c_kv); [k_nope ; v] = c_kv W_kvb (v heads v_head_dim wide);
              q = [q_nope ; RoPE(q_rope)], k = [k_nope ; RoPE(k_r)] with k_r shared by all heads; causal
              softmax(scale q k^T) v; heads joined through W_o. RoPE turns the pairs (2i, 2i + 1).
  YaRN        (DeepSeek-V3's form, which the latent-attention keys inherit) on the rope slices, d wide: f_i =
              theta^(-2i/d), corr(b) = d ln(L / (2 pi b)) / (2 ln theta) with L = rope_original_len, low =
              floor(corr(beta_fast)), high = ceil(corr(beta_slow)), ramp_i = clip((i - low) / (high - low), 0, 1),
              inv_freq_i = f_i (1 - ramp_i) + (f_i / factor) ramp_i; cos and sin times mscale(factor, rope_mscale)
              / mscale(factor, rope_mscale_all_dim), mscale(s, m) = 1 + 0.1 m ln s; scale = (nope + rope)^(-1/2)
              mscale(factor, rope_mscale_all_dim)^2.
  dense layer the first `n_dense_layers`: an MLP of width d_ff.
  expert layer s = sigmoid(x W_r) in float32; the k experts with the largest s + b; gates
              g = route_scale * s_sel / (sum s_sel + 1e-20); y = E_shared(x) + sum g_e E_e(x).

What the family changes is the residual path. n = hc_mult, C = d_model; a token's stream is X in R^{n x C}, x =
vec(X) in R^{n C} (copy i the channels i C .. (i + 1) C - 1). The first layer's X is the embedding n times; behind
the last layer sum_i X[i] goes to the final norm and the head (Hyper-Connections, arXiv:2409.19606). Every part p
(a layer's attention, then its feed-forward part) owns phi_p [n C, 2n + n^2], a bias b_p and three scalars alpha_pre,
alpha_post, alpha_res (mHC, arXiv:2512.24880 section 4):

    r      = (mean(x^2) + norm_eps)^(-1/2)
    m      = r * (phi_p^T x)
    Hpre   = sigmoid(alpha_pre m[0:n] + b[0:n])
    Hpost  = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])
    M0     = exp(clip(alpha_res mat(m[2n:]) + mat(b[2n:]), -hc_res_clamp, hc_res_clamp))      row-major
    hc_sinkhorn_iters times:  M <- M / (column sums + hc_eps);  M <- M / (row sums + hc_eps);    Hres = M
    y      = sum_i Hpre[i] X[i];  o = F_p(y);  X'[i] = sum_j Hres[i, j] X[j] + Hpost[i] o

Assumed, where config.json is silent: hc_eps enters the denominators; the clamp is on the logits before exp; the
norm in front of phi has no weight (it folds into phi); the stream starts as n copies and ends as their sum. No
MTP module: the row does not say how one reads n streams, and the program refuses it too.

The share: `model["experts_held"] = (index, of)` says which contiguous share of the experts the tree holds
(`w_gate` has that many); the router scores all `n_experts`, and what the experts held elsewhere would add is left
out. The heads held are the tree's (`wq_b`, `wkv_b`, `wo` have that many; `wq_a`, `wkv_a` and their norms are whole).
A sliced vocabulary is a smaller one.

Departures for reading the program's tree: the program keeps the rotated columns in the order its rotation pairs
them, (i, i + d/2); `_published_order` puts them back before the pairs (2i, 2i + 1) are turned. A part's phi, b and
alphas are ONE leaf, `<part>_hc` [n C + 2, 2n + n^2] = [phi ; b ; alpha_pre alpha_post alpha_res 0 ..].

`dtype=float32` is the reference. `dtype=bfloat16` is the same code with parameters and activations rounded to
bfloat16 and default matrix precision (statistics, the router and a hyper-connection's coefficients from m on stay
float32, b and the alphas with them; phi is rounded where it is used): the yardstick of what bfloat16 costs at this depth, in whose multiples a tolerance is
stated. `selection` (a list, one [B, S, k] int array an expert layer) makes the layers use those experts in place of
their own top-k: a near tie between the k-th and the next score is decided by rounding, and a comparison of losses
holds the arithmetic to account only where both sides use the same experts; what was chosen, and by what margin,
comes back for a comparison of its own (`forward`'s third).
"""
import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
FLOAT32_LEAVES = ("attn_hc", "mlp_hc")  # handed on as held: b and the alphas are float32 in every dtype, phi is rounded at its use


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _published_order(x):
    """Columns kept as (i, i + d/2) pairs -> the checkpoint's (2i, 2i + 1) order."""
    d = x.shape[-1]
    return jnp.stack([x[..., :d // 2], x[..., d // 2:]], axis=-1).reshape(x.shape)


def _mscale(factor, mscale):
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def yarn_range(model, d):
    """(low, high): the pairs of a rotated slice d wide between which the frequencies are blended."""
    corr = lambda turns: d * math.log(model["rope_original_len"] / (2 * math.pi * turns)) / (2 * math.log(model["rope_theta"]))  # noqa: E731
    return max(math.floor(corr(model["rope_beta_fast"])), 0), min(math.ceil(corr(model["rope_beta_slow"])), d - 1)


def softmax_scale(model):
    d = model["qk_nope_head_dim"] + model["qk_rope_head_dim"]
    factor = model.get("rope_factor", 1.0)
    return d ** -0.5 * (_mscale(factor, model.get("rope_mscale_all_dim", 0.0)) ** 2 if factor != 1 else 1.0)


def _rope_pairs(x, model):
    """x [B, S, H, D]: the pairs (2i, 2i + 1) turned by position * inv_freq_i, YaRN's blend where rope_factor scales."""
    s, d = x.shape[1], x.shape[-1]
    i = jnp.arange(0, d // 2, dtype=jnp.float32)
    freqs, factor, by = model["rope_theta"] ** (-i / (d // 2)), model.get("rope_factor", 1.0), 1.0
    if factor != 1:
        low, high = yarn_range(model, d)
        ramp = jnp.clip((i - low) / max(high - low, 1e-3), 0, 1)
        freqs = freqs * (1 - ramp) + freqs / factor * ramp
        by = _mscale(factor, model.get("rope_mscale", 0.0)) / _mscale(factor, model.get("rope_mscale_all_dim", 0.0))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = by * jnp.cos(angles)[None, :, None, :], by * jnp.sin(angles)[None, :, None, :]
    pairs = x.astype(jnp.float32).reshape(*x.shape[:-1], d // 2, 2)
    a, b = pairs[..., 0], pairs[..., 1]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin], axis=-1).reshape(x.shape).astype(x.dtype)


def _attention(q, k, v, scale):
    """Causal multi-head attention, q k [B, S, H, D], v [B, S, H, Dv], QUERY_BLOCK queries at a time."""
    b, s, h, d = q.shape
    size = min(QUERY_BLOCK, s)
    blocks = -(-s // size)

    @jax.checkpoint
    def block(start, qb):
        scores = jnp.einsum("bqhd,bphd->bhqp", qb, k, preferred_element_type=jnp.float32) * scale
        seen = jnp.arange(s)[None, :] <= (start + jnp.arange(size))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqp,bphd->bqhd", probs.astype(v.dtype), v)

    # (queries past the end, where the last block is not full, see every key and are cut)
    padded = jnp.pad(q, ((0, 0), (0, blocks * size - s), (0, 0), (0, 0)))
    out = jax.lax.map(lambda a: block(*a), (jnp.arange(blocks) * size,
                                            padded.reshape(b, blocks, size, h, d).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, blocks * size, h, v.shape[-1])[:, :s]


def attention_part(y, lp, model):
    """Latent attention's output for the part's input y [B, S, C] (no residual: the hyper-connection writes it)."""
    eps, nope, kvr = model["norm_eps"], model["qk_nope_head_dim"], model["kv_lora_rank"]
    h = _rms_norm(y, lp["attn_norm"], eps)
    q = jnp.einsum("bsr,rhk->bshk", _rms_norm(h @ lp["wq_a"], lp["q_norm"], eps), lp["wq_b"])
    q = jnp.concatenate([q[..., :nope], _rope_pairs(_published_order(q[..., nope:]), model)], -1)
    ckv = h @ lp["wkv_a"]
    k_rot = _rope_pairs(_published_order(ckv[:, :, None, kvr:]), model)
    kv = jnp.einsum("bsr,rhk->bshk", _rms_norm(ckv[..., :kvr], lp["kv_norm"], eps), lp["wkv_b"])
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rot, (*kv.shape[:3], k_rot.shape[-1]))], -1)
    return jnp.einsum("bshk,hkd->bsd", _attention(q, k, kv[..., nope:], softmax_scale(model)), lp["wo"])


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


def sinkhorn(m, model):
    """M0 [.., n, n] (positive) -> Hres: hc_sinkhorn_iters rounds of columns, then rows."""
    for _ in range(model["hc_sinkhorn_iters"]):
        m = m / (m.sum(-2, keepdims=True) + model["hc_eps"])
        m = m / (m.sum(-1, keepdims=True) + model["hc_eps"])
    return m


def coefficients(x, hc, model):
    """x = vec(X) [B, S, n C] and a part's leaf [phi ; b ; alphas] -> (Hpre [B, S, n], Hpost [B, S, n], Hres [B, S, n, n]), float32."""
    n, clamp = model["hc_mult"], model["hc_res_clamp"]
    phi, b, alpha = hc[:-2].astype(x.dtype), hc[-2].astype(jnp.float32), hc[-1, :3].astype(jnp.float32)
    r = jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True) + model["norm_eps"])
    m = r * jnp.einsum("bsd,dk->bsk", x, phi, preferred_element_type=jnp.float32)
    pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + b[:n])
    post = 2 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + b[n:2 * n])
    logits = jnp.clip(alpha[2] * m[..., 2 * n:] + b[2 * n:], -clamp, clamp)
    return pre, post, sinkhorn(jnp.exp(logits).reshape(*logits.shape[:-1], n, n), model)


def hyper_connection(X, part, lp, name, model):
    """One part through its hyper-connection. X [B, S, n, C]; part(y) -> (o, what it reports); the leaf
    `<name>_hc`. Returns (X', the part's report)."""
    b, s, n, c = X.shape
    pre, post, res = jax.checkpoint(lambda x, hc: coefficients(x, hc, model))(X.reshape(b, s, n * c), lp[f"{name}_hc"])
    x32 = X.astype(jnp.float32)
    y = jnp.einsum("bsn,bsnc->bsc", pre, x32).astype(X.dtype)
    o, report = part(y)
    mixed = jnp.einsum("bsij,bsjc->bsic", res, x32) + post[..., None] * o.astype(jnp.float32)[:, :, None, :]
    return mixed.astype(X.dtype), report


def _layer(X, lp, model, dtype, chosen=None):
    """One block over the n streams X [B, S, n, C]. lp: the layer's leaves as held (float32); everything but the
    routed experts, which are cast one at a time, and the coefficients' own leaves is rounded to `dtype` here."""
    keep = {name: a for name, a in lp.items()
            if name in FLOAT32_LEAVES or ("router" in lp and name in ("w_gate", "w_up", "w_down"))}
    lp = {**jax.tree.map(lambda a: a.astype(dtype), {n: a for n, a in lp.items() if n not in keep}), **keep}
    X, _ = hyper_connection(X, lambda y: (attention_part(y, lp, model), None), lp, "attn", model)

    def feed_forward(y):
        h = _rms_norm(y, lp["mlp_norm"], model["norm_eps"])
        if "router" not in lp:
            return _mlp(h, lp["w_gate"], lp["w_up"], lp["w_down"]), None
        return expert_layer(h, lp, model, chosen)

    return hyper_connection(X, feed_forward, lp, "mlp", model)


def forward(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, S] -> (logits [B, S, vocab], [] (no MTP module), [routing an expert layer]), float32."""
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        eps, n = model["norm_eps"], model["hc_mult"]
        cast = lambda a: a.astype(dtype)  # noqa: E731
        embed, head = cast(params["embed"]), cast(params["lm_head"])
        selection = list(selection) if selection is not None else None
        routings = []

        def stack(X, layers):
            """X through a stack of like layers [layers, ...]; the expert layers' routings are
            appended, each given its own selection (made over at least this many positions)."""
            count = layers["attn_norm"].shape[0]
            chosen = None
            if selection is not None and "router" in layers:
                chosen = jnp.stack([c[:, :X.shape[1]] for c in selection[len(routings):len(routings) + count]])
            X, routed = jax.lax.scan(
                jax.checkpoint(lambda X, xs: _layer(X, xs[0], model, dtype, xs[1])), X, (layers, chosen))
            if routed is not None:
                routings.extend(jax.tree.map(lambda a: a[i], routed) for i in range(count))
            return X

        e = embed[tokens]
        X = jnp.broadcast_to(e[:, :, None, :], (*e.shape[:2], n, e.shape[-1]))  # every copy the embedding
        for name in ("dense_layers", "layers"):
            if name in params:
                X = stack(X, params[name])
        x = X.astype(jnp.float32).sum(2).astype(dtype)  # the head reads the sum of the copies
        # logits are rounded to `dtype` before they are widened, as a decoder that
        # computes in `dtype` hands them over
        logits = (_rms_norm(x, cast(params["final_norm"]), eps) @ head).astype(jnp.float32)
        return logits, [], routings


def _losses(logits, targets):
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]


def position_losses(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, T] -> (next-token losses [B, T - 1], [] (no MTP module), routings): one number a position."""
    logits, _, routings = forward(params, tokens[:, :-1], model, dtype, selection)
    return _losses(logits, tokens[:, 1:]), [], routings


def next_token_losses(params, tokens, model: dict, dtype=jnp.float32):
    return position_losses(params, tokens, model, dtype)[0]


def loss(params, tokens, model: dict, dtype=jnp.float32, selection=None, parts=False):
    """The training loss of tokens [B, T]: mean next-token cross entropy (no auxiliary loss: `noaux_tc`; no MTP
    module). parts=True: (loss, {"ce_loss", "mtp_loss" (zero), "position_losses", "routings"}), as
    `jax.value_and_grad(..., has_aux=True)` takes it."""
    main, _, routings = position_losses(params, tokens, model, dtype, selection)
    total = main.mean()
    if not parts:
        return total
    return total, {"ce_loss": total, "mtp_loss": jnp.zeros(()), "routings": routings, "position_losses": main}
