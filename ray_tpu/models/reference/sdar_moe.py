"""The plain reference of the `sdar_moe` family (SDAR-30B-A3B) under its training objective,
block diffusion: forward pass, loss, gradients by `jax.grad(loss)`. Straightforward
jax.numpy, float32, matrix products at the highest precision; no kernel, no cache:
attention is a masked softmax a block of query rows at a time with the mask's rule written
out, experts run one at a time, the head a block of positions at a time, and the batch a
sequence at a time (`lax.map`). `jax.checkpoint` around a sequence, a layer, a block of
queries, an expert and a block of the head says what the backward pass keeps (their inputs)
and changes no number.

The objective (BD3-LMs, arXiv:2503.09573, as SDAR trains it). With Bk = `diffusion_block`,
a sequence x0 of L tokens (L a multiple of Bk) and the batch's realised noise (`masked`
[B, L], drawn at the rate `p_mask` [B]): xt_i = `diffusion_mask_token` where masked_i, else
x0_i. The model runs ONE row u = [xt ; x0] of 2L ids at the positions [0..L-1 ; 0..L-1].
With half(r) = noised for r < L and clean otherwise, blk(r) = (r mod L) // Bk, key c is
kept for query r iff

    (half(c) = clean and blk(c) < blk(r))  or  (half(c) = half(r) and blk(c) = blk(r)):

a noised block sees the clean blocks before it and itself, both ways inside the block; a
clean block sees the clean blocks up to and including itself; no clean row sees a noised
one. The head reads the noised half, logits_i for i < L, and

    loss = (1 / (B L)) sum_b sum_i masked_bi CE(logits_bi, x0_bi) / p_mask_b:

each masked position predicts the token at its OWN position (no shift).

Every layer, with u = RMSNorm(x), D wide (eps `norm_eps`, no bias anywhere):

  attention  q, k, v by three products (H / KV / KV heads); q and k normed a head (RMSNorm
             over the head's width, one weight each) BEFORE the rotation; q and k rotated
             (halves, theta `rope_theta`) at the repeated positions;
             softmax(q k^T / sqrt(head)) v over the kept keys, H / KV query heads a
             key/value head; heads joined through W_o; x <- x + that.
  experts    s = softmax(u W_r) over ALL `n_experts` in float32; the k experts with the
             largest s; gates g = s_sel / sum s_sel;
             x <- x + sum g_e (silu(u W_gate^e) * (u W_up^e)) W_down^e.
             No shared expert, no selection bias, no auxiliary loss.

then a final RMSNorm and an untied head.

Departures from the family's published code, each of which changes no number: the mask is
built from row indices a block of queries at a time where the published code hands a
[2L, 2L] mask to one softmax; grouped-query heads are repeated, not grouped; the experts
run on every row, weighted by a gate that is zero where they were not chosen, where the
published code gathers; the loss is computed a block of positions at a time.

The share: the tree holds the experts and vocabulary rows of one chip; the counts are read
off the leaves. `model["experts_held"] = (index, of)` says which contiguous share of the
experts `w_gate` holds; the router scores all `n_experts`, and what the experts held
elsewhere would add is left out.

`dtype=float32` is the reference. `dtype=bfloat16` is the same code with parameters and
activations rounded to bfloat16 and default matrix precision (norms' statistics, the
router's products and the softmaxes stay float32): the yardstick of what bfloat16 costs at
this depth, in whose multiples a tolerance is stated. `selection` (a list, one [B, 2L, k]
int array a layer, over the doubled row) makes the layers use those experts in place of
their own top-k: a near tie between the k-th and the next score is decided by rounding, and
a comparison of losses holds the arithmetic to account only where both sides use the same
experts; what was chosen, and by what margin, comes back for a comparison of its own.
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
HEAD_BLOCK = 2048


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, positions, theta):
    """x [B, R, H, D] at `positions` [R]: the pairs (i, i + D/2) rotated, float32."""
    d = x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = positions.astype(jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def keep(r, c, half, block):
    """Whether query row r of the doubled row [noised ; clean] (`half` positions each) keeps
    key row c: the rule of the module's docstring, written out."""
    clean_r, clean_c = r >= half, c >= half
    blk_r, blk_c = (r % half) // block, (c % half) // block
    return (clean_c & (blk_c < blk_r)) | ((clean_c == clean_r) & (blk_c == blk_r))


def _attention(q, k, v, block):
    """Attention over the doubled row, q [B, 2L, H, D], k and v [B, 2L, KV, D], QUERY_BLOCK
    query rows at a time against every key, under `keep`."""
    b, rows, h, d = q.shape
    k, v = (jnp.repeat(m, h // m.shape[2], axis=2) for m in (k, v))
    size = min(QUERY_BLOCK, rows)
    blocks = -(-rows // size)

    @jax.checkpoint
    def some(start, qb):
        scores = jnp.einsum("bqhd,bphd->bhqp", qb, k,
                            preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(d))
        r, c = (start + jnp.arange(size))[:, None], jnp.arange(rows)[None, :]
        # (a query past the end, where the last block is not full, keeps key 0 and is cut)
        seen = keep(jnp.minimum(r, rows - 1), c, rows // 2, block)
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqp,bphd->bqhd", probs.astype(v.dtype), v)

    padded = jnp.pad(q, ((0, 0), (0, blocks * size - rows), (0, 0), (0, 0)))
    out = jax.lax.map(lambda a: some(*a), (jnp.arange(blocks) * size,
                                           padded.reshape(b, blocks, size, h, d).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, blocks * size, h, d)[:, :rows]


def attention_part(x, lp, model):
    """x [B, 2L, D], the doubled row -> attention's output (before the residual)."""
    half = x.shape[1] // 2
    positions = jnp.arange(x.shape[1]) % half
    u = _rms_norm(x, lp["attn_norm"], model["norm_eps"])
    q, k, v = (jnp.einsum("bsd,dhk->bshk", u, lp[name]) for name in ("wq", "wk", "wv"))
    q = _rms_norm(q, lp["q_head_norm"], model["norm_eps"])
    k = _rms_norm(k, lp["k_head_norm"], model["norm_eps"])
    q, k = _rope(q, positions, model["rope_theta"]), _rope(k, positions, model["rope_theta"])
    out = _attention(q, k, v, model["diffusion_block"])
    return jnp.einsum("bshk,hkd->bsd", out, lp["wo"])


def _mlp(x, w_gate, w_up, w_down):
    return (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down


def expert_layer(x, lp, model, chosen=None):
    """x [B, R, D] (normed) -> (the held routed experts' part of the layer, {"chosen":
    [B, R, k] as used, "own": the layer's own top-k, "margin": [B, R] how far its k-th
    score lies above the next})."""
    k, n = model["moe_top_k"], model["n_experts"]
    with jax.default_matmul_precision("highest"):  # the router is float32 in every dtype
        logits = x.astype(jnp.float32) @ lp["router"].astype(jnp.float32)
    scores = jax.nn.softmax(logits, axis=-1)
    top, own = jax.lax.top_k(scores, k + 1)
    own, margin = own[..., :k], top[..., k - 1] - top[..., k]
    chosen = own if chosen is None else chosen
    gates = jnp.take_along_axis(scores, chosen, axis=-1)
    gates = gates / gates.sum(-1, keepdims=True)
    index, of = model["experts_held"]
    held = n // of

    @jax.checkpoint
    def one(out, e):  # one expert at a time, on every row, weighted (0 where not chosen)
        w_gate, w_up, w_down, number = e
        weight = jnp.sum(jnp.where(chosen == number, gates, 0.0), axis=-1)
        return out + weight[..., None].astype(x.dtype) * _mlp(
            x, w_gate.astype(x.dtype), w_up.astype(x.dtype), w_down.astype(x.dtype)), None

    out, _ = jax.lax.scan(one, jnp.zeros_like(x), (
        lp["w_gate"], lp["w_up"], lp["w_down"], index * held + jnp.arange(held)))
    return out, {"chosen": chosen, "own": own, "margin": margin}


def _layer(x, lp, model, dtype, chosen=None):
    """One layer: attention, then the experts, each behind its norm and residual. lp: the
    leaves as held (float32); everything but the routed experts, which are cast one at a
    time, is rounded to `dtype` here."""
    held = ("w_gate", "w_up", "w_down")
    lp = {name: a if name in held else a.astype(dtype) for name, a in lp.items()}
    x = x + attention_part(x, lp, model)
    y, routing = expert_layer(_rms_norm(x, lp["mlp_norm"], model["norm_eps"]), lp, model, chosen)
    return x + y, routing


def doubled_row(batch, model):
    """The batch -> the ids of the row [xt ; x0], [B, 2L]."""
    tokens = batch["tokens"]
    noised = jnp.where(batch["masked"], jnp.asarray(model["diffusion_mask_token"], tokens.dtype), tokens)
    return jnp.concatenate([noised, tokens], axis=1)


def _hidden(params, batch, model, dtype, selection):
    """The batch -> (the noised half's last layer output behind the final norm [B, L, D] in
    `dtype`, [routing a layer, over all 2L rows]), one sequence at a time; of each only its
    ids and its selection are kept for the backward pass."""
    cast = lambda a: a.astype(dtype)  # noqa: E731
    layers = params["layers"]["router"].shape[0]

    @jax.checkpoint
    def one(row):
        ids, selection = row
        routings = []
        x = cast(params["embed"])[ids[None]]
        for i in range(layers):
            lp = jax.tree.map(lambda a: a[i], params["layers"])  # noqa: B023
            chosen = None if selection is None else selection[i][None]
            x, routed = jax.checkpoint(lambda x, lp, c: _layer(x, lp, model, dtype, c))(x, lp, chosen)
            routings.append(jax.tree.map(lambda a: a[0], routed))
        x = x[:, :ids.shape[0] // 2]  # the head reads the noised half
        return _rms_norm(x, cast(params["final_norm"]), model["norm_eps"])[0], routings

    return jax.lax.map(one, (doubled_row(batch, model), selection))


def position_losses(params, batch, model: dict, dtype=jnp.float32, selection=None):
    """The batch -> (the noised half's cross entropy against x0 at EVERY position [B, L],
    masked or not, [] (the family has no MTP module), routings): the head and its softmax
    HEAD_BLOCK positions at a time."""
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        hidden, routings = _hidden(params, batch, model, dtype, selection)
        head, targets = params["lm_head"].astype(dtype), batch["tokens"]
        b, s, d = hidden.shape
        size = min(HEAD_BLOCK, s)
        blocks = -(-s // size)
        pad = blocks * size - s  # (positions past the end are cut)

        @jax.checkpoint
        def block(xs):
            h, t = xs
            # logits are rounded to `dtype` before they are widened, as a model that
            # computes in `dtype` hands them over
            logp = jax.nn.log_softmax((h @ head).astype(jnp.float32), axis=-1)
            return -jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]

        losses = jax.lax.map(block, (
            jnp.pad(hidden, ((0, 0), (0, pad), (0, 0))).reshape(b, blocks, size, d).swapaxes(0, 1),
            jnp.pad(targets, ((0, 0), (0, pad))).reshape(b, blocks, size).swapaxes(0, 1)))
        return losses.swapaxes(0, 1).reshape(b, blocks * size)[:, :s], [], routings


def loss(params, batch, model: dict, dtype=jnp.float32, selection=None, parts=False):
    """The training loss of a batch {"tokens" [B, L], "masked" [B, L], "p_mask" [B]}: the
    masked positions' cross entropy, each weighted by 1 / p_mask, over B x L (no auxiliary
    loss). parts=True: (loss, {"ce_loss": the plain mean over the masked positions,
    "masked_tokens": their count, "position_losses", "routings"}), as
    `jax.value_and_grad(..., has_aux=True)` takes it."""
    every, _, routings = position_losses(params, batch, model, dtype, selection)
    masked = batch["masked"].astype(jnp.float32)
    total = (every * masked / batch["p_mask"].astype(jnp.float32)[:, None]).sum() / masked.size
    if not parts:
        return total
    count = masked.sum()
    return total, {"ce_loss": (every * masked).sum() / jnp.maximum(count, 1.0), "masked_tokens": count,
                   "routings": routings, "position_losses": every}
