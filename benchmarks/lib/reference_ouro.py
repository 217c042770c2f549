"""The plain reference of the `ouro` family (Ouro-2.6B; "Scaling Latent Reasoning via Looped
Language Models", arXiv:2510.25741) under its training objective, the expected-exit loss with its
entropy term: forward pass, loss, gradients by `jax.grad(loss)`. Straightforward jax.numpy,
float32, matrix products at the highest precision; no kernel, no cache, no scan over layers: a
Python loop over the recurrences and, inside it, over the layers, attention a masked softmax a
block of query rows at a time, the head and its softmax a block of positions at a time.
`jax.checkpoint` around a layer, a block of queries and a block of the head says what the backward
pass keeps (their inputs) and changes no number.

The model. L layers, ONE set of weights, run T = `loop_steps` times (the published
`total_ut_steps`). A layer, with four RMSNorms N1..N4 of its own, D wide (eps `norm_eps`), no bias
anywhere:

    x <- x + N2(Attn(N1(x)))        Attn: q, k, v by three products (H heads each: as many
                                    key/value heads as query heads in the published model; fewer
                                    are repeated); q and k rotated (halves, theta `rope_theta`,
                                    the whole head width); causal softmax(q k^T / sqrt(head)) v;
                                    heads joined through W_o
    x <- x + N4(MLP(N3(x)))         MLP(u) = (silu(u W_gate) * (u W_up)) W_down

The loop: h_0 = E[tokens]; for t = 1..T: g_t = the L layers applied in order to h_{t-1}, n_t =
Norm_f(g_t) (one final norm, shared by the recurrences), h_t = n_t (ASSUMED: the normed output is
what the next recurrence starts from; the other reading, h_t = g_t, is the one line marked below).
Behind every recurrence: logits z_t = n_t W_head (an untied head) and, for t < T, the exit gate
lambda_t = sigmoid(w_e . n_t + b_e), one number a position (the leaf `exit_gate` is [w_e ; b_e],
D + 1 numbers). The exit distribution a position:

    p_t = lambda_t prod_{j<t} (1 - lambda_j)   for t < T,        p_T = prod_{j<T} (1 - lambda_j).

The objective (the paper's first stage, which trains gate and model together), with l_t the
next-token cross entropy of z_t a position and beta = `exit_entropy_weight`:

    loss = mean over positions of [ sum_t p_t l_t - beta H(p) ],      H(p) = -sum_t p_t log p_t.

`dtype=float32` is the reference. `dtype=bfloat16` is the same code with parameters and
activations rounded to bfloat16 and default matrix precision (norms' statistics, the softmaxes,
the exit gate's product and everything behind it stay float32): the yardstick of what bfloat16
costs at this depth, in whose multiples a tolerance is stated. `selection` is the signature every
family's reference has (benchmarks/FAMILIES.md): the family routes nothing, so it is not read and
`routings` comes back empty.
"""
import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
HEAD_BLOCK = 2048
FLOAT32_LEAVES = ("exit_gate",)  # the gate's product is float32 in every `dtype`


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


def _attention(q, k, v):
    """Causal attention, q [B, S, H, D], k and v [B, S, KV, D], QUERY_BLOCK query rows at a
    time against every key."""
    b, s, h, d = q.shape
    k, v = (jnp.repeat(m, h // m.shape[2], axis=2) for m in (k, v))
    size = min(QUERY_BLOCK, s)
    blocks = -(-s // size)

    @jax.checkpoint
    def some(start, qb):
        scores = jnp.einsum("bqhd,bphd->bhqp", qb, k,
                            preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(d))
        # (a query past the end, where the last block is not full, sees every key and is cut)
        seen = jnp.arange(s)[None, :] <= (start + jnp.arange(size))[:, None]
        probs = jax.nn.softmax(jnp.where(seen[None, None], scores, -jnp.inf), axis=-1)
        return jnp.einsum("bhqp,bphd->bqhd", probs.astype(v.dtype), v)

    padded = jnp.pad(q, ((0, 0), (0, blocks * size - s), (0, 0), (0, 0)))
    out = jax.lax.map(lambda a: some(*a), (jnp.arange(blocks) * size,
                                           padded.reshape(b, blocks, size, h, d).swapaxes(0, 1)))
    return out.swapaxes(0, 1).reshape(b, blocks * size, h, d)[:, :s]


def attention_part(x, lp, model):
    """x [B, S, D] -> attention's output, before its own norm and the residual."""
    u = _rms_norm(x, lp["attn_norm"], model["norm_eps"])
    q, k, v = (jnp.einsum("bsd,dhk->bshk", u, lp[name]) for name in ("wq", "wk", "wv"))
    out = _attention(_rope(q, model["rope_theta"]), _rope(k, model["rope_theta"]), v)
    return jnp.einsum("bshk,hkd->bsd", out, lp["wo"])


def mlp_part(x, lp, model):
    u = _rms_norm(x, lp["mlp_norm"], model["norm_eps"])
    return (jax.nn.silu(u @ lp["w_gate"]) * (u @ lp["w_up"])) @ lp["w_down"]


def layer(x, lp, model):
    """One layer: attention, then the MLP, each between a norm on its input and one on its output."""
    x = x + _rms_norm(attention_part(x, lp, model), lp["attn_post_norm"], model["norm_eps"])
    return x + _rms_norm(mlp_part(x, lp, model), lp["mlp_post_norm"], model["norm_eps"])


def recurrences(params, tokens, model, dtype):
    """tokens [B, S] -> [n_1 .. n_T], each [B, S, D] in `dtype`: the final norm's output of every
    recurrence of the L layers over the same weights."""
    layers = jax.tree.map(lambda a: a.astype(dtype), params["layers"])
    final_norm = params["final_norm"].astype(dtype)
    x = params["embed"].astype(dtype)[tokens]
    outs = []
    for _ in range(model["loop_steps"]):
        g = x
        for i in range(layers["wq"].shape[0]):
            lp = jax.tree.map(lambda a: a[i], layers)  # noqa: B023
            g = jax.checkpoint(lambda g, lp: layer(g, lp, model))(g, lp)
        n = _rms_norm(g, final_norm, model["norm_eps"])
        outs.append(n)
        x = n  # ASSUMED: h_t = n_t (the other reading: `x = g`)
    return outs


def _head_losses(hidden, head, targets):
    """hidden [B, S, D] behind the final norm -> the cross entropy of `targets` [B, S] a position,
    the head and its softmax HEAD_BLOCK positions at a time."""
    b, s, d = hidden.shape
    size = min(HEAD_BLOCK, s)
    blocks = -(-s // size)
    pad = blocks * size - s  # (positions past the end are cut)

    @jax.checkpoint
    def block(xs):
        h, t = xs
        # logits are rounded to `dtype` before they are widened, as a model that computes in
        # `dtype` hands them over
        logp = jax.nn.log_softmax((h @ head).astype(jnp.float32), axis=-1)
        return -jnp.take_along_axis(logp, t[..., None], axis=-1)[..., 0]

    losses = jax.lax.map(block, (
        jnp.pad(hidden, ((0, 0), (0, pad), (0, 0))).reshape(b, blocks, size, d).swapaxes(0, 1),
        jnp.pad(targets, ((0, 0), (0, pad))).reshape(b, blocks, size).swapaxes(0, 1)))
    return losses.swapaxes(0, 1).reshape(b, blocks * size)[:, :s]


def exit_distribution(gates):
    """lambda_1 .. lambda_{T-1} [T - 1, ...] -> p_1 .. p_T [T, ...], by the products as written."""
    stayed, p = jnp.ones_like(gates[0]), []
    for gate in gates:
        p.append(gate * stayed)
        stayed = stayed * (1.0 - gate)
    return jnp.stack(p + [stayed])


def position_losses(params, tokens, model: dict, dtype=jnp.float32, selection=None):
    """tokens [B, S + 1] -> (the LAST recurrence's next-token cross entropy a position [B, S], {"ce":
    every recurrence's [T, B, S], "gates": lambda_t [T - 1, B, S], "p": the exit distribution
    [T, B, S]}, [] (the family routes nothing))."""
    with jax.default_matmul_precision("highest" if dtype == jnp.float32 else "default"):
        outs = recurrences(params, tokens[:, :-1], model, dtype)
        head = params["lm_head"].astype(dtype)
        ce = jnp.stack([_head_losses(n, head, tokens[:, 1:]) for n in outs])
    with jax.default_matmul_precision("highest"):  # the gate is float32 in every dtype
        w_e, b_e = params["exit_gate"][:-1], params["exit_gate"][-1]  # one leaf: the weights, then the bias
        gates = jnp.stack([jax.nn.sigmoid(n.astype(jnp.float32) @ w_e + b_e)
                           for n in outs[:-1]])
    return ce[-1], {"ce": ce, "gates": gates, "p": exit_distribution(gates)}, []


def next_token_losses(params, tokens, model: dict, dtype=jnp.float32):
    """What a forward pass alone gives: the last recurrence's cross entropy a position [B, S]."""
    return position_losses(params, tokens, model, dtype)[0]


def loss(params, tokens, model: dict, dtype=jnp.float32, selection=None, parts=False):
    """The training loss of tokens [B, S + 1]: the mean over positions of the expected cross
    entropy under the exit distribution less `exit_entropy_weight` times its entropy. parts=True:
    (loss, {"ce_loss": the expectation alone, "exit_entropy", "exit_step_mean": the mean of sum_t
    t p_t, "ce_by_step" [T], "position_losses": the last recurrence's, "routings": []}), as
    `jax.value_and_grad(..., has_aux=True)` takes it."""
    last, every, routings = position_losses(params, tokens, model, dtype, selection)
    p, ce = every["p"], every["ce"]
    expected, entropy = (p * ce).sum(0).mean(), jax.scipy.special.entr(p).sum(0).mean()
    total = expected - model["exit_entropy_weight"] * entropy
    if not parts:
        return total
    steps = jnp.arange(1, p.shape[0] + 1, dtype=jnp.float32)[:, None, None]
    return total, {"ce_loss": expected, "exit_entropy": entropy, "exit_step_mean": (p * steps).sum(0).mean(),
                   "ce_by_step": ce.mean((1, 2)), "position_losses": last, "routings": routings}
