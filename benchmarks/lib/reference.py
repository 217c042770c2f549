"""The plain reference: the decoder's forward pass and next-token loss in
straightforward jax.numpy, float32, matrix multiplications at the highest
precision, no kernel, no remat, no cache, no batching tricks.

RMSNorm, rotate-half RoPE (the Hugging Face convention), grouped-query causal
attention, SwiGLU; for a mixture of experts, softmax over all experts, top-k,
renormalised over the chosen k, every token served by all its k experts (no
capacity, no drop). It reads the program's parameter tree (the names
`models/llama.py` gives its leaves, layers stacked on the first axis) and
nothing else of the program.

`dtype=float32` is the reference. `dtype=bfloat16` is the same plain code with
parameters and activations rounded to bfloat16 and default matrix precision:
it is the yardstick's own measure of what bfloat16 costs at this depth and
these sequences, so a tolerance is stated as a multiple of it and needs no
number tuned to a depth (see drivers/train.py).
"""
import jax
import jax.numpy as jnp


def _rms_norm(x, scale, eps):
    # statistics in float32 whatever the type of the activations, as any
    # mixed-precision decoder keeps them
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(jnp.square(x32), axis=-1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    # x [B, S, H, D]
    s, d = x.shape[1], x.shape[-1]
    freqs = theta ** (-jnp.arange(0, d // 2, dtype=jnp.float32) / (d // 2))
    angles = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs[None, :]
    cos, sin = jnp.cos(angles)[None, :, None, :], jnp.sin(angles)[None, :, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1).astype(x.dtype)


def _attention(q, k, v):
    # q [B,S,H,D], k/v [B,S,KV,D]: each group of H/KV query heads shares one KV head
    b, s, h, d = q.shape
    kv = k.shape[2]
    q = q.reshape(b, s, kv, h // kv, d)
    scores = jnp.einsum("bqkgd,bpkd->bkgqp", q, k,
                        preferred_element_type=jnp.float32) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal[None, None, None], scores, -jnp.inf)
    probs = jax.nn.softmax(scores, axis=-1).astype(v.dtype)
    return jnp.einsum("bkgqp,bpkd->bqkgd", probs, v).reshape(b, s, h, d)


def _dense_mlp(x, lp):
    return (jax.nn.silu(x @ lp["w_gate"]) * (x @ lp["w_up"])) @ lp["w_down"]


def _moe_mlp(x, lp, top_k):
    # the router decides in float32 whatever the type of the rest
    probs = jax.nn.softmax(x.astype(jnp.float32) @ lp["router"].astype(jnp.float32), axis=-1)
    vals, idx = jax.lax.top_k(probs, top_k)                    # [B,S,k]
    vals = vals / vals.sum(-1, keepdims=True)
    n_experts = lp["router"].shape[-1]
    weight = jnp.sum(jax.nn.one_hot(idx, n_experts) * vals[..., None], axis=-2)  # [B,S,E]
    out = jnp.zeros_like(x)
    for e in range(n_experts):  # every expert on every token, weighted: plain and exact
        w_gate, w_up, w_down = (lp[k][e].astype(x.dtype) for k in ("w_gate", "w_up", "w_down"))
        y = (jax.nn.silu(x @ w_gate) * (x @ w_up)) @ w_down
        out = out + weight[..., e:e + 1].astype(x.dtype) * y
    return out


def forward(params, tokens, model: dict, dtype=jnp.float32):
    """tokens [B, S] -> logits [B, S, vocab], float32."""
    precision = "highest" if dtype == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        eps, theta = model["norm_eps"], model["rope_theta"]
        cast = lambda a: a.astype(dtype)  # noqa: E731
        x = cast(params["embed"])[tokens]
        for i in range(model["n_layers"]):
            # one layer at a time, and one expert at a time inside _moe_mlp: a
            # float32 copy of all the weights would not fit beside a served model
            lp = jax.tree.map(lambda a: a[i], params["layers"])
            experts = {k: lp.pop(k) for k in ("w_gate", "w_up", "w_down")} \
                if model.get("n_experts", 0) else {}
            lp = dict(jax.tree.map(cast, lp), **experts)
            h = _rms_norm(x, lp["attn_norm"], eps)
            q = _rope(jnp.einsum("bsd,dhk->bshk", h, lp["wq"]), theta)
            k = _rope(jnp.einsum("bsd,dhk->bshk", h, lp["wk"]), theta)
            v = jnp.einsum("bsd,dhk->bshk", h, lp["wv"])
            x = x + jnp.einsum("bshk,hkd->bsd", _attention(q, k, v), lp["wo"])
            h = _rms_norm(x, lp["mlp_norm"], eps)
            x = x + (_moe_mlp(h, lp, model["moe_top_k"]) if experts else _dense_mlp(h, lp))
        x = _rms_norm(x, cast(params["final_norm"]), eps)
        head = params["embed"].T if model.get("tie_embeddings") else params["lm_head"]
        # logits are rounded to `dtype` before they are widened, as a decoder that
        # computes in `dtype` hands them over
        return (x @ cast(head)).astype(jnp.float32)


def next_token_losses(params, tokens, model: dict, dtype=jnp.float32):
    """Cross entropy of each of tokens[:, 1:] under the logits of
    tokens[:, :-1]: [B, S-1], float32, one number a position."""
    logits = forward(params, tokens[:, :-1], model, dtype)
    logp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(logp, tokens[:, 1:, None], axis=-1)[..., 0]
