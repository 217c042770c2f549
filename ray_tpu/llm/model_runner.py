"""Jitted prefill/decode over a slot-based device-resident KV cache.

This is the TPU replacement for vLLM's GPU model runner (reference
python/ray/llm/_internal/serve/deployments/llm/vllm/vllm_engine.py:180): instead
of paged attention over dynamically allocated GPU blocks, the cache is a static
[L, slots, max_len, kv_heads, head_dim] array — XLA-friendly static shapes, with
raggedness expressed as a per-slot ``lengths`` vector that masks attention and
indexes scatter-writes. Slots are the continuous-batching unit: prefill fills one
slot, decode advances all slots in a single fused step.

Sharding: params via INFER_RULES (heads/mlp/vocab → tp), cache kv_heads → tp and
slots → dp, so TP rides ICI inside each decode step and DP widens throughput.
"""
from __future__ import annotations

import functools
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ray_tpu.models import llama
from ray_tpu.models.config import ModelConfig
from ray_tpu.parallel.sharding import INFER_RULES, named_sharding, shard_pytree

from . import sampling


class DecodeState(NamedTuple):
    """Device-resident serving state. lengths[s] = tokens currently cached in slot s."""

    k: jax.Array  # [L, slots, max_len, kv_heads, head_dim]
    v: jax.Array
    lengths: jax.Array  # [slots] int32


CACHE_SPEC = P(None, "dp", None, "tp", None)
# pipelined engines: each pp stage holds its layers' cache slice; slots still
# shard over dp replicas (pp x dp composition)
CACHE_SPEC_PP = P("pp", "dp", None, "tp", None)
LENGTHS_SPEC = P("dp")


def _present(mesh: Mesh, spec: P) -> P:
    """Drop axis names the mesh doesn't have (engine-built meshes carry all of
    pp/dp/ep/tp; user-supplied meshes may name only a subset)."""
    return P(*((ax if ax in mesh.shape else None) for ax in spec))


def init_state(cfg: ModelConfig, slots: int, max_len: int, mesh: Mesh) -> DecodeState:
    shape = (cfg.n_layers, slots, max_len, cfg.n_kv_heads, cfg.head_dim)
    dtype = cfg.activation_dtype
    pp = "pp" in mesh.shape and mesh.shape["pp"] > 1
    kv_sh = NamedSharding(mesh, _present(mesh, CACHE_SPEC_PP if pp else CACHE_SPEC))
    len_sh = NamedSharding(mesh, _present(mesh, LENGTHS_SPEC))
    return DecodeState(
        k=jax.device_put(jnp.zeros(shape, dtype), kv_sh),
        v=jax.device_put(jnp.zeros(shape, dtype), kv_sh),
        lengths=jax.device_put(jnp.zeros((slots,), jnp.int32), len_sh),
    )


def infer_rules_for_mesh(mesh: Mesh):
    """INFER_RULES, plus the scanned layer axis over "pp" when the mesh has it."""
    from ray_tpu.parallel.sharding import AxisRules

    if "pp" in mesh.shape and mesh.shape["pp"] > 1:
        return AxisRules({**INFER_RULES.rules, "layer": "pp"})
    return INFER_RULES


def shard_params(params, cfg: ModelConfig, mesh: Mesh):
    return shard_pytree(params, llama.param_axes(cfg), mesh, infer_rules_for_mesh(mesh))


# ------------------------------------------------------------------------- prefill

@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def prefill(
    params,
    state: DecodeState,
    tokens: jax.Array,  # [1, S_pad] int32 (padded to a bucket length)
    true_len: jax.Array,  # scalar int32
    slot: jax.Array,  # scalar int32
    cfg: ModelConfig,
) -> Tuple[DecodeState, jax.Array]:
    """Run the prompt through the model, install its KV into `slot`, return the
    logits at the last real token ([vocab] f32)."""
    s_pad = tokens.shape[1]
    tmp = llama.init_kv_cache(cfg, batch=1, max_len=s_pad, dtype=state.k.dtype)
    # pad positions beyond the real prompt must not claim MoE expert capacity
    token_mask = (jnp.arange(s_pad)[None, :] < true_len).astype(jnp.float32)
    logits, tmp, _ = llama.forward(params, tokens, cfg, cache=tmp,
                                   token_mask=token_mask, return_aux=True)
    # install [L, 1, S_pad, KV, HD] into the big cache at (slot, 0)
    start = (0, slot, 0, 0, 0)
    k = jax.lax.dynamic_update_slice(state.k, tmp.k, start)
    v = jax.lax.dynamic_update_slice(state.v, tmp.v, start)
    lengths = state.lengths.at[slot].set(true_len)
    last = logits[0, true_len - 1].astype(jnp.float32)
    return DecodeState(k=k, v=v, lengths=lengths), last


@functools.partial(jax.jit, static_argnames=("cfg",))
def prefill_detached(
    params,
    tokens: jax.Array,  # [1, S_pad]
    true_len: jax.Array,  # scalar int32
    cfg: ModelConfig,
):
    """Prefill WITHOUT installing into a decode state: returns (k, v, last_logits)
    with k/v [L, 1, S_pad, KV, HD]. The P/D-disaggregated serving path runs this on
    a prefill replica; the KV then travels (host/DCN) to a decode replica which
    installs it via install_kv (reference: prefill_decode_disagg deployments)."""
    s_pad = tokens.shape[1]
    tmp = llama.init_kv_cache(cfg, batch=1, max_len=s_pad, dtype=cfg.activation_dtype)
    token_mask = (jnp.arange(s_pad)[None, :] < true_len).astype(jnp.float32)
    logits, tmp, _ = llama.forward(params, tokens, cfg, cache=tmp,
                                   token_mask=token_mask, return_aux=True)
    last = logits[0, true_len - 1].astype(jnp.float32)
    return tmp.k, tmp.v, last


@functools.partial(jax.jit, donate_argnames=("state",))
def install_kv(
    state: DecodeState,
    k: jax.Array,  # [L, 1, S_pad, KV, HD]
    v: jax.Array,
    true_len: jax.Array,  # scalar int32
    slot: jax.Array,  # scalar int32
) -> DecodeState:
    """Install transferred prefill KV into a decode slot."""
    start = (0, slot, 0, 0, 0)
    nk = jax.lax.dynamic_update_slice(state.k, k.astype(state.k.dtype), start)
    nv = jax.lax.dynamic_update_slice(state.v, v.astype(state.v.dtype), start)
    lengths = state.lengths.at[slot].set(true_len)
    return DecodeState(k=nk, v=nv, lengths=lengths)


# -------------------------------------------------------------------------- decode

def _window_core(x, lp, cfg: ModelConfig, lengths, active, cache_rw):
    """One layer over a W-token window for every slot, shared by every cache
    layout: the block's parts (models/llama.py) around the serving attention.
    Decode is a window of one; speculative verify is [last token, drafts].

    x [S,W,D]; K/V written at positions lengths[s]+0..W-1 through the layout
    adapter; each query w attends to cache positions <= lengths[s]+w (causal
    within the window, full history before it) — per-slot lengths, which
    ops.attention's scalar q_offset / kv_valid_len cannot say.

    cache_rw(k_new [S,W,KV,HD], v_new, pos [S,W]) -> (ck [S,max_len,KV,HD],
    cv, storage): the adapter writes the window's K/V into its layout at the
    absolute positions `pos` and returns per-slot full-history views for
    attention plus the updated storage, which is threaded back to the caller
    untouched. active [S] bool: inactive slots compute but must not claim MoE
    expert capacity.
    """
    dt = x.dtype
    s, wlen, _ = x.shape
    kvh, hd = cfg.n_kv_heads, cfg.head_dim
    g = cfg.n_heads // kvh
    pos = lengths[:, None] + jnp.arange(wlen)[None, :]  # [S,W]

    q, k, v = llama.qkv_proj(x, lp, cfg, pos)
    ck, cv, storage = cache_rw(k, v, pos)
    max_len = ck.shape[1]

    qg = q.reshape(s, wlen, kvh, g, hd) * (hd**-0.5)
    scores = jnp.einsum("swkgd,stkd->swkgt", qg.astype(jnp.float32),
                        ck.astype(jnp.float32))
    valid = (jnp.arange(max_len)[None, None, :] <= pos[:, :, None])  # [S,W,T]
    scores = jnp.where(valid[:, :, None, None, :], scores, sampling.NEG_INF)
    w = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("swkgt,stkd->swkgd", w, cv.astype(jnp.float32)).astype(dt)
    x = llama.attn_out(x, o.reshape(s, wlen, cfg.n_heads, hd), lp)

    token_mask = jnp.broadcast_to(active[:, None], (s, wlen)).astype(jnp.float32)
    x, _ = llama.feed_forward(x, lp, cfg, token_mask)
    return x, storage


def _slot_block(x, lp, cfg: ModelConfig, ck, cv, lengths, active):
    """One layer's window against the slot cache. x [S,W,D]; ck/cv
    [S,max_len,KV,HD]; K/V scattered in at absolute positions lengths[s]+w
    (writes past max_len dropped)."""
    rows = jnp.arange(x.shape[0])[:, None]

    def cache_rw(k_new, v_new, pos):
        nk = ck.at[rows, pos].set(k_new.astype(ck.dtype), mode="drop")
        nv = cv.at[rows, pos].set(v_new.astype(cv.dtype), mode="drop")
        return nk, nv, (nk, nv)

    x, (nk, nv) = _window_core(x, lp, cfg, lengths, active, cache_rw)
    return x, nk, nv


def _layer_loop(layer_fn, x, layers, k, v):
    """The serving programs' layer loop: layer_fn(h, lp, k_l, v_l) -> (h, k_l,
    v_l) over the stacked layers and their K/V storage (slot cache or block
    pool). Returns (x, new k, new v)."""

    def body(h, xs):
        lp, k_l, v_l = xs
        h, k_l, v_l = layer_fn(h, lp, k_l, v_l)
        return h, (k_l, v_l)

    x, (nk, nv) = jax.lax.scan(body, x, (layers, k, v))
    return x, nk, nv


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def decode_step(
    params,
    state: DecodeState,
    tokens: jax.Array,  # [slots] int32 — last sampled token per slot
    active: jax.Array,  # [slots] bool — inactive slots compute but don't advance
    cfg: ModelConfig,
) -> Tuple[DecodeState, jax.Array]:
    """One decode step for every slot. Returns (state, logits [slots, vocab] f32).

    Inactive slots still flow through the matmuls (static shapes) but their cache
    write lands at position lengths[s] of a slot whose contents the next prefill
    overwrites, and their length does not advance.
    """
    x = llama.embed_tokens(params, tokens[:, None], cfg)  # [S,1,D]
    x, nk, nv = _layer_loop(
        lambda h, lp, ck, cv: _slot_block(h, lp, cfg, ck, cv, state.lengths, active),
        x, params["layers"], state.k, state.v)
    logits = llama.output_head(params, x, cfg)[:, 0]
    lengths = jnp.where(active, state.lengths + 1, state.lengths)
    return DecodeState(k=nk, v=nv, lengths=lengths), logits


def spec_accept(window, greedy, draft_len, active, lengths, rng, temperature,
                top_p, top_k, logits0):
    """Shared accept logic: longest draft prefix matching argmax, +1 bonus;
    temperature>0 slots (no drafts) get a properly SAMPLED first token."""
    tok0 = sampling.sample(rng, logits0, temperature, top_p, top_k)
    greedy = greedy.at[:, 0].set(jnp.where(temperature > 0, tok0, greedy[:, 0]))
    wlen = window.shape[1]
    draft = window[:, 1:]
    idx = jnp.arange(wlen - 1)[None, :]
    match = (draft == greedy[:, :-1]) & (idx < draft_len[:, None])
    n_acc = jnp.sum(jnp.cumprod(match.astype(jnp.int32), axis=1), axis=1)
    advance = jnp.where(active, n_acc + 1, 0)
    return greedy, n_acc, lengths + advance


def spec_driver(params, k0, v0, lengths, window, draft_len, active, cfg,
                rng, temperature, top_p, top_k, layer_fn=None,
                layers_pass=None):
    """Shared speculative-verify pipeline (embed -> layers -> norm -> head ->
    accept); the cache layout differs only in layer_fn(h, lp, k, v). MoE models
    verify too: _window_core routes the whole window through moe_mlp with
    inactive slots masked out of expert capacity. `layers_pass(x) -> (x, nk,
    nv)` replaces the whole layer loop (the pp schedule owns its own loop)."""
    x = llama.embed_tokens(params, window, cfg)
    if layers_pass is not None:
        x, nk, nv = layers_pass(x)
    else:
        x, nk, nv = _layer_loop(layer_fn, x, params["layers"], k0, v0)
    logits = llama.output_head(params, x, cfg)
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    greedy, n_acc, new_lengths = spec_accept(
        window, greedy, draft_len, active, lengths, rng, temperature,
        top_p, top_k, logits[:, 0])
    return nk, nv, new_lengths, greedy, n_acc


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def spec_verify_step(
    params,
    state: DecodeState,
    window: jax.Array,  # [S,W] int32 — [last_token, draft_1..draft_k] (0-padded)
    draft_len: jax.Array,  # [S] int32 — valid drafts per slot (<= W-1)
    active: jax.Array,  # [S] bool
    cfg: ModelConfig,
    rng: jax.Array,
    temperature: jax.Array,  # [S] f32
    top_p: jax.Array,  # [S] f32
    top_k: jax.Array,  # [S] i32
) -> Tuple[DecodeState, jax.Array, jax.Array]:
    """Speculative verify (reference: vLLM ngram/prompt-lookup spec decoding):
    ONE forward over the W-token window scores every draft; greedy
    accept = longest prefix where draft[i] == argmax(logits[i-1]).

    Returns (state, out_tokens [S,W], n_accepted [S]): out_tokens[s,:n+1] are
    this step's emitted tokens (n accepted drafts + 1 bonus/correction);
    lengths advance by n+1 for active slots."""
    nk, nv, lengths, greedy, n_acc = spec_driver(
        params, state.k, state.v, state.lengths, window, draft_len, active,
        cfg, rng, temperature, top_p, top_k,
        lambda h, lp, ck, cv: _slot_block(h, lp, cfg, ck, cv, state.lengths,
                                          active))
    return DecodeState(k=nk, v=nv, lengths=lengths), greedy, n_acc


def spec_verify_step_pp(params, state: DecodeState, window, draft_len, active,
                        rng, temperature, top_p, top_k, *,
                        cfg: ModelConfig, mesh: Mesh):
    """Speculative verify through the pipeline schedule (slot layout): same
    tick structure as decode_step_pp but the microbatch payload is the whole
    [smb, W, D] verify window. Slots shard over dp replicas, layers + cache
    over pp stages; bubble-tick cache writes are discarded with the same
    valid-mask select the decode schedule uses. The accept logic is
    spec_driver's, via its layers_pass seam."""
    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    s, w = window.shape
    if s % (pp * dp):
        raise ValueError(f"max_num_seqs {s} must be divisible by pp*dp {pp * dp}")

    def layers_pass(x):  # [S, W, D]
        return _pp_slot_layers(
            params, state.k, state.v, x, state.lengths, active, mesh, width=w,
            block_fn=lambda c, lp, ck, cv, ln, ac:
                _slot_block(c, lp, cfg, ck, cv, ln, ac))

    nk, nv, lengths, greedy, n_acc = spec_driver(
        params, state.k, state.v, state.lengths, window, draft_len, active,
        cfg, rng, temperature, top_p, top_k, layers_pass=layers_pass)
    return DecodeState(k=nk, v=nv, lengths=lengths), greedy, n_acc


def propose_ngram_device(hist: jax.Array, hlen: jax.Array, last: jax.Array,
                         k: int, nmax: int) -> Tuple[jax.Array, jax.Array]:
    """On-device prompt-lookup proposal (the host-side _propose_ngram, jittable
    so it can run INSIDE a fused burst): for each slot, find the most recent
    earlier occurrence of the trailing n-gram (longest n <= nmax first) in the
    slot's token history and propose the k tokens that followed it.

    hist [S,L] int32 (prompt + emitted tokens), hlen [S] valid length,
    last [S] == hist[hlen-1]. Returns (window [S,k+1], draft_len [S])."""
    s_n, L = hist.shape
    best_start = jnp.zeros((s_n,), jnp.int32)
    best_n = jnp.zeros((s_n,), jnp.int32)
    for n in range(nmax, 0, -1):  # static unroll: longest n wins
        tail = jax.vmap(
            lambda h, e: jax.lax.dynamic_slice(h, (jnp.maximum(e - n, 0),), (n,))
        )(hist, hlen)  # [S, n]
        eq = jnp.ones((s_n, L - n), bool)
        for i in range(n):
            eq &= hist[:, i:L - n + i] == tail[:, i:i + 1]
        j = jnp.arange(L - n)[None, :]
        eq &= j < (hlen - n)[:, None]  # strictly before the tail's own start
        start = jnp.max(jnp.where(eq, j, -1), axis=1)  # most recent occurrence
        # a match whose continuation is empty (occurrence butts against the
        # tail) is useless — fall through to a shorter n, like the host
        # proposer's `if cont:` retry
        found = eq.any(axis=1) & (hlen - (start + n) > 0)
        pick = found & (best_n == 0)
        best_start = jnp.where(pick, start.astype(jnp.int32), best_start)
        best_n = jnp.where(pick, n, best_n)
    cont = jnp.minimum(best_start + best_n, L - k)  # continuation start, clamped
    drafts = jax.vmap(
        lambda h, s: jax.lax.dynamic_slice(h, (s,), (k,)))(hist, cont)  # [S,k]
    avail = jnp.clip(hlen - (best_start + best_n), 0, k)
    draft_len = jnp.where(best_n > 0, avail, 0).astype(jnp.int32)
    keep = jnp.arange(k)[None, :] < draft_len[:, None]
    window = jnp.zeros((s_n, k + 1), jnp.int32)
    window = window.at[:, 0].set(last)
    window = window.at[:, 1:].set(jnp.where(keep, drafts, 0))
    return window, draft_len


def spec_multi_impl(params, state, hist, hlen, active, cfg, rngs, temperature,
                    top_p, top_k, m, k, nmax, proposer, layer_fn_for,
                    advance_state):
    """Layout-generic fused speculation: m propose->verify->accept windows
    chained in one lax.scan. The cache layout differs only in
    layer_fn_for(state) (the verify layer adapter) and
    advance_state(state, nk, nv, lengths) (how the storage threads forward)."""

    def body(carry, rng):
        st, h, hl, last = carry
        window, draft_len = proposer(h, hl, last, k, nmax)
        draft_len = jnp.where(temperature > 0, 0, draft_len)
        nk, nv, lengths, greedy, n_acc = spec_driver(
            params, st.k, st.v, st.lengths, window, draft_len, active,
            cfg, rng, temperature, top_p, top_k, layer_fn_for(st))
        st = advance_state(st, nk, nv, lengths)
        adv = jnp.where(active, n_acc + 1, 0)
        rows = jnp.arange(h.shape[0])
        for t in range(k + 1):  # static: scatter this window's emitted tokens
            pos = jnp.clip(hl + t, 0, h.shape[1] - 1)
            h = h.at[rows, pos].set(
                jnp.where(t < adv, greedy[:, t], h[rows, pos]))
        new_last = jnp.where(
            adv > 0,
            jnp.take_along_axis(
                greedy, jnp.maximum(adv - 1, 0)[:, None], axis=1)[:, 0],
            last)
        return (st, h, hl + adv, new_last), (greedy, n_acc, draft_len)

    last = jnp.take_along_axis(
        hist, jnp.maximum(hlen - 1, 0)[:, None], axis=1)[:, 0]
    (state, _, _, _), (toks_m, acc_m, drafted_m) = jax.lax.scan(
        body, (state, hist, hlen, last), rngs)
    return state, toks_m, acc_m, drafted_m


@functools.partial(
    jax.jit, static_argnames=("cfg", "m", "k", "nmax", "propose_fn"),
    donate_argnames=("state",))
def spec_multi(
    params,
    state: DecodeState,
    hist: jax.Array,  # [S, max_len] int32 — prompt + emitted tokens per slot
    hlen: jax.Array,  # [S] int32 — valid history length
    active: jax.Array,  # [S] bool — FIXED for the whole burst
    cfg: ModelConfig,
    rngs: jax.Array,  # [m] stacked PRNG keys
    temperature: jax.Array,
    top_p: jax.Array,
    top_k: jax.Array,
    m: int,
    k: int,
    nmax: int,
    propose_fn=None,  # test seam: (hist, hlen, last, k, nmax) -> (window, dlen)
):
    """m fused speculative windows per host sync: propose (on-device n-gram
    lookup) -> verify forward -> accept, chained in a lax.scan — composing
    vLLM's multi-step scheduling with prompt-lookup speculation. Per sync the
    engine emits between m and m*(k+1) tokens. Greedy slots speculate;
    temperature>0 slots ride along sampling one token per window.

    Returns (state, toks_m [m,S,k+1], acc_m [m,S], drafted_m [m,S])."""
    return spec_multi_impl(
        params, state, hist, hlen, active, cfg, rngs, temperature, top_p,
        top_k, m, k, nmax, propose_fn or propose_ngram_device,
        lambda st: lambda x, lp, ck, cv: _slot_block(
            x, lp, cfg, ck, cv, st.lengths, active),
        lambda st, nk, nv, lengths: DecodeState(k=nk, v=nv, lengths=lengths))


@functools.partial(jax.jit, static_argnames=("cfg",), donate_argnames=("state",))
def decode_multi(
    params,
    state: DecodeState,
    tokens: jax.Array,  # [slots] int32 — last sampled token per slot
    active: jax.Array,  # [slots] bool — FIXED for the whole burst
    cfg: ModelConfig,
    rngs: jax.Array,  # [K] stacked PRNG keys, one per step
    temperature: jax.Array,  # [slots] f32
    top_p: jax.Array,  # [slots] f32
    top_k: jax.Array,  # [slots] i32
    steps_left: jax.Array,  # [slots] int32 — per-slot step budget within K
) -> Tuple[DecodeState, jax.Array]:
    """K fused decode+sample steps per host sync (vLLM multi-step scheduling).

    Returns (state, tokens_k [K, slots]). ``steps_left`` makes the burst
    barrier-free: a slot near its max_tokens/KV budget stops advancing at its
    own limit (step t treats it as inactive) instead of capping K for the
    whole batch — so one short request no longer collapses everyone's burst.
    Slots that hit EOS mid-burst keep decoding (the host discards their tail);
    only the first steps_left[s] rows of tokens_k are meaningful for slot s.
    """
    def body(carry, xs):
        rng, t = xs
        st, toks = carry
        act_t = active & (t < steps_left)
        st, logits = decode_step(params, st, toks, act_t, cfg)
        nxt = sampling.sample(rng, logits, temperature, top_p, top_k)
        nxt = jnp.where(act_t, nxt, toks).astype(jnp.int32)
        return (st, nxt), nxt

    (state, _), toks_k = jax.lax.scan(
        body, (state, tokens.astype(jnp.int32)),
        (rngs, jnp.arange(rngs.shape[0], dtype=jnp.int32)))
    return state, toks_k


# ------------------------------------------------------- pipeline-parallel decode

def _pp_schedule(x_mb, kv, step_mb, *, axis_name: str = "pp"):
    """Shared GPipe-style inference tick skeleton (call inside a shard_map
    manual over `axis_name`): M microbatches through pp stages, activations
    hopping stage->stage via ppermute while stages work different microbatches.

    step_mb(x_in, kv, jc, valid) -> (h, kv): one stage's work on its CURRENT
    microbatch jc (clipped; `valid` is False on fill/drain bubble ticks — the
    callback must discard or redirect its cache writes then). kv is an
    arbitrary pytree threaded through the scan (slot caches, block pools).
    Returns (outs [M, ...] — the last stage's outputs psum-broadcast to every
    stage — and the final kv). One implementation so the slot-decode,
    spec-verify, and paged-decode pp variants cannot drift apart.
    """
    from ray_tpu.parallel.sharding import vary_like

    pp_size = jax.lax.psum(1, axis_name)
    stage = jax.lax.axis_index(axis_name)
    m = x_mb.shape[0]
    ticks = m + pp_size - 1
    fwd = [(i, i + 1) for i in range(pp_size - 1)]

    def tick(carry, t):
        x_recv, kv, outs = carry
        j = t - stage
        jc = jnp.clip(j, 0, m - 1)
        valid = (j >= 0) & (j < m)
        x_in = jnp.where(stage == 0, x_mb[jc], x_recv)
        h, kv = step_mb(x_in, kv, jc, valid)
        out_j = t - (pp_size - 1)
        outs_new = jax.lax.dynamic_update_index_in_dim(
            outs, h, jnp.clip(out_j, 0, m - 1), 0)
        outs = jnp.where((stage == pp_size - 1) & (out_j >= 0), outs_new, outs)
        x_send = jax.lax.ppermute(h, axis_name, fwd) if pp_size > 1 else h
        return (x_send, kv, outs), None

    def _vary(z):
        return vary_like(z, x_mb, extra=(axis_name,))

    buf0 = _vary(jnp.zeros_like(x_mb[0]))
    outs0 = _vary(jnp.zeros_like(x_mb))
    (_, kv, outs), _ = jax.lax.scan(tick, (buf0, kv, outs0), jnp.arange(ticks))
    outs = jax.lax.psum(
        jnp.where(stage == pp_size - 1, outs, jnp.zeros_like(outs)), axis_name)
    return outs, kv


def _pp_shard_map(inner, params_layers, mesh: Mesh, arrays):
    """Shared shard_map scaffolding for every pp inference variant: layers
    manual over "pp" (stage-stacked leading axis), k/v over ("pp", dp), every
    other array over dp on its slot axis; dp joins the manual set only when
    the mesh names it. inner(layers_local, *local_arrays) -> (outs, k, v)."""
    from ray_tpu.parallel.sharding import manual_axes

    layer_specs = jax.tree_util.tree_map(lambda _: P("pp"), params_layers)
    dp_ax = "dp" if "dp" in mesh.shape else None
    manual = {"pp", "dp"} if dp_ax else {"pp"}
    n_rest = len(arrays) - 2  # beyond k and v
    mapped = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(layer_specs, P("pp", dp_ax), P("pp", dp_ax))
                 + (P(dp_ax),) * n_rest,
        out_specs=(P(dp_ax), P("pp", dp_ax), P("pp", dp_ax)),
        axis_names=manual,
    )
    with manual_axes(*manual):
        return mapped(params_layers, *arrays)


def _pp_slot_layers(params, k0, v0, x, lengths, active, mesh: Mesh, *,
                    width: int, block_fn):
    """Slot-cache layer pass through the pp schedule, shared by decode
    (width=1) and spec verify (width=W). block_fn(h, lp, ck, cv, mb_lengths,
    mb_active) -> (h, ck, cv) on one microbatch's slot-sliced cache; bubble
    ticks' cache writes are discarded wholesale by the valid mask."""
    m = mesh.shape["pp"]

    def inner(layers_local, k_local, v_local, x_local, lengths, active_i):
        s_l = x_local.shape[0]  # this dp replica's slot count
        smb = s_l // m
        x_mb = x_local.reshape(m, smb, width, x_local.shape[-1])

        def step_mb(x_in, kv, jc, valid):
            k, v = kv
            mb_lengths = jax.lax.dynamic_slice(lengths, (jc * smb,), (smb,))
            mb_active = jax.lax.dynamic_slice(active_i, (jc * smb,), (smb,)) > 0
            k_mb = jax.lax.dynamic_slice_in_dim(k, jc * smb, smb, axis=1)
            v_mb = jax.lax.dynamic_slice_in_dim(v, jc * smb, smb, axis=1)

            h, nk_mb, nv_mb = _layer_loop(
                lambda c, lp, ck, cv: block_fn(c, lp, ck, cv, mb_lengths, mb_active),
                x_in, layers_local, k_mb, v_mb)
            k_new = jax.lax.dynamic_update_slice_in_dim(k, nk_mb, jc * smb,
                                                        axis=1)
            v_new = jax.lax.dynamic_update_slice_in_dim(v, nv_mb, jc * smb,
                                                        axis=1)
            return h, (jnp.where(valid, k_new, k), jnp.where(valid, v_new, v))

        outs, (k, v) = _pp_schedule(x_mb, (k_local, v_local), step_mb)
        return outs.reshape(s_l, width, outs.shape[-1]), k, v

    return _pp_shard_map(inner, params["layers"], mesh,
                         (k0, v0, x, lengths, active.astype(jnp.int32)))


def decode_step_pp(params, state: DecodeState, tokens: jax.Array, active: jax.Array,
                   cfg: ModelConfig, mesh: Mesh):
    """Decode with the layer stack split across the "pp" mesh axis, microbatched
    over slots (reference: the reference passes pipeline_parallel_size to vLLM,
    vllm_models.py:125-139; here the schedule is native).

    Layout: params["layers"] leaves and the KV cache are sharded P("pp") on the
    layer axis, so each stage holds L/pp layers and THEIR cache — the point of
    inference PP is fitting a model + cache that one device group can't. Slots
    first shard over dp replicas (cache slot axis is P("dp"); each replica's
    slots are a contiguous range), then split into pp microbatches within the
    replica; activations hop stage→stage via ppermute while stages work
    different microbatches (GPipe-style fill/drain per step). tp and ep stay
    GSPMD auto axes inside the stage. Embedding/head run outside in auto mode.
    """
    pp = mesh.shape["pp"]
    dp = mesh.shape.get("dp", 1)
    s = tokens.shape[0]
    if s % (pp * dp):
        raise ValueError(f"max_num_seqs {s} must be divisible by pp*dp {pp * dp}")

    x = llama.embed_tokens(params, tokens[:, None], cfg)  # [S,1,D]
    h, nk, nv = _pp_slot_layers(
        params, state.k, state.v, x, state.lengths, active, mesh, width=1,
        block_fn=lambda c, lp, ck, cv, ln, ac:
            _slot_block(c, lp, cfg, ck, cv, ln, ac))
    logits = llama.output_head(params, h, cfg)[:, 0]
    lengths = jnp.where(active, state.lengths + 1, state.lengths)
    return DecodeState(k=nk, v=nv, lengths=lengths), logits


# ------------------------------------------------------------------------- sampler

@jax.jit
def sample_tokens(rng, logits, temperature, top_p, top_k):
    return sampling.sample(rng, logits, temperature, top_p, top_k)
