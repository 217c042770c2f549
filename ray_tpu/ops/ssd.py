"""The Mamba-2 recurrence over a sequence, in chunks (state-space duality, arXiv:2405.21060).

A head with scalar decay, state S [P, N]:

    S_t = exp(dt_t A) S_{t-1} + dt_t x_t B_t^T        y_t = S_t C_t

Run as written it is T dependent steps of a few thousand operations each. In chunks of Q
positions, with a_t = dt_t A (<= 0) and L_t its running sum inside a chunk, it is four
batched products and one small sum over chunks:

    inside a chunk    Y_diag[t] = sum_{s<=t} exp(L_t - L_s) (C_t . B_s) dt_s x_s
    a chunk's state   sum_s exp(L_end - L_s) dt_s x_s B_s^T
    chunk to chunk    the state before chunk c = sum_{c'<c} exp(sum of L_end between) state_c'
    from before       Y_off[t] = exp(L_t) (S_before C_t)

Every decay is the exponential of a DIFFERENCE of running sums, taken under the mask that
says which differences the formula has (s <= t, c' < c), all of them <= 0: nothing is
divided and no exponential of a positive number is formed, whatever the decays. (What a
difference of float32 sums costs: a decay's relative error is the sums' rounding, |L| x
6e-8, so a chunk's summed |dt A| belongs in the hundreds: 128 positions at the published
dt <= 0.1 and |A| <= 16 reach 205.)

Plain `jax.numpy`: einsums over [batch, chunk, head] with float32 operands at the highest
matrix precision, differentiated by JAX under the block's rematerialisation. The products
are small (at 16 heads of 64, state 128 and 8,192 positions 7 GFLOP forward a layer) and
the layer is bound by what it reads and writes, so float32 costs little here and keeps
the scan's own error below what the bfloat16 projections around it make. A kernel that
keeps a chunk's [Q, Q] decays in fast memory would save their round trip to HBM: PERF.md
section 5 has what the trace shows of it.
"""
import jax
import jax.numpy as jnp

_HI = jax.lax.Precision.HIGHEST


def _masked_exp(diff: jax.Array, allowed: jax.Array) -> jax.Array:
    """exp(diff) where allowed, 0 elsewhere; the exponential never sees what is masked."""
    return jnp.exp(jnp.where(allowed, diff, -jnp.inf))


def ssd_scan(x: jax.Array, dt: jax.Array, a: jax.Array, b: jax.Array, c: jax.Array,
             chunk: int) -> jax.Array:
    """x [B, T, H, P], dt [B, T, H] (> 0, after its softplus), a [H] (< 0), b and c
    [B, T, G, N]; head h reads group h // (H / G). Returns y [B, T, H, P] in float32: the
    recurrence's output from a zero state, without the skip (D x)."""
    bsz, t, h, p = x.shape
    g, n = b.shape[2:]
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the scan's chunk {chunk}")
    nc, r = t // chunk, h // g
    f32 = jnp.float32
    x6 = x.astype(f32).reshape(bsz, nc, chunk, g, r, p)
    b5 = b.astype(f32).reshape(bsz, nc, chunk, g, n)
    c5 = c.astype(f32).reshape(bsz, nc, chunk, g, n)
    # heads in front of the positions: a chunk's [Q, Q] decays lie in whole tiles
    dt_h = jnp.moveaxis(dt.astype(f32).reshape(bsz, nc, chunk, h), 2, 3)  # [B, C, H, Q]
    run = jnp.cumsum(dt_h * a.astype(f32)[:, None], axis=-1)  # L, [B, C, H, Q]

    # inside a chunk
    lower = jnp.tril(jnp.ones((chunk, chunk), bool))
    decay = _masked_exp(run[..., :, None] - run[..., None, :], lower)  # [B, C, H, t, s]
    cb = jnp.einsum("bktgn,bksgn->bkgts", c5, b5, precision=_HI)
    scores = (cb[:, :, :, None] * decay.reshape(bsz, nc, g, r, chunk, chunk)
              * dt_h.reshape(bsz, nc, g, r, 1, chunk))
    y = jnp.einsum("bkgrts,bksgrp->bktgrp", scores, x6, precision=_HI)

    # a chunk's own state, and the state each chunk starts from
    to_end = jnp.exp(run[..., -1:] - run) * dt_h  # [B, C, H, s]
    weighted = x6 * jnp.moveaxis(to_end, 2, 3).reshape(bsz, nc, chunk, g, r, 1)
    states = jnp.einsum("bksgn,bksgrp->bkgrpn", b5, weighted, precision=_HI)
    total = run[..., -1]  # a chunk's whole decay (log), [B, C, H]
    upto = jnp.cumsum(total, axis=1)
    earlier = jnp.tril(jnp.ones((nc, nc), bool), -1)[None, :, :, None]
    # the decays from the end of chunk j to the start of chunk k > j
    between = _masked_exp((upto - total)[:, :, None] - upto[:, None, :], earlier)  # [B, k, j, H]
    before = jnp.einsum("bkjh,bjhpn->bkhpn", between, states.reshape(bsz, nc, h, p, n),
                        precision=_HI).reshape(bsz, nc, g, r, p, n)
    carried = jnp.einsum("bktgn,bkgrpn->bktgrp", c5, before, precision=_HI)
    y = y + carried * jnp.moveaxis(jnp.exp(run), 2, 3).reshape(bsz, nc, chunk, g, r, 1)
    return y.reshape(bsz, t, h, p)

