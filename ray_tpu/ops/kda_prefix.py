"""The running sum of the log decays inside a chunk (ops/kda.py's G) as two Pallas TPU kernels behind
a `jax.custom_vjp`: for a chunk of a head's g [Q, K] float32 (<= 0),

    G_t = sum_{s <= t} g_s            and backward            dg_s = sum_{t >= s} (dG_t + dG'_t)

`_lead(jnp.cumsum(g, axis=2))` of ops/kda.py to the rounding of a float32 sum in another order. What
differs is where every array lies, and how often it crosses HBM. The forward kernel reads a chunk of
`kda_overlaps._per_step` heads a grid step out of [B x H, T, K], a head's positions together: the order
XLA itself gives the decay's low-rank product and its cotangent (so the transposes to and from the
mixer's [B, T, H, K] are changes of names; read a position's heads side by side, as the scan's other
kernels read q, k and v, g cost a stand-alone copy and a re-tiling reshape a pass: `[8192, 32, 128]` and
`[64, 128, 4096]` tile differently, PERF.md section 6, PRs 61 and 64), and writes G with the chunks
leading, [chunks, B, H, Q, K]: the order its four consumers read (the overlaps' and the parts' kernels,
both passes). XLA ran a `reduce-window` over the chunk's positions and then stored a transposed copy.
The function hands G back TWICE, one buffer under two names, one for `kda._overlaps` and one for
`kda.chunk_parts`: JAX then gives the backward rule the two halves' cotangents apart instead of summing
them in a pass of its own (`add_any`), and the backward kernel reads both as `kda_overlaps_bwd` and
`kda_parts_bwd` wrote them, adds them in fast memory, takes the reversed running sum and writes dg a
head's positions together, where the decay's backward fusion reads it: no transposed copy of dG, no
reversed `reduce-window`.

Handed what g is made of, `prefix` is the same pair with g made where it is summed: the forward kernel takes the product's
result as the mixer holds it (`decay`, bfloat16 in the cells), dt_bias and -exp(A_log) and computes
g = -exp(A_log) softplus(decay + dt_bias) (`log_decay`, the plain expression, letter for letter: the
float32 sum, the maximum, one exponential of a non-positive number and one log1p, the chip's own as in
XLA's fusion) a head at a time in fast memory, so g itself never reaches HBM in a forward pass (201 MB a
pass at 32 heads for the 536 of a stored g read again). Its backward rule is `kda_prefix_bwd` followed by
`jax.vjp` of `log_decay`.

The sum itself is adds on the vector unit, float32 throughout, every term added exactly once: inside
every register of 8 positions three shifted adds (a position adds the one 1, 2, 4 before it, the rows
rolled and the first ones masked), and the registers' totals carried along the chunk one after another,
so G_t is its register's small partial sum plus ONE rounding at |G|'s size and the differences G_t - G_s
that the decays are made of stand as close to the true ones as a position-by-position sum's (on the chip
G reads 1.5e-7 of its largest entry from a float64 sum where XLA's `reduce-window` reads 4.4e-7, and a
neighbours' difference one rounding of |G| where a sequential sum's is half of one; seven shifted adds
over the whole chunk, Hillis and Steele's scan, run as fast and carried the kernels' path to 1.04e-5 of
the recurrence's largest output where `jnp.cumsum` reads 0.68e-5 and this form 0.74e-5: the limit
of tests/test_kda_scan.py is 1e-5). Nothing is summed or stored below float32. One product with the
[Q, Q] triangle of ones at the highest matrix precision is the same sum again (the ones are exact in
bfloat16) and was measured slower on the chip: the MXU's six passes bind it at 504 GB/s forward and 605
backward, where the adds wait on HBM, 652 and 677 GB/s of the 268 and 403 MB a call moves at 32 heads;
the forward call that makes g too, 201 MB, takes 0.373 ms (540 GB/s: its softplus binds it) for the 0.31 + 0.41
of g's fusion and the sum (PERF.md section 6, PR 64). A grid step walks its heads in a loop (one body,
traced once). VMEM: 2 and 3 blocks of 64 KB a head at 128 x 128, twice for the pipeline's two buffers: 8
heads a step in both passes.

`kda_overlaps.supports` says which shapes go to the kernels (ops/kda.py's `takes_kernels` routes all
four pairs by it); off a TPU they run in Pallas' interpreter. Elsewhere `jnp.cumsum` runs, which is
also what these are tested against (tests/test_kda_scan.py, beside a float64 sum).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa
from .kda_overlaps import _ROWS, _per_step


def log_decay(decay, dt_bias, a_log):
    """g [B, T, H, K] float32 (<= 0), the log of a channel's decay, from the mixer's product decay [B, T, H, K],
    dt_bias [H, K] and A_log [H]: the plain expression."""
    return -jnp.exp(a_log)[:, None] * jax.nn.softplus(decay.astype(jnp.float32) + dt_bias)


def _running(x, reverse: bool):
    """The running sum of x [Q, K] over its rows (from the last row if `reverse`): inside every register of 8 rows
    three shifted adds, and the registers' totals carried along the chunk one after another."""
    row = jax.lax.broadcasted_iota(jnp.int32, (_ROWS, 1), 0)
    starts = range(0, x.shape[0], _ROWS)
    out, carry = [], None
    for lo in (reversed(starts) if reverse else starts):
        block = x[lo:lo + _ROWS]
        for shift in (1, 2, 4):
            if reverse:
                block = block + jnp.where(row < _ROWS - shift, pltpu.roll(block, _ROWS - shift, 0), 0.0)
            else:
                block = block + jnp.where(row >= shift, pltpu.roll(block, shift, 0), 0.0)
        total = block[:1] if reverse else block[_ROWS - 1:]
        out.append(block if carry is None else block + carry)
        carry = total if carry is None else carry + total
    return jnp.concatenate(out[::-1] if reverse else out, 0)


def _fwd_kernel(x_ref, *made_of_and_run):
    """x_ref holds g, or the mixer's product where `made_of_and_run` brings dt_bias and -exp(A_log) [per, 1, K] too."""
    *made_of, run_ref = made_of_and_run

    def head(j, _):
        g = x_ref[j].astype(jnp.float32)
        if made_of:  # `log_decay`, with `jax.nn.softplus` written out
            bias_ref, rate_ref = made_of
            x = g + bias_ref[j]
            g = rate_ref[j] * (jnp.maximum(x, 0.0) + jnp.log1p(jnp.exp(-jnp.abs(x))))
        run_ref[j] = _running(g, False)

    jax.lax.fori_loop(0, run_ref.shape[0], head, None)


def _bwd_kernel(first_ref, second_ref, dg_ref):
    def head(j, _):
        dg_ref[j] = _running(first_ref[j] + second_ref[j], True)

    jax.lax.fori_loop(0, dg_ref.shape[0], head, None)


def _call(kernel, name: str, args, by_head, shape, backward: bool):
    """`_per_step` heads of a chunk a grid step, the grid over (chunk, row of the batch, head) as the scan's other
    kernels walk it; `shape` = [B, H, chunks, Q, K]. Forward an array a head's positions together (and `by_head`,
    [H, 1, K] a head) -> G [chunks, B, H, Q, K] in the grid's own order; backward two of those -> one a head's
    positions together."""
    batch, heads, chunks, size, width = shape
    n = chunks * batch * heads
    per = _per_step(heads, 4 * size * width * (len(args) + 1))

    def together(i):  # the block of [B x H / per, chunks x Q, K] that step i of the grid holds
        chunk_row, head = (i * per) // heads, (i * per) % heads
        return ((chunk_row % batch) * heads + head) // per, chunk_row // batch, 0

    head_block = pl.BlockSpec((per, size, width), together)
    block = pl.BlockSpec((per, size, width), lambda i: (i, 0, 0))
    a_head = pl.BlockSpec((per, 1, width), lambda i: ((i * per) % heads // per, 0, 0))
    flat = (batch * heads, chunks * size, width)
    return pl.pallas_call(
        kernel, name=name, interpret=_fa._interpret(), grid=(n // per,),
        in_specs=[block if backward else head_block] * len(args) + [a_head] * len(by_head),
        out_specs=head_block if backward else block,
        out_shape=jax.ShapeDtypeStruct(flat if backward else (n, size, width), jnp.float32),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)))(
            *[x.reshape((n, size, width) if backward else flat) for x in args], *by_head
    ).reshape(shape if backward else (chunks, batch, heads, size, width))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def prefix(made_of, chunk: int):
    """(G, G) [chunks, B, H, Q, K], the running sum over every chunk's Q positions twice (one buffer: the module's
    docstring has why), from `made_of`: (g,) [B, T, H, K] float32, or (decay, dt_bias, a_log), `log_decay`'s
    arguments, and then g is made in the kernel that sums it."""
    return _prefix_fwd(made_of, chunk)[0]


def _prefix_fwd(made_of, chunk):
    x, *leaves = made_of
    batch, t, heads, width = x.shape
    if leaves:
        dt_bias, a_log = leaves
        leaves = [dt_bias[:, None], jnp.broadcast_to(-jnp.exp(a_log)[:, None, None], (heads, 1, width))]
    # a head's positions together: the order XLA gives the decay's product, so the transpose is a change of names
    run = _call(_fwd_kernel, "kda_prefix_fwd", (x.transpose(0, 2, 1, 3),), leaves, (batch, heads, t // chunk, chunk, width), False)
    return (run, run), (made_of if leaves else None)


def _prefix_bwd(chunk, made_of, cts):
    chunks, batch, heads, size, width = cts[0].shape
    dg = _call(_bwd_kernel, "kda_prefix_bwd", cts, (), (batch, heads, chunks, size, width), True)
    dg = dg.reshape(batch, heads, chunks * size, width).transpose(0, 2, 1, 3)
    return ((dg,) if made_of is None else jax.vjp(log_decay, *made_of)[1](dg),)


prefix.defvjp(_prefix_fwd, _prefix_bwd)
