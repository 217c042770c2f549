"""The walk over a head's chunks (ops/kda.py's join S <- M S + N and its outputs P S_0 + O0) as two
Pallas TPU kernels behind one `jax.custom_vjp`: from the chunks' four matrices P, O0 [Q, K] and M, N
[K, K] of every chunk and head, float32, and a zero state before the first chunk,

    o_c = P_c S_c + O0_c        S_{c+1} = M_c S_c + N_c        S_0 = 0

`kda._walk` letter for letter: the same two products a chunk and head of float32 operands at the highest
precision (Mosaic's contract_precision<fp32>, six passes of the MXU), the same sums in the same order,
nothing reordered across chunks. What differs is where the state lives: the grid is (rows of the batch x
heads / `kda_overlaps._per_step`, chunks) with the chunks innermost, a grid step holds a chunk of a few
heads and walks the heads in a loop (one body, traced once), and a scratch [heads a step, K, K] holds
their states in fast memory from a head's first chunk to its last, zeroed where the chunk's index is 0 (so
a row of the batch never starts from another's). The two products share their right operand and are ONE
on the MXU, [P_c ; M_c] S_c, [Q + K, K] x [K, K], as `kda_parts._fwd_kernel` stacks its own. The state
reaches HBM only as `starts`, every chunk's S_c [chunks, B, H, K, K]: what the backward kernel keeps
beside P and M, and what `kda._walk`'s `lax.scan` stacks; the call whose residuals nobody reads (the
forward pass under a remat policy that makes the layer again) does not write it.

o is written where the mixer reads it: a head's positions together, [B, H, chunks x Q, K], the order XLA
gives the head norm, the gate and the output product behind the scan (on a v5e it lays their operand
`[T, H, K]` out heads-major whatever produced it: written a position's heads side by side, as q, k and v
come, o was copied once more in every pass, 5 ms a step in the Kimi-Linear cell: PERF.md section 6, PR 61).
`kda.walk` hands it on as [B, chunks, Q, H, K] through a transpose that the compiler's layout assignment
makes a change of names, and o's cotangent comes back the same way: XLA stores no transposed copy of either.

The backward kernel walks the same grid from the last chunk to the first with d S in the scratch, zero
behind the last chunk (nobody reads the final state). With cot = [d o_c ; d S_{c+1}], [Q + K, K]:

    [d P_c ; d M_c] = cot S_c^T        d N_c = d S_{c+1}        d O0_c = d o_c
    d S_c = [P_c ; M_c]^T cot = P_c^T d o_c + M_c^T d S_{c+1}

two products a chunk and head; the one that contracts its left operand's rows gets it transposed in fast
memory. d O0 is d o in the order `kda_parts_bwd` reads its cotangents in, the chunks leading: this kernel
holds the block and writes it there (64 KB a chunk and head), so no XLA transpose of it is left either.

VMEM a grid step: forward 6 blocks of 64 KB a head at 128 x 128 (P, O0, M, N in, o and S_c out), backward
8 (d o, P, M, S_c in, d P, d O0, d M, d N out), twice for the pipeline's two buffers, and the states'
scratch: 8 heads a step in both passes by `_per_step`'s rule, the one all eight of the scan's kernels share.

`kda_overlaps.supports` says which shapes go to the kernels (ops/kda.py's `takes_kernels` routes all four
pairs by it); off a TPU they run in Pallas' interpreter.
"""
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa
from .kda_overlaps import _NN, _NT, _TN, _dot, _per_step


def _fwd_kernel(p_ref, o0_ref, m_ref, n_ref, o_ref, *starts_and_state):
    """`starts_and_state`: every chunk's start state (an output, where the caller keeps it) and the scratch."""
    *starts_ref, state_ref = starts_and_state
    per, size, width = p_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        state_ref[:] = jnp.zeros_like(state_ref)

    def head(j, _):
        state = state_ref[j]
        both = _dot(jnp.concatenate([p_ref[j], m_ref[j]], 0), state, _NN)  # [P ; M] S: one right operand
        o_ref[j] = both[:size] + o0_ref[j]
        for ref in starts_ref:
            ref[j] = state
        state_ref[j] = both[size:] + n_ref[j]

    jax.lax.fori_loop(0, per, head, None)


def _bwd_kernel(do_ref, p_ref, m_ref, starts_ref, dp_ref, do0_ref, dm_ref, dn_ref, dstate_ref):
    """dstate_ref (scratch): the cotangent of the state BEHIND the step's chunk; the grid walks the chunks backward."""
    per, size, width = p_ref.shape

    @pl.when(pl.program_id(1) == 0)
    def _():
        dstate_ref[:] = jnp.zeros_like(dstate_ref)

    def head(j, _):
        do, d_end = do_ref[j], dstate_ref[j]
        cot = jnp.concatenate([do, d_end], 0)  # of [P ; M] S, [Q + K, K]
        d_both = _dot(cot, starts_ref[j], _NT)
        dp_ref[j] = d_both[:size]
        dm_ref[j] = d_both[size:]
        dn_ref[j] = d_end
        do0_ref[j] = do
        dstate_ref[j] = _dot(jnp.concatenate([p_ref[j], m_ref[j]], 0), cot, _TN)

    jax.lax.fori_loop(0, per, head, None)


def _call(kernel, name: str, by_head, chunked, outs, backward: bool):
    """`_per_step` heads of a row of the batch a grid step, the chunks innermost and in order (from the last if
    `backward`): `by_head` [B, H, chunks, Q, K] and `chunked` [chunks, B, H, ., K] in blocks of a chunk's heads ->
    results by `outs`: "by_head" or the rows of a chunked one. One scratch [per, K, K] lives through a walk."""
    chunks, batch, heads, size, width = chunked[0].shape
    extents = [x.shape[3] for x in chunked] + [e for e in outs if e != "by_head"]
    per = _per_step(heads, 4 * width * (size * (len(by_head) + outs.count("by_head")) + sum(extents)))
    chunk = (lambda c: chunks - 1 - c) if backward else (lambda c: c)
    head_block = pl.BlockSpec((per, size, width), lambda i, c: (i, chunk(c), 0))
    block = lambda e: head_block if e == "by_head" else pl.BlockSpec(  # noqa: E731
        (None, per, e, width), lambda i, c: (chunk(c), i, 0, 0))
    flat = lambda e: (batch * heads, chunks * size, width) if e == "by_head" else (chunks, batch * heads, e, width)  # noqa: E731
    results = pl.pallas_call(
        kernel, name=name, interpret=_fa._interpret(), grid=(batch * heads // per, chunks),
        in_specs=[head_block] * len(by_head) + [block(x.shape[3]) for x in chunked],
        out_specs=[block(e) for e in outs],
        out_shape=[jax.ShapeDtypeStruct(flat(e), jnp.float32) for e in outs],
        scratch_shapes=[pltpu.VMEM((per, width, width), jnp.float32)],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "arbitrary")))(
            *[x.reshape(flat("by_head")) for x in by_head], *[x.reshape(flat(x.shape[3])) for x in chunked])
    return [r.reshape((batch, heads, chunks, size, width) if e == "by_head" else (chunks, batch, heads, e, width))
            for r, e in zip(results, outs)]


@jax.custom_vjp
def walk(p, o0, m, n):
    """o [B, H, chunks, Q, K], a head's positions together, of the module's docstring from P, O0
    [chunks, B, H, Q, K] and M, N [chunks, B, H, K, K], float32, K values a key."""
    return _call(_fwd_kernel, "kda_walk_fwd", (), (p, o0, m, n), ("by_head",), False)[0]


def _walk_fwd(p, o0, m, n):
    o, starts = _call(_fwd_kernel, "kda_walk_fwd", (), (p, o0, m, n), ("by_head", p.shape[-1]), False)
    return o, (p, m, starts)


def _walk_bwd(kept, do):
    p, m, starts = kept
    size, width = p.shape[-2:]
    return tuple(_call(_bwd_kernel, "kda_walk_bwd", (do,), kept, (size, size, width, width), True))


walk.defvjp(_walk_fwd, _walk_bwd)
