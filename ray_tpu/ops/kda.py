"""The Kimi-Delta-Attention recurrence over a sequence, in chunks (Kimi Linear,
arXiv:2510.26692: a gated delta rule whose decay is a CHANNEL's).

A head with keys and values K and V wide, state S [K, V], from a zero state:

    S_t = (I - beta_t k_t k_t^T) Diag(alpha_t) S_{t-1} + beta_t k_t v_t^T       o_t = S_t^T q_t

with alpha_t = exp(g_t), g_t [K] <= 0. Run as written it is T dependent steps. The
transition is not diagonal, so unlike ops/ssd.py's a chunk is not four products: with
u_t = beta_t (v_t - (Diag(alpha_t) S_{t-1})^T k_t) the step is S_t = Diag(alpha_t) S_{t-1}
+ k_t u_t^T, and inside a chunk of Q positions that starts from S_0, with G_t the running
sum of g inside the chunk (<= 0, falling),

    S_t = Diag(exp G_t) S_0 + sum_{s<=t} Diag(exp(G_t - G_s)) k_s u_s^T
    A_ts = beta_t sum_c k_tc k_sc exp(G_tc - G_sc)   (s < t)      the keys' decayed overlaps
    B_ts =        sum_c q_tc k_sc exp(G_tc - G_sc)   (s <= t)     the queries' with the keys
    (I + A) U = beta (V - (K exp G) S_0)      the WY form's triangular system: U = U0 - W S_0,
                                              [W | U0] = (I + A)^-1 [beta K exp G | beta V]
    O     = (Q exp G) S_0 + B U      = P S_0 + O0,   P = Q exp G - B W,       O0 = B U0
    S_end = Diag(exp G_Q) S_0 + Kend^T U = M S_0 + N,  M = Diag(exp G_Q) - Kend^T W,  N = Kend^T U0
                                              Kend_s = k_s exp(G_Q - G_s)

P, O0, M, N are a chunk's own (nothing carried): every chunk's at once, in batched
products; the chunks are then joined by S <- M S + N, one [K, K] x [K, V] product a head and
chunk, the only dependent steps (T / Q of them), and a chunk's outputs are P S_0 + O0 from the
state it starts from. `walk` hands that last step to the two Pallas kernels of ops/kda_walk.py
wherever `takes_kernels` says so: a head's state stays in fast memory from its first chunk to
its last, the two products of a chunk share their right operand and are one on the MXU, and o
is written, and its cotangent read, a head's positions together, [B, H, T, K]: the order XLA
gives the norm, the gate and the output product behind the scan, so the transpose to the
mixer's [B, T, H, K] is a change of names. Any other shape, and any shape under a mesh, runs
`_walk`: a `lax.scan` over the chunks with M, N and every chunk's start through HBM, the
outputs one batched product more and a transpose to the mixer's order; the same sums in the
same order, and what the kernels are tested against.

Every decay is the exponential of a non-positive number, whatever g holds: exp G_t,
exp(G_Q - G_s), and the pairs' exp(G_tc - G_sc). That one is NOT split into
exp(G_t) exp(-G_s): a channel's sum over 64 positions reaches -100 at the seeded decays
and exp(100) is no float32. A chunk is cut into sub-chunks of `_SUB` positions:
  pairs inside one  from the differences themselves, [_SUB, _SUB, K] a sub-chunk, under the
                    mask s <= t, which the exponential never sees outside of, reduced over
                    the channels where they are made (0.5 MB a sub-chunk and head at 32; a
                    whole chunk's [Q, Q, K] would be 8 MB at 128);
  pairs of two      through the running sum at the later sub-chunk's start, G_b:
                    exp(G_t - G_s) = exp(G_t - G_b) exp(G_b - G_s), s < b <= t, both factors
                    <= 1 because G falls: a product [_SUB, K] x [K, Q] a sub-chunk. Where
                    G_b - G_s < -87 the second factor underflows to 0 and the pair with it,
                    whose true weight is below exp(-87) = 1.6e-38: that is the bound.
Where they are made decides what is alive: `overlaps` hands a chunk to the two Pallas kernels
of ops/kda_overlaps.py wherever they tile it (`kda_overlaps.supports`: channels in whole
128-lane registers, sub-chunks in whole registers of 8 rows; the Solar-Open2 and Kimi-Linear
cells' 128 / 32 / 128), a chunk of up to 8 heads a grid step (`kda_overlaps._per_step`, the
rule the running sum's, the second half's and the walk's kernels share), and then a chunk's differences, decayed keys and
factors live and die in fast memory in both passes and the backward pass keeps q, k and G
alone. The kernels cut the pairs inside a sub-chunk once more, the same sums under the same
bound: a pair of two BLOCKS of 8 positions of one sub-chunk goes through a second reference,
G_r at the end of the earlier block (s <= r < t, so exp(G_t - G_r) and exp(G_r - G_s) are
both <= 1), and joins the pairs of two sub-chunks in their one product a sub-chunk on the
MXU; only the 8 x 8 blocks on the diagonal are made from the differences themselves, on the
vector unit (that file's docstring; on the chip the kernels' sums stand within 2e-6 of
`_decayed_overlaps`' largest entry, the MXU's six passes over float32 operands; PERF.md
section 6, PR 59). (kk and b carry no name for the remat policies: kept they would spare a
rematerialised layer the forward kernel's second run for 201 MB a step, which takes the
Solar-Open2 step's temporaries from 4.79 to 5.00 GB, over what its compile test allows; the
second half's kernels freed 3 MB of them, not 200: PERF.md section 6, PRs 38 and 51). Any
other shape (a width of 16, a chunk of 16: tier-1's) runs
`_decayed_overlaps`, the same sums in `jax.numpy` differentiated by JAX, which is also what
the kernels are tested against; there every chunk's differences are alive at once, which
only a small shape affords. The shape alone chooses: no flag, and nothing to set. Under an ambient mesh
with an axis still automatic (heads or batch sharded by GSPMD) the `jax.numpy` path runs
whatever the shape, because GSPMD cannot partition a Mosaic call and the compiler does
partition plain operations; no listed cell trains this family under a mesh (a wrap as
ops/attention.py's `_flash_per_shard` is the step to take when one does).
(What a difference of float32 sums costs: a decay's relative error is the sums' rounding,
|G| x 6e-8: 6e-6 at -100, where a chunk-long decay itself is 4e-44; so a chunk's summed
|g| belongs in the hundreds, as ops/ssd.py's.)
The triangular system is solved by substitution, not by the series I - A + A^2 - ...: at
beta near 2 and keys that repeat, the powers of A grow to 2^i C(Q, i) before they cancel.
Blocks of `_SOLVE` rows go to `solve_triangular` (the TPU compiler's own kernel) and are
joined in halves by products; (I + A)^-1 is made explicitly, once: its backward rule is
two products (plain differentiation solves two more systems), and it carries a name
(`INVERSE_NAME`) under which models/llama.py's remat policies keep it, [Q, Q] a chunk and
head, so that a rematerialised layer does not substitute again (on a v5e 5.2 ms a layer
and pass at blocks of 128, 1.0 at 32: PERF.md section 6, PR 37).

The chunks' four matrices P, O0, M, N are made the same way: `chunk_parts` hands every chunk
to the two Pallas kernels of ops/kda_parts.py wherever `takes_kernels` says so (the same rule
routes both halves), a chunk of a few heads a grid step, and then exp G, beta [K exp G | V],
[W | U0] = (I + A)^-1 [..] and Kend live and die in fast memory: the forward kernel reads q,
k, v, G, beta, (I + A)^-1 and B and writes P, O0, M, N alone; the backward kernel keeps
nothing but those seven inputs, makes [W | U0] again and writes their seven gradients (10 and
16 blocks of [Q, Q] a chunk and head, 1.3 and 2.1 MB of VMEM with the pipeline's second
buffers at 128). Any other shape, and any shape under a mesh, runs `_chunk_parts`: the same
algebra in `jax.numpy`, differentiated by JAX, every chunk's [Q, K + V] intermediates
through HBM; it is what the kernels are tested against. Both halves' kernels read q, k and v
where the mixer wrote them, [B, T, H x K] with a position's heads side by side, and write
their gradients there; only the `jax.numpy` forms take copies with the chunks leading. P, O0,
M, N lead with the chunks, [chunks, B, H, ., .]: the walk's kernels read a chunk of a few heads
out of them as the parts' kernels wrote it.

The running sum G is made the same way: `running_sum` hands g to the two Pallas kernels of
ops/kda_prefix.py wherever `takes_kernels` says so (the one rule of all four pairs). The forward
kernel reads a chunk of a few heads out of [B x H, T, K], a head's positions together (the order XLA
gives the decay's product: g is the one operand the scan does NOT read in the mixer's order), sums
the chunk's positions by shifted adds in fast memory and writes G with the chunks leading, where the
overlaps' and the parts' kernels read it; where the mixer hands over what g is made of (`LogDecay`:
its product, dt_bias and A_log) the kernel computes g too, and g crosses HBM in no forward pass. G
comes back under two names, one a half, so that the halves' cotangents reach the backward kernel
apart: it adds them, sums from the chunk's end and writes dg a head's positions together. Any other
shape, and any shape under a mesh, runs `jnp.cumsum` over the positions in the mixer's order and a
transposed copy with the chunks leading (XLA's `reduce-window`, differentiated by JAX): the same sum
in another order, and what the kernels are tested against.

What is left outside the eight kernels is plain `jax.numpy`, differentiated by JAX: the strict
mask and beta over the keys' overlaps and the inverse (the compiler's substitution, above). Every
operand anywhere is float32 and every product, XLA's or a kernel's, runs at the highest matrix
precision. PERF.md section 5 has the trace.
"""
from typing import NamedTuple

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.parallel.sharding import partitioned_by_gspmd

from . import kda_overlaps, kda_parts, kda_prefix, kda_walk

_HI = jax.lax.Precision.HIGHEST
_SUB = 32  # positions of a sub-chunk: the differences are [_SUB, _SUB, K] a sub-chunk
_SOLVE = 32  # rows of a triangular system solved by substitution; larger ones in halves
INVERSE_NAME = "kda_inverse"  # (I + A)^-1 of every chunk: kept under every remat policy (llama._maybe_remat)


def _sub(size: int) -> int:
    return _SUB if size % _SUB == 0 else size


def takes_kernels(size: int, width: int) -> bool:
    """Whether a chunk of `size` positions at `width` channels goes to the Pallas kernels."""
    return kda_overlaps.supports(size, _sub(size), width) and not partitioned_by_gspmd()


def _lead(x):
    """[B, chunks, Q, H, ...] (the positions in the mixer's order, cut in chunks) -> [chunks, B, H, Q, ...]."""
    return x.transpose(1, 0, 3, 2, *range(4, x.ndim))


class LogDecay(NamedTuple):
    """g = -exp(A_log) softplus(decay + dt_bias) (`kda_prefix.log_decay`) as what the mixer makes it from: its product
    decay [B, T, H, K], dt_bias [H, K] and A_log [H]. `kda_scan` takes it in g's place, and the running sum's kernel
    then makes g where it sums it."""
    decay: jax.Array
    dt_bias: jax.Array
    a_log: jax.Array


def running_sum(g, chunk: int):
    """G [chunks, B, H, Q, K], the running sum of g [B, T, H, K] (or a `LogDecay`) over every chunk's Q positions, TWICE
    (one value under two names, the overlaps' and the parts': ops/kda_prefix.py has why): by the Pallas kernels where
    they tile the shape (they read g, or what it is made from, as XLA holds the decay's product and write G where it
    is read), else by `jnp.cumsum` and a transposed copy."""
    made_of = tuple(g) if isinstance(g, LogDecay) else (g.astype(jnp.float32),)
    bsz, t, h, width = made_of[0].shape
    if takes_kernels(chunk, width):
        return kda_prefix.prefix(made_of, chunk)
    g = kda_prefix.log_decay(*made_of) if isinstance(g, LogDecay) else made_of[0]
    run = _lead(jnp.cumsum(g.reshape(bsz, t // chunk, chunk, h, width), axis=2))
    return run, run


def overlaps(q, k, run):
    """`_decayed_overlaps`' two sums [chunks, B, H, Q, Q] from q, k [B, chunks, Q, H, K] and run
    [chunks, B, H, Q, K]: by the Pallas kernels where they tile the shape (they read q and k out of
    the mixer's order in place), else by it."""
    size, width = run.shape[-2:]
    if takes_kernels(size, width):
        return kda_overlaps.overlaps(q, k, run, _sub(size))
    return _decayed_overlaps(_lead(q), _lead(k), run)


def _decayed_overlaps(q, k, run):
    """(sum_c k_tc k_sc exp(G_tc - G_sc), sum_c q_tc k_sc exp(G_tc - G_sc)) [..., Q, Q] for
    s <= t, 0 elsewhere; q, k, run (G) [..., Q, K]. In sub-chunks of `_SUB` positions (the
    module's docstring): the pairs inside one from their own differences, the pairs of two
    through the sums at the later one's start."""
    *lead, size, width = k.shape
    sub = _sub(size)
    nb = size // sub
    blocks = lambda x: x.reshape(*lead, nb, sub, width)  # noqa: E731
    q_b, k_b, run_b = blocks(q), blocks(k), blocks(run)
    lower = jnp.tril(jnp.ones((sub, sub), bool))
    # [..., i, t, s, c]: exp(G_tc - G_sc) where s <= t in sub-chunk i, 0 elsewhere
    decay = jnp.exp(jnp.where(lower[:, :, None], run_b[..., :, None, :] - run_b[..., None, :, :], -jnp.inf))
    k_decayed = k_b[..., None, :, :] * decay
    same = jnp.eye(nb, dtype=k.dtype)[:, None, :, None]  # a sub-chunk's pairs on the diagonal of [i, t, j, s]
    inside = [(jnp.sum(x[..., :, None, :] * k_decayed, -1)[..., None, :] * same).reshape(*lead, size, size)
              for x in (k_b, q_b)]
    if nb == 1:
        return inside
    # the sums before each sub-chunk's first position: G at the end of the one before
    start = jnp.concatenate([jnp.zeros_like(run_b[..., :1, -1, :]), run_b[..., :-1, -1, :]], -2)  # [..., i, K]
    since = jnp.exp(run_b - start[..., None, :])  # exp(G_t - G_start(i)), t in i: <= 1
    earlier = (jnp.arange(size) // sub)[None, :] < jnp.arange(nb)[:, None]  # [i, s]: s before sub-chunk i
    upto = jnp.exp(jnp.where(earlier[:, :, None], start[..., :, None, :] - run[..., None, :, :], -jnp.inf))
    k_upto = k[..., None, :, :] * upto  # [..., i, s, c]: k_sc exp(G_start(i) - G_sc), <= |k_sc|
    across = [jnp.einsum("...itc,...isc->...its", x * since, k_upto, precision=_HI).reshape(*lead, size, size)
              for x in (k_b, q_b)]
    return [a + b for a, b in zip(inside, across)]


def _substituted(a):
    """(I + a)^-1, a strictly lower triangular [..., n, n]: blocks of `_SOLVE` by the
    compiler's substitution kernel, joined in halves by products,
    [[T11, 0], [-T22 a21 T11, T22]]."""
    n = a.shape[-1]
    if n > _SOLVE and n % 2 == 0:
        half = n // 2
        t11, t22 = _substituted(a[..., :half, :half]), _substituted(a[..., half:, half:])
        t21 = -jnp.einsum("...ij,...jk,...kl->...il", t22, a[..., half:, :half], t11, precision=_HI)
        return jnp.concatenate([jnp.concatenate([t11, jnp.zeros_like(t21.mT)], -1),
                                jnp.concatenate([t21, t22], -1)], -2)
    eye = jnp.eye(n, dtype=a.dtype)
    return jax.scipy.linalg.solve_triangular(a + eye, jnp.broadcast_to(eye, a.shape), lower=True,
                                             unit_diagonal=True)


def _unit_lower_inverse_fwd(a):
    # named here, inside the rule: what the backward pass keeps is this value, and a name
    # put on the function's result outside would be on a copy no policy can reach
    t = checkpoint_name(_substituted(a), INVERSE_NAME)
    return t, t


@jax.custom_vjp
def _unit_lower_inverse(a):
    """(I + a)^-1 for a strictly lower triangular [..., Q, Q], by substitution. Its backward
    rule is two products, d a = -T^T (d T) T^T, where plain differentiation solves two more
    systems."""
    return _unit_lower_inverse_fwd(a)[0]


def _unit_lower_inverse_bwd(t, dt):
    da = -jnp.einsum("...ji,...jk,...lk->...il", t, dt, t, precision=_HI)
    return (jnp.where(jnp.tril(jnp.ones(t.shape[-2:], bool), -1), da, 0.0),)


_unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def _overlaps(q, k, run, beta):
    """Every chunk at once: q, k [B, chunks, Q, H, K], run [chunks, B, H, Q, K], beta [chunks, B, H, Q]
    -> (A, B) [chunks, B, H, Q, Q] of the module's docstring."""
    size = run.shape[-2]
    kk, b = overlaps(q, k, run)
    return jnp.where(jnp.tril(jnp.ones((size, size), bool), -1), kk * beta[..., :, None], 0.0), b


def chunk_parts(q, k, v, run, beta, inverse, b):
    """`_chunk_parts`' four matrices [chunks, B, H, ., .] from q, k, v [B, chunks, Q, H, K] and run,
    beta, inverse, b [chunks, B, H, ...]: by the Pallas kernels where they tile the shape, else by it."""
    size, width = run.shape[-2:]
    if takes_kernels(size, width):
        return kda_parts.parts(q, k, v, run, beta, inverse, b)
    return _chunk_parts(_lead(q), _lead(k), _lead(v), run, beta, inverse, b)


def _chunk_parts(q, k, v, run, beta, inverse, b):
    """Every chunk at once, every leading axis a batch: q, k, v, run (G) [..., Q, K], beta
    [..., Q], inverse ((I + A)^-1) and b [..., Q, Q] -> P [..., Q, K], O0 [..., Q, V], M
    [..., K, K], N [..., K, V] of the module's docstring."""
    from_start = jnp.exp(run)
    rhs = jnp.concatenate([k * from_start, v], -1) * beta[..., None]
    solved = jnp.einsum("...ts,...sc->...tc", inverse, rhs, precision=_HI)
    w, u0 = solved[..., :k.shape[-1]], solved[..., k.shape[-1]:]
    k_end = k * jnp.exp(run[..., -1:, :] - run)
    p = q * from_start - jnp.einsum("...ts,...sc->...tc", b, w, precision=_HI)
    o0 = jnp.einsum("...ts,...sv->...tv", b, u0, precision=_HI)
    m = -jnp.einsum("...sc,...sd->...cd", k_end, w, precision=_HI)
    m = m + from_start[..., -1, :, None] * jnp.eye(k.shape[-1], dtype=m.dtype)
    n = jnp.einsum("...sc,...sv->...cv", k_end, u0, precision=_HI)
    return p, o0, m, n


def walk(p, o0, m, n):
    """`_walk`'s outputs [B, chunks, Q, H, V], the positions in the mixer's order, from P, O0 [chunks, B, H, Q, .] and
    M, N [chunks, B, H, K, .]: by the Pallas kernels where they tile the shape (they hold the state in fast memory
    from a head's first chunk to its last and write o as the mixer's next steps read it), else by it."""
    size, width = p.shape[-2:]
    if takes_kernels(size, width):
        return kda_walk.walk(p, o0, m, n).transpose(0, 2, 3, 1, 4)  # a head's positions together: a change of names to XLA
    return _walk(p, o0, m, n)


def _walk(p, o0, m, n):
    """The chunks joined, S <- M S + N from a zero state, one [K, K] x [K, V] product a head and chunk and the
    only dependent steps, and the outputs P S_0 + O0 of the module's docstring, one batched product more."""
    def join(state, mn):  # the state each chunk starts from
        m_c, n_c = mn
        return jnp.einsum("bhcd,bhdv->bhcv", m_c, state, precision=_HI) + n_c, state

    _, starts = jax.lax.scan(join, jnp.zeros(n.shape[1:], n.dtype), (m, n))
    o = jnp.einsum("kbhtc,kbhcv->kbhtv", p, starts, precision=_HI) + o0
    return o.transpose(1, 0, 3, 2, 4)


def kda_scan(q: jax.Array, k: jax.Array, v: jax.Array, g, beta: jax.Array, chunk: int) -> jax.Array:
    """q, k, v [B, T, H, K] (q scaled, k of unit length: the caller's), g [B, T, H, K]
    (<= 0: the log of a channel's decay) or the `LogDecay` it is made from, beta [B, T, H] -> o [B, T, H, K]
    in float32: the recurrence's output from a zero state."""
    bsz, t, h, width = q.shape
    if t % chunk:
        raise ValueError(f"sequence length {t} is not a multiple of the scan's chunk {chunk}")
    nc = t // chunk
    f32 = jnp.float32

    def split(x):  # [B, T, H, ...] -> [B, chunks, Q, H, ...]
        return x.astype(f32).reshape(bsz, nc, chunk, h, *x.shape[3:])

    # q, k, v stay in the mixer's order, a position's heads side by side: the kernels read a chunk of a
    # head out of it, and only `_decayed_overlaps` and `_chunk_parts` take [chunks, B, H, Q, K] copies.
    # G leads with the chunks, as beta and what the halves hand on do; each half gets it under a name of its own
    q, k, v, beta = split(q), split(k), split(v), _lead(split(beta))
    run, run_again = running_sum(g, chunk)
    a, b = _overlaps(q, k, run, beta)
    p, o0, m, n = chunk_parts(q, k, v, run_again, beta, _unit_lower_inverse(a), b)
    return walk(p, o0, m, n).reshape(bsz, t, h, width)
