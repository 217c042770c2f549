"""A chunk's four matrices (ops/kda.py's P, O0, M, N) as two Pallas TPU kernels behind one
`jax.custom_vjp`: for a chunk of a head's q, k, v, G = the running sum of the log decays
inside the chunk [Q, K], beta [Q], T = (I + A)^-1 and b (the queries' decayed overlaps with
the keys) [Q, Q], all float32,

    [W | U0] = T (beta [k exp G | v])            Kend_s = k_s exp(G_Q - G_s)
    P = q exp G - b W      O0 = b U0      M = Diag(exp G_Q) - Kend^T W      N = Kend^T U0

`kda._chunk_parts` letter for letter (ops/kda.py's docstring has the algebra): the same
three products [Q, Q] x [Q, K + V] of float32 operands at the highest precision (Mosaic's
contract_precision<fp32>, six passes of the MXU), the same two decays, each the exponential
of a non-positive number. What differs is where the intermediates live: a grid step holds a
chunk of `kda_overlaps._per_step` heads and walks the heads in a loop (one body, traced once), and a
head's exp G, beta [k exp G | v], [W | U0] and Kend are made, used and dropped in fast
memory. Nothing with the extents [Q, K + V] reaches HBM in either pass; the forward kernel
writes P, O0, M, N and nothing else, the chunks leading, [chunks, B, H, ., .]: what the walk
over the chunks reads (ops/kda_walk.py's kernels a chunk of a few heads a grid step, the state
in fast memory; `kda._walk`'s `lax.scan` elsewhere), and the order its backward pass hands
dP, dO0, dM, dN back in (dO0 is o's cotangent, which that kernel writes with the chunks leading).

q, k and v are read where the mixer wrote them, [B, chunks x Q, H x K] with a position's
heads side by side (`kda_overlaps.rows_block`: a step's block is the chunk's Q rows of its
heads' lanes), and dq, dk, dv are written there: XLA makes no [chunks, B, H, Q, K] copy of
them or of their gradients for either half's kernels (five transposes a pass before). G
comes with the chunks leading, as beta, T and b do: ops/kda_prefix.py's kernel writes it so
(PR 64; before it XLA summed it in the mixer's order and transposed it once; summed or
reverse-summed behind a reshape to [.., Q, H x K] its `reduce-window` took 1.9 ms a call where
that took 0.1: PERF.md section 6, PR 51).

The backward kernel keeps nothing but the inputs: it makes [W | U0] again (one product) and
from dP, dO0, dM, dN writes dq, dk, dv, dG, dbeta, dT, db with six products more. With
X = [k exp G | v], R = beta X, S = T R, dY = [-dP | dO0], dZ = [-dM | dN]:

    dS = b^T dY + Kend dZ       db = dY S^T       dKend = S dZ^T       dT = dS R^T
    dR = T^T dS                 dbeta = sum_c dR X                     dX = beta dR
    dq = dP exp G      dv = dX_v      dk = dX_k exp G + dKend exp(G_Q - G)
    dG = (dP q + dX_k k) exp G - dKend Kend,  and into the last row, G_Q's: the diagonal of dM
         times exp G_Q (M's own) and the column sums of dKend Kend (every Kend_s reads G_Q)

The products that contract a left operand's rows (Kend^T S, b^T dY, T^T dS) get the operand
transposed in fast memory ([Q, Q] or [Q, K] float32, the transpose unit's work, which the
products do not wait on); nothing comes transposed from XLA. beta is read as a row of lanes
[1, Q] and dbeta written as one; a column [Q, 1] is made from a row, and back, through the
diagonal of a [Q, Q] select and one sum (exact: a value plus zeros).

VMEM a grid step: forward 10 blocks of 64 KB a head at 128 x 128 (q, k, v, G, T, b in, P,
O0, M, N out) and beta's row, twice for the pipeline's two buffers: 1.3 MB a head; backward
16 blocks (the four cotangents in, six gradients and dbeta's row out), 2.1 MB.
`kda_overlaps._per_step` (beside `rows_block`: one rule for all eight kernels) takes as many of a
chunk's heads a step as `_VMEM_BLOCKS` allows (4 at 128 x 128).

`kda_overlaps.supports` says which shapes go to the kernels (ops/kda.py's `takes_kernels`
routes both halves by it); off a TPU they run in Pallas' interpreter.
"""
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from . import flash_attention as _fa
from .kda_overlaps import _NN, _NT, _dot, _per_step, rows_block


def _eye(n: int):
    return jax.lax.broadcasted_iota(jnp.int32, (n, n), 0) == jax.lax.broadcasted_iota(jnp.int32, (n, n), 1)


def _column(row):
    """A row of lanes [1, n] as a column [n, 1]."""
    return jnp.sum(jnp.where(_eye(row.shape[1]), row, 0.0), 1, keepdims=True)


def _diagonal_row(x):
    """The diagonal of x [n, n] as a row of lanes [1, n]; of a column [n, 1], the column as a row."""
    return jnp.sum(jnp.where(_eye(x.shape[0]), x, 0.0), 0, keepdims=True)


def _solved(k, v, g, beta, t):
    """exp G, X = [k exp G | v], R = beta X and S = T R = [W | U0] of a chunk; beta a column."""
    from_start = jnp.exp(g)
    x = jnp.concatenate([k * from_start, v], -1)
    rhs = x * beta
    return from_start, x, rhs, _dot(t, rhs, _NN)


def _fwd_kernel(q_ref, k_ref, v_ref, beta_ref, g_ref, t_ref, b_ref, p_ref, o0_ref, m_ref, n_ref):
    per, size = beta_ref.shape
    width = k_ref.shape[1] // per

    def head(j, _):
        lanes = pl.ds(pl.multiple_of(j * width, width), width)
        q, k, g = q_ref[:, lanes], k_ref[:, lanes], g_ref[j]
        from_start, _, _, solved = _solved(k, v_ref[:, lanes], g, _column(beta_ref[pl.ds(j, 1), :]), t_ref[j])
        k_end = k * jnp.exp(g[size - 1:] - g)
        # [b ; Kend^T] [W | U0]: the outputs' and the state's products share their right operand
        both = _dot(jnp.concatenate([b_ref[j], k_end.T], 0), solved, _NN)
        p_ref[j] = q * from_start - both[:size, :width]
        o0_ref[j] = both[:size, width:]
        m_ref[j] = jnp.where(_eye(width), from_start[size - 1:], 0.0) - both[size:, :width]
        n_ref[j] = both[size:, width:]

    jax.lax.fori_loop(0, per, head, None)


def _bwd_kernel(q_ref, k_ref, v_ref, beta_ref, g_ref, t_ref, b_ref, dp_ref, do0_ref, dm_ref, dn_ref,
                dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dt_ref, db_ref):
    per, size = beta_ref.shape
    width = k_ref.shape[1] // per
    last = jax.lax.broadcasted_iota(jnp.int32, (size, 1), 0) == size - 1

    def head(j, _):
        lanes = pl.ds(pl.multiple_of(j * width, width), width)
        q, k, g, t, dp, dm = q_ref[:, lanes], k_ref[:, lanes], g_ref[j], t_ref[j], dp_ref[j], dm_ref[j]
        beta = _column(beta_ref[pl.ds(j, 1), :])
        from_start, x, rhs, solved = _solved(k, v_ref[:, lanes], g, beta, t)
        to_end = jnp.exp(g[size - 1:] - g)
        k_end = k * to_end
        dy = jnp.concatenate([-dp, do0_ref[j]], -1)  # of b S, [Q, K + V]
        dz = jnp.concatenate([-dm, dn_ref[j]], -1)  # of Kend^T S, [K, K + V]
        d_solved = _dot(jnp.concatenate([b_ref[j].T, k_end], -1), jnp.concatenate([dy, dz], 0), _NN)
        db_ref[j] = _dot(dy, solved, _NT)
        d_k_end = _dot(solved, dz, _NT)
        dt_ref[j] = _dot(d_solved, rhs, _NT)
        d_rhs = _dot(t.T, d_solved, _NN)
        dbeta_ref[pl.ds(j, 1), :] = _diagonal_row(jnp.sum(d_rhs * x, -1, keepdims=True))
        dx = d_rhs * beta
        dv_ref[:, lanes] = dx[:, width:]
        dq_ref[:, lanes] = dp * from_start
        dk_ref[:, lanes] = dx[:, :width] * from_start + d_k_end * to_end
        through_end = d_k_end * k_end  # d(G_Q - G_s) of Kend_s
        dg = (dp * q + dx[:, :width] * k) * from_start - through_end
        at_end = _diagonal_row(dm) * from_start[size - 1:] + jnp.sum(through_end, 0, keepdims=True)
        dg_ref[j] = jnp.where(last, dg + at_end, dg)

    jax.lax.fori_loop(0, per, head, None)


def _call(kernel, name: str, rows, beta, chunked, outs):
    """`_per_step` heads of a chunk a grid step: `rows` [B, chunks, Q, H, K] through
    `kda_overlaps.rows_block`, beta [chunks, B, H, Q] in rows of lanes, `chunked` [chunks, B, H, ., .]
    in the grid's own order -> results by `outs`: "rows", "beta" or the two extents of a chunked one."""
    batch, chunks, size, heads, width = rows[0].shape
    n = chunks * batch * heads
    extents = [x.shape[3:] for x in chunked] + [e for e in outs if not isinstance(e, str)]
    per = _per_step(heads, 4 * (size * width * (len(rows) + outs.count("rows")) + sum(math.prod(e) for e in extents)))
    shape = {"rows": (batch * chunks, size, heads * width), "beta": (n // per, per, size)}
    spec = {"rows": rows_block(size, width, per, batch, chunks, heads),
            "beta": pl.BlockSpec((None, per, size), lambda i: (i, 0, 0))}  # [N / per, per, Q]: a step's rows whole
    block = lambda e: spec.get(e) or pl.BlockSpec((per, *e), lambda i: (i, 0, 0))  # noqa: E731
    results = pl.pallas_call(
        kernel, name=name, interpret=_fa._interpret(), grid=(n // per,),
        in_specs=[spec["rows"]] * len(rows) + [spec["beta"]] + [block(x.shape[3:]) for x in chunked],
        out_specs=[block(e) for e in outs],
        out_shape=[jax.ShapeDtypeStruct(shape.get(e) or (n, *e), jnp.float32) for e in outs],
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)))(
            *[x.reshape(shape["rows"]) for x in rows], beta.reshape(shape["beta"]),
            *[x.reshape(n, *x.shape[3:]) for x in chunked])
    like = {"rows": rows[0].shape, "beta": beta.shape}
    return [r.reshape(like.get(e) or (chunks, batch, heads, *e)) for r, e in zip(results, outs)]


@jax.custom_vjp
def parts(q, k, v, run, beta, inverse, b):
    """(P, O0, M, N) [chunks, B, H, ., .] of the module's docstring from q, k, v [B, chunks, Q, H, K]
    (the positions in the mixer's order), run [chunks, B, H, Q, K], beta [chunks, B, H, Q], inverse
    and b [chunks, B, H, Q, Q], float32."""
    return _parts_fwd(q, k, v, run, beta, inverse, b)[0]


def _parts_fwd(*kept):
    q, k, v, run, beta, inverse, b = kept
    size, width = run.shape[-2:]
    outs = ((size, width), (size, width), (width, width), (width, width))
    return tuple(_call(_fwd_kernel, "kda_parts_fwd", (q, k, v), beta, (run, inverse, b), outs)), kept


def _parts_bwd(kept, cts):
    q, k, v, run, beta, inverse, b = kept
    size, width = run.shape[-2:]
    return tuple(_call(_bwd_kernel, "kda_parts_bwd", (q, k, v), beta, (run, inverse, b, *cts),
                       ("rows",) * 3 + ((size, width), "beta", (size, size), (size, size))))


parts.defvjp(_parts_fwd, _parts_bwd)
