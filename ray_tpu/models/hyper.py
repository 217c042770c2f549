"""Manifold-constrained hyper-connections (mHC, arXiv:2512.24880 section 4, on Hyper-Connections, arXiv:2409.19606):
a residual stream of n = cfg.hc_mult copies of C = d_model channels a token, and what joins a part to it. Per token the
stream is X in R^{n x C}, carried FLAT, x = vec(X) = [X[0] ; .. ; X[n-1]] of n C channels ([B, T, n C]: a bfloat16
[B, T, n, C] would tile its 4 up to 16, four times the memory; C is whole 128-lane tiles, so a copy is a lane-aligned
slice and x is the coefficient product's operand as it stands). A part p (a layer's mixer, its feed-forward part) owns
phi_p [n C, 2n + n^2], a bias b_p [2n + n^2] and three scalars alpha_pre, alpha_post, alpha_res, held as ONE leaf
`<part>_hc` [n C + 2, 2n + n^2] = [phi ; b ; alpha_pre alpha_post alpha_res 0 ..] (a leaf of 3 or of 27 numbers is a row
of its own wherever gradients are compared a row a leaf, and the quotient of two errors of a few numbers has no bound:
such a row read 1.2 to 4.7 x the bfloat16 reference's error over seven seeds on the chip, PERF.md section 6, PR 62):

    r      = (mean(x^2) + norm_eps)^(-1/2)                      no learnable weight: it folds into phi
    m      = r * (phi^T x)                                      float32, from the bfloat16 stream and phi as every weight is used
    Hpre   = sigmoid(alpha_pre m[0:n] + b[0:n])
    Hpost  = 2 sigmoid(alpha_post m[n:2n] + b[n:2n])
    M0     = exp(clip(alpha_res mat(m[2n:]) + mat(b[2n:]), -hc_res_clamp, hc_res_clamp))     n x n, row-major
    cfg.hc_sinkhorn_iters times:  M <- M / (column sums + hc_eps);  M <- M / (row sums + hc_eps);   Hres = M
    y      = sum_i Hpre[i] X[i]                                 `read`: the part's input (its own norm follows, as always)
    X'[i]  = sum_j Hres[i, j] X[j] + Hpost[i] o                 `write`: o the part's output

The first layer's X is the embedding n times (`spread`); behind the last layer sum_i X[i] goes to the final norm and the
head (`gather`). Everything a token's coefficients pass through is float32 with the POSITIONS MINOR ([2n + n^2, B T]:
a float32 [B, T, n, n] pads its 4 x 4 64-fold, and twenty rounds kept for a backward pass would be a gigabyte a part);
the coefficients go to the two mixtures as one [B, T, 2n + n^2] array, whose columns a fusion broadcasts along C. No
float32 array of the stream's size is stored: the product reads the bfloat16 stream and accumulates in float32, r is a
reduction, a mixture is summed in float32 a channel and rounded once (tests/test_family_xing4_0.py holds the compiled step to it).

WHAT RUNS WHERE. A part goes through `enter` (the coefficients and its reading) and `write`. Where the shapes tile and
GSPMD partitions nothing (`takes_kernels`: the training cell), the passes over the stream are ops/hyper_mix.py's four
Pallas kernels, each reading the stream once and writing it at most once, positions minor as the compiled step holds it:
`hc_read_fwd` (r, the coefficient product, Hpre and y in one pass) and `hc_read_bwd` under `hc_pre`, `hc_write_fwd` and
`hc_write_bwd` under `hc_post`; XLA keeps what is [2n + n^2, B T] between them: Hpost and the logits (`hc_mix`) and the
projection (`hc_sinkhorn`). `enter` hands x on for `write` to take, so that the writing's cotangent for x comes back to
the entry's backward kernel as an operand and the stream's cotangent is summed in float32 and rounded ONCE; `spread`
and `gather` repeat the embedding and sum the copies the positions minor too, behind a pinned turn of the ACTIVATION
(59 MB, where XLA's layout assignment would turn the 235 MB stream), and y, the part's output and the two ends'
activations are turned outside `hc`, so that the parts' own fusions do not read as the hyper-connection's. Elsewhere
(a width that is no whole 128-lane tile, a partitioned program) the `jax.numpy` forms below run as XLA's fusions:
`coefficients`, `read` and `_write`, the reference the kernels are tested against (tests/test_hyper_mix.py). Which named
scope each equation lies under is SCOPES, by either path.
"""
from typing import Tuple

import jax
import jax.numpy as jnp

from ray_tpu.ops import hyper_mix
from ray_tpu.ops.quant import as_weight as _w
from ray_tpu.parallel.sharding import partitioned_by_gspmd

from .config import ModelConfig

# `hc` around every hyper-connection (inside its part's `attn` / `mlp`, or `embed` / `lm_head`), and inside it: the norm,
# the product and the sigmoids (by the kernels' path: phi's cast and reshaping, Hpost and the logits); the projection; the
# part's reading (and the embedding's repeat; by the kernels' path the entry's two kernels, which also make the norm, the
# product and Hpre); its writing (and the sum in front of the head; the writing's two kernels)
SCOPE, SCOPES = "hc", ("hc_mix", "hc_sinkhorn", "hc_pre", "hc_post")
PARTS = ("attn", "mlp")  # a layer's parts by the scope each runs under: its leaf is `<part>_hc`
AXES = {f"{part}_hc": (None, None) for part in PARTS}  # held whole: n C + 2 rows divide by no mesh axis, and it is 1.4 MB


def width(cfg: ModelConfig) -> int:
    """Coefficients a token and part: Hpre, Hpost and Hres."""
    return 2 * cfg.hc_mult + cfg.hc_mult ** 2


def n_params(cfg: ModelConfig) -> int:
    """What `init` makes for ONE part, counted (the alphas' row whole: its zeros are held too)."""
    return (cfg.hc_mult * cfg.d_model + 2) * width(cfg)


def init(key: jax.Array, cfg: ModelConfig, part: str) -> dict:
    """A part's hyper-connection, LIVE: phi ~ N(0, 1 / (n C)) makes m of unit variance on any stream (r x has unit
    mean square), the three alphas are 0.5 and the biases ~ N(0, 0.5^2), so that a token's logits have a standard
    deviation of ~0.5 about biases that differ: Hpre, Hpost and Hres all depend on the token and a fault in phi's path
    shows in the loss. (The papers start near the identity, alpha small and b large on Hres' diagonal: a trained state,
    where the dynamic part would be invisible to a comparison on seeded weights.)"""
    k_phi, k_b = jax.random.split(jax.random.fold_in(key, PARTS.index(part) + 1))
    d, w = cfg.hc_mult * cfg.d_model, width(cfg)
    return {f"{part}_hc": jnp.concatenate([
        jax.random.normal(k_phi, (d, w), jnp.float32) * d ** -0.5, 0.5 * jax.random.normal(k_b, (1, w), jnp.float32),
        jnp.zeros((1, w), jnp.float32).at[0, :3].set(0.5)])}


def parts_of(hc: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """A part's leaf [n C + 2, 2n + n^2] -> (phi [n C, 2n + n^2], b [2n + n^2], the three alphas)."""
    return hc[:-2], hc[-2], hc[-1, :3]


def spread(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The embedding [B, T, C] as the first layer's stream: every copy the embedding."""
    kernels = takes_kernels(x, cfg)
    if kernels:  # the embedding turned (outside `hc`, as `enter` turns y), then repeated as the first layer's kernels take the stream: no pass turns the stream
        x = hyper_mix.pinned(hyper_mix.turned(x))
    with jax.named_scope(SCOPE), jax.named_scope("hc_pre"):
        if kernels:
            return hyper_mix.turned(jnp.concatenate([x] * cfg.hc_mult, axis=1))
        return jnp.concatenate([x] * cfg.hc_mult, axis=-1)


def gather(x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """What the final norm and the head read of the last layer's stream: the sum of its copies, [B, T, C]."""
    with jax.named_scope(SCOPE), jax.named_scope("hc_post"):
        if not takes_kernels(x, cfg):
            return sum(c.astype(jnp.float32) for c in _copies(x, cfg)).astype(x.dtype)
        # summed the positions minor, as the last layer's kernel wrote it
        copies = hyper_mix.turned(x).reshape(x.shape[0], cfg.hc_mult, cfg.d_model, x.shape[1])
        total = copies.astype(jnp.float32).sum(1)
    # (rounded and turned outside `hc`: the final norm's fusions, forward and backward, take both into their bodies)
    return hyper_mix.turned(hyper_mix.pinned(total.astype(x.dtype)))


def _copies(x: jax.Array, cfg: ModelConfig):
    c = cfg.d_model
    return [x[..., i * c:(i + 1) * c] for i in range(cfg.hc_mult)]


def sinkhorn(logits: jax.Array, cfg: ModelConfig) -> jax.Array:
    """Logits [n, n, positions] (clipped) -> the doubly stochastic Hres [n, n, positions]: cfg.hc_sinkhorn_iters rounds,
    unrolled, of columns then rows. Differentiated as it stands: every round's values are positions-minor."""
    m = jnp.exp(logits)
    for _ in range(cfg.hc_sinkhorn_iters):
        m = m / (m.sum(0, keepdims=True) + cfg.hc_eps)
        m = m / (m.sum(1, keepdims=True) + cfg.hc_eps)
    return m


@jax.custom_vjp
def _product(x: jax.Array, phi: jax.Array) -> jax.Array:
    """x [B, T, n C] times phi [n C, 2n + n^2], both in the activations' type, accumulated and handed on in float32.
    The backward rule is written out so that the stream's cotangent leaves its product in the stream's type: left to
    the transpose of a float32 result it is a float32 [B, T, n C] array (470 MB at [1, 8192] x 14,336) that a second
    pass rounds."""
    return jnp.einsum("btd,dk->btk", x, phi, preferred_element_type=jnp.float32)


def _product_bwd(kept, dm):
    x, phi = kept
    dm = dm.astype(x.dtype)  # as every product's cotangent in the model is in the activations' type
    return (jnp.einsum("btk,dk->btd", dm, phi),
            jnp.einsum("btd,btk->dk", x, dm, preferred_element_type=jnp.float32).astype(phi.dtype))


_product.defvjp(lambda x, phi: (_product(x, phi), (x, phi)), _product_bwd)


def coefficients(x: jax.Array, hc: jax.Array, cfg: ModelConfig) -> Tuple[jax.Array, jax.Array]:
    """The stream x [B, T, n C] and a part's leaf -> (its coefficients [B, T, 2n + n^2] float32: Hpre | Hpost | Hres
    row-major; the largest distance of Hres' row sums and of its column sums from 1, [2], which no gradient reaches)."""
    n, (b, t, _) = cfg.hc_mult, x.shape
    with jax.named_scope("hc_mix"):
        phi, bias, alpha = parts_of(hc)
        r = jax.lax.rsqrt(jnp.mean(jnp.square(x.astype(jnp.float32)), -1) + cfg.norm_eps)
        m = _product(x, _w(phi, x.dtype)) * r[..., None]
        m = m.reshape(b * t, -1).T  # positions minor from here on
        alpha, bias = alpha.astype(jnp.float32), bias.astype(jnp.float32)[:, None]
        pre = jax.nn.sigmoid(alpha[0] * m[:n] + bias[:n])
        post, logits = _post_and_logits(m, alpha, bias, cfg)
    with jax.named_scope("hc_sinkhorn"):
        res, err = _projected(logits, cfg)
        coef = jnp.concatenate([pre, post, res]).T.reshape(b, t, -1)
    return coef, err


def _post_and_logits(m: jax.Array, alpha: jax.Array, bias: jax.Array, cfg: ModelConfig):
    """m [2n + n^2, positions], the alphas [3] and the bias [2n + n^2, 1], float32 -> (Hpost [n, positions], Hres'
    clipped logits [n^2, positions])."""
    n = cfg.hc_mult
    post = 2 * jax.nn.sigmoid(alpha[1] * m[n:2 * n] + bias[n:2 * n])
    return post, jnp.clip(alpha[2] * m[2 * n:] + bias[2 * n:], -cfg.hc_res_clamp, cfg.hc_res_clamp)


def _projected(logits: jax.Array, cfg: ModelConfig):
    """Logits [n^2, positions] -> (Hres [n^2, positions] row-major, the projection's error [2])."""
    n = cfg.hc_mult
    res = sinkhorn(logits.reshape(n, n, -1), cfg)
    sums = jax.lax.stop_gradient(res)
    err = jnp.stack([jnp.abs(sums.sum(1) - 1).max(), jnp.abs(sums.sum(0) - 1).max()])
    return res.reshape(n * n, -1), err


def takes_kernels(x: jax.Array, cfg: ModelConfig) -> bool:
    """Whether the passes over the stream x [B, T, n C] go to ops/hyper_mix.py's kernels: the shapes tile and no mesh
    axis is GSPMD's to partition (a Pallas call there needs a `shard_map` around it)."""
    return hyper_mix.supports(cfg.hc_mult, cfg.d_model, x.shape[1]) and not partitioned_by_gspmd()


def enter(x: jax.Array, hc: jax.Array, cfg: ModelConfig):
    """A part's entry (called inside the part's scope; `hc` is opened here): the stream x [B, T, n C] and the part's
    leaf -> (y [B, T, C], what the part reads; its coefficients, for `write` alone to read; the projection's error [2];
    x again, which `write` is to take: by the kernels' path the writing's cotangent for x then reaches the entry's
    backward kernel as an operand)."""
    with jax.named_scope(SCOPE):
        if not takes_kernels(x, cfg):
            coef, err = coefficients(x, hc, cfg)
            return read(x, coef, cfg), coef, err, x
        n = cfg.hc_mult
        with jax.named_scope("hc_mix"):
            phi, bias, alpha = parts_of(hc)
            alpha, bias = alpha.astype(jnp.float32), bias.astype(jnp.float32)[:, None]
            ab = jnp.concatenate([jnp.broadcast_to(alpha[0], (n, 1)), bias[:n]], axis=1)
            phi = _w(phi, x.dtype)
        with jax.named_scope("hc_pre"):
            y, m, x = hyper_mix.read(x, phi, ab, n, cfg.norm_eps)
        with jax.named_scope("hc_mix"):
            post, logits = _post_and_logits(m, alpha, bias, cfg)
        with jax.named_scope("hc_sinkhorn"):
            res, err = _projected(logits, cfg)
            coef = jnp.concatenate([post, res])  # [n + n^2, B T]: the rows the writing's kernels broadcast along C
    return hyper_mix.turned(y), coef, err, x  # (the turn outside `hc`: the fusion that reads y through it is the part's)


def read(x: jax.Array, coef: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The part's input y [B, T, C]: the copies of x weighted by Hpre, summed in float32, rounded once."""
    with jax.named_scope("hc_pre"):
        return sum(coef[..., i:i + 1] * c.astype(jnp.float32) for i, c in enumerate(_copies(x, cfg))).astype(x.dtype)


def write(x: jax.Array, out: jax.Array, coef: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The stream behind the part, [B, T, n C], from what `enter` handed on (x and coef) and the part's output
    (called inside the part's scope; `hc` is opened here)."""
    if not takes_kernels(x, cfg):
        with jax.named_scope(SCOPE):
            return _write(x, out, coef, cfg)
    out = hyper_mix.turned(out)  # (outside `hc`: the product that writes it through the turn is the part's)
    with jax.named_scope(SCOPE), jax.named_scope("hc_post"):
        return hyper_mix.write(x, out, coef, cfg.hc_mult)


def _write(x: jax.Array, out: jax.Array, coef: jax.Array, cfg: ModelConfig) -> jax.Array:
    """The stream behind the part, [B, T, n C]: copy i is sum_j Hres[i, j] X[j] + Hpost[i] out."""
    n = cfg.hc_mult
    with jax.named_scope("hc_post"):
        copies, out = [c.astype(jnp.float32) for c in _copies(x, cfg)], out.astype(jnp.float32)
        at = lambda k: coef[..., k:k + 1]  # noqa: E731
        return jnp.concatenate([
            (sum(at(2 * n + i * n + j) * c for j, c in enumerate(copies)) + at(n + i) * out).astype(x.dtype)
            for i in range(n)], axis=-1)
