"""The Kimi-Delta-Attention mixer (Kimi Linear, arXiv:2510.26692) as solar_open2 uses it:
one layer of a pattern of single-part layers (config.layer_pattern, `K`), behind its own
norm and residual. Per head, keys and values 128 wide:

    [q~ | k~ | v~] = conv(RMSNorm(x) W_qkv)    causal, depthwise, `kda_conv_taps` taps, no bias
    q = l2norm(silu(q~)) * 128^-1/2,  k = l2norm(silu(k~)),  v = silu(v~)
    g = -exp(A_log) * softplus(W_f_up (W_f_down u) + dt_bias)    a CHANNEL [H, 128], <= 0; float32
    beta = 2 * sigmoid(u W_beta)               a head (the 2: kda_neg_eigval); float32
    o = scan(q, k, v, g, beta)                 ops/kda.py: the gated delta rule, in chunks
    y = (RMSNorm_head(o) * sigmoid(W_g_up (W_g_down u))) W_o     one [128] norm weight for all heads

The count is what the layer HOLDS. A tensor-parallel share of a published layer is fewer
heads of the same width: q, k, v, the convolution, beta, the two up-projections, dt_bias
and A_log divide by heads, W_o by its rows; the two low-rank down-projections (rank 128,
0.5 M each) and the norm weight are whole in every share. 8 shares of 8 heads add up to
the layer of 64 through W_o (tests/test_family_solar_open2.py).

The second and third lines (convolution, silu, the two L2 norms, q's scale, the one rounding
to the activation's type) are ONE pass over HBM each way where ops/short_conv.py's two
Pallas kernels tile the shape (a head width of whole 128-lane registers, at most 9 taps, no
mesh axis left to GSPMD: the Solar-Open2 cell's 8 heads of 128, 4 taps): q, k and v in one
call a pass, the results float32 arrays of values rounded to the activation's type, so that
the scan's float32 cotangents come back unrounded (ops/short_conv.py's docstring has what
that is worth, and why the cell's gradient comparison reads 0.876 where the plain form's
program read 0.848). Anywhere else `_conv_silu_norm` runs, the plain form: a float32
copy of q|k|v padded by the taps, the taps' shifted products, silu, the norms and the casts,
a pass of XLA's each (tier-1's width of 16; what the kernels are tested against).

The fourth line is handed to the scan as what it is made of (`ops.kda.LogDecay`: the low-rank
product in the activation's type, dt_bias, A_log): where the scan's kernels run, the one that sums g
over a chunk computes it too, from the product as XLA wrote it, and g crosses HBM in no forward pass
(ops/kda_prefix.py); anywhere else `kda_prefix.log_decay` runs, that line as it stands.

Leaves: kda_norm [D], kda_qkv [D, 3, H, K], kda_conv [taps, 3, H, K] (the last tap is the
current position's), kda_f_down [D, r], kda_f_up [r, H, K], kda_dt_bias [H, K], kda_A_log
[H], kda_beta [D, H], kda_g_down [D, r], kda_g_up [r, H, K], kda_o_norm [K], kda_out
[H, K, D]. Packed documents and a KV cache are refused (llama._block): state and
convolution would have to start again at a boundary, and no recurrent state is kept.

Under remat `full` a layer keeps `[q~ | k~ | v~]` before the convolution, [B, T, 3 H K], by name
(`IN_PROJ_NAME`, beside the layer's input: llama._maybe_remat): ONE array as the product wrote it
and as the convolution reads it, forward and backward, so the rematerialised layer runs no q|k|v
product. It is named BEHIND the reshape: named as [B, T, 3, H, K] the TPU compiler stored it with
the positions minor and copied it into the kernels' layout twice a part (tests/test_tpu_compile.py).
The norm, the low-rank pairs, beta (a tenth of the part's input work), the convolution, the scan
(its inverses kept under every policy), the gate and W_o are made again.
"""
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import kda, short_conv
from ray_tpu.ops.quant import as_weight as _w

from .attn import rms_norm
from .config import ModelConfig
from .ssm import _causal_conv

# what llama.py's table of layer kinds reads of a mixer (its comment says what each is);
# kept under every remat policy: the inverses of the scan's triangular systems; under `full`
# (the 'dots' policies keep every product): q | k | v as the input product wrote them
IN_PROJ_NAME = "kda_qkv_proj"  # [B, T, 3 H K], as the convolution reads it
LEAF, RECURRENT, SCOPE = "kda_qkv", "Kimi-Delta-Attention", "attn"
KEPT = {"every": (kda.INVERSE_NAME,), "full": (IN_PROJ_NAME,)}
AXES = {
    "kda_norm": ("embed",), "kda_qkv": ("embed", None, "heads", "head_dim"),
    "kda_conv": (None, None, "heads", "head_dim"),
    "kda_f_down": ("embed", None), "kda_f_up": (None, "heads", "head_dim"),
    "kda_dt_bias": ("heads", "head_dim"), "kda_A_log": ("heads",), "kda_beta": ("embed", "heads"),
    "kda_g_down": ("embed", None), "kda_g_up": (None, "heads", "head_dim"),
    "kda_o_norm": ("head_dim",), "kda_out": ("heads", "head_dim", "embed"),
}


def init(key: jax.Array, cfg: ModelConfig):
    """Seeded weights whose decays lie in a trained layer's range and not all at 0 or 1, as
    ssm.init's: A_log the log of uniform [1, 16] a head, dt_bias the inverse softplus of a
    log-uniform draw in [ssm_dt_min, ssm_dt_max] floored at ssm_dt_floor a channel."""
    d, h, width, rank, taps = cfg.d_model, cfg.kda_n_heads, cfg.kda_head_dim, cfg.kda_rank, cfg.kda_conv_taps
    ks = jax.random.split(key, 10)

    def normal(k, shape, scale):
        return jax.random.normal(k, shape, jnp.float32) * scale

    dt = jnp.exp(jax.random.uniform(ks[0], (h, width), jnp.float32, jnp.log(cfg.ssm_dt_min),
                                    jnp.log(cfg.ssm_dt_max)))
    dt = jnp.maximum(dt, cfg.ssm_dt_floor)
    return {
        "kda_norm": jnp.ones((d,), jnp.float32),
        "kda_qkv": normal(ks[1], (d, 3, h, width), d**-0.5),
        "kda_conv": normal(ks[2], (taps, 3, h, width), taps**-0.5),
        "kda_f_down": normal(ks[3], (d, rank), d**-0.5),
        "kda_f_up": normal(ks[4], (rank, h, width), rank**-0.5),
        "kda_dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) == dt
        "kda_A_log": jnp.log(jax.random.uniform(ks[5], (h,), jnp.float32, 1.0, 16.0)),
        "kda_beta": normal(ks[6], (d, h), d**-0.5),
        "kda_g_down": normal(ks[7], (d, rank), d**-0.5),
        "kda_g_up": normal(ks[8], (rank, h, width), rank**-0.5),
        "kda_o_norm": jnp.ones((width,), jnp.float32),
        "kda_out": normal(ks[9], (h, width, d), (2 * cfg.n_layers * h * width) ** -0.5),
    }


def n_params(cfg: ModelConfig) -> int:
    d, inner = cfg.d_model, cfg.kda_d_inner
    return (d + d * 3 * inner + cfg.kda_conv_taps * 3 * inner + d * cfg.kda_n_heads  # the norm; q k v, beta
            + 2 * (d + inner) * cfg.kda_rank  # the decay's and the gate's low-rank pairs
            + cfg.kda_n_heads + inner + cfg.kda_head_dim + inner * d)  # A_log, dt_bias, norm, W_o


def _l2norm(x: jax.Array) -> jax.Array:
    return x * jax.lax.rsqrt(jnp.sum(jnp.square(x), axis=-1, keepdims=True) + 1e-6)


def _conv_silu_norm(x: jax.Array, w: jax.Array, width: int):
    """x [B, T, 3 C] (q | k | v before the convolution), w [taps, 3 C] -> q, k, v [B, T, C] in
    x's type, q and k of unit length over every `width` channels (a head) and q scaled: the
    plain form, a pass of XLA's over HBM an operation. What ops/short_conv.py's kernels
    compute in one, and what tier-1 holds them to."""
    bsz, t, channels = x.shape
    a = jax.nn.silu(_causal_conv(x, w, 0.0)).reshape(bsz, t, 3, channels // (3 * width), width)
    q, k, v = jnp.moveaxis(a, 2, 0)
    q, k = _l2norm(q) * width**-0.5, _l2norm(k)
    return tuple(a.astype(x.dtype).reshape(bsz, t, channels // 3) for a in (q, k, v))


def mixer(x: jax.Array, lp, cfg: ModelConfig) -> jax.Array:
    """x [B, T, D] -> the layer's output (llama._block adds it to x)."""
    dt_, f32 = x.dtype, jnp.float32
    bsz, t, _ = x.shape
    h, width = cfg.kda_n_heads, cfg.kda_head_dim
    channels = 3 * h * width
    with jax.named_scope("kda_in_proj"):
        u = rms_norm(x, lp["kda_norm"], cfg.norm_eps)
        qkv = jnp.einsum("btd,dphk->btphk", u, _w(lp["kda_qkv"], dt_)).reshape(bsz, t, channels)
        qkv = checkpoint_name(qkv, IN_PROJ_NAME)
        decay = jnp.einsum("btr,rhk->bthk", jnp.einsum("btd,dr->btr", u, _w(lp["kda_f_down"], dt_)),
                           _w(lp["kda_f_up"], dt_))
        gate = jnp.einsum("btr,rhk->bthk", jnp.einsum("btd,dr->btr", u, _w(lp["kda_g_down"], dt_)),
                          _w(lp["kda_g_up"], dt_))
        beta = jnp.einsum("btd,dh->bth", u, _w(lp["kda_beta"], dt_))
    with jax.named_scope("kda_conv"):
        conv_w = lp["kda_conv"].reshape(-1, channels)
        if short_conv.takes_kernels(channels, 3, width, conv_w.shape[0]):
            q, k, v = short_conv.short_conv(qkv, conv_w, None, (width**-0.5, 1.0, None), width)
        else:
            q, k, v = _conv_silu_norm(qkv, conv_w, width)
    with jax.named_scope("kda_scan"):
        q, k, v = (a.reshape(bsz, t, h, width) for a in (q, k, v))
        g = kda.LogDecay(decay, lp["kda_dt_bias"], lp["kda_A_log"])  # made where the scan sums it
        beta = jax.nn.sigmoid(beta.astype(f32)) * (2.0 if cfg.kda_neg_eigval else 1.0)
        o = kda.kda_scan(q, k, v, g, beta, cfg.kda_chunk)
    with jax.named_scope("kda_norm_gate"):
        o = o * jax.lax.rsqrt(jnp.mean(jnp.square(o), axis=-1, keepdims=True) + cfg.norm_eps)
        o = (o * lp["kda_o_norm"] * jax.nn.sigmoid(gate.astype(f32))).astype(dt_)
    with jax.named_scope("kda_out_proj"):
        return jnp.einsum("bthk,hkd->btd", o, _w(lp["kda_out"], dt_))
