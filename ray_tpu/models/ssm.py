"""The Mamba-2 mixer (arXiv:2405.21060) as nemotron_h states it: one layer of a pattern
of single-part layers (config.layer_pattern, `M`), behind its own norm and residual.

    [z | xBC | dt] = RMSNorm(x) W_in          widths d_inner, d_inner + 2 G N, H; no bias
    xBC = silu(conv(xBC) + b)                 causal, depthwise, `ssm_conv_taps` taps
    x [T, H, P], B [T, G, N], C [T, G, N] = split(xBC)
    dt = softplus(dt + dt_bias), A = -exp(A_log)          a head; float32
    y = scan(x, dt, A, B, C) + D x            ops/ssd.py: the recurrence, in chunks
    y = RMSNorm_group(y * silu(z)) W_out      gate first, then a norm over each group's
                                              d_inner / G channels, a weight a channel

The counts are what the layer HOLDS. A tensor-parallel share of a published layer is
fewer heads and groups of the same widths (H / G heads read one group, so heads and
groups divide together, and the grouped norm is a share's own): 8 shares of 16 heads
and 1 group add up to the layer of 128 heads and 8 groups through W_out
(tests/test_family_nemotron_h.py).

Leaves, as the published checkpoint lays them out: in_proj [D, 2 d_inner + 2 G N + H],
conv_w [taps, d_inner + 2 G N] (the last tap is the current position's), conv_b,
dt_bias / A_log / D [H], gate_norm [d_inner], out_proj [d_inner, D], ssm_norm [D].
Packed documents are refused (llama._block): state and convolution would have to start
again at a boundary.

Under remat `full` a layer keeps `[z | xBC | dt]` [B, T, 2 d_inner + 2 G N + H] by name
(`IN_PROJ_NAME`, beside the layer's input: llama._maybe_remat): ONE array as the product wrote
it, which z, xBC, dt are slices of forward and backward, so the rematerialised layer runs no
input product. The norm, the convolution, the scan, the gate and W_out are made again.
"""
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops import ssd
from ray_tpu.ops.quant import as_weight as _w

from .attn import rms_norm
from .config import ModelConfig

# what llama.py's table of layer kinds reads of a mixer (its comment says what each is)
IN_PROJ_NAME = "ssm_zxbcdt"  # [z | xBC | dt] as the input product wrote it, [B, T, 2 d_inner + 2 G N + H]
LEAF, RECURRENT, SCOPE, KEPT = "in_proj", "Mamba-2", None, {"full": (IN_PROJ_NAME,)}
AXES = {
    "ssm_norm": ("embed",), "in_proj": ("embed", None), "conv_w": (None, None), "conv_b": (None,),
    "dt_bias": (None,), "A_log": (None,), "D": (None,), "gate_norm": (None,),
    "out_proj": (None, "embed"),
}


def init(key: jax.Array, cfg: ModelConfig):
    """Seeded weights whose decays lie in a trained layer's range and not all at 0 or 1:
    A_log the log of uniform [1, 16]; dt_bias the inverse softplus of a log-uniform draw
    in [ssm_dt_min, ssm_dt_max] floored at ssm_dt_floor (Mamba-2's own initialisation);
    D = 1."""
    d, h, taps = cfg.d_model, cfg.ssm_n_heads, cfg.ssm_conv_taps
    d_in, conv_dim = cfg.ssm_d_inner, cfg.ssm_conv_dim
    ks = jax.random.split(key, 5)
    dt = jnp.exp(jax.random.uniform(ks[2], (h,), jnp.float32, jnp.log(cfg.ssm_dt_min),
                                    jnp.log(cfg.ssm_dt_max)))
    dt = jnp.maximum(dt, cfg.ssm_dt_floor)
    return {
        "ssm_norm": jnp.ones((d,), jnp.float32),
        "in_proj": jax.random.normal(ks[0], (d, d_in + conv_dim + h), jnp.float32) * d**-0.5,
        "conv_w": jax.random.normal(ks[1], (taps, conv_dim), jnp.float32) * taps**-0.5,
        "conv_b": jnp.zeros((conv_dim,), jnp.float32),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),  # softplus(dt_bias) == dt
        "A_log": jnp.log(jax.random.uniform(ks[3], (h,), jnp.float32, 1.0, 16.0)),
        "D": jnp.ones((h,), jnp.float32),
        "gate_norm": jnp.ones((d_in,), jnp.float32),
        "out_proj": jax.random.normal(ks[4], (d_in, d), jnp.float32) * (2 * cfg.n_layers * d_in) ** -0.5,
    }


def n_params(cfg: ModelConfig) -> int:
    d = cfg.d_model
    return (d + d * (cfg.ssm_d_inner + cfg.ssm_conv_dim + cfg.ssm_n_heads)  # the norm; z | xBC | dt
            + (cfg.ssm_conv_taps + 1) * cfg.ssm_conv_dim + 3 * cfg.ssm_n_heads
            + cfg.ssm_d_inner + cfg.ssm_d_inner * d)


def _causal_conv(x: jax.Array, w: jax.Array, bias: jax.Array) -> jax.Array:
    """x [B, T, C], w [taps, C]: channel by channel, y_t = sum_k w_k x_{t - (taps-1) + k} + b,
    zeros before the sequence. float32."""
    taps, t = w.shape[0], x.shape[1]
    padded = jnp.pad(x.astype(jnp.float32), ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(padded[:, k:k + t] * w[k] for k in range(taps)) + bias


def mixer(x: jax.Array, lp, cfg: ModelConfig) -> jax.Array:
    """x [B, T, D] -> the layer's output (llama._block adds it to x)."""
    dt_ = x.dtype
    bsz, t, _ = x.shape
    h, p, g, n = cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_n_groups, cfg.ssm_state
    d_in, conv_dim = cfg.ssm_d_inner, cfg.ssm_conv_dim
    with jax.named_scope("ssm_in_proj"):
        u = rms_norm(x, lp["ssm_norm"], cfg.norm_eps)
        joined = checkpoint_name(jnp.einsum("btd,de->bte", u, _w(lp["in_proj"], dt_)), IN_PROJ_NAME)
        z, xbc, dt = jnp.split(joined, [d_in, d_in + conv_dim], axis=-1)
    with jax.named_scope("ssm_conv"):
        xbc = jax.nn.silu(_causal_conv(xbc, lp["conv_w"], lp["conv_b"])).astype(dt_)
    with jax.named_scope("ssm_scan"):
        xs, b, c = jnp.split(xbc, [d_in, d_in + g * n], axis=-1)
        xs = xs.reshape(bsz, t, h, p)
        dt = jax.nn.softplus(dt.astype(jnp.float32) + lp["dt_bias"])
        y = ssd.ssd_scan(xs, dt, -jnp.exp(lp["A_log"]), b.reshape(bsz, t, g, n),
                         c.reshape(bsz, t, g, n), cfg.ssm_chunk)
        y = y + lp["D"][:, None] * xs.astype(jnp.float32)
    with jax.named_scope("ssm_norm"):
        y = y.reshape(bsz, t, g, d_in // g) * jax.nn.silu(z.astype(jnp.float32)).reshape(bsz, t, g, d_in // g)
        y = y * jax.lax.rsqrt(jnp.mean(jnp.square(y), axis=-1, keepdims=True) + cfg.norm_eps)
        y = (y.reshape(bsz, t, d_in) * lp["gate_norm"]).astype(dt_)
    with jax.named_scope("ssm_out_proj"):
        return jnp.einsum("bte,ed->btd", y, _w(lp["out_proj"], dt_))
