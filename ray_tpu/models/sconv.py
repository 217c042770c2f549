"""The gated short convolution as lfm2 / lfm2_moe use it (the family's `Lfm2ShortConv`):
one layer of a pattern of single-part layers (config.layer_pattern, `C`), behind its own
norm and residual. All three parts d_model wide, no bias anywhere, no activation inside:

    [B | C | x] = RMSNorm(h) W_in              W_in [D, 3, D]: the thirds in this order
    z_t = B_t * x_t                            elementwise
    c_t = sum_k w_k z_{t - (taps-1) + k}       causal, depthwise, `conv_taps` taps (conv_L_cache),
                                               zeros before the sequence; float32
    y = (C * c) W_out                          W_out [D, D]

There is no state but the last `taps - 1` positions of z, and nothing to cut into heads:
the layer is replicated in a group that shares the experts, each chip on its own
sequences. Leaves: sconv_norm [D], sconv_in [D, 3, D], sconv_w [taps, D] (the last tap is
the current position's; the published [D, 1, taps] transposed), sconv_out [D, D]. Packed
documents and a KV cache are refused (llama._block): the convolution would have to start
again at a boundary, and no tail of z is kept.

Under remat `full` a layer keeps `[B | C | x]` [B, T, 3, D] by name (`IN_PROJ_NAME`, beside the
layer's input: llama._maybe_remat): ONE array as the product wrote it, which b, c, x are views
of forward and backward, so the rematerialised layer runs no input product. The norm, the
gate, the float32 convolution and y are made again.
"""
import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name

from ray_tpu.ops.quant import as_weight as _w

from .attn import rms_norm
from .config import ModelConfig
from .ssm import _causal_conv

# what llama.py's table of layer kinds reads of a mixer (its comment says what each is);
# under `attn`, as the Kimi-Delta-Attention mixer
IN_PROJ_NAME = "sconv_bcx"  # [B | C | x] as the input product wrote it, [B, T, 3, D]
LEAF, RECURRENT, SCOPE, KEPT = "sconv_in", "gated short-convolution", "attn", {"full": (IN_PROJ_NAME,)}
AXES = {"sconv_norm": ("embed",), "sconv_in": ("embed", None, None), "sconv_w": (None, None),
        "sconv_out": (None, "embed")}


def init(key: jax.Array, cfg: ModelConfig):
    d, taps = cfg.d_model, cfg.conv_taps
    k_in, k_w, k_out = jax.random.split(key, 3)
    return {
        "sconv_norm": jnp.ones((d,), jnp.float32),
        "sconv_in": jax.random.normal(k_in, (d, 3, d), jnp.float32) * d**-0.5,
        "sconv_w": jax.random.normal(k_w, (taps, d), jnp.float32) * taps**-0.5,
        "sconv_out": jax.random.normal(k_out, (d, d), jnp.float32) * (2 * cfg.n_layers * d) ** -0.5,
    }


def n_params(cfg: ModelConfig) -> int:
    d = cfg.d_model
    return d + d * 3 * d + cfg.conv_taps * d + d * d  # the norm, [B | C | x], the taps, W_out


def mixer(x: jax.Array, lp, cfg: ModelConfig) -> jax.Array:
    """x [B, T, D] -> the layer's output (llama._block adds it to x)."""
    dt = x.dtype
    with jax.named_scope("sconv"):
        with jax.named_scope("sconv_in_proj"):
            u = rms_norm(x, lp["sconv_norm"], cfg.norm_eps)
            bcx = checkpoint_name(jnp.einsum("btd,dpe->btpe", u, _w(lp["sconv_in"], dt)), IN_PROJ_NAME)
            b, c, v = jnp.moveaxis(bcx, 2, 0)
        with jax.named_scope("sconv_gate_conv"):
            y = (c.astype(jnp.float32) * _causal_conv(b * v, lp["sconv_w"], 0.0)).astype(dt)
        with jax.named_scope("sconv_out_proj"):
            return jnp.einsum("bte,ed->btd", y, _w(lp["sconv_out"], dt))
