"""The flash backward as ONE kernel (`flash_attention_bwd_dkv_dq*`, PR 53) against the plain
softmax's gradients, in the interpreter: every walk x group x packing x head width; and the two
kernels that run where K and V are not one span (`fa._fuses`) against the one."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import attention_reference
from flash_cases import flash_names, kernel_names, packed_segments, rand

# a walk: (what the call is given, its kernels' suffix); the row is 256 positions in 64-row tiles
# (under the block-diffusion mask [noised ; clean] of 128 each), the window not a whole tile
WALKS = {
    "causal": (dict(causal=True), ""),
    "window": (dict(causal=True, window=100), "_window"),
    "bd": (dict(causal=False, block_diffusion=4), "_bd"),
}
S, TILE = 256, 64


def _grads(fn, q, k, v, g, **kw):
    def loss(q, k, v):
        return jnp.sum(fn(q, k, v, **kw).astype(jnp.float32) * g.astype(jnp.float32))
    return jax.jit(jax.grad(loss, argnums=(0, 1, 2)))(q, k, v)


def _inputs(group, width, dtype=jnp.float32):
    """q, k, v and the output's cotangent; `width` a number, or (q's and k's, v's and the output's)."""
    qk, v = width if isinstance(width, tuple) else (width, width)
    return (rand((1, S, heads, w), i, dtype) for i, (heads, w) in enumerate(((group, qk), (1, qk), (1, v), (group, v))))


# (the block-diffusion mask takes one document a row, `_block_diffusion`)
CASES = [(walk, group, packed, width) for walk in WALKS for group in (1, 4, 8) for packed in (False, True)
         for width in (64, 128, 256) if not (packed and walk == "bd")]
# q and k 192 wide (on 256 lanes) beside v 128 wide: latent attention without a q latent (Kimi Linear); and the
# other way round, v the wider
CASES += [("causal", 1, False, (192, 128)), ("causal", 4, True, (192, 128)), ("window", 4, True, (192, 128)),
          ("bd", 4, False, (192, 128)), ("causal", 4, True, (128, 256))]


def _width_id(d):
    return f"w{d[0]}v{d[1]}" if isinstance(d, tuple) else f"w{d}"


@pytest.mark.parametrize("walk,group,packed,width", CASES,
                         ids=[f"{w}-gqa{g}-{'packed' if p else 'one-document'}-{_width_id(d)}" for w, g, p, d in CASES])
def test_the_one_backward_kernel_matches_the_reference(walk, group, packed, width):
    """dq, dk and dv of the one backward kernel (by name) under a random cotangent, at the
    tolerance the dQ and dK/dV kernels' cases hold (`test_fwd_and_grads_over_tilings`): a group
    of 1, 4 and 8 query heads summed in the kernel's resident dK and dV, segment ids down the
    tile's rows and along its columns, heads of 64 on padded lanes, 128 and 256; q and k 192 wide on
    256 lanes beside v 128 wide (dq and dk come back 192 wide, dv 128: the reference's shapes)."""
    kw, suffix = WALKS[walk]
    q, k, v, g = _inputs(group, width)
    kw = dict(kw, segment_ids=packed_segments(1, S, (70, 150, 201)) if packed else None)
    flash = dict(kw, block_q=TILE, block_kv=TILE)
    names = kernel_names(jax.make_jaxpr(lambda q: _grads(fa.flash_attention, q, k, v, g, **flash))(q).jaxpr)
    assert sorted(names) == flash_names(suffix), names
    got = _grads(fa.flash_attention, q, k, v, g, **flash)
    want = _grads(attention_reference, q, k, v, g, **kw)
    for name, a, ref in zip(("dq", "dk", "dv"), got, want):
        assert a.dtype == ref.dtype and a.shape == ref.shape, name
        scale = max(1.0, float(jnp.max(jnp.abs(ref))))
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(ref) / scale, rtol=0, atol=5e-3, err_msg=name)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("walk", list(WALKS))
def test_the_two_kernels_run_where_k_and_v_are_not_one_span_and_agree_with_the_one(walk, dtype, monkeypatch):
    """`_fuses` is a predicate of the shapes: under a span budget that cuts K and V in two the
    call's backward is dQ's and dK/dV's kernels (whose lowered bodies at [1, 32768, 32 / 8, 128]
    `tests/test_tpu_compile.py` holds to their text at PR 53's parent), and the two forms make
    the same dq, dk and dv from the same products in another order of the sums."""
    kw, suffix = WALKS[walk]
    q, k, v, g = _inputs(4, 128, dtype)
    kw = dict(kw, block_q=TILE, block_kv=TILE, segment_ids=None if walk == "bd" else packed_segments(1, S, (90, 130)))

    def run(one_backward):
        t = fa._tiling(S, S, TILE, TILE, 128, jnp.dtype(dtype).itemsize, 4)
        assert fa._fuses(t, S) == one_backward and (t.kv_span == S) == one_backward, t
        names = kernel_names(jax.make_jaxpr(lambda q: _grads(fa.flash_attention, q, k, v, g, **kw))(q).jaxpr)
        assert sorted(names) == flash_names(suffix, one_backward), names
        return _grads(fa.flash_attention, q, k, v, g, **kw)

    one = run(True)
    monkeypatch.setattr(fa, "SPAN_VMEM_BYTES", (64 << 10) * jnp.dtype(dtype).itemsize)  # K and V in two spans of two tiles
    two = run(False)
    for name, a, b in zip(("dq", "dk", "dv"), one, two):
        assert a.dtype == b.dtype == dtype, name
        a, b = np.asarray(a, np.float32), np.asarray(b, np.float32)
        # f32: the sums' order alone; bf16: a sum near a rounding boundary may round the other way
        np.testing.assert_allclose(a, b, rtol=0, atol=(2e-5 if dtype == jnp.float32 else 2e-2) * max(1.0, np.abs(b).max()),
                                   err_msg=name)
