"""Flash attention kernel vs XLA reference, CPU interpret mode."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops.flash_attention import flash_attention


def _rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


@pytest.mark.parametrize("causal", [True, False])
def test_fwd_matches_reference(causal):
    b, s, h, d = 2, 128, 4, 64
    q, k, v = _rand((b, s, h, d), 0), _rand((b, s, h, d), 1), _rand((b, s, h, d), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_fwd_gqa():
    b, s, h, hkv, d = 1, 128, 8, 2, 64
    q = _rand((b, s, h, d), 0)
    k, v = _rand((b, s, hkv, d), 1), _rand((b, s, hkv, d), 2)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_fwd_segment_ids():
    b, s, h, d = 1, 128, 2, 64
    q, k, v = _rand((b, s, h, d), 0), _rand((b, s, h, d), 1), _rand((b, s, h, d), 2)
    seg = jnp.concatenate(
        [jnp.zeros((b, 64), jnp.int32), jnp.ones((b, 64), jnp.int32)], axis=1
    )
    out = flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=64, block_kv=64)
    ref = attention_reference(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("gqa", [False, True])
def test_grads_match_reference(gqa):
    b, s, h, d = 1, 128, 4, 64
    hkv = 2 if gqa else h
    q = _rand((b, s, h, d), 0)
    k, v = _rand((b, s, hkv, d), 1), _rand((b, s, hkv, d), 2)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_kv=64).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3)


def _packed(b, s, cuts):
    """Segment ids of documents that end at `cuts` (not on tile boundaries)."""
    return jnp.broadcast_to(jnp.searchsorted(jnp.asarray(cuts), jnp.arange(s), side="right")
                            .astype(jnp.int32), (b, s))


# Tilings of the (q, kv) plane: every case runs the forward kernel and both backward
# kernels against the f32 reference on f32 copies of the same inputs, with a random
# cotangent. Under `causal` a tiling with more than one block a side has tiles below
# the diagonal, on it, and above it (skipped, their index maps clamped).
TILINGS = {
    # name: (b, s, h, hkv, d, block_q, block_kv, causal, segment cuts, dtype)
    "causal-4x4": (1, 256, 2, 2, 64, 64, 64, True, None, jnp.float32),
    "causal-wide-kv-2x4": (1, 256, 2, 2, 64, 128, 64, True, None, jnp.float32),
    "causal-wide-q-4x2": (1, 256, 2, 2, 64, 64, 128, True, None, jnp.float32),
    "causal-one-tile": (1, 128, 2, 2, 64, 128, 128, True, None, jnp.float32),
    "causal-block-larger-than-seq": (1, 128, 2, 1, 64, 512, 512, True, None, jnp.float32),
    "noncausal-2x2": (1, 128, 4, 4, 64, 64, 64, False, None, jnp.float32),
    "noncausal-gqa-2x4": (1, 256, 4, 2, 64, 128, 64, False, None, jnp.float32),
    "segments-causal-4x4": (1, 256, 2, 2, 64, 64, 64, True, (40, 150, 200), jnp.float32),
    "segments-causal-gqa-2x4": (1, 256, 4, 2, 64, 128, 64, True, (100, 130), jnp.float32),
    "segments-noncausal-2x2": (1, 128, 2, 2, 64, 64, 64, False, (70,), jnp.float32),
    "gqa4-causal-4x4": (1, 256, 4, 1, 64, 64, 64, True, None, jnp.float32),
    "gqa4-causal-batch2-2x4": (2, 256, 8, 2, 64, 128, 64, True, None, jnp.float32),
    "bf16-causal-4x4": (1, 256, 2, 2, 64, 64, 64, True, None, jnp.bfloat16),
    "bf16-gqa4-causal-2x2": (1, 256, 8, 2, 128, 128, 128, True, None, jnp.bfloat16),
    "bf16-segments-causal-4x2": (1, 256, 4, 2, 64, 64, 128, True, (90, 180), jnp.bfloat16),
    "bf16-noncausal-2x2": (1, 128, 4, 4, 64, 64, 64, False, None, jnp.bfloat16),
}


@pytest.mark.parametrize("case", list(TILINGS))
def test_fwd_and_grads_over_tilings(case):
    b, s, h, hkv, d, bq, bkv, causal, cuts, dtype = TILINGS[case]
    q = _rand((b, s, h, d), 0, dtype)
    k, v = _rand((b, s, hkv, d), 1, dtype), _rand((b, s, hkv, d), 2, dtype)
    g = _rand((b, s, h, d), 3, dtype)
    seg = None if cuts is None else _packed(b, s, cuts)

    def run(fn, *xs, **kw):
        def loss(q, k, v):
            o = fn(q, k, v, causal=causal, segment_ids=seg, **kw)
            return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(*xs)
        return (o, *grads)

    got = run(flash_attention, q, k, v, block_q=bq, block_kv=bkv)
    want = run(attention_reference, *(x.astype(jnp.float32) for x in (q, k, v)))
    # bf16: inputs, p and ds reach the MXU with 8 bits of mantissa, and the results
    # are rounded to bf16; statistics and accumulators are f32 either way
    tol = 5e-3 if dtype == jnp.float32 else 3e-2
    for name, a, ref in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == ref.shape, name
        scale = max(1.0, float(jnp.max(jnp.abs(ref))))
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale, np.asarray(ref) / scale,
                                   rtol=0, atol=tol, err_msg=f"{case}: {name}")


def test_products_take_the_inputs_dtype():
    """Every matrix product of the three kernels is fed the call's own dtype and
    accumulates in f32; nothing else in the kernels is bf16."""
    q = _rand((1, 128, 4, 64), 0, jnp.bfloat16)
    k = _rand((1, 128, 2, 64), 1, jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_kv=64).astype(
            jnp.float32).sum()

    def dots(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append((tuple(v.aval.dtype for v in eqn.invars), eqn.outvars[0].aval.dtype))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                dots(sub, found)
        return found

    found = dots(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k).jaxpr, [])
    assert len(found) == 2 + 3 + 4  # forward, dQ, dK/dV
    for ins, out in found:
        assert ins == (jnp.bfloat16, jnp.bfloat16) and out == jnp.float32, (ins, out)
