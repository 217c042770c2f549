"""The Mamba-2 scan in chunks (ops/ssd.py) against the recurrence a position at a time (the
nemotron_h reference's), at a small size on the CPU."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import _highest, highest  # noqa: F401  (autouse: every product at the highest precision)
from ray_tpu.models.reference import nemotron_h as ref
from ray_tpu.ops import ssd


def _scan_inputs(t, regime, seed=0, b=2, h=4, p=8, g=2, n=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    x = jax.random.normal(ks[0], (b, t, h, p))
    bm, cm = jax.random.normal(ks[1], (b, t, g, n)), jax.random.normal(ks[2], (b, t, g, n))
    # exp(dt a): near 1 (0.98 and above: a long memory), near 0 (0.14 down to exp(-24): none),
    # and both in one layer (dt a from -0.001 to -32)
    lo, hi, least = {"near_one": (1e-4, 1e-3, 1.0), "near_zero": (0.5, 1.5, 4.0), "mixed": (1e-3, 2.0, 1.0)}[regime]
    dt = jnp.exp(jax.random.uniform(ks[3], (b, t, h), minval=jnp.log(lo), maxval=jnp.log(hi)))
    a = -jnp.exp(jax.random.uniform(ks[4], (h,), minval=jnp.log(least), maxval=jnp.log(16.0)))
    return x, dt, a, bm, cm


@pytest.mark.parametrize("chunks", [1, 5])
@pytest.mark.parametrize("regime", ["near_one", "near_zero", "mixed"])
def test_the_chunked_scan_is_the_recurrence(chunks, regime):
    """Forward and every input's gradient, over one chunk and several, with decays near 1
    and near 0: the chunked form has no quotient of decays to overflow or vanish."""
    chunk = 8
    args = _scan_inputs(chunks * chunk, regime)
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)

    def value_and_grads(fn):  # a scan's value and every input's gradient, as one program
        return jax.jit(lambda *a: (fn(*a), jax.grad(
            lambda *b: jnp.sum(fn(*b) * cot), argnums=(0, 1, 2, 3, 4))(*a)))(*args)

    (y, grads), (want, r_grads) = value_and_grads(lambda *a: ssd.ssd_scan(*a, chunk)), value_and_grads(ref.recurrence)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y, want, atol=2e-5 * float(jnp.abs(want).max()))
    for name, g, r in zip("x dt a b c".split(), grads, r_grads):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(g, r, atol=5e-5 * float(jnp.abs(r).max()) + 1e-9, err_msg=name)


def test_the_scan_asserts_whole_chunks():
    args = _scan_inputs(20, "mixed")
    with pytest.raises(ValueError, match="multiple of the scan's chunk"):
        ssd.ssd_scan(*args, 8)
