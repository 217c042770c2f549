"""What the flash kernels' test files share (tests/test_flash_attention.py: the triangle, the
block-diffusion mask, the rotation in front; tests/test_flash_window.py: the band;
tests/test_flash_backward.py: the one backward kernel): seeded inputs, packed documents' segment
ids, and the names of a program's kernels. pytest does not collect this module (its name)."""
import jax
import jax.numpy as jnp


def rand(shape, key, dtype=jnp.float32):
    return jax.random.normal(jax.random.PRNGKey(key), shape, jnp.float32).astype(dtype)


def kernel_names(jaxpr):
    """The names of a program's Pallas kernels, nested calls included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(eqn.params["name"])
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += kernel_names(sub)
    return names


def flash_names(suffix="", one_backward=True):
    """A differentiated flash call's kernels, sorted: the forward kernel and ONE backward kernel
    where K and V are one span (`fa._fuses`; PR 53), else dQ's and dK/dV's beside it."""
    backward = ["bwd_dkv_dq"] if one_backward else ["bwd_dkv", "bwd_dq"]
    return [f"flash_attention_{kernel}{suffix}" for kernel in (*backward, "fwd")]


def packed_segments(b, s, cuts):
    """Segment ids of documents that end at `cuts` (not on tile boundaries)."""
    return jnp.broadcast_to(jnp.searchsorted(jnp.asarray(cuts), jnp.arange(s), side="right")
                            .astype(jnp.int32), (b, s))
