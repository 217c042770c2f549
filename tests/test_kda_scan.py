"""The gated delta rule in chunks (ops/kda.py) and its eight Pallas kernels (the running sum's,
ops/kda_prefix.py, the overlaps', ops/kda_overlaps.py, the chunks' four matrices', ops/kda_parts.py, and the
walk over the chunks', ops/kda_walk.py, in the interpreter here) against the recurrence a position at a time (the
solar_open2 reference's), at a small size on the CPU; and that the shape alone says which path runs."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from compiled_step_text import optimised  # noqa: F401  (a fixture)
from family_contract import _highest, highest  # noqa: F401  (autouse: every product at the highest precision)
from ray_tpu.models.reference import solar_open2 as ref
from ray_tpu.ops import kda as kda_op

# the kernels' path is held to the recurrence within 1e-5 of its largest entry, a bound written against the sums'
# order in XLA's optimised CPU programs (unoptimised, one element of 131,072 reads 1.01e-5): this file keeps the optimiser
pytestmark = pytest.mark.usefixtures("optimised")


_PROGRAMS = {}  # (what, shape, path) -> the jitted program: the regimes of a shape share one trace and compile


def _value_and_pull(fn, args, cot, key=None):
    """fn(*args) and the pull-back of `cot` to every argument, as one program; with a `key`, the program of
    the first call under it (the same fn at the same shapes: its caller's word) runs again."""
    def run(args, cot):
        value, pull = jax.vjp(fn, *args)
        return value, pull(cot)
    program = _PROGRAMS.setdefault(key, jax.jit(run)) if key else jax.jit(run)
    return program(args, cot)


def _scan_inputs(t, regime, seed=0, b=2, h=3, width=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, width)) * width**-0.5
    k = jax.random.normal(ks[1], (b, t, h, width))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, width))
    # exp(g): near 1 (a long memory), a chunk's sum below -100 (none: 16 positions of -7 to
    # -30 a channel), and both in one layer; beta over (0, 2) or within 0.1 of 2, where
    # I - beta k k^T is all but a reflection
    # below -87 inside a sub-chunk of 32 and not inside every block of 8 (4 to 30 a position)
    lo, hi = {"near_one": (1e-4, 1e-2), "below_minus_100_a_chunk": (7.0, 30.0), "mixed": (1e-3, 30.0),
              "beta_near_2": (1e-3, 1.0), "below_minus_87_a_sub_chunk": (4.0, 30.0)}[regime]
    g = -jnp.exp(jax.random.uniform(ks[3], (b, t, h, width), minval=jnp.log(lo), maxval=jnp.log(hi)))
    beta = jax.random.uniform(ks[4], (b, t, h), minval=1.9 if regime == "beta_near_2" else 0.0, maxval=2.0)
    return q, k, v, g, beta


@pytest.mark.parametrize("chunk,sub", [(16, 4), (16, 16), (64, 16)])
@pytest.mark.parametrize("regime", ["near_one", "below_minus_100_a_chunk", "mixed", "beta_near_2"])
def test_the_chunked_scan_is_the_recurrence(regime, chunk, sub, monkeypatch):
    """ops/kda.py against the recurrence a position at a time (the reference's), output
    and the gradient of every input, over several chunks and sub-chunks."""
    monkeypatch.setattr(kda_op, "_SUB", sub)
    args = _scan_inputs(64, regime)
    if regime == "below_minus_100_a_chunk":
        assert float(args[3].reshape(2, 64 // chunk, chunk, 3, 16).sum(2).max()) < -100
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    want, theirs = _value_and_pull(ref.recurrence, args, cot)
    got, mine = _value_and_pull(lambda *a: kda_op.kda_scan(*a, chunk), args, cot)
    assert np.isfinite(np.asarray(got)).all()
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    for name, mine, theirs in zip("q k v g beta".split(), mine, theirs):
        assert np.isfinite(np.asarray(mine)).all(), name
        # (a float32 rounding of values and cotangents of order 1, where the gradient is all but zero)
        np.testing.assert_allclose(mine, theirs, atol=3e-5 * float(jnp.abs(theirs).max()) + 1e-6, err_msg=name)


def test_no_decay_is_the_exponential_of_a_positive_number():
    """Whatever A_log and dt_bias hold: decays of exp(-3000) a position neither overflow
    nor poison the gradient (0 x inf), and the scan of chunks whose sums fall to -4e4 is the
    recurrence's to the bound ops/kda.py states (a decay's relative error is the running
    sums' rounding, |G| x 6e-8: the one position in five that forgets nothing, g = -0.001,
    is a difference of sums near -4e4, which float32 keeps to 0.004)."""
    q, k, v, _, beta = _scan_inputs(32, "mixed")
    g = jnp.full(q.shape, -3000.0).at[:, ::5].set(-1e-3)
    want = ref.recurrence(q, k, v, g, beta)
    got, grads = jax.value_and_grad(lambda *a: jnp.sum(kda_op.kda_scan(*a, 16) * want), argnums=(0, 1, 2, 3, 4))(
        q, k, v, g, beta)
    bound = 2 * 6e-8 * 13 * 3000 * float(jnp.abs(want).max())
    np.testing.assert_allclose(kda_op.kda_scan(q, k, v, g, beta, 16), want, atol=bound)
    assert np.isfinite(float(got)) and all(np.isfinite(np.asarray(x)).all() for x in grads)
    # the same layer at decays a trained layer has is the recurrence's to a float32 rounding
    g = g / 3000
    np.testing.assert_allclose(kda_op.kda_scan(q, k, v, g, beta, 16), ref.recurrence(q, k, v, g, beta), atol=2e-6)


def _pallas_calls(fn, *args):
    """The names of the Pallas kernels a function's jaxpr holds, nested calls included."""
    names = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call":
                names.append(eqn.params["name"])
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(fn)(*args).jaxpr)
    return names


@pytest.mark.parametrize("chunk,sub,t,b,h", [
    (32, 8, 128, 1, 2), (32, 32, 64, 1, 2), (128, 32, 256, 1, 2),
    (128, 32, 128, 1, 8),  # 8 heads a grid step in both of the overlaps' kernels, 4 in the second half's backward
    (32, 32, 64, 2, 4),  # 4 heads a step out of two rows of a batch: `rows_block`'s map at `per` > 1
    (32, 32, 256, 2, 2),  # 8 chunks a row: the walk's state carried through seven of them, and zeroed for the second row
])
@pytest.mark.parametrize("regime", ["near_one", "below_minus_100_a_chunk", "mixed", "beta_near_2",
                                    "below_minus_87_a_sub_chunk"])
def test_the_kernel_path_is_the_jnp_path_and_the_recurrence(regime, chunk, sub, t, b, h, monkeypatch):
    """At a width of 128 the running sum, both halves and the walk over the chunks go to their Pallas kernels
    (ops/kda_prefix.py, ops/kda_overlaps.py, ops/kda_parts.py and ops/kda_walk.py, in the interpreter here): the
    scan's output and the gradient of q, k, v, g and beta against the same scan with `_decayed_overlaps`,
    `_chunk_parts` and `_walk` in their place (given the same G) and against the recurrence a position at a time, 2 to
    8 heads (2 to 8 of them a grid step), 1 to 8 chunks (the state the walk's kernels carry in fast
    memory against the recurrence's own), sub-chunks of 8 (one diagonal block each: no second
    reference) and 32.
    In the last regime the decays between two positions of ONE sub-chunk fall below exp(-87)
    and the factor through the end of a block of 8 columns underflows with them: the bound
    ops/kda.py states for a pair of two sub-chunks, held for the pairs of two blocks of 8."""
    monkeypatch.setattr(kda_op, "_SUB", sub)
    args = _scan_inputs(t, regime, b=b, h=h, width=128)
    if regime == "below_minus_100_a_chunk":
        assert float(args[3].reshape(b, t // chunk, chunk, h, 128).sum(2).max()) < -100
    if regime == "below_minus_87_a_sub_chunk":
        assert float(args[3].reshape(b, t // 32, 32, h, 128)[:, :, 1:].sum(2).max()) < -87
        across_8 = args[3].reshape(b, t // 8, 8, h, 128)[:, :, 1:].sum(2)
        assert float(across_8.min()) < -87 < float(across_8.max())
    scan = lambda *a: kda_op.kda_scan(*a, chunk)  # noqa: E731
    shape = (chunk, sub, t, b, h)
    if ("kernels", *shape) not in _PROGRAMS:
        assert sorted(_pallas_calls(jax.grad(lambda *a: jnp.sum(scan(*a)), argnums=(0, 1, 3)), *args)) == [
            "kda_overlaps_bwd", "kda_overlaps_fwd", "kda_parts_bwd", "kda_parts_fwd", "kda_prefix_bwd", "kda_prefix_fwd",
            "kda_walk_bwd", "kda_walk_fwd"]
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got, mine = _value_and_pull(scan, args, cot, key=("kernels", *shape))
    want, theirs = _value_and_pull(ref.recurrence, args, cot, key=("recurrence", t, b, h))
    with monkeypatch.context() as m:
        m.setattr(kda_op.kda_overlaps, "supports", lambda *shape: False)
        # G is the kernels' own on both sides: summed in another order it is a rounding of |G| apart, which the
        # decays carry into o (1.3e-5 of the largest entry at chunks of 128); the test below holds it to `jnp.cumsum`
        m.setattr(kda_op, "running_sum", lambda g, chunk: kda_op.kda_prefix.prefix((g,), chunk))
        if ("plain", *shape) not in _PROGRAMS:
            assert _pallas_calls(scan, *args) == ["kda_prefix_fwd"]
        plain, plains = _value_and_pull(scan, args, cot, key=("plain", *shape))
    assert np.isfinite(np.asarray(got)).all()
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, plain, atol=2e-6 * scale)  # the same sums: a rounding apart
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    for name, x, same, theirs in zip("q k v g beta".split(), mine, plains, theirs):
        assert np.isfinite(np.asarray(x)).all(), name
        top = float(jnp.abs(theirs).max())
        np.testing.assert_allclose(x, same, atol=1e-5 * top + 1e-6, err_msg=name)
        np.testing.assert_allclose(x, theirs, atol=3e-5 * top + 1e-6, err_msg=name)


def _decay_inputs(t, regime, b, h, seed=0):
    """A mixer's product, dt_bias and A_log whose `log_decay` lies in `_scan_inputs`' range of the regime: the
    product in bfloat16 as the cells hold it, the bias the inverse softplus of a draw of -g a channel, A_log 0."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    minus_g = -_scan_inputs(t, regime, seed=seed, b=b, h=h, width=128)[3]
    middle = jnp.exp(jnp.mean(jnp.log(minus_g), axis=(0, 1)))  # [H, K]
    bias = middle + jnp.log(-jnp.expm1(-middle))
    wanted = minus_g + jnp.log(-jnp.expm1(-minus_g))  # softplus(wanted) == -g
    a_log = jnp.log(jax.random.uniform(ks[0], (h,), minval=0.5, maxval=2.0))
    return kda_op.LogDecay((wanted - bias).astype(jnp.bfloat16), bias, a_log)


@pytest.mark.parametrize("chunk,h,b", [
    (32, 1, 1), (32, 2, 2),  # 1 and 2 heads a grid step; two rows of a batch, which the blocks find a head's positions together
    (128, 8, 1), (32, 8, 2),  # 8 heads a step, at the cells' chunk and out of two rows of a batch
])
@pytest.mark.parametrize("regime", ["near_one", "below_minus_100_a_chunk", "mixed", "beta_near_2"])
@pytest.mark.parametrize("made_of", ["g", "decay"])
def test_the_running_sums_kernels_are_cumsum(made_of, regime, chunk, h, b):
    """ops/kda_prefix.py's two kernels (in the interpreter here) against `_lead(jnp.cumsum(..))` differentiated by
    JAX and against a float64 running sum: G under both of its names and the pull-back of two DISTINCT cotangents
    (the overlaps' and the parts': the rule gets them apart and adds them itself), each within 1e-6 of the largest
    entry and no further from the float64 sum than `jnp.cumsum` is, times two, or than sqrt(Q) roundings of the
    largest entry. From g itself, and from what the mixer makes it of (`LogDecay`: the forward kernel computes
    `log_decay` where it sums it, the backward rule is the kernel and then that expression's own): there every
    gradient, the bfloat16 product's, dt_bias' and A_log's, against the plain expression's under `jnp.cumsum`."""
    t = 3 * chunk
    if made_of == "decay":
        args, g_of, given = tuple(_decay_inputs(t, regime, b, h)), kda_op.kda_prefix.log_decay, kda_op.LogDecay
    else:
        args, g_of, given = (_scan_inputs(t, regime, b=b, h=h, width=128)[3],), (lambda g: g), (lambda g: g)
    g = g_of(*args)
    sums = lambda *a: kda_op.running_sum(given(*a), chunk)  # noqa: E731
    assert kda_op.takes_kernels(chunk, 128)
    shape = (made_of, chunk, h, b)
    if ("prefix", *shape) not in _PROGRAMS:
        assert _pallas_calls(sums, *args) == ["kda_prefix_fwd"]
        assert _pallas_calls(lambda *a: jax.vjp(sums, *a)[1], *args) == ["kda_prefix_fwd"]
    plain = lambda *a: (kda_op._lead(jnp.cumsum(g_of(*a).reshape(b, 3, chunk, h, 128), axis=2)),) * 2  # noqa: E731
    cots = tuple(jax.random.normal(key, (3, b, h, chunk, 128)) for key in jax.random.split(jax.random.PRNGKey(9)))
    got, mine = _value_and_pull(sums, args, cots, key=("prefix", *shape))
    want, theirs = _value_and_pull(plain, args, cots, key=("plain prefix", *shape))
    lead = lambda x: x.transpose(1, 0, 3, 2, 4)  # noqa: E731
    exact = lead(np.cumsum(np.asarray(g, np.float64).reshape(b, 3, chunk, h, 128), axis=2))
    both = lead(np.asarray(cots[0], np.float64) + np.asarray(cots[1], np.float64))  # back to [B, chunks, Q, H, K]
    exact_pull = np.flip(np.cumsum(np.flip(both, 2), axis=2), 2).reshape(b, t, h, 128)
    np.testing.assert_array_equal(got[0], got[1])
    pairs = [("G", got[0], want[0], exact)]
    if made_of == "g":
        pairs.append(("dg", mine[0], theirs[0], exact_pull))
    for name, x, y, true in pairs:
        assert x.shape == y.shape == true.shape and np.isfinite(np.asarray(x)).all(), name
        top = float(np.abs(true).max())
        np.testing.assert_allclose(x, y, atol=1e-6 * top, rtol=0, err_msg=name)
        # (a float32 sum of Q terms in another order)
        assert np.abs(x - true).max() <= max(2 * np.abs(y - true).max(), chunk**0.5 * 6e-8 * top), name
    if made_of == "decay":
        for name, x, y in zip(("decay", "dt_bias", "A_log"), mine, theirs):
            assert x.shape == y.shape and x.dtype == y.dtype and np.isfinite(np.asarray(x, np.float32)).all(), name
            x, y = np.asarray(x, np.float32), np.asarray(y, np.float32)
            # (the product's gradient is rounded to bfloat16 on both sides: a rounding of the two float32 sums apart)
            np.testing.assert_allclose(x, y, atol=(2**-7 if name == "decay" else 2e-6) * float(np.abs(y).max()), rtol=0, err_msg=name)


def _parts_inputs(t, regime, chunk, seed=0, b=1):
    """What `kda_scan` hands the second half at `_scan_inputs`' draws: q, k, v [B, chunks, Q, H, K] (the
    positions in the mixer's order), G [chunks, B, H, Q, K], beta [chunks, B, H, Q], T = (I + A)^-1 and b
    [chunks, B, H, Q, Q]."""
    def first_half(q, k, v, g, beta):
        q, k, v, g, beta = (x.reshape(b, t // chunk, chunk, *x.shape[2:]) for x in (q, k, v, g, beta))
        run, beta = kda_op._lead(jnp.cumsum(g, 2)), kda_op._lead(beta)
        a, overlap = kda_op._overlaps(q, k, run, beta)
        return q, k, v, run, beta, kda_op._unit_lower_inverse(a), overlap

    program = _PROGRAMS.setdefault(("first half", chunk, kda_op._SUB, t, b), jax.jit(first_half))
    return program(*_scan_inputs(t, regime, seed=seed, b=b, h=2, width=128))


@pytest.mark.parametrize("chunk,sub,t,b", [(32, 8, 128, 1), (32, 32, 64, 2), (128, 32, 256, 1)])
@pytest.mark.parametrize("regime", ["near_one", "below_minus_100_a_chunk", "mixed", "beta_near_2"])
def test_the_second_halfs_kernels_are_chunk_parts(regime, chunk, sub, t, b, monkeypatch):
    """ops/kda_parts.py's two kernels (in the interpreter here) against `_chunk_parts` in
    `jax.numpy` differentiated by JAX, at the shapes the test above walks (one of them with two
    rows of a batch, which the kernels' blocks find in the mixer's order): P, O0, M, N and the
    pull-back of a random cotangent to all seven inputs, q, k, v, G, beta, T and b, each to a
    float32 rounding of its largest entry (or of the terms of order 1 it is a difference of,
    where the decays leave all but nothing of it)."""
    monkeypatch.setattr(kda_op, "_SUB", sub)
    args = _parts_inputs(t, regime, chunk, b=b)
    shape = (chunk, sub, t, b)
    assert kda_op.takes_kernels(chunk, 128)
    if ("parts", *shape) not in _PROGRAMS:
        assert _pallas_calls(lambda *a: jax.vjp(kda_op.chunk_parts, *a)[1], *args) == ["kda_parts_fwd"]
    plain = lambda *a: kda_op._chunk_parts(*(kda_op._lead(x) for x in a[:3]), *a[3:])  # noqa: E731
    want = jax.eval_shape(plain, *args)
    cot = tuple(jax.random.normal(key, x.shape) for key, x in zip(jax.random.split(jax.random.PRNGKey(9), 4), want))
    got, mine = _value_and_pull(kda_op.chunk_parts, args, cot, key=("parts", *shape))
    want, theirs = _value_and_pull(plain, args, cot, key=("plain parts", *shape))
    for name, x, y in zip("P O0 M N".split(), got, want):
        assert x.shape == y.shape and np.isfinite(np.asarray(x)).all(), name
        np.testing.assert_allclose(x, y, atol=2e-6 * float(jnp.abs(y).max()) + 1e-30, err_msg=name)
    for name, x, y in zip("q k v G beta T b".split(), mine, theirs):
        assert x.shape == y.shape and np.isfinite(np.asarray(x)).all(), name
        np.testing.assert_allclose(x, y, atol=5e-6 * float(jnp.abs(y).max()) + 1e-6, err_msg=name)


def _walk_inputs(chunks, b, h, size=32, width=128, seed=0):
    """P, O0 [chunks, B, H, Q, K] and M, N [chunks, B, H, K, K] of the sizes the second half hands on (M a
    contraction: a state carried through every chunk neither dies nor grows), and a cotangent of o."""
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    p = jax.random.normal(ks[0], (chunks, b, h, size, width)) * width**-0.5
    o0 = jax.random.normal(ks[1], p.shape)
    m = jax.random.normal(ks[2], (chunks, b, h, width, width)) * 0.9 * width**-0.5
    n = jax.random.normal(ks[3], m.shape)
    return (p, o0, m, n), jax.random.normal(ks[4], (b, chunks, size, h, width))


@pytest.mark.parametrize("b,h", [(1, 2), (2, 2), (2, 4), (1, 8), (2, 8)])
@pytest.mark.parametrize("chunks", [1, 2, 8])
def test_the_walks_kernels_are_the_join_and_the_output_product(chunks, b, h):
    """ops/kda_walk.py's two kernels (in the interpreter here) against `_walk`, the `lax.scan` over M S + N, the
    batched P S_0 + O0 and the transpose to the mixer's order, differentiated by JAX: o and the pull-back of a
    random cotangent to P, O0, M, N, the same sums in the same order (a rounding of the largest entry apart, where
    XLA's CPU products and the interpreter's differ at all); 1, 2 and 8 chunks (no state, one carried, seven), 2, 4
    and 8 heads a grid step, one and two rows of a batch. The call that keeps no residuals (no `starts` written) is
    the other's o bit for bit. A second row of a batch starts from a ZERO state whatever the first row left in
    the scratch: its output and its gradients do not move when the first row's inputs do."""
    args, cot = _walk_inputs(chunks, b, h)
    assert kda_op.takes_kernels(*args[0].shape[-2:])
    shape = (chunks, b, h)
    if ("walk", *shape) not in _PROGRAMS:
        assert _pallas_calls(kda_op.walk, *args) == ["kda_walk_fwd"]
        assert _pallas_calls(lambda *a: jax.vjp(kda_op.walk, *a)[1](cot), *args) == ["kda_walk_fwd", "kda_walk_bwd"]
    got, mine = _value_and_pull(kda_op.walk, args, cot, key=("walk", *shape))
    want, theirs = _value_and_pull(kda_op._walk, args, cot, key=("plain walk", *shape))
    assert got.shape == want.shape == cot.shape and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(got, want, atol=2e-6 * float(jnp.abs(want).max()))
    np.testing.assert_array_equal(_PROGRAMS.setdefault(("walk alone", *shape), jax.jit(kda_op.walk))(*args), got)
    for name, x, y in zip("P O0 M N".split(), mine, theirs):
        assert x.shape == y.shape and np.isfinite(np.asarray(x)).all(), name
        np.testing.assert_allclose(x, y, atol=2e-6 * float(jnp.abs(y).max()) + 1e-30, err_msg=name)
    if b > 1:
        moved = tuple(x.at[:, 0].multiply(-3.0) for x in args)
        again, pulled = _value_and_pull(kda_op.walk, moved, cot, key=("walk", *shape))
        assert float(jnp.abs(again[0] - got[0]).max()) > 1e-3
        np.testing.assert_array_equal(again[1], got[1])
        for x, y in zip(pulled, mine):
            np.testing.assert_array_equal(x[:, 1], y[:, 1])


def test_no_decay_is_the_exponential_of_a_positive_number_in_the_kernels():
    """The case above on the kernel path (width 128, chunks of 32): decays of exp(-3000) a
    position, chunks whose sums fall to -8e4; a value and gradients that are finite say that
    no exponential saw a positive number (exp(3000) is inf, and 0 x inf poisons a sum), the
    masks cut before it in both of the overlaps' kernels, the factors across sub-chunks and,
    inside one, through the end of a block of 8 columns (a chunk here is ONE sub-chunk of 32:
    every pair outside the diagonal blocks goes through that second reference) are <= 1, and
    the second half's kernels take exp G and exp(G_Q - G) of sums that only fall. 4 heads, all
    of them one grid step's."""
    q, k, v, _, beta = _scan_inputs(64, "mixed", b=1, h=4, width=128)
    g = jnp.full(q.shape, -3000.0).at[:, ::5].set(-1e-3)
    assert _pallas_calls(lambda *a: kda_op.kda_scan(*a, 32), q, k, v, g, beta) == [
        "kda_prefix_fwd", "kda_overlaps_fwd", "kda_parts_fwd", "kda_walk_fwd"]
    want = ref.recurrence(q, k, v, g, beta)
    got, grads = jax.value_and_grad(lambda *a: jnp.sum(kda_op.kda_scan(*a, 32) * want), argnums=(0, 1, 2, 3, 4))(
        q, k, v, g, beta)
    bound = 2 * 6e-8 * 26 * 3000 * float(jnp.abs(want).max())
    np.testing.assert_allclose(kda_op.kda_scan(q, k, v, g, beta, 32), want, atol=bound)
    assert np.isfinite(float(got)) and all(np.isfinite(np.asarray(x)).all() for x in grads)
    g = g / 3000
    np.testing.assert_allclose(kda_op.kda_scan(q, k, v, g, beta, 32), ref.recurrence(q, k, v, g, beta), atol=2e-6)


@pytest.mark.parametrize("chunk,width,sub,kernels", [
    (128, 128, 32, True),  # the Solar-Open2 cell's
    (32, 128, 8, True), (32, 128, 32, True), (256, 256, 32, True),
    (48, 128, 32, True),  # no whole sub-chunks of 32: one sub-chunk of 48, six registers of 8 rows
    (64, 16, 16, False), (16, 16, 4, False),  # a width of 16: the cases above this section, tier-1's own
    (128, 64, 32, False),  # half a register of lanes
    (20, 128, 32, False),  # one sub-chunk of 20 rows: no whole registers
    (32, 128, 4, False),  # sub-chunks of half a register
])
def test_the_shape_alone_says_which_path_runs(chunk, width, sub, kernels, monkeypatch):
    """`kda.takes_kernels` reads the chunk and the width (and the sub-chunk they imply); the
    scan's jaxpr holds the running sum's, both halves' and the walk's forward kernels exactly where it says so.
    Nobody sets it."""
    monkeypatch.setattr(kda_op, "_SUB", sub)
    assert kda_op.takes_kernels(chunk, width) == kernels
    assert kda_op.kda_overlaps.supports(chunk, kda_op._sub(chunk), width) == kernels
    args = _scan_inputs(2 * chunk, "mixed", b=1, h=2, width=width)
    assert _pallas_calls(lambda *a: kda_op.kda_scan(*a, chunk), *args) == (
        ["kda_prefix_fwd", "kda_overlaps_fwd", "kda_parts_fwd", "kda_walk_fwd"] if kernels else [])


def test_under_a_mesh_that_shards_the_heads_the_jnp_path_runs():
    """GSPMD cannot partition a Mosaic call: with an axis of the ambient mesh still automatic
    the scan runs `jnp.cumsum`, `_decayed_overlaps`, `_chunk_parts` and `_walk` at the kernels' own shape,
    partitioned by the compiler, and is the single-device scan's value; with every axis of size one all
    four pairs of kernels run."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh

    args = _scan_inputs(64, "mixed", b=2, h=2, width=128)
    scan = lambda *a: kda_op.kda_scan(*a, 32)  # noqa: E731
    want = scan(*args)
    mesh = build_mesh(MeshSpec(dp=2, tp=2), jax.devices()[:4])
    with use_mesh(mesh):
        assert not kda_op.takes_kernels(32, 128) and not _pallas_calls(scan, *args)
        heads = NamedSharding(mesh, P("dp", None, "tp", None))
        sharded = [jax.device_put(x, heads if x.ndim == 4 else NamedSharding(mesh, P("dp", None, "tp"))) for x in args]
        got = jax.jit(scan)(*sharded)
        assert got.sharding.spec[2] == "tp"
    np.testing.assert_allclose(got, want, atol=1e-5 * float(jnp.abs(want).max()))
    with use_mesh(build_mesh(MeshSpec(dp=1), jax.devices()[:1])):
        assert kda_op.takes_kernels(32, 128) and _pallas_calls(scan, *args) == [
            "kda_prefix_fwd", "kda_overlaps_fwd", "kda_parts_fwd", "kda_walk_fwd"]


def test_the_scan_asserts_whole_chunks():
    q, k, v, g, beta = _scan_inputs(24, "mixed")
    with pytest.raises(ValueError, match="multiple of the scan's chunk"):
        kda_op.kda_scan(q, k, v, g, beta, 16)
