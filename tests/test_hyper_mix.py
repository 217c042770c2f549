"""The hyper-connections' four Pallas kernels (ops/hyper_mix.py, in the interpreter here) against the `jax.numpy`
forms they replace (models/hyper.py: `coefficients`, `read`, `_write`), at small sizes on the CPU: values and every
cotangent (the stream, the part's output, the coefficients, phi, the bias, the alphas); which shapes go to them; and a
whole layer's stream cotangent by both paths. float32 inputs agree to 2e-6 of an array's largest entry (the sums'
order differs); bfloat16 inputs to one rounding of the result (2^-7 of the largest entry: a last bit at the top)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from compiled_step_text import optimised  # noqa: F401  (a fixture)
from family_contract import _highest, highest  # noqa: F401  (autouse: every product at the highest precision)
from ray_tpu.models import get_config, hyper, llama
from ray_tpu.ops import hyper_mix

# the float32 bounds below are written against the sums' order in XLA's optimised CPU programs: this file keeps the optimiser
pytestmark = pytest.mark.usefixtures("optimised")

TINY = get_config("xing-tiny")
F32, BF16 = jnp.float32, jnp.bfloat16
ROUNDING = 2.0 ** -7

# (n, C, positions a row, rows of the batch, the stream's type)
CASES = {
    "n4-one-lane-tile-of-channels-two-rows": (4, 128, 128, 2, F32),
    "n4-the-cells-3584-channels-bfloat16": (4, 3584, 128, 1, BF16),
    "n2-two-position-tiles-of-128": (2, 256, 256, 1, F32),
    "n4-three-position-tiles-bfloat16": (4, 128, 384, 1, BF16),
    "n4-two-channel-blocks": (4, 1024, 128, 1, F32),
    "n4-two-tiles-of-512-in-each-of-two-rows": (4, 128, 1024, 2, F32),
}
UNTILED = {"channels-64": (4, 64, 128), "positions-96": (4, 128, 96), "one-copy": (1, 128, 128)}


def _cfg(n, channels):
    return dataclasses.replace(TINY, d_model=channels, hc_mult=n)


def _close(got, want, tol, what=""):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    assert got.shape == want.shape, (what, got.shape, want.shape)
    assert np.abs(got - want).max() <= tol * np.abs(want).max(), (what, np.abs(got - want).max() / np.abs(want).max())


def _inputs(n, channels, positions, batch, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    x = jax.random.normal(ks[0], (batch, positions, n * channels), F32).astype(dtype)
    out = jax.random.normal(ks[1], (batch, positions, channels), F32).astype(dtype)
    hc = hyper.init(ks[2], _cfg(n, channels), "attn")["attn_hc"]
    hc = hc.at[:-2].set(hc[:-2].astype(BF16).astype(F32))  # phi at values both types hold: a float32 run is the bfloat16 run's exact
    return x, out, hc, ks[3]


def _pulled(fn, args, cots):
    def run(args, cots):
        value, pull = jax.vjp(fn, *args)
        return value, pull(cots)
    return jax.jit(run)(args, cots)


@pytest.mark.parametrize("case", CASES)
def test_the_writings_kernels_are_the_plain_writing(case):
    """`hc_write_fwd` / `hc_write_bwd` against `hyper._write`: the stream behind the part, and the cotangents of the
    stream, of the part's output and of Hpost and Hres a position (no cotangent reaches Hpre through the writing)."""
    n, channels, positions, batch, dtype = CASES[case]
    cfg, tol = _cfg(n, channels), (2e-6 if dtype == F32 else ROUNDING)
    x, out, _, key = _inputs(n, channels, positions, batch, dtype)
    k_coef, k_cot = jax.random.split(key)
    coef = jax.nn.sigmoid(jax.random.normal(k_coef, (batch, positions, hyper.width(cfg))))
    cot = jax.random.normal(k_cot, x.shape, F32).astype(dtype)
    assert hyper.takes_kernels(x, cfg)

    want, theirs = _pulled(lambda x, out, coef: hyper._write(x, out, coef, cfg), (x, out, coef), cot)
    rows = lambda coef: coef.reshape(batch * positions, -1).T[n:]  # noqa: E731  ([n + n^2, B T]: Hpost, Hres)
    got, mine = _pulled(lambda x, out, coef: hyper_mix.write(x, hyper_mix.turned(out), rows(coef), n), (x, out, coef), cot)
    assert got.dtype == x.dtype and mine[0].dtype == x.dtype and mine[1].dtype == out.dtype
    _close(got, want, tol, "the stream behind the part")
    _close(mine[0], theirs[0], tol, "d x")
    _close(mine[1], theirs[1], tol, "d out")
    _close(mine[2][..., n:], theirs[2][..., n:], 2e-6 if dtype == F32 else 1e-5, "d Hpost, d Hres")  # float32 sums by either path
    assert not np.asarray(mine[2][..., :n]).any() and not np.asarray(theirs[2][..., :n]).any()


def _entered(path):
    """fn(x, hc, cfg) -> (y, Hpost and Hres [n + n^2, B T], x again) by `path`: "kernels" or "plain"."""
    def fn(x, hc, cfg):
        n = cfg.hc_mult
        if path == "kernels":
            y, coef, _, through = hyper.enter(x, hc, cfg)
            return y, coef, through
        coef, _ = hyper.coefficients(x, hc, cfg)
        return hyper.read(x, coef, cfg), coef.reshape(-1, coef.shape[-1]).T[n:], x
    return fn


@pytest.mark.parametrize("case", CASES)
def test_the_entrys_kernels_are_the_plain_entry(case):
    """`hc_read_fwd` / `hc_read_bwd` (with XLA's Hpost, logits and projection behind m) against `coefficients` and
    `read`: y, Hpost and Hres; the cotangents of the stream (through y, through the coefficients, and what the
    writing hands back through `x again`, summed in the kernel) and of the part's leaf: phi, the bias, the three alphas."""
    n, channels, positions, batch, dtype = CASES[case]
    cfg = _cfg(n, channels)
    x, _, hc, key = _inputs(n, channels, positions, batch, dtype)
    ks = jax.random.split(key, 3)
    cots = (jax.random.normal(ks[0], (batch, positions, channels), F32).astype(dtype),
            jax.random.normal(ks[1], (n + n * n, batch * positions)),
            jax.random.normal(ks[2], x.shape, F32).astype(dtype))
    (y, coef, through), (dx, dhc) = _pulled(lambda x, hc: _entered("kernels")(x, hc, cfg), (x, hc), cots)
    (y_, coef_, _), (dx_, dhc_) = _pulled(lambda x, hc: _entered("plain")(x, hc, cfg), (x, hc), cots)
    assert y.dtype == x.dtype and dx.dtype == x.dtype and dhc.dtype == hc.dtype
    np.testing.assert_array_equal(through, x)
    tol = 2e-6 if dtype == F32 else ROUNDING
    _close(y, y_, tol, "y")
    _close(coef, coef_, 2e-6 if dtype == F32 else 1e-4, "Hpost, Hres")  # float32 by either path, behind twenty rounds
    _close(dhc[:-2], dhc_[:-2], tol, "d phi")
    _close(dhc[-2], dhc_[-2], 2e-6 if dtype == F32 else 1e-3, "d bias")
    _close(dhc[-1, :3], dhc_[-1, :3], 2e-5 if dtype == F32 else 1e-3, "d alpha")  # sums over every position, of both signs
    assert not np.asarray(dhc[-1, 3:]).any()
    if dtype == F32:
        return _close(dx, dx_, tol, "d x")
    # the plain form rounds three arrays of the stream's size and then their sum, the kernel the float32 sum, once:
    # held to the exact (the plain form on the same values in float32), it is within a rounding and the nearer of the two
    as_f32 = tuple(c.astype(F32) for c in cots)
    _, (exact, _) = _pulled(lambda x, hc: _entered("plain")(x, hc, cfg), (x.astype(F32), hc), as_f32)
    far = lambda d: np.abs(np.asarray(d, np.float32) - np.asarray(exact)).max() / np.abs(np.asarray(exact)).max()  # noqa: E731
    assert far(dx) <= 0.75 * ROUNDING and far(dx) <= far(dx_), (far(dx), far(dx_))


@pytest.mark.parametrize("case", UNTILED)
def test_a_shape_that_does_not_tile_takes_the_plain_forms(case):
    """Channels that are no whole 128-lane tile, positions that are no whole tile, a stream of one copy: `supports`
    says no, `enter` and `write` run `coefficients`, `read` and `_write`, and no Pallas call is traced."""
    n, channels, positions = UNTILED[case]
    cfg = _cfg(max(n, 2), channels)
    assert not hyper_mix.supports(n, channels, positions)
    if n == 1:
        return
    x, out, hc, _ = _inputs(n, channels, positions, 1, F32)
    assert not hyper.takes_kernels(x, cfg)

    def part(x, out, hc):
        y, coef, err, x = hyper.enter(x, hc, cfg)
        return hyper.write(x, out + y, coef, cfg), coef
    assert "pallas_call" not in str(jax.make_jaxpr(jax.grad(lambda *a: part(*a)[0].sum(), argnums=(0, 1, 2)))(x, out, hc))
    new, coef = part(x, out, hc)
    want, _ = hyper.coefficients(x, hc, cfg)
    np.testing.assert_array_equal(coef, want)
    np.testing.assert_array_equal(new, hyper._write(x, out + hyper.read(x, want, cfg), want, cfg))


def test_a_partitioned_program_takes_the_plain_forms():
    """Under a mesh whose axes are GSPMD's, the kernels give way (a Pallas call there needs a `shard_map`)."""
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh

    cfg = _cfg(4, 128)
    x, out, hc, _ = _inputs(4, 128, 128, 2, F32)

    def part(x, out, hc):
        y, coef, _, x = hyper.enter(x, hc, cfg)
        return hyper.write(x, out + y, coef, cfg)
    want = part(x, out, hc)
    with use_mesh(build_mesh(MeshSpec(dp=2), jax.devices()[:2])):
        assert not hyper.takes_kernels(x, cfg) and "pallas_call" not in str(jax.make_jaxpr(part)(x, out, hc))
    with use_mesh(build_mesh(MeshSpec(dp=1), jax.devices()[:1])):
        assert hyper.takes_kernels(x, cfg) and "pallas_call" in str(jax.make_jaxpr(part)(x, out, hc))
        _close(jax.jit(part)(x, out, hc), want, 2e-6)


@pytest.mark.parametrize("dtype", [F32, BF16], ids=["float32", "bfloat16"])
def test_a_whole_hyper_connection_by_both_paths(dtype, monkeypatch):
    """`enter`, a part (here a nonlinear function a channel) and `write`, as llama._block joins them: the stream
    behind the part, the projection's error, and the cotangents of the stream and of the leaf, by the kernels and with
    `takes_kernels` held false."""
    n, channels, positions, batch = 4, 256, 256, 2
    cfg = _cfg(n, channels)
    x, _, hc, key = _inputs(n, channels, positions, batch, dtype, seed=3)
    cot = jax.random.normal(key, x.shape, F32).astype(dtype)

    def part():  # (a function a path: a trace is kept by the function it traced)
        def part(x, hc):
            y, coef, err, x = hyper.enter(x, hc, cfg)
            return hyper.write(x, jnp.tanh(y), coef, cfg), err
        return part

    (new, err), pull = jax.vjp(part(), x, hc)
    dx, dhc = pull((cot, jnp.zeros_like(err)))
    assert "pallas_call" in str(jax.make_jaxpr(part())(x, hc))
    monkeypatch.setattr(hyper, "takes_kernels", lambda x, cfg: False)
    assert "pallas_call" not in str(jax.make_jaxpr(part())(x, hc))
    (new_, err_), pull = jax.vjp(part(), x, hc)
    dx_, dhc_ = pull((cot, jnp.zeros_like(err_)))
    tol = 5e-6 if dtype == F32 else 2 * ROUNDING  # (bfloat16: y's last bit moves tanh(y)'s, and d x is three roundings by the plain path)
    _close(new, new_, tol, "the stream behind the part")
    _close(dx, dx_, tol, "d x")
    _close(dhc[:-2], dhc_[:-2], tol, "d phi")
    _close(dhc[-2:, :], dhc_[-2:, :], 2e-5 if dtype == F32 else 1e-2, "d bias, d alpha")
    np.testing.assert_allclose(err, err_, atol=1e-6)


def test_a_layers_stream_cotangent_by_both_paths(monkeypatch):
    """llama._block over a stream of four copies of 128 channels (latent attention, then routed experts beside a
    shared one, each inside its hyper-connection): the stream behind the layer and its cotangent, by the kernels (8
    calls forward, 4 backward in the jaxpr) and by the plain forms."""
    cfg = dataclasses.replace(TINY, d_model=128)
    lp = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), cfg)["layers"])
    x = jax.random.normal(jax.random.PRNGKey(7), (2, 128, cfg.hc_mult * cfg.d_model))
    cot = jax.random.normal(jax.random.PRNGKey(8), x.shape)

    def both():  # (a function a path: a trace is kept by the function it traced)
        def both(x):
            new, pull = jax.vjp(lambda x: llama._block(x, lp, cfg, jnp.arange(x.shape[1])[None], None)[0], x)
            return new, pull(cot)[0]
        return both

    text = str(jax.make_jaxpr(both())(x))
    assert [text.count(f"name={name}\n") for name in ("hc_read_fwd", "hc_write_fwd", "hc_read_bwd", "hc_write_bwd")] == [2, 2, 2, 2]
    new, dx = jax.jit(both())(x)
    monkeypatch.setattr(hyper, "takes_kernels", lambda x, cfg: False)
    assert "pallas_call" not in str(jax.make_jaxpr(both())(x))
    new_, dx_ = jax.jit(both())(x)
    _close(new, new_, 1e-5, "the stream behind the layer")
    _close(dx, dx_, 1e-5, "d x")


def test_the_models_loss_and_gradients_by_both_paths(monkeypatch):
    """The whole tiny model at 128 channels over 128 positions (the embedding repeated and the copies summed in front of
    the head the positions minor, `hyper.spread` / `hyper.gather`; three layers' parts through the kernels): the loss
    and every leaf's gradient against the plain forms'."""
    cfg = dataclasses.replace(TINY, d_model=128)
    params = llama.init(jax.random.PRNGKey(5), cfg)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(6), (2, 129), 0, cfg.vocab_size)}

    def run():  # (a function a path: a trace is kept by the function it traced)
        return jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, batch, cfg)[0]))(params)

    loss, grads = run()
    monkeypatch.setattr(hyper, "takes_kernels", lambda x, cfg: False)
    loss_, grads_ = run()
    assert abs(float(loss) - float(loss_)) < 1e-5 * abs(float(loss_))
    flat, flat_ = jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(grads_)
    for (path, got), want in zip(flat, flat_):
        _close(got, want, 5e-5, jax.tree_util.keystr(path))
