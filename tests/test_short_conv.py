"""ops/short_conv.py: the causal depthwise convolution, silu and per-head L2 norms in front of
the delta-rule scan as two Pallas kernels (one pass over HBM each way, all parts in one call),
in Pallas' interpreter against the plain `jax.numpy` form it replaces (models/kda.py:
`_conv_silu_norm`, around `ssm._causal_conv`)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from compiled_step_text import optimised  # noqa: F401  (a fixture)
from family_contract import model_of
from ray_tpu.models import get_config, kda, llama, sconv
from ray_tpu.models.reference import lfm2_moe as lfm2_ref
from ray_tpu.models.ssm import _causal_conv
from ray_tpu.ops import short_conv

# the kernels' results are held to the plain form's TO THE BIT, both sides one XLA program on the CPU: the same
# float32 arithmetic in the same order only where XLA contracts and fuses both alike, so this file keeps the optimiser
pytestmark = pytest.mark.usefixtures("optimised")

QKV = (128**-0.5, 1.0, None)  # the delta-rule mixer's parts: q normed and scaled, k normed, v not


def _plain(x, w, bias, scales, width):
    """The plain form over any parts: float32 convolution tap by tap, silu, a normed part's
    L2 norm a head times its scale, ONE rounding to x's type; held in float32 as the kernels'."""
    b, t, c = x.shape
    a = jax.nn.silu(_causal_conv(x, w, 0.0 if bias is None else bias))
    own, out = c // len(scales), []
    for i, scale in enumerate(scales):
        part = a[..., i * own:(i + 1) * own]
        if scale is not None:
            part = kda._l2norm(part.reshape(b, t, own // width, width)).reshape(b, t, own) * scale
        out.append(part.astype(x.dtype).astype(jnp.float32))
    return tuple(out)


def _inputs(b, t, c, dtype, taps, bias, parts, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed + t), 4)
    x = jax.random.normal(ks[0], (b, t, c)).astype(dtype)
    w = 0.5 * jax.random.normal(ks[1], (taps, c))
    bias = 0.1 * jax.random.normal(ks[2], (c,)) if bias else None
    return x, w, bias, tuple(jax.random.normal(ks[3], (parts, b, t, c // parts)))


def _both(f, scales, width):
    """jitted (results, every gradient): one program a side, as the step is one program."""
    def run(x, w, bias, ct):
        y, pull = jax.vjp(lambda x, w, bias: f(x, w, bias, scales, width), x, w, bias)
        return (jnp.stack(y),) + tuple(g for g in pull(ct) if g is not None)
    return jax.jit(run)


def _conv_calls(f, *args):
    """The module's `pallas_call` equations in a function's jaxpr, nested calls included."""
    found = []

    def walk(jaxpr):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "pallas_call" and eqn.params["name"].startswith("short_conv"):
                found.append(eqn)
            for sub in jax.core.jaxprs_in_params(eqn.params):
                walk(sub)

    walk(jax.make_jaxpr(f)(*args).jaxpr)
    return found


def _conv_names(f, *args):
    return [eqn.params["name"] for eqn in _conv_calls(f, *args)]


@pytest.mark.parametrize("b,t,c,dtype,taps,bias,scales,width,tile", [
    (2, 128, 768, jnp.bfloat16, 4, False, QKV, 128, 64),    # the mixer's: two tiles, two heads a part
    (1, 150, 768, jnp.bfloat16, 4, False, QKV, 128, 64),    # T no multiple of the tile, nor of a register of rows
    (1, 512, 384, jnp.bfloat16, 4, False, QKV, 128, 512),   # two chunks of 256 rows in one tile
    (1, 100, 384, jnp.float32, 4, True, QKV, 128, 1024),    # one tile shorter than `_TILE`, a bias, float32
    (2, 96, 384, jnp.bfloat16, 2, False, QKV, 128, 32),     # 2 taps, three tiles
    (1, 70, 384, jnp.bfloat16, 9, False, QKV, 128, 32),     # 9 taps: all 8 rows carried from tile to tile
    (1, 64, 512, jnp.float32, 4, True, (None,), None, 32),  # no part normed (a Mamba-2 convolution's shape of call)
    (1, 64, 512, jnp.bfloat16, 3, False, (2.0, 2.0), 256, 32),  # every part normed, heads of two registers
])
def test_the_kernels_are_the_plain_form(b, t, c, dtype, taps, bias, scales, width, tile, monkeypatch):
    """The results equal the plain form's TO THE BIT after the one rounding to x's type (the
    same float32 arithmetic in the same order: both sides one XLA program on the CPU); d x
    within a rounding of x's type, d w and d b within float32's of sums over B x T terms."""
    monkeypatch.setattr(short_conv, "_TILE", tile)
    assert short_conv.takes_kernels(c, len(scales), width, taps)
    x, w, bias, ct = _inputs(b, t, c, dtype, taps, bias, len(scales))
    got = _both(short_conv.short_conv, scales, width)(x, w, bias, ct)
    want = _both(_plain, scales, width)(x, w, bias, ct)
    assert got[0].dtype == jnp.float32 and got[0].shape == (len(scales), b, t, c // len(scales))
    np.testing.assert_array_equal(got[0], want[0])
    np.testing.assert_array_equal(got[0], got[0].astype(dtype).astype(jnp.float32))  # values of x's type
    assert got[1].dtype == dtype and got[2].dtype == w.dtype
    # the plain form's cotangents pass through its cast to x's type (XLA drops that on a TPU, not here)
    loose = 2.0**-7 if dtype == jnp.bfloat16 else 1e-5
    for name, mine, theirs in zip(("dx", "dw", "db"), got[1:], want[1:]):
        mine, theirs = np.asarray(mine, np.float32), np.asarray(theirs, np.float32)
        np.testing.assert_allclose(mine, theirs, atol=loose * np.abs(theirs).max(), err_msg=name)


def test_float32_cotangents_are_not_rounded_on_the_way():
    """The results are float32 arrays so that their cotangents arrive in float32 (the module's
    docstring): a cotangent with nothing but bits below bfloat16's gives the gradients float32
    arithmetic gives, where one rounded to x's type on the way would give zeros."""
    x, w, _, ct = _inputs(1, 64, 384, jnp.bfloat16, 4, False, 3)
    ct = jnp.stack(ct)
    small = ct * 2.0**-12
    fine = (1.0 + small) - 1.0  # exactly representable differences of 1: lost by a rounding to bfloat16 of 1 + .
    run = _both(short_conv.short_conv, QKV, 128)
    got, ones, tiny = (run(x, w, None, tuple(c)) for c in (1.0 + small, jnp.ones_like(ct), fine))
    dw, dw_ones, dw_tiny = (np.asarray(g[2]) for g in (got, ones, tiny))
    assert np.abs(dw_tiny).max() > 0
    np.testing.assert_allclose(dw - dw_ones, dw_tiny, atol=1e-5 * np.abs(dw_ones).max())
    assert np.abs(dw - dw_ones).max() > 0.3 * np.abs(dw_tiny).max()


@pytest.mark.parametrize("channels,parts,width,taps,kernels", [
    (3072, 3, 128, 4, True),    # the Solar-Open2 cell's: 8 heads of 128 a part
    (768, 3, 256, 2, True), (512, 1, None, 9, True), (3072, 3, 128, 1, True),
    (96, 3, 16, 4, False),      # tier-1's width of 16: lanes not whole registers
    (576, 3, 64, 4, False),     # half a register of lanes a head
    (3072, 3, 128, 10, False),  # more taps than the 8 rows kept in front of a tile
    (1000, 3, 128, 4, False),   # the parts are not equal
    (640, 1, None, 4, True), (700, 1, None, 4, False),
])
def test_the_shape_alone_says_which_path_runs(channels, parts, width, taps, kernels):
    assert short_conv.supports(channels, parts, width, taps) == kernels
    assert short_conv.takes_kernels(channels, parts, width, taps) == kernels  # no mesh here


def _mixer_cfg(width):
    return dataclasses.replace(get_config("solar-tiny"), kda_head_dim=width, dtype="bfloat16")


def _mixer_inputs(cfg, t=48):
    lp = kda.init(jax.random.PRNGKey(3), cfg)
    lp["kda_conv"] = lp["kda_conv"] + 0.3  # taps that do not sum to nothing
    x = jax.random.normal(jax.random.PRNGKey(4), (2, t, cfg.d_model)).astype(jnp.bfloat16)
    return x, lp


def _mixer_grads(cfg, x, lp):
    def loss(x, lp):
        return jnp.sum(jnp.square(kda.mixer(x, lp, cfg).astype(jnp.float32)))
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))(x, lp)


@pytest.mark.parametrize("width,kernels", [(128, True), (16, False)])
def test_the_mixer_takes_the_kernels_where_they_tile_it_and_its_gradients_are_the_plain_forms(
        width, kernels, monkeypatch):
    """`kda.mixer` at a head width of whole registers runs both kernels (one call each way
    for q, k and v together) and at tier-1's width of 16 runs none; value and every gradient
    are those of the mixer with the plain form in the kernels' place."""
    cfg = _mixer_cfg(width)
    x, lp = _mixer_inputs(cfg)
    calls = _conv_names(jax.grad(lambda x: jnp.sum(kda.mixer(x, lp, cfg).astype(jnp.float32))), x)
    assert calls == (["short_conv_fwd", "short_conv_bwd"] if kernels else [])
    value, grads = _mixer_grads(cfg, x, lp)
    monkeypatch.setattr(short_conv, "takes_kernels", lambda *a: False)
    assert not _conv_names(lambda x: kda.mixer(x, lp, cfg), x)
    plain_value, plain_grads = _mixer_grads(cfg, x, lp)
    np.testing.assert_allclose(value, plain_value, rtol=1e-5)
    for (path, mine), theirs in zip(jax.tree_util.tree_leaves_with_path(grads), jax.tree.leaves(plain_grads)):
        mine, theirs = np.asarray(mine, np.float32), np.asarray(theirs, np.float32)
        # bfloat16 activations: the plain form's cotangents are rounded once more on the CPU (see above)
        np.testing.assert_allclose(mine, theirs, atol=(2.0**-6 if kernels else 1e-6) * np.abs(theirs).max(),
                                   err_msg=jax.tree_util.keystr(path))


def test_under_a_mesh_that_leaves_an_axis_to_gspmd_the_plain_form_runs():
    """GSPMD cannot partition a Mosaic call (ops/kda.py's rule): with an axis of the ambient
    mesh still automatic the mixer runs the plain form at the kernels' own shape and gives
    the single-device mixer's values; with every axis of size one the kernels run."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh

    cfg = _mixer_cfg(128)
    x, lp = _mixer_inputs(cfg)
    mixer = lambda x: kda.mixer(x, lp, cfg)  # noqa: E731
    want = jax.jit(mixer)(x)
    names = lambda: _conv_names(mixer, x)  # noqa: E731
    assert names() == ["short_conv_fwd"]
    mesh = build_mesh(MeshSpec(dp=2, tp=2), jax.devices()[:4])
    with use_mesh(mesh):
        assert not short_conv.takes_kernels(3 * cfg.kda_n_heads * 128, 3, 128, cfg.kda_conv_taps) and not names()
        got = jax.jit(mixer)(jax.device_put(x, NamedSharding(mesh, P("dp", None, None))))
    np.testing.assert_allclose(np.asarray(got, np.float32), np.asarray(want, np.float32),
                               atol=2.0**-7 * float(jnp.abs(want.astype(jnp.float32)).max()))
    with use_mesh(build_mesh(MeshSpec(dp=1), jax.devices()[:1])):
        assert names() == ["short_conv_fwd"]


def test_a_rematerialised_mixer_holds_one_kernel_a_pass_whatever_the_parts():
    """What keeps a step's first call short (PERF.md section 6, PR 44): q, k and v are ONE
    kernel call a pass, so a layer under remat `full` has two forward calls and one backward
    in its program (a call a part was nine), each with a grid whose first axis is the parts,
    and the kernels' bodies loop over a tile's rows (a `scan` in the body's jaxpr)."""
    cfg = dataclasses.replace(_mixer_cfg(128), remat=True, remat_policy="full")
    x, lp = _mixer_inputs(cfg)
    part = llama._maybe_remat(lambda x, lp: kda.mixer(x, lp, cfg), cfg)
    found = _conv_calls(jax.grad(lambda x, lp: jnp.sum(part(x, lp).astype(jnp.float32)), argnums=(0, 1)), x, lp)
    assert sorted(eqn.params["name"] for eqn in found) == ["short_conv_bwd", "short_conv_fwd", "short_conv_fwd"]
    for eqn in found:
        assert eqn.params["grid_mapping"].grid[0] == 3
        assert any(e.primitive.name in ("scan", "while") for e in eqn.params["jaxpr"].eqns)


# ------------------------------------------------- the gated short convolution (models/sconv.py: lfm2's mixer)

LFM2 = get_config("lfm2-tiny")


@pytest.mark.parametrize("taps", [3, 4])
def test_the_gated_short_convolution_is_the_position_at_a_time_loop(taps):
    """c_t = sum_k w_k z_{t-(taps-1)+k} with z = B * x, zeros before the sequence (the first
    taps - 1 positions see them), times C, through W_out: the mixer, the reference's layer and
    a loop over positions that keeps the last taps - 1 values of z, as a decoder would."""
    cfg = dataclasses.replace(LFM2, conv_taps=taps)
    lp = sconv.init(jax.random.PRNGKey(3), cfg)
    lp["sconv_norm"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(4), lp["sconv_norm"].shape)
    assert lp["sconv_in"].shape == (64, 3, 64) and lp["sconv_w"].shape == (taps, 64)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 9, 64))
    u = llama.rms_norm(x, lp["sconv_norm"], cfg.norm_eps)
    b, c, v = (u @ lp["sconv_in"][:, i] for i in range(3))  # the thirds in this order: B, C, x
    tail, out = jnp.zeros((2, taps - 1, 64)), []
    for t in range(x.shape[1]):
        window = jnp.concatenate([tail, (b[:, t] * v[:, t])[:, None]], axis=1)  # oldest first
        out.append((c[:, t] * jnp.einsum("bkd,kd->bd", window, lp["sconv_w"])) @ lp["sconv_out"])
        tail = window[:, 1:]
    want = x + jnp.stack(out, axis=1)
    np.testing.assert_allclose(x + sconv.mixer(x, lp, cfg), want, atol=2e-5)  # (`_block` adds the mixer's output to x)
    np.testing.assert_allclose(lfm2_ref.conv_layer(x, lp, model_of(cfg)), want, atol=2e-5)
    # position 0 sees only its own z through the LAST tap
    first = (c[:, 0] * (b[:, 0] * v[:, 0]) * lp["sconv_w"][-1]) @ lp["sconv_out"]
    np.testing.assert_allclose(sconv.mixer(x, lp, cfg)[:, 0], first, atol=2e-5)
    # causal: what comes later changes nothing earlier
    later = x.at[:, 5:].add(1.0)
    np.testing.assert_array_equal(sconv.mixer(later, lp, cfg)[:, :5], sconv.mixer(x, lp, cfg)[:, :5])
