"""The solar_open2 family (Solar-Open2) on the training path, at a small size on the CPU
with seeded weights: every published layer two parts of a pattern (a mixer, then experts);
Kimi-Delta-Attention mixers and their chunked delta rule, softmax attention without
rotation and with an output gate, sigmoid-routed SwiGLU experts at 8 of 320 beside a
shared one, and the shares of a layer's heads and experts a chip holds. The anchor is the
plain reference (ray_tpu/models/reference/)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import checkpoint, get_config, kda, llama, moe
from ray_tpu.models.reference import solar_open2 as ref
from ray_tpu.ops import kda as kda_op

ROOT = os.path.join(os.path.dirname(__file__), "..")
CFG = get_config("solar-tiny")
CELL = "solaropen2-train-tp8ep40share-s8192"
CONFIG = "solar-open2-train-tp8-ep40"


def _model(cfg):
    return dataclasses.asdict(cfg)


def _params(cfg, seed=0):
    p = llama.init(jax.random.PRNGKey(seed), cfg)
    if "layers" in p:  # a selection bias that changes who is chosen
        p["layers"]["router_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(seed + 5), p["layers"]["router_bias"].shape)
    if "kda_layers" in p:  # and a norm weight that is not one
        p["kda_layers"]["kda_o_norm"] = 1 + 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 6), p["kda_layers"]["kda_o_norm"].shape)
    return p


def _tokens(cfg, shape=(2, 41), seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, cfg.vocab_size)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _experts_share(p, cfg):
    """The tree with the routed experts `cfg.experts_held` says, of a tree that holds all."""
    lo, hi = moe.held_range(cfg)
    return {**p, "layers": {name: a[:, lo:hi] if name in ("w_gate", "w_up", "w_down") else a
                            for name, a in p["layers"].items()}}


# ---------------------------------------------------------------- against the reference

@pytest.mark.parametrize("pattern,held", [
    ("*EKEKEKE", (0, 1)),   # one period, everything held
    ("*EKEKEKE", (1, 4)),   # a quarter of the experts
    ("KEKE", (0, 2)),       # two periods of KE: the scan over periods
    ("K*K", (0, 1)),        # no expert part at all
])
def test_loss_and_every_gradient_match_the_reference(pattern, held):
    cfg = dataclasses.replace(CFG, layer_pattern=pattern, n_layers=len(pattern))
    assert llama.pattern_period(pattern)[1] == (2 if pattern == "KEKE" else 1)
    p, t = _params(cfg), _tokens(cfg)
    if "E" in pattern:
        cfg = dataclasses.replace(cfg, experts_held=held)
        p = _experts_share(p, cfg)
    (loss, m), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(p, {"tokens": t}, cfg)
    (r_loss, parts), r_grads = jax.value_and_grad(ref.loss, has_aux=True)(
        p, t, _model(cfg), jnp.float32, None, True)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-6)
    np.testing.assert_allclose(m["ce_loss"], parts["ce_loss"], rtol=1e-6)
    assert "mtp_loss" not in m
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    r_flat = dict(jax.tree_util.tree_flatten_with_path(r_grads)[0])
    assert len(flat) == len(r_flat) >= 15
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # selects, never weights: no gradient reaches it
            assert not np.asarray(g).any() and not np.asarray(r_flat[path]).any(), name
            continue
        scale = float(jnp.abs(r_flat[path]).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, r_flat[path], atol=2e-5 * scale + 1e-9, err_msg=name)
    if "E" in pattern:  # what the step chose is what the reference chose, layer by layer
        assert m["expert_load"].shape == (pattern.count("E"), cfg.n_experts)
        for mine, r in zip(m["experts_chosen"], parts["routings"]):
            own = np.asarray(r["own"])
            np.testing.assert_array_equal(
                np.sort(np.asarray(mine).reshape(own.shape), -1), np.sort(own, -1))
    else:
        assert "expert_load" not in m


def test_bfloat16_activations_err_as_the_rounded_reference_does():
    """The benchmark's comparison at a small size: the system with bfloat16 activations
    against the float32 reference, loss and every leaf's gradient, in multiples of the
    error the same plain reference makes in bfloat16, on the experts the system chose."""
    cfg = dataclasses.replace(CFG, dtype="bfloat16")
    p, t = _params(cfg), _tokens(cfg, (2, 65))
    (loss, m), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(p, {"tokens": t}, cfg)
    chosen = [np.asarray(c).reshape(2, 64, -1) for c in m["experts_chosen"]]
    exact, e_grads = jax.value_and_grad(ref.loss)(p, t, _model(cfg), jnp.float32, chosen)
    coarse, c_grads = jax.value_and_grad(ref.loss)(p, t, _model(cfg), jnp.bfloat16, chosen)
    assert abs(float(loss - exact)) < 3 * abs(float(coarse - exact)) + 1e-3 * float(exact)
    square = lambda a, b: sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(  # noqa: E731
        jax.tree.leaves(a), jax.tree.leaves(b)))
    mine, yardstick = square(grads, e_grads), square(c_grads, e_grads)
    assert 0 < mine < 1.5 ** 2 * yardstick, (mine, yardstick)
    assert yardstick < 0.05 ** 2 * square(e_grads, jax.tree.map(jnp.zeros_like, e_grads))


def test_the_reference_and_the_benchmarks_copy_agree():
    """benchmarks/lib/ keeps its own copy, so that no PR that claims a gain can change
    the yardstick by editing the program's tree: the two say the same."""
    sys.path.insert(0, ROOT)
    from benchmarks.lib import reference_solar_open2 as copy

    with open(ref.__file__) as a, open(copy.__file__) as b:
        assert a.read() == b.read()
    p, t = _params(CFG), _tokens(CFG)
    for mine, theirs in zip(jax.tree.leaves(ref.position_losses(p, t, _model(CFG))),
                            jax.tree.leaves(copy.position_losses(p, t, _model(CFG)))):
        np.testing.assert_array_equal(mine, theirs)


def test_the_coarse_reference_is_the_same_code_rounded():
    """bfloat16: the yardstick. Near the float32 reference, not equal to it; the decays'
    own leaves stay float32."""
    p, t = _params(CFG), _tokens(CFG)
    exact = ref.loss(p, t, _model(CFG))
    coarse = ref.loss(p, t, _model(CFG), jnp.bfloat16)
    assert 1e-6 < abs(float(coarse - exact)) / float(exact) < 2e-2
    assert set(ref.FLOAT32_LEAVES) == {"kda_A_log", "kda_dt_bias"}
    assert ref.next_token_losses(p, t, _model(CFG)).shape == (2, 40)


# ------------------------------------------------------------------- the chunked scan

def _scan_inputs(t, regime, seed=0, b=2, h=3, width=16):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    q = jax.random.normal(ks[0], (b, t, h, width)) * width**-0.5
    k = jax.random.normal(ks[1], (b, t, h, width))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(ks[2], (b, t, h, width))
    # exp(g): near 1 (a long memory), a chunk's sum below -100 (none: 16 positions of -7 to
    # -30 a channel), and both in one layer; beta over (0, 2) or within 0.1 of 2, where
    # I - beta k k^T is all but a reflection
    lo, hi = {"near_one": (1e-4, 1e-2), "below_minus_100_a_chunk": (7.0, 30.0), "mixed": (1e-3, 30.0),
              "beta_near_2": (1e-3, 1.0)}[regime]
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
    want, pull = jax.vjp(ref.recurrence, *args)
    got, pull_mine = jax.vjp(lambda *a: kda_op.kda_scan(*a, chunk), *args)
    assert np.isfinite(np.asarray(got)).all()
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    for name, mine, theirs in zip("q k v g beta".split(), pull_mine(cot), pull(cot)):
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


@pytest.mark.parametrize("chunk,sub,t", [(32, 8, 128), (32, 32, 64), (128, 32, 256)])
@pytest.mark.parametrize("regime", ["near_one", "below_minus_100_a_chunk", "mixed", "beta_near_2"])
def test_the_kernel_path_is_the_jnp_path_and_the_recurrence(regime, chunk, sub, t, monkeypatch):
    """At a width of 128 the overlaps go to the two Pallas kernels (ops/kda_overlaps.py, in
    the interpreter here): the scan's output and the gradient of q, k, v, g and beta against
    the same scan with `_decayed_overlaps` in their place and against the recurrence a
    position at a time, 2 heads, 2 to 4 chunks, sub-chunks of 8 and 32."""
    monkeypatch.setattr(kda_op, "_SUB", sub)
    args = _scan_inputs(t, regime, b=1, h=2, width=128)
    if regime == "below_minus_100_a_chunk":
        assert float(args[3].reshape(1, t // chunk, chunk, 2, 128).sum(2).max()) < -100
    scan = lambda *a: kda_op.kda_scan(*a, chunk)  # noqa: E731
    assert sorted(set(_pallas_calls(jax.grad(lambda *a: jnp.sum(scan(*a)), argnums=(0, 1, 3)), *args))) == [
        "kda_overlaps_bwd", "kda_overlaps_fwd"]
    cot = jax.random.normal(jax.random.PRNGKey(9), args[0].shape)
    got, pull_mine = jax.vjp(scan, *args)
    mine = pull_mine(cot)
    want, pull = jax.vjp(ref.recurrence, *args)
    with monkeypatch.context() as m:
        m.setattr(kda_op.kda_overlaps, "supports", lambda *shape: False)
        assert not _pallas_calls(scan, *args)
        plain, pull_plain = jax.vjp(scan, *args)
        plains = pull_plain(cot)
    assert np.isfinite(np.asarray(got)).all()
    scale = float(jnp.abs(want).max())
    np.testing.assert_allclose(got, plain, atol=2e-6 * scale)  # the same sums: a rounding apart
    np.testing.assert_allclose(got, want, atol=1e-5 * scale)
    for name, x, same, theirs in zip("q k v g beta".split(), mine, plains, pull(cot)):
        assert np.isfinite(np.asarray(x)).all(), name
        top = float(jnp.abs(theirs).max())
        np.testing.assert_allclose(x, same, atol=1e-5 * top + 1e-6, err_msg=name)
        np.testing.assert_allclose(x, theirs, atol=3e-5 * top + 1e-6, err_msg=name)


def test_no_decay_is_the_exponential_of_a_positive_number_in_the_kernels():
    """The case above on the kernel path (width 128, chunks of 32): decays of exp(-3000) a
    position, chunks whose sums fall to -8e4; a value and gradients that are finite say that
    no exponential saw a positive number (exp(3000) is inf, and 0 x inf poisons a sum), the
    masks cut before it in both kernels, and the factors across sub-chunks are <= 1."""
    q, k, v, _, beta = _scan_inputs(64, "mixed", b=1, h=2, width=128)
    g = jnp.full(q.shape, -3000.0).at[:, ::5].set(-1e-3)
    assert _pallas_calls(lambda *a: kda_op.kda_scan(*a, 32), q, k, v, g, beta) == ["kda_overlaps_fwd"]
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
    scan's jaxpr holds the forward kernel exactly where it says so. Nobody sets it."""
    monkeypatch.setattr(kda_op, "_SUB", sub)
    assert kda_op.takes_kernels(chunk, width) == kernels
    assert kda_op.kda_overlaps.supports(chunk, kda_op._sub(chunk), width) == kernels
    args = _scan_inputs(2 * chunk, "mixed", b=1, h=2, width=width)
    assert _pallas_calls(lambda *a: kda_op.kda_scan(*a, chunk), *args) == (["kda_overlaps_fwd"] if kernels else [])


def test_under_a_mesh_that_shards_the_heads_the_jnp_path_runs():
    """GSPMD cannot partition a Mosaic call: with an axis of the ambient mesh still automatic
    the scan runs `_decayed_overlaps` at the kernels' own shape, partitioned by the compiler,
    and is the single-device scan's value; with every axis of size one the kernels run."""
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
        assert kda_op.takes_kernels(32, 128) and _pallas_calls(scan, *args) == ["kda_overlaps_fwd"]


def test_the_scan_asserts_whole_chunks_and_packed_documents_are_refused():
    q, k, v, g, beta = _scan_inputs(24, "mixed")
    with pytest.raises(ValueError, match="multiple of the scan's chunk"):
        kda_op.kda_scan(q, k, v, g, beta, 16)
    p, t = _params(CFG), _tokens(CFG, (2, 33))
    with pytest.raises(NotImplementedError, match="Kimi-Delta-Attention layer over packed documents"):
        llama.loss_fn(p, {"tokens": t, "segment_ids": jnp.ones_like(t)}, CFG)
    with pytest.raises(NotImplementedError, match="KV cache"):
        llama.forward(p, t, CFG, cache=llama.init_kv_cache(CFG, 2, 64))


# ------------------------------------------------------------------- the shares

@pytest.mark.parametrize("part", ["kda_8_head_shares", "gated_gqa_8_head_shares", "40_expert_shares"])
def test_the_shares_add_up_to_the_uncut_layer(part):
    """What a chip of the deployment holds: 8 head shares of a mixer add up to the whole
    layer through W_o (the low-rank down-projections and the norm weight whole in every
    share), 40 expert shares with the shared expert counted once to the uncut expert part."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, CFG.d_model))
    if part == "kda_8_head_shares":
        whole = dataclasses.replace(CFG, kda_n_heads=16)
        share = dataclasses.replace(whole, kda_n_heads=2)
        lp = kda.init(jax.random.PRNGKey(3), whole)
        lp["kda_o_norm"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), lp["kda_o_norm"].shape)
        want = ref.kda_layer(x, lp, _model(whole)) - x
        by_heads = {"kda_qkv": 2, "kda_conv": 2, "kda_f_up": 1, "kda_dt_bias": 0, "kda_A_log": 0, "kda_beta": 1,
                    "kda_g_up": 1, "kda_out": 0}  # the axis the heads lie on; every other leaf is whole
        parts = []
        for i in range(8):
            mine = {name: jnp.take(a, jnp.arange(2 * i, 2 * i + 2), axis=by_heads[name]) if name in by_heads else a
                    for name, a in lp.items()}
            assert mine["kda_f_down"].shape == (CFG.d_model, 16) and mine["kda_qkv"].shape == (CFG.d_model, 3, 2, 16)
            parts.append(kda.mixer(x, mine, share) - x)
    elif part == "gated_gqa_8_head_shares":
        whole = dataclasses.replace(CFG, n_heads=8, n_kv_heads=2, layer_pattern="*", n_layers=1)
        share = dataclasses.replace(whole, attn_heads_held=(1, 1))
        lp = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), whole)["attn_layers"])
        assert lp["wq"].shape == lp["wo_gate"].shape == (CFG.d_model, 8, 24) and lp["wk"].shape == (CFG.d_model, 2, 24)
        held = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), share)["attn_layers"])
        assert held["wq"].shape == held["wo_gate"].shape == (CFG.d_model, 1, 24)
        want = ref.attention_layer(x, lp, _model(whole)) - x
        ungated = ref.attention_layer(x, {n: a for n, a in lp.items() if n != "wo_gate"}, _model(whole)) - x
        assert float(jnp.abs(want - ungated).max()) > 0.1 * float(jnp.abs(want).max())  # the gate gates
        positions = jnp.arange(x.shape[1])[None]
        parts = []
        for i in range(8):  # a query head, its gate, and the key/value head it reads (4 share one)
            mine = {"attn_norm": lp["attn_norm"], "wq": lp["wq"][:, i:i + 1], "wo": lp["wo"][i:i + 1],
                    "wo_gate": lp["wo_gate"][:, i:i + 1],
                    "wk": lp["wk"][:, i // 4:i // 4 + 1], "wv": lp["wv"][:, i // 4:i // 4 + 1]}
            parts.append(llama._block(x, mine, share, positions, None)[0] - x)
    else:
        whole = dataclasses.replace(CFG, n_experts=320, moe_top_k=8)
        lp = moe.init_expert_weights(jax.random.PRNGKey(3), whole)
        lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (320,))
        want, _ = ref.expert_layer(x, lp, _model(whole))
        shared = moe._mlp(x, tuple(lp[n] for n in ("shared_gate", "shared_up", "shared_down")))
        parts = [shared]
        for i in range(40):
            cfg = dataclasses.replace(whole, experts_held=(i, 40))
            mine = {**lp, **{n: lp[n][8 * i:8 * i + 8] for n in ("w_gate", "w_up", "w_down")}}
            y, _ = moe.expert_layer(x.reshape(-1, CFG.d_model), mine, cfg)
            parts.append(y.reshape(x.shape) - shared)
    total = sum(parts)
    np.testing.assert_allclose(total, want, atol=3e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(parts[1]).max()) > 1e-3  # a share is a part, not nothing


def test_route_at_8_of_320_is_the_references_choice_and_the_window_a_fortieths():
    """320 experts are no power of two and 8 of them a fortieth: the choice, the gates
    (normalised, times 1.0), the count, and the window of the cell's expert parts."""
    cfg = dataclasses.replace(CFG, n_experts=320, moe_top_k=8, experts_held=(3, 40))
    x = jax.random.normal(jax.random.PRNGKey(0), (384, 64))
    lp = moe.init_expert_weights(jax.random.PRNGKey(1), cfg)
    lp["router_bias"] = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (320,))
    idx, gates = moe.route(x, lp["router"], lp["router_bias"], cfg)
    _, routing = ref.expert_layer(x[None], {**lp, "router_bias": lp["router_bias"]}, _model(cfg))
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(routing["own"][0], -1))
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(moe.expert_load(idx, 320), np.bincount(np.asarray(idx).ravel(), minlength=320))
    assert moe.held_range(cfg) == (24, 32)
    cell = _cell_config()[2]
    assert 8192 * 8 // 40 == 1638 and moe.window_rows(cell, 8192) == 3584 == 7 * 512
    assert moe.combine_from_rows(8192, 8, 3584)  # the combine follows the window's rows


# ------------------------------------------------------------------- the step

def test_the_bias_moves_by_the_balance_rule_a_row_an_expert_part_in_pattern_order():
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    tx = make_optimizer(learning_rate=1e-3, warmup_steps=1, total_steps=10)
    state = init_state(jax.random.PRNGKey(0), CFG, tx)
    before = np.asarray(state.params["layers"]["router_bias"])
    t = _tokens(CFG, (2, 33))
    state, m = make_train_step(CFG, tx, donate=False)(state, {"tokens": t})
    load = np.asarray(m["expert_load"])
    assert load.shape == (4, CFG.n_experts)  # the pattern's four expert parts
    rule = before + CFG.moe_bias_update_rate * np.sign(load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(state.params["layers"]["router_bias"], rule, atol=1e-7)
    for layer in range(4):  # the counters are of what each part chose, in that order
        np.testing.assert_array_equal(
            load[layer], np.bincount(np.asarray(m["experts_chosen"][layer]).ravel(), minlength=CFG.n_experts))
    assert np.isfinite(float(m["loss"])) and "mtp_loss" not in m


def test_the_stacks_and_the_seeded_decays():
    assert llama._layer_kinds(CFG) == {"attn_layers": (1, "attn", None), "layers": (4, None, "experts"),
                                       "kda_layers": (3, "kda", None)}
    p = _params(CFG)
    axes = llama.param_axes(CFG)
    assert set(axes) == set(p)
    for name, stack in axes.items():
        if isinstance(stack, dict):
            assert set(stack) == set(p[name]), name
            assert all(len(stack[leaf]) == p[name][leaf].ndim for leaf in stack), name
    # the seeded decays lie in a trained layer's range: -exp(A_log) softplus(dt_bias) in [-1.6, -0.001]
    lp = kda.init(jax.random.PRNGKey(0), dataclasses.replace(CFG, kda_n_heads=64))
    g = -jnp.exp(lp["kda_A_log"])[:, None] * jax.nn.softplus(lp["kda_dt_bias"])
    assert -1.7 < float(g.min()) < -0.5 and -0.01 < float(g.max()) < -0.0009
    with pytest.raises(ValueError, match="K \\(Kimi Delta Attention\\)"):
        dataclasses.replace(CFG, layer_pattern="*EKEKEKX")
    with pytest.raises(ValueError, match="kda_n_heads"):
        dataclasses.replace(CFG, kda_n_heads=0)


# ------------------------------------------------------------------- the configuration

PAIRS = {  # published key -> ModelConfig field
    "hidden_size": "d_model", "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size", "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
    "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
    "max_position_embeddings": "max_seq_len", "first_k_dense_replace": "n_dense_layers",
    "use_rope": "attention_rotation", "use_gqa_gate": "attn_output_gate", "kda_allow_neg_eigval": "kda_neg_eigval",
    "n_shared_experts": "n_shared_experts", "norm_topk_prob": "moe_norm_topk",
    "routed_scaling_factor": "moe_route_scale", "num_experts_per_tok": "moe_top_k",
}


def _cell_config():
    sys.path.insert(0, ROOT)
    from benchmarks.lib import modelcfg

    with open(os.path.join(ROOT, "benchmarks", "configs", f"{CONFIG}.json")) as f:
        config = json.load(f)
    model = modelcfg.model_keys(config)
    return config, model, modelcfg.model_config(model)


def _config_from(hf: dict):
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(hf, f)
        return checkpoint.config_from_hf(d)


def test_configuration_files_program_group_equals_its_published_keys():
    config, _, cfg = _cell_config()
    for published, field in PAIRS.items():
        assert getattr(cfg, field) == config[published], (published, field)
    assert sorted(config["reduced"]) == sorted(config["published"])
    linear = config["linear_attn_config"]
    # the published widths, every one. head_dim 128 stands at the top level, as every number
    # of the source does; lib/modelcfg.py takes it only where head_dim x n_heads = d_model, so
    # the program group says n_heads 32 for that check alone: the layer reads its heads from
    # attn_heads_held and their width from attn_head_dim (the file's `cut` says so)
    assert config["head_dim"] == cfg.head_dim == cfg.attn_head_dim == 128
    assert config["num_attention_heads"] == 64 and cfg.n_heads * config["head_dim"] == cfg.d_model
    assert (cfg.d_model, cfg.kda_head_dim, cfg.kda_conv_taps, cfg.kda_rank, cfg.d_ff_expert, cfg.shared_width,
            cfg.moe_top_k, cfg.n_experts) == (4096, linear["head_dim"], linear["short_conv_kernel_size"], 128,
                                              1280, 1280, 8, 320)
    # what is held here, and of what: the chip's share of a group that shares each layer
    published = config["published"]
    assert config["gqa_layers"] == published["gqa_layers"][:1] == [0] and config["gqa_interval"] == 3
    assert cfg.layer_pattern == "*EKEKEKE" and cfg.n_layers == 2 * config["num_hidden_layers"] == 8
    assert cfg.kda_n_heads == linear["num_heads"] // 8 == 8
    assert (cfg.heads_held, cfg.kv_heads_held) == (config["num_attention_heads"] // 8, cfg.n_kv_heads // 8) == (8, 1)
    assert cfg.n_experts == published["n_routed_experts"] and cfg.n_experts_held == config["n_routed_experts"] == 8
    assert cfg.vocab_size == published["vocab_size"] // 8 and cfg.mtp_depth == 0
    assert cfg.moe_dropless and cfg.moe_select_bias and cfg.moe_scoring == "sigmoid" and cfg.mlp_activation == "silu_gated"
    assert abs(cfg.n_params - 840.9e6) < 0.1e6  # the issue's arithmetic
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    held = sum(int(np.prod(a.shape)) for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]
               if "router_bias" not in jax.tree_util.keystr(path))
    assert held == cfg.n_params
    count = lambda stack: sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes[stack]))  # noqa: E731
    assert abs(count("kda_layers") - 18.14e6) < 0.01e6 and abs(count("attn_layers") - 13.64e6) < 0.01e6
    assert abs(count("layers") - 320 - 142.87e6) < 0.01e6
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    assert len(config["assumed"]) >= 6 and config["trainer"]["reference"] == "reference_solar_open2"
    # the program's own mapping of the published keys says the same, share apart
    hf = {k: v for k, v in config.items() if k not in ("program", "trainer", "published", "reduced")}
    hf.update(published, num_hidden_layers=4, gqa_layers=[0])
    mapped = _config_from(hf)
    assert dataclasses.replace(
        mapped, name=cfg.name, vocab_size=cfg.vocab_size, n_heads=cfg.n_heads, kda_n_heads=8, attn_heads_held=(8, 1),
        experts_held=(0, 40),
        kda_proj_rank=128, kda_chunk=cfg.kda_chunk, d_ff_shared=1280, remat_policy="full", dtype="bfloat16") == cfg


def test_n_params_of_the_published_keys_is_250_b():
    config = _cell_config()[0]
    hf = {k: v for k, v in config.items() if k not in ("program", "trainer", "published", "reduced")}
    hf.update(config["published"])
    cfg = _config_from(hf)
    assert cfg.layer_pattern == "*EKEKEKE" * 12 and cfg.n_layers == 96 and cfg.head_dim == 128
    assert abs(cfg.n_params / 250e9 - 1) < 0.01
    active = cfg.n_params - 48 * (320 - 8) * 3 * 4096 * 1280  # 8 of 320 experts a token, and everything else
    assert abs(active / 14.7e9 - 1) < 0.01


def test_config_from_hf_maps_the_family_and_refuses_what_is_not_runnable():
    base = dict(model_type="solar_open2", vocab_size=256, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=24, intermediate_size=96, num_hidden_layers=4, gqa_layers=[0],
                linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
                use_rope=False, use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
                n_routed_experts=20, num_experts_per_tok=3, moe_intermediate_size=40, n_shared_experts=1,
                routed_scaling_factor=1, norm_topk_prob=True, first_k_dense_replace=0, rms_norm_eps=1e-5,
                max_position_embeddings=128, rope_theta=500000.0)
    cfg = _config_from(base)
    assert dataclasses.replace(cfg, name="solar-tiny", dtype="float32", kda_chunk=8) == CFG
    for bad, what in ((dict(use_rope=True), "use_rope"), (dict(kda_use_full_proj=True), "kda_use_full_proj"),
                      (dict(first_k_dense_replace=1), "dense layers"), (dict(norm_topk_prob=False), "not normalised"),
                      (dict(n_group=2), "group-limited"), (dict(sliding_window=4096), "window"),
                      (dict(linear_attn_config={"head_dim": 16, "num_heads": 4, "num_kv_heads": 2}), "num_kv_heads"),
                      (dict(linear_attn_config=None), "linear_attn_config"),
                      (dict(num_nextn_predict_layers=1), "MTP"), (dict(n_routed_experts=0), "routed experts")):
        with pytest.raises(ValueError, match=what):
            _config_from({**base, **bad})


def test_llm_refuses_the_family_by_name_of_what_is_missing():
    from ray_tpu.llm.config import LLMConfig

    with pytest.raises(NotImplementedError) as e:
        LLMConfig(model_source="solar-tiny").resolve_model_config()
    for what in ("delta-rule state", "output gate", "dropless", "convolution tails"):
        assert what in str(e.value)


# ------------------------------------------------------------------- the benchmark's files

def test_the_familys_flops_file_counts_one_chips_share():
    from benchmarks.lib import flops_solar_open2 as flops

    _, model, cfg = _cell_config()
    layer = flops.layer_flops_per_token(model, (8192 + 1) / 2)
    weights = 4096 * 3 * 1024 + 2 * (4096 + 1024) * 128 + 4096 * 8 + 1024 * 4096  # 18.12 M in products
    assert layer["K"] - flops.scan_flops_per_token(model) == 2 * weights
    assert layer["E"] == 2 * (4096 * 320 + 3 * 4096 * 1280 + 8 / 40 * flops.expert_params(model))
    assert flops.expert_params(model) == 3 * 4096 * 1280
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    assert set(fwd) == {"K", "*", "E", "head"}
    total = sum(fwd.values())
    assert 0.38 < fwd["head"] / total < 0.40  # the floors' doing: an eighth of the vocabulary over 4 layers
    assert 0.20 < fwd["K"] / total < 0.23 and 0.30 < fwd["E"] / total < 0.32 and fwd["*"] / total < 0.09
    assert abs(flops.train_flops_per_token(model, 8192) * 8192 / 12.75e12 - 1) < 0.01
    # the scan's yardstick is the file's own chunk, whatever the program's scan runs at
    assert flops.scan_step_work({**model, "kda_chunk": 32}, 8192) == flops.scan_step_work(model, 8192)
    work = flops.scan_step_work(model, 8192)
    assert work["bytes"] == 3 * 3 * 8192 * 8 * (3 * 2 * 128 + 4 * 128 + 4 + 4 * 128)
    assert work["bytes"] / 819e9 > work["flops"] / 197e12  # bound by what it reads and writes on a v5e
    assert flops.grouped_products_flops(model, 1638) == 3 * 2 * 1638 * 3 * 4096 * 1280


def test_the_scan_roofline_reader_reads_the_kda_scope():
    sys.path.insert(0, ROOT)
    from benchmarks.lib import flops_solar_open2 as flops
    from benchmarks.readers import trace_scope_share, train_scan_roofline

    config, model, _ = _cell_config()
    work = flops.scan_step_work(model, 8192)
    needed = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    result = {"traced_steps": 5, "tokens_per_step": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "trace": {"busy_s": 2.0, "op_seconds": {"%a": 0.04, "%b": 0.06, "%c": 1.9},
                        "op_scopes": {"%a": ["attn", "kda_scan"], "%b": ["attn", "kda_conv"], "%c": ["moe_experts"]}}}
    ctx = {"result": result, "config": config, "model": model, "rehearse": False}
    with open(os.path.join(ROOT, "benchmarks", "metrics", "train_kda_scan_roofline_pct.json")) as f:
        args = json.load(f)["args"]
    assert train_scan_roofline.read(ctx, **args) == pytest.approx(100 * 5 * needed / 0.04)
    with open(os.path.join(ROOT, "benchmarks", "metrics", "train_kda_pct.json")) as f:
        args = json.load(f)["args"]
    assert trace_scope_share.read(ctx, **args) == pytest.approx(100 * 0.10 / 2.0)
    # a program without the scope (the parent of this PR): nothing to read, and nothing raised
    result["trace"]["op_scopes"] = {"%c": ["moe_experts"]}
    assert train_scan_roofline.read(ctx, "kda_scan") is None and trace_scope_share.read(ctx, "^kda_") is None


def test_the_compiled_step_names_the_mixers_scopes_inside_attn():
    """What `train_kda_pct`, `train_kda_scan_roofline_pct` and the accepted readers that
    know `attn` read: the compiled program's instructions carry the mixer's scopes, each
    beside `attn`, forward and backward."""
    sys.path.insert(0, ROOT)
    from benchmarks.lib import scope_seconds

    p, t = _params(CFG), _tokens(CFG, (1, 33))
    def loss(p):  # as train/step.py names the model: the outermost scope is the transformations'
        with jax.named_scope("model"):
            return llama.loss_fn(p, {"tokens": t}, CFG)[0]

    text = jax.jit(jax.grad(loss)).lower(p).compile().as_text()
    by_instruction = scope_seconds.scopes_by_instruction(text)
    scopes = set().union(*by_instruction.values())
    names = {"kda_in_proj", "kda_conv", "kda_scan", "kda_norm_gate", "kda_out_proj"}
    assert names | {"moe_router", "moe_experts", "moe_shared", "attn", "mlp"} <= scopes, sorted(scopes)
    assert all("attn" in found for found in by_instruction.values() if found & names)


def test_the_manifest_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [w["name"] for w in manifest["workloads"]][4] == CELL and len(manifest["workloads"]) >= 5
    assert manifest["workloads"][4]["chips"] == 1 and manifest["configs"][4]["name"] == CONFIG
    config = _cell_config()[0]
    assert manifest["configs"][4]["reduced"] == config["reduced"]
    assert manifest["configs"][4]["source"] == config["source"]
    with open(os.path.join(ROOT, "benchmarks", "workloads", f"{CELL}.json")) as f:
        assert json.load(f)["why"] == manifest["workloads"][4]["why"]
    reported = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct", "train_kda_pct", "train_kda_scan_roofline_pct", "train_mfu_kda_moe_pct",
        "train_kda_conv_pct"}
    for name in ("train_kda_pct", "train_kda_scan_roofline_pct", "train_mfu_kda_moe_pct", "train_kda_conv_pct"):
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics", f"{name}.json"))


def test_the_new_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="1")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", CELL,
         "--seed", "3000000007", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=220)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert all(window["checks"].values()), window["checks"]
    assert {"selection_agrees_beyond_margin", "step_losses_match_reference",
            "step_gradients_match_reference", "step_update_follows_its_moments",
            "router_bias_moved_by_the_rule"} <= set(window["checks"])
    assert window["parity"]["gradient"]["rows"] > 30
    assert set(window["parity"]["losses"]) == {"loss", "ce_loss"}
    assert window["parity"]["positions"] == 2 * 64
    values = next(ln for ln in lines if ln.get("phase") == "rehearsal_values")["values"]
    assert values["train_moe_imbalance"]["value"] >= 1.0
    assert lines[-1]["correct"] is False and lines[-1]["metrics"] == {}
