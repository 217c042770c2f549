"""The nemotron_h family (Nemotron-3-Super) on the training path, at a small size on the
CPU with seeded weights: a pattern of single-part layers (Mamba-2 mixers and their
chunked scan, attention without rotation, latent relu2 experts routed many a token beside
a shared one, the dense relu2 MLP), the MTP module, and the shares of a layer's heads and
experts a chip holds. The anchor is the plain reference (ray_tpu/models/reference/)."""
import dataclasses
import json
import os
import re
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import checkpoint, get_config, llama, moe, ssm
from ray_tpu.models.config import ModelConfig
from ray_tpu.models.reference import nemotron_h as ref
from ray_tpu.ops import ssd

ROOT = os.path.join(os.path.dirname(__file__), "..")
CFG = get_config("nemotron-tiny")
CELL = "nemotron3super-train-tp8ep64share-s8192"
CONFIG = "nemotron-3-super-train-tp8-ep64"


def _model(cfg):
    return dataclasses.asdict(cfg)


def _params(cfg, seed=0):
    p = llama.init(jax.random.PRNGKey(seed), cfg)
    for name in ("layers", "mtp"):  # a selection bias that changes who is chosen
        if name in p and "router_bias" in p[name]:
            p[name]["router_bias"] = 0.05 * jax.random.normal(
                jax.random.PRNGKey(seed + 5), p[name]["router_bias"].shape)
    if "ssm_layers" in p:  # and a convolution bias that is not zero
        p["ssm_layers"]["conv_b"] = 0.1 * jax.random.normal(
            jax.random.PRNGKey(seed + 6), p["ssm_layers"]["conv_b"].shape)
    return p


def _tokens(cfg, shape=(2, 41), seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, cfg.vocab_size)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


def _leaves_match(grads, r_grads, atol=2e-5, least=20):
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    r_flat = dict(jax.tree_util.tree_flatten_with_path(r_grads)[0])
    assert len(flat) == len(r_flat) >= least
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # selects, never weights: no gradient reaches it
            assert not np.asarray(g).any() and not np.asarray(r_flat[path]).any(), name
            continue
        scale = float(jnp.abs(r_flat[path]).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, r_flat[path], atol=atol * scale + 1e-9, err_msg=name)


# ---------------------------------------------------------------- against the reference

@pytest.mark.parametrize("pattern,mtp,held", [
    ("MEM*E-", 1, (0, 1)),   # every character, the MTP module, everything held
    ("MEM*E-", 0, (1, 4)),   # no MTP term; a quarter of the experts
    ("ME*ME*", 1, (0, 2)),   # two periods of ME*: the scan over periods
    ("M*-", 0, (0, 1)),      # no expert layer at all
])
def test_loss_and_every_gradient_match_the_reference(pattern, mtp, held):
    cfg = dataclasses.replace(CFG, layer_pattern=pattern, n_layers=len(pattern), mtp_depth=mtp,
                              mtp_layer_pattern="*E" if mtp else "", experts_held=held)
    assert llama.pattern_period(pattern)[1] == (2 if pattern == "ME*ME*" else 1)
    p, t = _params(cfg), _tokens(cfg)
    assert ("mtp" in p) == bool(mtp)
    (loss, m), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(p, {"tokens": t}, cfg)
    (r_loss, parts), r_grads = jax.value_and_grad(ref.loss, has_aux=True)(
        p, t, _model(cfg), jnp.float32, None, True)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-6)
    np.testing.assert_allclose(m["ce_loss"], parts["ce_loss"], rtol=1e-6)
    assert ("mtp_loss" in m) == bool(mtp)
    if mtp:
        np.testing.assert_allclose(m["mtp_loss"], parts["mtp_loss"], rtol=1e-6)
        np.testing.assert_allclose(loss, m["ce_loss"] + cfg.mtp_loss_weight * m["mtp_loss"], rtol=1e-6)
    _leaves_match(grads, r_grads, least=12)
    # a row an expert layer in the pattern's order, the MTP module's last: what the step
    # chose is what the reference chose, layer by layer
    expert_layers = pattern.count("E") + mtp
    if expert_layers:
        assert m["expert_load"].shape == (expert_layers, cfg.n_experts)
        assert len(parts["routings"]) == expert_layers
        for mine, r in zip(m["experts_chosen"], parts["routings"]):
            own = np.asarray(r["own"])
            mine = np.asarray(mine).reshape(t.shape[0], -1, cfg.moe_top_k)[:, :own.shape[1]]
            np.testing.assert_array_equal(np.sort(mine, -1), np.sort(own, -1))
    else:
        assert "expert_load" not in m


def test_the_reference_and_the_benchmarks_copy_agree():
    """benchmarks/lib/ keeps its own copy, so that no PR that claims a gain can change
    the yardstick by editing the program's tree: the two say the same."""
    sys.path.insert(0, ROOT)
    from benchmarks.lib import reference_nemotron_h as copy

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
    assert set(ref.FLOAT32_LEAVES) == {"A_log", "dt_bias", "D"}


# ------------------------------------------------------------------- the chunked scan

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
    mine = lambda *a: jnp.sum(ssd.ssd_scan(*a, chunk) * cot)  # noqa: E731
    theirs = lambda *a: jnp.sum(ref.recurrence(*a) * cot)  # noqa: E731
    y, want = ssd.ssd_scan(*args, chunk), ref.recurrence(*args)
    assert np.isfinite(np.asarray(y)).all()
    np.testing.assert_allclose(y, want, atol=2e-5 * float(jnp.abs(want).max()))
    grads = jax.grad(mine, argnums=(0, 1, 2, 3, 4))(*args)
    r_grads = jax.grad(theirs, argnums=(0, 1, 2, 3, 4))(*args)
    for name, g, r in zip("x dt a b c".split(), grads, r_grads):
        assert np.isfinite(np.asarray(g)).all(), name
        np.testing.assert_allclose(g, r, atol=5e-5 * float(jnp.abs(r).max()) + 1e-9, err_msg=name)


def test_the_scan_asserts_whole_chunks_and_packed_documents_are_refused():
    args = _scan_inputs(20, "mixed")
    with pytest.raises(ValueError, match="multiple of the scan's chunk"):
        ssd.ssd_scan(*args, 8)
    p, t = _params(CFG), _tokens(CFG)
    with pytest.raises(NotImplementedError, match="packed documents"):
        llama.loss_fn(p, {"tokens": t, "segment_ids": jnp.zeros_like(t)}, CFG)


# ------------------------------------------------------------------- the shares add up

def _mamba_share(lp, cfg, i, of):
    """Share i of `of` of a Mamba-2 layer's leaves: its heads, its groups, and the rows of
    W_out they feed; the layer's own norm whole."""
    d_in, gn = cfg.ssm_d_inner, cfg.ssm_n_groups * cfg.ssm_state
    cols = lambda start, width: np.arange(start + i * width // of, start + (i + 1) * width // of)  # noqa: E731
    z, x, b, c = cols(0, d_in), cols(d_in, d_in), cols(2 * d_in, gn), cols(2 * d_in + gn, gn)
    dt = cols(2 * d_in + 2 * gn, cfg.ssm_n_heads)
    conv = np.concatenate([x, b, c]) - d_in
    heads = cols(0, cfg.ssm_n_heads)
    return {"ssm_norm": lp["ssm_norm"], "in_proj": lp["in_proj"][:, np.concatenate([z, x, b, c, dt])],
            "conv_w": lp["conv_w"][:, conv], "conv_b": lp["conv_b"][conv],
            "dt_bias": lp["dt_bias"][heads], "A_log": lp["A_log"][heads], "D": lp["D"][heads],
            "gate_norm": lp["gate_norm"][z], "out_proj": lp["out_proj"][z]}


@pytest.mark.parametrize("part", ["mamba_8_head_shares", "attention_8_head_shares", "64_expert_shares"])
def test_the_shares_add_up_to_the_uncut_layer(part):
    """What every share of a layer computes, summed, with what all compute alike (the
    shared expert) counted once, is the uncut reference's layer: the system given a share
    runs the published layer's part and nothing stands in for the rest."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, CFG.d_model))
    if part == "mamba_8_head_shares":
        whole = dataclasses.replace(CFG, ssm_n_heads=16, ssm_n_groups=8)
        share = dataclasses.replace(whole, ssm_n_heads=2, ssm_n_groups=1)
        lp = ssm.init(jax.random.PRNGKey(3), whole)
        lp["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), lp["conv_b"].shape)
        lp["gate_norm"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), lp["gate_norm"].shape)
        want = ref.mamba_layer(x, lp, _model(whole)) - x
        parts = [ssm.mixer(x, _mamba_share(lp, whole, i, 8), share) - x for i in range(8)]
    elif part == "attention_8_head_shares":
        whole = dataclasses.replace(CFG, n_heads=8, n_kv_heads=2, layer_pattern="*", n_layers=1, mtp_depth=0,
                                    mtp_layer_pattern="")
        share = dataclasses.replace(whole, attn_heads_held=(1, 1))
        lp = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), whole)["attn_layers"])
        assert lp["wq"].shape == (CFG.d_model, 8, 24) and lp["wk"].shape == (CFG.d_model, 2, 24)
        held = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), share)["attn_layers"])
        assert held["wq"].shape == (CFG.d_model, 1, 24) and held["wk"].shape == (CFG.d_model, 1, 24)
        want = ref.attention_layer(x, lp, _model(whole)) - x
        positions = jnp.arange(x.shape[1])[None]
        parts = []
        for i in range(8):  # a query head and the key/value head it reads (4 share one)
            mine = {"attn_norm": lp["attn_norm"], "wq": lp["wq"][:, i:i + 1], "wo": lp["wo"][i:i + 1],
                    "wk": lp["wk"][:, i // 4:i // 4 + 1], "wv": lp["wv"][:, i // 4:i // 4 + 1]}
            parts.append(llama._block(x, mine, share, positions, None)[0] - x)
    else:
        whole = dataclasses.replace(CFG, n_experts=64, moe_top_k=5)
        lp = moe.init_expert_weights(jax.random.PRNGKey(3), whole)
        lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (64,))
        want, _ = ref.expert_layer(x, lp, _model(whole))
        shared = moe._mlp(x, (lp["shared_up"], lp["shared_down"]))
        parts = [shared]
        for i in range(64):
            cfg = dataclasses.replace(whole, experts_held=(i, 64))
            mine = {**lp, "w_up": lp["w_up"][i:i + 1], "w_down": lp["w_down"][i:i + 1]}
            y, _ = moe.expert_layer(x.reshape(-1, CFG.d_model), mine, cfg)
            parts.append(y.reshape(x.shape) - shared)
    total = sum(parts)
    np.testing.assert_allclose(total, want, atol=3e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(parts[1]).max()) > 1e-3  # a share is a part, not nothing


# ------------------------------------------------------------------- the expert layer

ROUTED = ModelConfig(
    name="nemotron-routing", vocab_size=256, d_model=64, n_layers=1, n_heads=4, n_kv_heads=2, d_ff=96,
    dtype="float32", n_experts=512, moe_top_k=22, moe_capacity_factor=0.0, d_ff_expert=24,
    n_shared_experts=1, d_ff_shared=48, moe_latent_dim=32, mlp_activation="relu2", moe_scoring="sigmoid",
    moe_route_scale=5.0, moe_select_bias=True, experts_held=(3, 64))


def test_route_at_22_of_512_is_the_references_choice_and_gates():
    x = jax.random.normal(jax.random.PRNGKey(0), (384, 64))
    lp = moe.init_expert_weights(jax.random.PRNGKey(1), ROUTED)
    lp["router_bias"] = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (512,))
    assert x.shape[0] * 22 * 512 > moe._MASK_ELEMENTS  # the slot-at-a-time path
    idx, gates = moe.route(x, lp["router"], lp["router_bias"], ROUTED)
    _, routing = ref.expert_layer(x[None], lp, _model(ROUTED))
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(routing["own"][0], -1))
    scores = jax.nn.sigmoid(x @ lp["router"])
    want = jnp.take_along_axis(scores, idx, -1)
    np.testing.assert_allclose(gates, 5.0 * want / want.sum(-1, keepdims=True), rtol=1e-6)
    np.testing.assert_allclose(gates.sum(-1), 5.0, rtol=1e-6)
    np.testing.assert_array_equal(moe.expert_load(idx, 512), np.bincount(np.asarray(idx).ravel(), minlength=512))
    # both forms of the pick and the count say the same (4 of 64 at 8,192 tokens is the other)
    small = idx[:16]
    assert small.size * 512 <= moe._MASK_ELEMENTS
    np.testing.assert_array_equal(moe._chosen_scores(scores[:16], small), jnp.take_along_axis(scores[:16], small, -1))
    np.testing.assert_array_equal(moe.expert_load(small, 512), np.bincount(np.asarray(small).ravel(), minlength=512))


def _plain_gates(x, w, bias, idx, scale):
    """`route`'s formula written plainly on a given choice, in whatever type it is handed."""
    scores = jax.nn.sigmoid(jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST))
    picked = jnp.take_along_axis(scores, idx, -1)
    return scale * picked / (picked.sum(-1, keepdims=True) + 1e-20) + 0 * bias.sum()


@pytest.mark.parametrize("scores", ["random", "ties"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("form", ["slots_22_of_512", "mask_4_of_64"])
def test_routes_backward_rule_is_the_gradient_of_the_plain_formula(form, dtype, scores):
    """`moe.route` differentiates by a rule of its own (it keeps the scores it made and
    writes the pick's gradient in one pass): against plain differentiation of the same
    formula (`take_along_axis`) on the same choice, for both forms of the forward pick,
    with float32 and bfloat16 activations, float64 as the yardstick. `ties`: triples
    of experts with equal weights (equal scores in every token, and k is no multiple of
    3, so every token's k-th and (k+1)-th scores are equal and the choice cuts a tie),
    and tokens repeated."""
    n_experts, k, tokens = (512, 22, 384) if form.startswith("slots") else (64, 4, 96)
    cfg = dataclasses.replace(ROUTED, n_experts=n_experts, moe_top_k=k)
    assert (tokens * k * n_experts > moe._MASK_ELEMENTS) == form.startswith("slots")
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, n_experts)) * 0.125
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (n_experts,))
    if scores == "ties":
        first = jnp.arange(n_experts) // 3 * 3  # experts 3j, 3j + 1, 3j + 2 score alike everywhere
        w, bias = w[:, first], bias[first]
        x = x.at[tokens // 2:].set(x[:tokens // 2])
    x = x.astype(dtype)
    cot = jax.random.normal(jax.random.PRNGKey(3), (tokens, k))

    def mine(x, w, bias):
        idx, gates = moe.route(x, w, bias, cfg)
        return jnp.sum(gates * cot), idx

    (_, idx), (dx, dw, db) = jax.jit(jax.value_and_grad(mine, argnums=(0, 1, 2), has_aux=True))(x, w, bias)
    assert dx.dtype == x.dtype and dw.dtype == w.dtype and not np.asarray(db).any()
    assert all(len(set(row)) == k for row in np.asarray(idx).tolist())
    if scores == "ties":  # k is no multiple of 3: every token's choice cuts a triple of equal scores
        assert all(np.bincount(np.asarray(row) // 3).max() == 3 and set(np.bincount(np.asarray(row) // 3)) > {0, 3}
                   for row in np.asarray(idx))

    def plain(x, w, bias):
        return jnp.sum(_plain_gates(x.astype(w.dtype), w, bias, idx, cfg.moe_route_scale) * cot.astype(w.dtype))

    p_dx, p_dw, p_db = jax.jit(jax.grad(plain, argnums=(0, 1, 2)))(x, w, bias)
    assert not np.asarray(p_db).any()
    with jax.enable_x64(True):
        exact = jax.grad(plain, argnums=(0, 1))(
            np.asarray(x.astype(jnp.float32), np.float64), np.asarray(w, np.float64), np.asarray(bias, np.float64))
    for name, got, same_type, want in (("dx", dx, p_dx, exact[0]), ("dw", dw, p_dw, exact[1])):
        want = np.asarray(want)
        scale = np.abs(want).max()
        assert scale > 0, name
        err = np.abs(np.asarray(got.astype(jnp.float32), np.float64) - want).max() / scale
        yardstick = np.abs(np.asarray(same_type.astype(jnp.float32), np.float64) - want).max() / scale
        # float32 roundings (dx in bfloat16: one rounding of the result), and no further
        # from float64 than plain differentiation in the same types is
        assert err <= (2 ** -8 if got.dtype == jnp.bfloat16 else 2e-6), (name, err)
        assert err <= 1.5 * yardstick + 1e-7, (name, err, yardstick)


def test_the_compiled_layer_has_no_operand_of_tokens_by_k_by_experts_and_no_scatter():
    """22 of 512 at 384 tokens: value and every gradient of the layer. No shape in the
    program has the extents of tokens, k and experts together (as a mask [T, k, E] has,
    4.3 M elements here and 92 M at the cell's size), nothing is scattered, and the
    layer's output and gradients are the reference's."""
    tokens = 384
    x = jax.random.normal(jax.random.PRNGKey(0), (tokens, 64))
    lp = moe.init_expert_weights(jax.random.PRNGKey(1), ROUTED)
    cot = jax.random.normal(jax.random.PRNGKey(3), x.shape)

    def mine(x, lp):
        return jnp.sum(moe.expert_layer(x, lp, ROUTED)[0] * cot)

    def theirs(x, lp):
        return jnp.sum(ref.expert_layer(x[None], lp, _model(ROUTED))[0][0] * cot)

    fn = jax.jit(jax.value_and_grad(mine, argnums=(0, 1)))
    text = fn.lower(x, lp).compile().as_text()
    assert not re.search(r" scatter\(", text)
    shapes = {tuple(int(n) for n in dims.split(",")) for dims in re.findall(r"\[([0-9]+(?:,[0-9]+)+)\]", text)}
    wide = [s for s in shapes if int(np.prod(s)) >= tokens * 22 * 512]
    assert not wide, wide[:4]
    assert not [s for s in shapes if {tokens, 22, 512} <= set(s)]
    (value, grads), (want, r_grads) = fn(x, lp), jax.value_and_grad(theirs, argnums=(0, 1))(x, lp)
    np.testing.assert_allclose(value, want, rtol=2e-5)
    _leaves_match(grads, r_grads, least=8)


@pytest.mark.parametrize("load", ["under", "one_over", "every"])
def test_the_window_walk_serves_latent_relu2_experts_at_any_load(load):
    """The routed path's buffer at a 64th held is far smaller than tokens x k; a router
    steered onto the held experts overflows it, and the walk serves every row: output and
    gradients are the reference's under, one row over, and with every assignment held."""
    cfg = dataclasses.replace(ROUTED, n_experts=128, moe_top_k=2, experts_held=(1, 16), d_ff_expert=8)
    tokens = 2048
    n, rows = tokens * cfg.moe_top_k, moe.window_rows(cfg, tokens)
    assert rows == 512 < n
    held_rows = {"under": rows // 2 + 3, "one_over": rows + 1, "every": n}[load]
    lo, hi = moe.held_range(cfg)
    lp = moe.init_expert_weights(jax.random.PRNGKey(7), cfg)
    lp.pop("router_bias")
    # the router reads the first columns of x: the first held_rows / k tokens choose held experts
    router = np.zeros((64, 128), np.float32)
    router[np.arange(hi - lo), lo + np.arange(hi - lo)] = 8.0
    x = np.array(jax.random.normal(jax.random.PRNGKey(8), (tokens, 64))) * 0.5
    steer = np.arange(tokens) < -(-held_rows // 2)
    x[:, :hi - lo] = -1.0
    x[steer, :2] = 1.0
    if held_rows % 2:  # the last steered token holds one assignment only
        x[-(-held_rows // 2) - 1, 1] = -1.0
    lp["router"], x = jnp.asarray(router), jnp.asarray(x)
    cot = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    names = ("router", "w_up", "w_down", "latent_down", "latent_up")

    def mine(x, w):
        y, counted = moe.expert_layer(x, {**lp, **w}, cfg)
        return jnp.sum(y * cot), counted

    def theirs(x, w):
        return jnp.sum(ref.expert_layer(x[None], {**lp, **w}, _model(cfg))[0][0] * cot)

    w = {name: lp[name] for name in names}
    (value, counted), grads = jax.jit(jax.value_and_grad(mine, argnums=(0, 1), has_aux=True))(x, w)
    want, r_grads = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1)))(x, w)
    assert float(counted["load"][lo:hi].sum()) == held_rows
    assert int(moe.windows_walked(jnp.int32(held_rows), rows)) == {"under": 1, "one_over": 2, "every": 8}[load]
    np.testing.assert_allclose(value, want, rtol=2e-5, atol=1e-5)
    _leaves_match(grads, r_grads, least=6)


# The combine (`moe._put`) against a plain sum by token written here: float32 sums of a
# token's rows in slot order, rounded once. Routing is made by hand (who chose whom), then
# sorted as `expert_layer` sorts it. (tokens, k, experts, held, skew, from the window's
# side): token 0 chooses held experts with as many of its slots as there are held experts,
# token 1 with none; "skew" sends every token's first slots to the held experts, so the
# load overflows the window and the windows at start > 0 hold rows too.
COMBINE = {
    "22_of_512_a_64th_held": (1024, 22, 512, (0, 64), False, True),
    "22_of_512_a_64th_held_skewed": (1024, 22, 512, (0, 64), True, True),
    "22_of_512_a_64th_held_last_window_padded": (1000, 22, 512, (0, 64), True, True),
    "22_of_512_a_16th_held_every_slot_of_a_token": (2048, 22, 512, (0, 16), False, True),
    "22_of_512_an_8th_held": (1024, 22, 512, (0, 8), False, True),
    "4_of_64_an_8th_held": (1024, 4, 64, (0, 8), False, True),
    "4_of_64_an_8th_held_skewed": (1024, 4, 64, (0, 8), True, True),
    "22_of_512_a_quarter_held_every_slot_of_a_token": (1024, 22, 512, (0, 4), False, False),
    "4_of_64_a_quarter_held_skewed": (1024, 4, 64, (1, 4), True, False),
    "4_of_64_a_64th_held": (1024, 4, 64, (5, 64), False, True),
    "2_of_128_a_16th_held_skewed": (2048, 2, 128, (1, 16), True, True),
    "2_of_8_every_expert_held": (96, 2, 8, (0, 1), False, False),
}


def _sorted_assignments(tokens, k, n_experts, held, skew, seed=0):
    """(order padded to whole windows, inverse, rows, held rows) for a routing made by hand."""
    rng = np.random.default_rng(seed)
    cfg = dataclasses.replace(ROUTED, n_experts=n_experts, moe_top_k=k, experts_held=held)
    lo, hi = moe.held_range(cfg)
    inside, outside = np.arange(lo, hi), np.setdiff1d(np.arange(n_experts), np.arange(lo, hi))
    idx = np.argsort(rng.random((tokens, n_experts)), -1)[:, :k]
    most = min(k, hi - lo)
    for t in range(tokens) if skew else (0,):  # as many slots as can be, on held experts
        idx[t] = np.concatenate([rng.permutation(inside)[:most], rng.permutation(outside)[:k - most]])
    if len(outside) >= k:
        idx[1] = rng.permutation(outside)[:k]
    key = np.where((idx >= lo) & (idx < hi), idx - lo, hi - lo).reshape(-1)
    order = np.argsort(key, kind="stable").astype(np.int32)
    rows = moe.window_rows(cfg, tokens)
    return (np.pad(order, (0, -order.size % rows)), np.argsort(order).astype(np.int32), rows,
            int((key < hi - lo).sum()))


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", list(COMBINE))
def test_the_combine_is_a_float32_sum_by_token_rounded_once(case, dtype):
    """Rows and scalars (the gates' case, k = 1), every window the load needs, both
    directions: `_put` is the plain sum by token, its transpose the plain gather, whichever
    side `combine_from_rows` says the layer sums from. Rows of 1 + 2^-5 sum exactly in
    float32 in any order: a token with 22 of them reads 22.75 in bfloat16, and 22.25 where
    the running sum was kept in bfloat16."""
    tokens, k, n_experts, held, skew, from_rows = COMBINE[case]
    order, inverse, rows, held_rows = _sorted_assignments(tokens, k, n_experts, held, skew)
    n, width = tokens * k, 16
    assert moe.combine_from_rows(tokens, k, rows) == from_rows
    windows = -(-held_rows // rows)
    assert (windows > 1) == (skew and rows < n)
    rng = np.random.default_rng(1)
    fullest = 0
    for start in range(0, windows * rows, rows):
        at = order[start:start + rows]
        valid = start + np.arange(rows) < n
        token = np.where(valid, at // k, tokens)
        fullest = max(fullest, int(np.bincount(token[valid]).max()))
        for rows_of in ("normal", "one_and_a_32nd"):
            b = rng.standard_normal((rows, width)).astype(np.float32) if rows_of == "normal" \
                else np.full((rows, width), 1.03125, np.float32)
            b = jnp.asarray(b, dtype)
            want = np.zeros((tokens + 1, width), np.float32)
            slots = np.argsort(np.where(valid, at, n), kind="stable")  # a token's slots in order
            np.add.at(want, token[slots], np.asarray(b, np.float32)[slots])  # one by one, in float32
            want = np.asarray(jnp.asarray(want[:tokens]).astype(dtype), np.float32)
            got, pull = jax.vjp(lambda b: moe._put(b, order, inverse, np.int32(start), rows, k), b)
            got = np.asarray(got, np.float32)
            if rows_of == "normal" and dtype == jnp.float32:  # the additions' order may differ
                np.testing.assert_allclose(got, want, rtol=1e-6, atol=2e-6)
            elif rows_of == "normal":  # ... and may round a sum at a tie's edge the other way
                assert (got != want).mean() < 2e-3
                np.testing.assert_allclose(got, want, rtol=2 ** -7)
            else:
                np.testing.assert_array_equal(got, want)
                if "every_slot" in case and dtype == jnp.bfloat16:
                    assert got[0, 0] == 22.75  # a bfloat16 running sum reads 22.25
            g = jnp.asarray(rng.standard_normal((tokens, width)), dtype)
            back = np.asarray(pull(g)[0], np.float32)  # a row's cotangent is its token's
            np.testing.assert_array_equal(back[valid], np.asarray(g, np.float32)[token[valid]])
        # the scalars: every assignment's value, in float32 to the bit
        v = jnp.asarray(rng.standard_normal(rows), jnp.float32) * (1 + 2.0 ** -20)
        want = np.zeros(n + 1, np.float32)
        want[np.where(valid, at, n)] = np.where(valid, np.asarray(v), 0)
        got, pull = jax.vjp(lambda v: moe._put(v, order, inverse, np.int32(start), rows, 1), v)
        np.testing.assert_array_equal(got, want[:n])
        g = jnp.asarray(rng.standard_normal(n), jnp.float32)
        np.testing.assert_array_equal(np.asarray(pull(g)[0])[valid], np.asarray(g)[at[valid]])
    if "every_slot" in case:
        assert fullest == k == 22  # the case a bfloat16 running sum fails
    assert fullest >= min(k, n_experts // held[1]) or skew


def test_the_cells_window_is_twice_what_its_experts_can_expect():
    cfg = _cell_config()[2]
    assert moe.window_rows(cfg, 8192) == 5632 == 11 * 512
    assert 8192 * 22 // 64 == 2816  # what 8 of 512 experts can expect of 180,224 assignments
    walked = moe.windows_walked(jnp.asarray([2816, 5632, 5633, 180224], jnp.int32), 5632)
    np.testing.assert_array_equal(walked, [1, 1, 2, 32])


def test_route_refuses_by_name_what_the_layer_cannot_do():
    x, w = jnp.zeros((4, 64)), jnp.zeros((64, 512))
    with pytest.raises(NotImplementedError, match="group-limited routing"):
        moe.route(x, w, None, dataclasses.replace(ROUTED, moe_n_group=8))
    with pytest.raises(NotImplementedError, match="not normalised"):
        moe.route(x, w, None, dataclasses.replace(ROUTED, moe_norm_topk=False))


# ------------------------------------------------------------------- the step

def test_the_bias_moves_by_the_balance_rule_a_row_an_expert_layer_in_pattern_order():
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    tx = make_optimizer(learning_rate=1e-3, warmup_steps=1, total_steps=10)
    state = init_state(jax.random.PRNGKey(0), CFG, tx)
    before = jax.tree.map(np.asarray, {n: state.params[n]["router_bias"] for n in ("layers", "mtp")})
    t = _tokens(CFG, (2, 33))
    state, m = make_train_step(CFG, tx, donate=False)(state, {"tokens": t})
    load = np.asarray(m["expert_load"])
    assert load.shape == (3, CFG.n_experts)  # the pattern's two expert layers, then the MTP module's
    rule = lambda b, rows: b + CFG.moe_bias_update_rate * np.sign(rows.mean(-1, keepdims=True) - rows)  # noqa: E731
    np.testing.assert_allclose(state.params["layers"]["router_bias"], rule(before["layers"], load[:2]), atol=1e-7)
    np.testing.assert_allclose(state.params["mtp"]["router_bias"], rule(before["mtp"], load[2:]), atol=1e-7)
    for layer in range(3):  # the counters are of what each layer chose, in that order
        np.testing.assert_array_equal(
            load[layer], np.bincount(np.asarray(m["experts_chosen"][layer]).ravel(), minlength=CFG.n_experts))
    assert np.isfinite(float(m["loss"])) and "mtp_loss" in m


def test_llama_and_glm_keep_their_stacks_and_blocks():
    """Every other family is the pattern 'attention + feed-forward' of period 1: the same
    stacks under the same names, and a block with both parts."""
    assert llama._layer_kinds(get_config("test-tiny")) == {"layers": (2, "attn", "dense")}
    assert llama._layer_kinds(get_config("glm-tiny")) == {
        "dense_layers": (1, "attn", "dense"), "layers": (2, "attn", "experts")}
    assert llama._layer_kinds(CFG) == {"ssm_layers": (2, "ssm", None), "layers": (2, None, "experts"),
                                       "attn_layers": (1, "attn", None), "mlp_layers": (1, None, "dense")}
    assert set(llama.param_axes(CFG)) == set(_params(CFG))
    for name, stack in llama.param_axes(CFG).items():
        if isinstance(stack, dict):
            assert set(stack) == set(_params(CFG)[name]), name


@pytest.mark.parametrize("pattern,unit,n", [
    ("ME", "ME", 1), ("MEME*EMEME*E", "MEME*E", 2), ("MMMM", "M", 4), ("MEMEMEM*EME", "MEMEMEM*EME", 1)])
def test_a_patterns_period(pattern, unit, n):
    assert llama.pattern_period(pattern) == (unit, n)


def test_a_pattern_is_checked_against_the_depth_and_the_kinds_it_names():
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(CFG, layer_pattern="MEM")
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(CFG, layer_pattern="MEMXE-")
    with pytest.raises(NotImplementedError, match="mtp_layer_pattern"):
        dataclasses.replace(CFG, mtp_layer_pattern="ME")
    with pytest.raises(NotImplementedError, match="KV cache"):
        llama.forward(_params(CFG), _tokens(CFG), CFG, cache=llama.init_kv_cache(CFG, 2, 64))


# ------------------------------------------------------------------- the configuration

PAIRS = {  # published key -> ModelConfig field
    "hidden_size": "d_model", "hybrid_override_pattern": "layer_pattern", "num_hidden_layers": "n_layers",
    "mamba_num_heads": "ssm_n_heads", "mamba_head_dim": "ssm_head_dim", "n_groups": "ssm_n_groups",
    "ssm_state_size": "ssm_state", "conv_kernel": "ssm_conv_taps", "chunk_size": "ssm_chunk",
    "time_step_min": "ssm_dt_min", "time_step_max": "ssm_dt_max", "time_step_floor": "ssm_dt_floor",
    "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
    "moe_latent_size": "moe_latent_dim", "moe_intermediate_size": "d_ff_expert",
    "moe_shared_expert_intermediate_size": "d_ff_shared", "n_shared_experts": "n_shared_experts",
    "num_experts_per_tok": "moe_top_k", "routed_scaling_factor": "moe_route_scale",
    "num_nextn_predict_layers": "mtp_depth", "norm_eps": "norm_eps", "intermediate_size": "d_ff",
    "n_group": "moe_n_group", "norm_topk_prob": "moe_norm_topk", "vocab_size": "vocab_size",
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
    # the published widths, every one
    assert (cfg.d_model, cfg.ssm_head_dim, cfg.ssm_state, cfg.ssm_conv_taps, cfg.ssm_chunk, cfg.head_dim,
            cfg.moe_latent_dim, cfg.d_ff_expert, cfg.d_ff_shared, cfg.moe_top_k, cfg.n_experts) == (
        4096, 64, 128, 4, 128, 128, 1024, 2688, 5376, 22, 512)
    # what is held here, and of what: the chip's share of a group that shares each layer
    published = config["published"]
    assert config["hybrid_override_pattern"] == published["hybrid_override_pattern"][:11] == "MEMEMEM*EME"
    assert (cfg.ssm_n_heads, cfg.ssm_n_groups) == (published["mamba_num_heads"] // 8, published["n_groups"] // 8)
    assert (cfg.heads_held, cfg.kv_heads_held) == (cfg.n_heads // 8, 1) == (4, 1)
    assert cfg.n_experts == published["n_routed_experts"] and cfg.n_experts_held == config["n_routed_experts"] == 8
    assert cfg.vocab_size == published["vocab_size"] // 8 and cfg.mtp_depth == 0
    assert not cfg.attention_rotation and cfg.mlp_activation == "relu2" and cfg.moe_dropless
    assert abs(cfg.n_params - 700.9e6) < 0.1e6  # the issue's arithmetic
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    held = sum(int(np.prod(a.shape)) for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]
               if "router_bias" not in jax.tree_util.keystr(path))
    assert held == cfg.n_params
    # every number of the catalog's row stands in the file, or is in `reduced`
    rows = "/opt/skills/guides/model-configs/architectures.jsonl"
    if os.path.exists(rows):
        with open(rows) as f:
            row = next(r for r in map(json.loads, f) if r["source_url"] == config["source"])
        for key, value in row["config"].items():
            if isinstance(value, (int, float)) and not isinstance(value, bool) and key not in config["reduced"]:
                assert config[key] == value, key
        assert all(published[key] == row["config"][key] for key in published)
    # the same keys through the checkpoint reader give the uncut model of the same widths
    whole = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    whole.update(published)
    hf = _config_from(whole)
    for published_key, field in PAIRS.items():
        if published_key not in published:
            assert getattr(hf, field) == getattr(cfg, field), field
    assert (hf.n_layers, hf.ssm_n_heads, hf.ssm_n_groups, hf.n_experts, hf.experts_held, hf.mtp_depth,
            hf.attn_heads_held, hf.vocab_size) == (88, 128, 8, 512, (0, 1), 1, (0, 0), 131072)
    # the published size, and the MTP module (an attention and an expert layer, uncut: 2.94 B)
    assert abs(dataclasses.replace(hf, mtp_depth=0, mtp_layer_pattern="").n_params / 1e9 - 120.67) < 0.01
    assert abs(hf.n_params / 1e9 - 120.67 - 2.942) < 0.01


def test_n_params_counts_what_a_pattern_holds():
    for cfg in (CFG, dataclasses.replace(CFG, experts_held=(1, 4), attn_heads_held=(2, 1), mtp_depth=0,
                                         mtp_layer_pattern="")):
        p = llama.init(jax.random.PRNGKey(0), cfg)
        held = sum(a.size for path, a in jax.tree_util.tree_flatten_with_path(p)[0]
                   if "router_bias" not in jax.tree_util.keystr(path))
        assert held == cfg.n_params


def test_config_from_hf_maps_the_family_and_refuses_what_is_not_runnable():
    base = dict(model_type="nemotron_h", vocab_size=256, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, head_dim=24, intermediate_size=96, hybrid_override_pattern="MEM*E-",
                num_hidden_layers=6, mamba_num_heads=16, mamba_head_dim=8, expand=2, n_groups=2,
                ssm_state_size=16, conv_kernel=4, chunk_size=8, n_routed_experts=16, num_experts_per_tok=3,
                moe_intermediate_size=40, moe_shared_expert_intermediate_size=80, moe_latent_size=32,
                n_shared_experts=1, routed_scaling_factor=5.0, num_nextn_predict_layers=1,
                mtp_hybrid_override_pattern="*E", norm_eps=1e-5, max_position_embeddings=128)
    cfg = _config_from(base)
    assert dataclasses.replace(cfg, name="nemotron-tiny", dtype="float32", ssm_n_heads=8,
                               rope_theta=CFG.rope_theta) == CFG
    for bad, what in ((dict(n_group=2), "group-limited"), (dict(norm_topk_prob=False), "not normalised"),
                      (dict(mlp_hidden_act="silu"), "mlp_hidden_act"), (dict(use_bias=True), "biases"),
                      (dict(time_step_limit=[0.0, 1.0]), "clamp on dt"), (dict(sliding_window=4096), "window"),
                      (dict(mtp_hybrid_override_pattern="ME"), "MTP module"),
                      (dict(moe_latent_size=None), "full width"), (dict(mamba_num_heads=8), "expand")):
        with pytest.raises(ValueError, match=what):
            _config_from({**base, **bad})


def test_llm_refuses_the_family_by_name_of_what_is_missing():
    from ray_tpu.llm.config import LLMConfig

    with pytest.raises(NotImplementedError) as e:
        LLMConfig(model_source="nemotron-tiny").resolve_model_config()
    for what in ("recurrent state", "dropless", "drafts"):
        assert what in str(e.value)


# ------------------------------------------------------------------- the benchmark's files

def test_the_familys_flops_file_counts_one_chips_share():
    from benchmarks.lib import flops_nemotron_h as flops

    _, model, cfg = _cell_config()
    layer = flops.layer_flops_per_token(model, (8192 + 1) / 2)
    assert layer["M"] - flops.scan_flops_per_token(model) == 2 * (4096 * 2320 + 1024 * 4096)  # 13.70 M weights
    assert layer["E"] == 2 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 22 / 64 * flops.expert_params(model))
    assert flops.expert_params(model) == 2 * 1024 * 2688
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    assert set(fwd) == {"M", "*", "E", "-", "head", "mtp"} and fwd["-"] == fwd["mtp"] == 0
    total = sum(fwd.values())
    assert 0.15 < fwd["M"] / total < 0.20 and 0.60 < fwd["E"] / total < 0.70 and fwd["*"] / total < 0.04
    assert abs(flops.train_flops_per_token(model, 8192) * 8192 / 1e12 - 21.08) < 0.01  # TFLOP a step
    assert flops.grouped_products_flops(model, 2816) == 6 * 2816 * 2 * 1024 * 2688
    work = flops.scan_step_work(model, 8192)
    assert work["flops"] == 5 * 3 * 8192 * flops.scan_flops_per_token(model)
    assert work["bytes"] == 5 * 3 * 8192 * (2 * (1024 + 256) + 4 * 16 + 4 * 1024)
    # bound by what it reads and writes on a v5e: 0.12 ms a step against 0.05 ms of products
    assert work["bytes"] / 819e9 > work["flops"] / 197e12


def test_the_scan_roofline_reader_on_a_made_up_result():
    sys.path.insert(0, ROOT)
    from benchmarks.readers import trace_scope_share, train_scan_roofline

    config, model, _ = _cell_config()
    from benchmarks.lib import flops_nemotron_h as flops

    work = flops.scan_step_work(model, 8192)
    needed = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    result = {"traced_steps": 5, "tokens_per_step": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "trace": {"busy_s": 2.0, "op_seconds": {"%a": 0.04, "%b": 0.06, "%c": 1.9},
                        "op_scopes": {"%a": ["ssm_scan"], "%b": ["ssm_conv"], "%c": ["moe_experts"]}}}
    ctx = {"result": result, "config": config, "model": model, "rehearse": False}
    assert train_scan_roofline.read(ctx, "ssm_scan") == pytest.approx(100 * 5 * needed / 0.04)
    assert trace_scope_share.read(ctx, "^ssm_") == pytest.approx(100 * 0.10 / 2.0)
    # a program without the scope (the parent of the PR that names it), a run without a
    # trace, a flops file without the function: nothing to read, and nothing raised
    result["trace"]["op_scopes"] = {"%c": ["moe_experts"]}
    assert train_scan_roofline.read(ctx, "ssm_scan") is None and trace_scope_share.read(ctx, "^ssm_") is None
    assert train_scan_roofline.read({**ctx, "result": {**result, "trace": None}}, "ssm_scan") is None
    other = {**config, "trainer": {**config["trainer"], "flops": "flops_glm4_moe_lite"}}
    result["trace"]["op_scopes"] = {"%a": ["ssm_scan"]}
    assert train_scan_roofline.read({**ctx, "config": other}, "ssm_scan") is None


def test_the_compiled_step_names_the_mixers_scopes():
    """What `train_ssm_pct` and `train_ssm_scan_roofline_pct` read: the compiled program's
    instructions carry the mixer's scopes and the latent projections', forward and backward."""
    sys.path.insert(0, ROOT)
    from benchmarks.lib import scope_seconds

    p, t = _params(CFG), _tokens(CFG, (1, 33))
    text = jax.jit(jax.grad(lambda p: llama.loss_fn(p, {"tokens": t}, CFG)[0])).lower(p).compile().as_text()
    scopes = set().union(*scope_seconds.scopes_by_instruction(text).values())
    assert {"ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_norm", "ssm_out_proj", "moe_latent", "moe_router",
            "moe_experts", "moe_shared", "attn", "mlp"} <= scopes, sorted(scopes)


def test_the_manifest_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [w["name"] for w in manifest["workloads"]][3] == CELL and len(manifest["workloads"]) >= 4
    assert manifest["workloads"][3]["chips"] == 1 and manifest["configs"][3]["name"] == CONFIG
    config = _cell_config()[0]
    assert manifest["configs"][3]["reduced"] == config["reduced"]
    reported = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_attn_fwd_kernel_pct",
        "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct", "train_moe_imbalance",
        "train_ssm_pct", "train_ssm_scan_roofline_pct", "train_mfu_ssm_moe_pct",
        # PR 35: the device's own step and the shares of the scopes it made readable
        "train_device_step_ms", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct"}
    for name in ("train_ssm_pct", "train_ssm_scan_roofline_pct", "train_mfu_ssm_moe_pct"):
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
    assert window["parity"]["gradient"]["rows"] > 40
    assert set(window["parity"]["losses"]) == {"loss", "ce_loss"}  # the cell's cut has no MTP term
    assert window["parity"]["positions"] == 2 * 64
    values = next(ln for ln in lines if ln.get("phase") == "rehearsal_values")["values"]
    assert values["train_moe_imbalance"]["value"] >= 1.0
    assert lines[-1]["correct"] is False and lines[-1]["metrics"] == {}
