"""The dropless expert layer (models/moe.py: `route`, `expert_layer`, the window walk and its
combine) at a small size on the CPU, against the families' plain references
(ray_tpu/models/reference/): the choice and the gates at each family's counts, the router's
own backward rule, shares that overflow their window, the combine's float32 sums, and what a
rematerialised layer keeps of what its router made. (tests/test_moe.py holds the
capacity-based layer.)"""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import _highest, highest  # noqa: F401  (autouse: every product at the highest precision)
from family_contract import cell_config, leaves_match, model_of, params, tokens
from ray_tpu.models import get_config, llama, moe
from ray_tpu.models.config import ModelConfig
from ray_tpu.models.reference import glm4_moe_lite as glm_ref
from ray_tpu.models.reference import lfm2_moe as lfm2_ref
from ray_tpu.models.reference import nemotron_h as nemotron_ref
from ray_tpu.models.reference import solar_open2 as solar_ref

GLM, SOLAR, LFM2 = get_config("glm-tiny"), get_config("solar-tiny"), get_config("lfm2-tiny")


# ------------------------------------------------- glm4_moe_lite's: 2 of 8 and of 16, a shared expert

def _layer(cfg, seed=3, tokens=96):
    lp = moe.init_expert_weights(jax.random.PRNGKey(seed), cfg)
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 1), (cfg.n_experts,))
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (tokens, cfg.d_model))
    return lp, x


def test_dropless_under_a_forced_skew():
    """A router biased onto one held expert: every token's first choice is that expert
    (far past any capacity factor), every assignment is served, the counters agree."""
    cfg = dataclasses.replace(GLM, experts_held=(1, 4), n_shared_experts=0)  # holds 2, 3
    lp, x = _layer(cfg, tokens=200)
    lp = {n: a for n, a in lp.items() if not n.startswith("shared")}
    lp["router_bias"] = jnp.zeros((8,)).at[3].set(10.0)
    y, counted = moe.expert_layer(x, lp, cfg)
    load = np.asarray(counted["load"])
    assert load[3] == 200 and load.sum() == 200 * cfg.moe_top_k
    assert (np.asarray(counted["chosen"]) == 3).any(-1).all()
    want, routed = glm_ref.expert_layer(x[None], lp, model_of(cfg))
    np.testing.assert_allclose(y, want[0], atol=2e-5)
    np.testing.assert_array_equal(np.sort(counted["chosen"], -1), np.sort(routed["own"][0], -1))
    # through the model: the step's counters say the same
    p = params(cfg, biased=False)
    p["layers"]["router_bias"] = p["layers"]["router_bias"].at[:, 3].set(10.0)
    _, m = jax.jit(lambda p, t: llama.loss_fn(p, {"tokens": t}, cfg))(p, tokens(cfg))
    n_tokens = 2 * 40
    np.testing.assert_array_equal(m["expert_load"][:2, 3], [n_tokens, n_tokens])
    np.testing.assert_array_equal(m["fullest_held_expert_rows"][:2], [n_tokens, n_tokens])
    np.testing.assert_array_equal(m["held_assignments"], m["expert_load"][:, 2:4].sum(-1))
    assert (np.asarray(m["expert_load"]).sum(-1) == n_tokens * cfg.moe_top_k).all()


# The window walk: a layer that holds a share of the experts works on a buffer of
# moe.window_rows rows and walks the sorted held assignments in as many windows of it as
# the load needs. Sixteen experts, so that a share of an eighth holds two and every one
# of a token's two assignments can be held.
WALK = dataclasses.replace(GLM, n_experts=16)


def _steered(cfg, tokens, held_rows, seed=7):
    """A layer and tokens whose load on the held experts is `held_rows` exactly: each
    held expert's router column reads one feature of x alone, +1 for the tokens steered
    to it and -1 for the others (a score of 0.9997 or 0.0003 beside the other experts'
    0.1-0.9), so a token chooses the held experts it is steered to and no other."""
    lp, x = _layer(cfg, seed=seed, tokens=tokens)
    lo, hi = moe.held_range(cfg)
    j = np.arange(held_rows)  # round by round over the tokens, a round an assignment
    assert held_rows <= tokens * cfg.moe_top_k and hi - lo >= cfg.moe_top_k
    steer = np.zeros((tokens, hi - lo), bool)
    steer[j % tokens, (j % tokens + j // tokens) % (hi - lo)] = True
    lp["router"] = lp["router"].at[:, lo:hi].set(8.0 * jnp.eye(cfg.d_model, hi - lo))
    x = x.at[:, :hi - lo].set(jnp.where(steer, 1.0, -1.0))
    return lp, x


@pytest.mark.parametrize("held,tokens,load", [
    (held, tokens, load)
    for held, tokens in (((1, 4), 1024), ((3, 8), 1024), ((3, 8), 1000))  # 1,000: a last window not whole
    for load in ("under", "exactly", "one_over", "every")])
def test_the_window_walk_matches_the_reference(held, tokens, load):
    """Output and every gradient (x, the three weights, the router through the gates)
    at loads under the window's rows, at them, one over (a second window of one row) and
    with every assignment held (the most windows); the counter says how many were walked."""
    cfg = dataclasses.replace(WALK, experts_held=held)
    n, rows = tokens * cfg.moe_top_k, moe.window_rows(cfg, tokens)
    assert rows < n
    held_rows = {"under": rows // 2 + 3, "exactly": rows, "one_over": rows + 1, "every": n}[load]
    lp, x = _steered(cfg, tokens, held_rows)
    lo, hi = moe.held_range(cfg)
    lp.pop("router_bias")
    cot = jax.random.normal(jax.random.PRNGKey(11), x.shape)
    leaves = ("router", "w_gate", "w_up", "w_down")

    def mine(x, w):
        y, counted = moe.expert_layer(x, {**lp, **w}, cfg)
        return jnp.sum(y * cot), (y, counted)

    def theirs(x, w):
        y, _ = glm_ref.expert_layer(x[None], {**lp, **w}, model_of(cfg))
        return jnp.sum(y[0] * cot), y[0]

    w = {name: lp[name] for name in leaves}
    (_, (y, counted)), grads = jax.jit(jax.value_and_grad(mine, argnums=(0, 1), has_aux=True))(x, w)
    (_, want), r_grads = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1), has_aux=True))(x, w)
    assert float(counted["load"][lo:hi].sum()) == held_rows
    walked = moe.windows_walked(counted["load"][lo:hi].sum().astype(jnp.int32), rows)
    assert int(walked) == -(-held_rows // rows) == {
        "under": 1, "exactly": 1, "one_over": 2, "every": -(-n // rows)}[load]
    np.testing.assert_allclose(y, want, atol=2e-5 * float(jnp.abs(want).max()))
    for (path, g), r in zip(jax.tree_util.tree_flatten_with_path(grads)[0], jax.tree.leaves(r_grads)):
        scale = float(jnp.abs(r).max())
        assert scale > 0, path
        np.testing.assert_allclose(g, r, atol=2e-5 * scale, err_msg=jax.tree_util.keystr(path))


@pytest.mark.parametrize("tokens,k,n_experts,held,rows", [
    (8192, 4, 64, (0, 8), 8192),    # glm47flash-train-ep8share-s8192: a quarter of 32,768
    (96, 2, 8, (0, 1), 192),        # tier-1: every expert held, tokens x k
    (96, 2, 8, (1, 2), 192),        # half of them: twice the expected rows is all of them
    (96, 2, 8, (3, 8), 192),        # a tile is more than tokens x k
    (128, 2, 8, (1, 2), 256),       # the benchmark's rehearsal
    (1024, 2, 16, (1, 4), 1024),
    (1024, 2, 16, (3, 8), 512),
    (1000, 2, 16, (3, 8), 512),     # 500 rows expected twice, in whole tiles
    (8192, 4, 64, (5, 64), 1024),   # one expert of 64 held
])
def test_window_rows_follow_the_share_held(tokens, k, n_experts, held, rows):
    cfg = dataclasses.replace(GLM, n_experts=n_experts, moe_top_k=k, experts_held=held)
    assert moe.window_rows(cfg, tokens) == rows
    assert rows % 512 == 0 or rows == tokens * k


def _primitives(jaxpr):
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("held,tokens,loops", [((0, 1), 1024, False), ((1, 2), 1024, False),
                                               ((1, 4), 96, False), ((1, 4), 1024, True)])
def test_only_a_share_that_can_overflow_its_window_has_a_loop(held, tokens, loops):
    """One window, statically (every expert held, or a buffer no smaller than tokens x
    k): the program this layer always was, with no loop and no branch. A smaller window:
    a loop, and still no scatter in either direction."""
    cfg = dataclasses.replace(WALK, experts_held=held)
    lp, x = _layer(cfg, tokens=tokens)

    def loss(x, lp):
        return jnp.sum(moe.expert_layer(x, lp, cfg)[0])

    names = _primitives(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1)))(x, lp).jaxpr)
    assert ("while" in names) == loops and "cond" not in names, sorted(names)
    assert not any("scatter" in name for name in names), sorted(names)
    assert "ragged_dot" in names or "ragged_dot_general" in names, sorted(names)


def test_the_step_counts_the_windows_it_walked():
    """`expert_windows`, a row an expert layer and the MTP module's last: 1 at the
    benchmark's rehearsal size; with the stack's router biased onto the held experts,
    tokens x k over the window's rows there and still 1 in the MTP module."""
    rehearsal, _, cfg = cell_config("glm-4.7-flash-train-ep8", "rehearsal")
    tr = rehearsal["trainer"]
    t = tokens(cfg, (tr["batch"], tr["seq"] + 1))
    _, m = jax.jit(lambda p, t: llama.loss_fn(p, {"tokens": t}, cfg))(params(cfg), t)
    np.testing.assert_array_equal(m["expert_windows"], [1, 1, 1])
    cfg = dataclasses.replace(WALK, experts_held=(1, 4), max_seq_len=512)  # holds 4..7
    p, t = params(cfg, biased=False), tokens(cfg, (2, 513))
    p["layers"]["router_bias"] = p["layers"]["router_bias"].at[:, 4:6].set(10.0)
    (loss, m), grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": t}, cfg), has_aux=True))(p)
    rows = moe.window_rows(cfg, 1024)
    assert rows == 1024
    np.testing.assert_array_equal(m["held_assignments"][:2], [2048, 2048])
    np.testing.assert_array_equal(m["expert_windows"][:2], [2, 2])
    assert m["expert_windows"][2] == 1 and m["held_assignments"][2] <= rows
    r_loss, r_grads = jax.jit(jax.value_and_grad(lambda p: glm_ref.loss(p, t, model_of(cfg))))(p)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-6)
    for name in ("w_gate", "w_up", "w_down", "router"):
        scale = float(jnp.abs(r_grads["layers"][name]).max())
        np.testing.assert_allclose(grads["layers"][name], r_grads["layers"][name],
                                   atol=2e-5 * scale, err_msg=name)


def test_selection_is_by_score_plus_bias_and_gates_are_from_the_scores():
    cfg = dataclasses.replace(GLM, moe_top_k=2)
    w = jnp.eye(8)[:4]  # d_model 4: logits are x's own entries
    x = jnp.array([[2.0, 1.0, 0.0, -1.0]])
    cfg = dataclasses.replace(cfg, n_experts=8, d_model=4)
    bias = jnp.zeros(8).at[3].set(5.0)
    idx, gates = moe.route(x, w, bias, cfg)
    s = jax.nn.sigmoid(jnp.array([2.0, -1.0]))
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 3]  # 3 by its bias, 0 by its score
    by_expert = dict(zip(np.asarray(idx[0]).tolist(), np.asarray(gates[0]).tolist()))
    np.testing.assert_allclose([by_expert[0], by_expert[3]], 1.8 * s / s.sum(), rtol=1e-6)
    # without the bias the two largest scores win
    idx, _ = moe.route(x, w, None, cfg)
    assert sorted(np.asarray(idx[0]).tolist()) == [0, 1]
    # no gradient reaches the bias
    g = jax.grad(lambda b: moe.route(x, w, b, cfg)[1].sum())(bias)
    assert not np.asarray(g).any()


def test_remat_keeps_what_the_experts_chose(capsys):
    """The backward pass recomputes a layer's forward pass; it must not choose experts
    again (two scores within a rounding go the other way when XLA fuses the second
    pass otherwise): the choice is a named residual under every remat policy."""
    p, t = params(GLM), tokens(GLM, (2, 17))
    for policy in ("full", "dots"):
        cfg = dataclasses.replace(GLM, remat=True, remat_policy=policy)
        jax.ad_checkpoint.print_saved_residuals(lambda p: llama.loss_fn(p, {"tokens": t}, cfg)[0], p)
        saved = capsys.readouterr().out
        assert f"i32[32,{cfg.moe_top_k}] named 'experts_chosen'" in saved, policy  # the MTP block's
        assert f"i32[2,32,{cfg.moe_top_k}] output of scan" in saved, policy  # the stack's, a row a layer


def _highest_products(jaxpr):
    """(operand shapes, result shape) of every product a program asks for at the highest
    precision by name, nested programs included."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and eqn.params.get("precision") == (jax.lax.Precision.HIGHEST,) * 2:
            found.append(([v.aval.shape for v in eqn.invars], eqn.outvars[0].aval.shape))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            found += _highest_products(sub)
    return found


@pytest.mark.parametrize("policy", ["full", "dots", "dots_no_batch"])
def test_a_rematerialised_expert_layer_scores_once_and_keeps_what_the_router_made(policy, capsys):
    """The gradient of one expert layer under `_maybe_remat`: the router's three products
    at the highest precision (scores, dx, the weight's gradient) and no fourth, the
    scores made again; what the policy saves of the layer is what the router made, under
    its four names (`moe.ROUTER_NAMES`), and under `full` nothing else but arguments."""
    cfg = dataclasses.replace(GLM, remat=True, remat_policy=policy)
    lp = moe.init_expert_weights(jax.random.PRNGKey(0), cfg)
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(5), lp["router_bias"].shape)
    t, d, e, k = 32, cfg.d_model, cfg.n_experts, cfg.moe_top_k
    x = jax.random.normal(jax.random.PRNGKey(1), (t, d))
    cot = jax.random.normal(jax.random.PRNGKey(2), (t, d))

    def layer(x, lp):
        return jnp.sum(moe.expert_layer(x, lp, cfg)[0] * cot)

    body = llama._maybe_remat(layer, cfg)
    with jax.default_matmul_precision("default"):  # (this file's tests run at the highest: not here)
        products = _highest_products(jax.make_jaxpr(jax.grad(body, argnums=(0, 1)))(x, lp).jaxpr)
    assert sorted(result for _, result in products) == sorted([(t, e), (t, d), (d, e)]), products
    jax.ad_checkpoint.print_saved_residuals(body, x, lp)
    saved = [ln for ln in capsys.readouterr().out.splitlines()
             if "from the argument" not in ln and "from a constant" not in ln]  # (a constant: `cot`)
    of_the_router = [f"i32[{t},{k}] named 'experts_chosen'", f"f32[{t},{e}] ", f"f32[{t},{k}] ", f"f32[{e}] "]
    for shape in of_the_router:
        assert sum(ln.startswith(shape) for ln in saved) == 1, (shape, saved)
    if policy == "full":
        assert len(saved) == len(of_the_router), saved
    # and the gradient is the plain layer's
    value, grads = jax.jit(jax.value_and_grad(body, argnums=(0, 1)))(x, lp)
    want, plain = jax.jit(jax.value_and_grad(layer, argnums=(0, 1)))(x, lp)
    np.testing.assert_allclose(value, want, rtol=1e-6)
    for got, ref_ in zip(jax.tree.leaves(grads), jax.tree.leaves(plain)):
        np.testing.assert_allclose(got, ref_, atol=1e-6 * max(float(jnp.abs(ref_).max()), 1e-30))


@pytest.mark.parametrize("family", ["glm-tiny", "nemotron-tiny"])
def test_every_remat_policy_weighs_what_the_forward_pass_weighed(family):
    """Loss and every leaf's gradient of a tiny model of each family under remat `full`,
    `dots` and `none` agree to rounding: the backward pass weighs the experts with the
    scores the forward pass made (kept by name), whatever is made again around them."""
    base = get_config(family)
    p, t = llama.init(jax.random.PRNGKey(0), base), tokens(base)
    results = {}
    for policy in ("none", "full", "dots"):
        cfg = dataclasses.replace(base, remat=policy != "none", remat_policy=policy)
        results[policy] = jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, {"tokens": t}, cfg)[0]))(p)
    loss, grads = results["none"]
    assert len(jax.tree.leaves(grads)) >= 20
    for policy in ("full", "dots"):
        np.testing.assert_allclose(results[policy][0], loss, rtol=1e-6, err_msg=policy)
        for (path, got), want in zip(jax.tree_util.tree_flatten_with_path(results[policy][1])[0],
                                     jax.tree.leaves(grads)):
            np.testing.assert_allclose(got, want, atol=2e-6 * float(jnp.abs(want).max()) + 1e-12,
                                       err_msg=f"{policy} {jax.tree_util.keystr(path)}")


def test_route_takes_sigmoid_or_softmax_scores_and_no_bias_beside_softmax():
    """Softmax over all experts (PR 50; its own tests: tests/test_family_sdar_moe.py) chooses by the
    scores alone; any other scoring is refused by name."""
    x, w = jnp.zeros((4, GLM.d_model)), jnp.zeros((GLM.d_model, GLM.n_experts))
    idx, gates = moe.route(x, w, None, dataclasses.replace(GLM, moe_scoring="softmax", moe_route_scale=1.0))
    assert idx.shape == gates.shape == (4, GLM.moe_top_k)
    np.testing.assert_allclose(gates, 1 / GLM.moe_top_k, rtol=1e-6)  # equal scores: equal gates, summing to one
    with pytest.raises(NotImplementedError, match="softmax scores with a selection bias"):
        moe.route(x, w, jnp.zeros((GLM.n_experts,)), dataclasses.replace(GLM, moe_scoring="softmax"))
    with pytest.raises(NotImplementedError, match="sigmoid or softmax"):
        moe.route(x, w, None, dataclasses.replace(GLM, moe_scoring="tanh"))


# ------------------------------------------------- nemotron_h's: 22 of 512, latent relu2 experts

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
    _, routing = nemotron_ref.expert_layer(x[None], lp, model_of(ROUTED))
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
        return jnp.sum(nemotron_ref.expert_layer(x[None], lp, model_of(ROUTED))[0][0] * cot)

    fn = jax.jit(jax.value_and_grad(mine, argnums=(0, 1)))
    text = fn.lower(x, lp).compile().as_text()
    assert not re.search(r" scatter\(", text)
    shapes = {tuple(int(n) for n in dims.split(",")) for dims in re.findall(r"\[([0-9]+(?:,[0-9]+)+)\]", text)}
    wide = [s for s in shapes if int(np.prod(s)) >= tokens * 22 * 512]
    assert not wide, wide[:4]
    assert not [s for s in shapes if {tokens, 22, 512} <= set(s)]
    (value, grads), (want, r_grads) = fn(x, lp), jax.jit(jax.value_and_grad(theirs, argnums=(0, 1)))(x, lp)
    np.testing.assert_allclose(value, want, rtol=2e-5)
    leaves_match(grads, r_grads, least=8)


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
        return jnp.sum(nemotron_ref.expert_layer(x[None], {**lp, **w}, model_of(cfg))[0][0] * cot)

    w = {name: lp[name] for name in names}
    (value, counted), grads = jax.jit(jax.value_and_grad(mine, argnums=(0, 1), has_aux=True))(x, w)
    want, r_grads = jax.jit(jax.value_and_grad(theirs, argnums=(0, 1)))(x, w)
    assert float(counted["load"][lo:hi].sum()) == held_rows
    assert int(moe.windows_walked(jnp.int32(held_rows), rows)) == {"under": 1, "one_over": 2, "every": 8}[load]
    np.testing.assert_allclose(value, want, rtol=2e-5, atol=1e-5)
    leaves_match(grads, r_grads, least=6)


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
    cfg = cell_config("nemotron-3-super-train-tp8-ep64")[2]
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


# ------------------------------------------------- solar_open2's: 8 of 320; lfm2_moe's: 4 of 16 with an epsilon

def test_route_at_8_of_320_is_the_references_choice_and_the_window_a_fortieths():
    """320 experts are no power of two and 8 of them a fortieth: the choice, the gates
    (normalised, times 1.0), the count, and the window of the cell's expert parts."""
    cfg = dataclasses.replace(SOLAR, n_experts=320, moe_top_k=8, experts_held=(3, 40))
    x = jax.random.normal(jax.random.PRNGKey(0), (384, 64))
    lp = moe.init_expert_weights(jax.random.PRNGKey(1), cfg)
    lp["router_bias"] = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (320,))
    idx, gates = moe.route(x, lp["router"], lp["router_bias"], cfg)
    _, routing = solar_ref.expert_layer(x[None], {**lp, "router_bias": lp["router_bias"]}, model_of(cfg))
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(routing["own"][0], -1))
    np.testing.assert_allclose(gates.sum(-1), 1.0, rtol=1e-6)
    np.testing.assert_array_equal(moe.expert_load(idx, 320), np.bincount(np.asarray(idx).ravel(), minlength=320))
    assert moe.held_range(cfg) == (24, 32)
    cell = cell_config("solar-open2-train-tp8-ep40")[2]
    assert 8192 * 8 // 40 == 1638 and moe.window_rows(cell, 8192) == 3584 == 7 * 512
    assert moe.combine_from_rows(8192, 8, 3584)  # the combine follows the window's rows


def test_route_normalises_over_the_chosen_with_the_epsilon_a_field():
    """gates = s_chosen / (sum s_chosen + moe_gate_eps) * 1.0: lfm2_moe states 1e-6 where
    every accepted cell has 1e-20; the bias selects and never weights."""
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 64)) * 0.01
    lp = moe.init_expert_weights(jax.random.PRNGKey(1), LFM2)
    lp["router"] = lp["router"] - 3.0  # small scores, where an epsilon shows
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    idx, gates = moe.route(x, lp["router"], bias, LFM2)
    scores = jax.nn.sigmoid(x @ lp["router"])
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(jax.lax.top_k(scores + bias, 4)[1], -1))
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(gates, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    _, plain = moe.route(x, lp["router"], bias, dataclasses.replace(LFM2, moe_gate_eps=1e-20))
    np.testing.assert_allclose(plain.sum(-1), 1.0, rtol=1e-6)
    assert float(jnp.abs(gates.sum(-1) - 1).max()) > 1e-7 and ModelConfig.moe_gate_eps == 1e-20
    _, routing = lfm2_ref.expert_layer(x[None], {**lp, "router_bias": bias}, model_of(LFM2))
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(routing["own"][0], -1))
