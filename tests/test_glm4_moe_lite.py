"""The glm4_moe_lite family (GLM-4.7-Flash) on the training path, at a small size on the
CPU with seeded weights: latent attention, a leading dense layer, sigmoid-routed experts
served without drops as a share of an expert-parallel group beside a shared one, the
MTP module. The anchor is the plain reference (ray_tpu/models/reference/)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import checkpoint, get_config, llama, moe
from ray_tpu.models.reference import glm4_moe_lite as ref

ROOT = os.path.join(os.path.dirname(__file__), "..")
CFG = get_config("glm-tiny")


def _model(cfg):
    return dataclasses.asdict(cfg)


def _params(cfg, seed=0, biased=True):
    p = llama.init(jax.random.PRNGKey(seed), cfg)
    if biased:  # a selection bias that changes who is chosen
        for name in ("layers", "mtp"):
            p[name]["router_bias"] = 0.05 * jax.random.normal(
                jax.random.PRNGKey(seed + 5), p[name]["router_bias"].shape)
    return p


def _tokens(cfg, shape=(2, 41), seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, cfg.vocab_size)


@pytest.fixture(autouse=True)
def _highest():
    with jax.default_matmul_precision("highest"):
        yield


# ---------------------------------------------------------------- against the reference

@pytest.mark.parametrize("held", [(0, 1), (1, 2), (3, 4)])
def test_logits_of_both_heads_match_the_reference(held):
    cfg = dataclasses.replace(CFG, experts_held=held)
    p, t = _params(cfg), _tokens(cfg)
    logits, _, aux = llama.forward(p, t[:, :-1], cfg, return_aux=True)
    (mtp, mtp_aux), = llama.mtp_logits(p, aux["hidden"], t, cfg)
    r_logits, (r_mtp,), routings = ref.forward(p, t[:, :-1], _model(cfg))
    np.testing.assert_allclose(logits, r_logits, atol=2e-5)
    np.testing.assert_allclose(mtp, r_mtp, atol=2e-5)
    # what the system chose is what the reference chose, layer by layer
    chosen = [*aux["chosen"], mtp_aux["chosen"]]
    assert len(chosen) == len(routings) == cfg.n_layers - cfg.n_dense_layers + 1
    for mine, r in zip(chosen, routings):
        own = np.asarray(r["own"])
        mine = np.asarray(mine).reshape(t.shape[0], -1, cfg.moe_top_k)[:, :own.shape[1]]
        np.testing.assert_array_equal(np.sort(mine, -1), np.sort(own, -1))


@pytest.mark.parametrize("held", [(0, 1), (1, 2)])
def test_loss_with_the_mtp_term_and_every_gradient_match_the_reference(held):
    cfg = dataclasses.replace(CFG, experts_held=held)
    p, t = _params(cfg), _tokens(cfg)
    (loss, m), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(p, {"tokens": t}, cfg)
    r_loss, r_grads = jax.value_and_grad(ref.loss)(p, t, _model(cfg))
    np.testing.assert_allclose(loss, r_loss, rtol=1e-6)
    main, (mtp,), _ = ref.position_losses(p, t, _model(cfg))
    np.testing.assert_allclose(m["ce_loss"], main.mean(), rtol=1e-6)
    np.testing.assert_allclose(m["mtp_loss"], mtp.mean(), rtol=1e-6)
    np.testing.assert_allclose(loss, m["ce_loss"] + cfg.mtp_loss_weight * m["mtp_loss"], rtol=1e-6)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    r_flat = dict(jax.tree_util.tree_flatten_with_path(r_grads)[0])
    assert len(flat) == len(r_flat) >= 50
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # selects, never weights: no gradient reaches it
            assert not np.asarray(g).any() and not np.asarray(r_flat[path]).any(), name
            continue
        scale = float(jnp.abs(r_flat[path]).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, r_flat[path], atol=2e-5 * scale + 1e-9, err_msg=name)


def test_the_reference_and_the_benchmarks_copy_agree():
    """benchmarks/lib/ keeps its own copy, so that no PR that claims a gain can change
    the yardstick by editing the program's tree: the two say the same."""
    sys.path.insert(0, ROOT)
    from benchmarks.lib import reference_glm4_moe_lite as copy

    with open(ref.__file__) as a, open(copy.__file__) as b:
        assert a.read() == b.read()
    cfg = dataclasses.replace(CFG, experts_held=(1, 2))
    p, t = _params(cfg), _tokens(cfg)
    for mine, theirs in zip(jax.tree.leaves(ref.position_losses(p, t, _model(cfg))),
                            jax.tree.leaves(copy.position_losses(p, t, _model(cfg)))):
        np.testing.assert_array_equal(mine, theirs)


def test_reference_on_a_given_selection_uses_it():
    """The benchmark evaluates the reference on the system's experts: a selection handed
    in replaces the layer's own top-k, and the layer still reports its own."""
    cfg = dataclasses.replace(CFG, n_layers=2, mtp_depth=0)
    p, t = _params(cfg, biased=False), _tokens(cfg)
    p.pop("mtp", None)
    _, _, own = ref.forward(p, t, _model(cfg))
    other = [(r["chosen"] + 1) % cfg.n_experts for r in own]
    logits, _, routed = ref.forward(p, t, _model(cfg), selection=other)
    np.testing.assert_array_equal(routed[0]["chosen"], other[0])
    np.testing.assert_array_equal(routed[0]["own"], own[0]["own"])
    assert float(jnp.abs(logits - ref.forward(p, t, _model(cfg))[0]).max()) > 1e-3
    assert (np.asarray(own[0]["margin"]) >= 0).all()


# ------------------------------------------------------------------- the expert layer

def _layer(cfg, seed=3, tokens=96):
    lp = moe.init_expert_weights(jax.random.PRNGKey(seed), cfg)
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(seed + 1), (cfg.n_experts,))
    x = jax.random.normal(jax.random.PRNGKey(seed + 2), (tokens, cfg.d_model))
    return lp, x


def test_the_shares_add_up_to_the_uncut_layer():
    """Eight chips hold one expert each of the same layer: their routed parts, with the
    shared expert (which every chip computes alike) counted once, are what the uncut
    reference gives for the whole layer."""
    whole = dataclasses.replace(CFG, experts_held=(0, 1))
    lp, x = _layer(whole)
    shared = moe._mlp(x, (lp["shared_gate"], lp["shared_up"], lp["shared_down"]))
    total, loads = shared, []
    for i in range(8):
        share = dataclasses.replace(whole, experts_held=(i, 8))
        mine = dict(lp, **{n: lp[n][i:i + 1] for n in ("w_gate", "w_up", "w_down")})
        y, counted = moe.expert_layer(x, mine, share)
        total = total + (y - shared)
        loads.append(counted["load"])
        # a share's counter is over ALL experts: every chip counts the same
        np.testing.assert_array_equal(counted["load"], loads[0])
    uncut, _ = ref.expert_layer(x[None], lp, _model(whole))
    np.testing.assert_allclose(total, uncut[0], atol=2e-5)
    assert float(loads[0].sum()) == x.shape[0] * whole.moe_top_k
    y_all, _ = moe.expert_layer(x, lp, whole)
    np.testing.assert_allclose(y_all, uncut[0], atol=2e-5)


def test_dropless_under_a_forced_skew():
    """A router biased onto one held expert: every token's first choice is that expert
    (far past any capacity factor), every assignment is served, the counters agree."""
    cfg = dataclasses.replace(CFG, experts_held=(1, 4), n_shared_experts=0)  # holds 2, 3
    lp, x = _layer(cfg, tokens=200)
    lp = {n: a for n, a in lp.items() if not n.startswith("shared")}
    lp["router_bias"] = jnp.zeros((8,)).at[3].set(10.0)
    y, counted = moe.expert_layer(x, lp, cfg)
    load = np.asarray(counted["load"])
    assert load[3] == 200 and load.sum() == 200 * cfg.moe_top_k
    assert (np.asarray(counted["chosen"]) == 3).any(-1).all()
    want, routed = ref.expert_layer(x[None], lp, _model(cfg))
    np.testing.assert_allclose(y, want[0], atol=2e-5)
    np.testing.assert_array_equal(np.sort(counted["chosen"], -1), np.sort(routed["own"][0], -1))
    # through the model: the step's counters say the same
    p = _params(cfg, biased=False)
    p["layers"]["router_bias"] = p["layers"]["router_bias"].at[:, 3].set(10.0)
    _, m = llama.loss_fn(p, {"tokens": _tokens(cfg)}, cfg)
    tokens = 2 * 40
    np.testing.assert_array_equal(m["expert_load"][:2, 3], [tokens, tokens])
    np.testing.assert_array_equal(m["fullest_held_expert_rows"][:2], [tokens, tokens])
    np.testing.assert_array_equal(m["held_assignments"], m["expert_load"][:, 2:4].sum(-1))
    assert (np.asarray(m["expert_load"]).sum(-1) == tokens * cfg.moe_top_k).all()


# The window walk: a layer that holds a share of the experts works on a buffer of
# moe.window_rows rows and walks the sorted held assignments in as many windows of it as
# the load needs. Sixteen experts, so that a share of an eighth holds two and every one
# of a token's two assignments can be held.
WALK = dataclasses.replace(CFG, n_experts=16)


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
        y, _ = ref.expert_layer(x[None], {**lp, **w}, _model(cfg))
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
    cfg = dataclasses.replace(CFG, n_experts=n_experts, moe_top_k=k, experts_held=held)
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
    sys.path.insert(0, ROOT)
    from benchmarks.lib import modelcfg

    with open(os.path.join(ROOT, "benchmarks", "rehearsal", "glm-4.7-flash-train-ep8.json")) as f:
        rehearsal = json.load(f)
    cfg = modelcfg.model_config(modelcfg.model_keys(rehearsal))
    tr = rehearsal["trainer"]
    t = _tokens(cfg, (tr["batch"], tr["seq"] + 1))
    _, m = llama.loss_fn(_params(cfg), {"tokens": t}, cfg)
    np.testing.assert_array_equal(m["expert_windows"], [1, 1, 1])
    cfg = dataclasses.replace(WALK, experts_held=(1, 4), max_seq_len=512)  # holds 4..7
    p, t = _params(cfg, biased=False), _tokens(cfg, (2, 513))
    p["layers"]["router_bias"] = p["layers"]["router_bias"].at[:, 4:6].set(10.0)
    (loss, m), grads = jax.jit(jax.value_and_grad(
        lambda p: llama.loss_fn(p, {"tokens": t}, cfg), has_aux=True))(p)
    rows = moe.window_rows(cfg, 1024)
    assert rows == 1024
    np.testing.assert_array_equal(m["held_assignments"][:2], [2048, 2048])
    np.testing.assert_array_equal(m["expert_windows"][:2], [2, 2])
    assert m["expert_windows"][2] == 1 and m["held_assignments"][2] <= rows
    r_loss, r_grads = jax.jit(jax.value_and_grad(lambda p: ref.loss(p, t, _model(cfg))))(p)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-6)
    for name in ("w_gate", "w_up", "w_down", "router"):
        scale = float(jnp.abs(r_grads["layers"][name]).max())
        np.testing.assert_allclose(grads["layers"][name], r_grads["layers"][name],
                                   atol=2e-5 * scale, err_msg=name)


def test_selection_is_by_score_plus_bias_and_gates_are_from_the_scores():
    cfg = dataclasses.replace(CFG, moe_top_k=2)
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


def test_the_bias_moves_by_the_balance_rule_after_a_step():
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    np.testing.assert_allclose(
        moe.balance_bias(jnp.zeros(4), jnp.array([1.0, 3.0, 2.0, 2.0]), 0.5), [0.5, -0.5, 0, 0])
    cfg = CFG
    tx = make_optimizer()
    state = init_state(jax.random.PRNGKey(0), cfg, tx)
    before = jax.tree.map(np.asarray, {n: state.params[n]["router_bias"] for n in ("layers", "mtp")})
    state, m = make_train_step(cfg, tx, donate=False)(state, {"tokens": _tokens(cfg)})
    load = np.asarray(m["expert_load"])  # expert layers, then the MTP module's
    assert load.shape == (cfg.n_layers - cfg.n_dense_layers + cfg.mtp_depth, cfg.n_experts)
    want = cfg.moe_bias_update_rate * np.sign(load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(state.params["layers"]["router_bias"] - before["layers"], want[:-1],
                               atol=1e-7)
    np.testing.assert_allclose(state.params["mtp"]["router_bias"] - before["mtp"], want[-1:],
                               atol=1e-7)
    assert np.abs(want).max() > 0
    # the optimizer (weight decay) never touches it
    state2, _ = make_train_step(cfg, tx, donate=False)(state, {"tokens": _tokens(cfg, seed=2)})
    moved = np.abs(np.asarray(state2.params["layers"]["router_bias"]
                              - state.params["layers"]["router_bias"]))
    assert set(np.round(moved / cfg.moe_bias_update_rate, 3).ravel()) <= {0.0, 1.0}


def test_remat_keeps_what_the_experts_chose(capsys):
    """The backward pass recomputes a layer's forward pass; it must not choose experts
    again (two scores within a rounding go the other way when XLA fuses the second
    pass otherwise): the choice is a named residual under every remat policy."""
    for policy in ("full", "dots"):
        cfg = dataclasses.replace(CFG, remat=True, remat_policy=policy)
        p, t = _params(cfg), _tokens(cfg, (2, 17))
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
    cfg = dataclasses.replace(CFG, remat=True, remat_policy=policy)
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
    value, grads = jax.value_and_grad(body, argnums=(0, 1))(x, lp)
    want, plain = jax.value_and_grad(layer, argnums=(0, 1))(x, lp)
    np.testing.assert_allclose(value, want, rtol=1e-6)
    for got, ref_ in zip(jax.tree.leaves(grads), jax.tree.leaves(plain)):
        np.testing.assert_allclose(got, ref_, atol=1e-6 * max(float(jnp.abs(ref_).max()), 1e-30))


@pytest.mark.parametrize("family", ["glm-tiny", "nemotron-tiny"])
def test_every_remat_policy_weighs_what_the_forward_pass_weighed(family):
    """Loss and every leaf's gradient of a tiny model of each family under remat `full`,
    `dots` and `none` agree to rounding: the backward pass weighs the experts with the
    scores the forward pass made (kept by name), whatever is made again around them."""
    base = get_config(family)
    p, t = llama.init(jax.random.PRNGKey(0), base), _tokens(base)
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


# ------------------------------------------------------------------ stacks and slices

def test_leading_and_following_stacks():
    cfg = dataclasses.replace(CFG, n_layers=4, n_dense_layers=2)
    p = llama.init(jax.random.PRNGKey(0), cfg)
    axes = llama.param_axes(cfg)
    assert p["dense_layers"]["w_gate"].shape == (2, cfg.d_model, cfg.d_ff)
    assert p["layers"]["w_gate"].shape == (2, cfg.n_experts, cfg.d_model, cfg.d_ff_expert)
    assert "router" not in p["dense_layers"] and "router" in p["layers"]
    assert p["mtp"]["eh_proj"].shape == (1, 2 * cfg.d_model, cfg.d_model)
    same = jax.tree.map(lambda a, ax: a.ndim == len(ax), p, axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    assert all(jax.tree.leaves(same))
    t = _tokens(cfg)
    logits, _, aux = llama.forward(p, t, cfg, return_aux=True)
    assert aux["load"].shape == (2, cfg.n_experts)
    np.testing.assert_allclose(logits, ref.forward(p, t, _model(cfg))[0], atol=2e-5)
    # a one-kind model keeps its one stack and the keys it always drew
    dense = get_config("test-tiny")
    assert set(llama.init(jax.random.PRNGKey(0), dense)) == {
        "embed", "layers", "final_norm", "lm_head"}
    assert cfg.n_params == sum(a.size for a in jax.tree.leaves(p)) - sum(
        p[n]["router_bias"].size for n in ("layers", "mtp"))


def test_a_cache_over_two_stacks_is_refused():
    """llm/ refuses the family (llm/config.py:_served), so nothing walks a cache over a
    leading and a following stack: forward says so instead of carrying the code."""
    cfg = dataclasses.replace(CFG, mtp_depth=0)
    p = llama.init(jax.random.PRNGKey(0), cfg)
    cache = llama.init_kv_cache(cfg, 1, 16, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="more than one kind"):
        llama.forward(p, _tokens(cfg, (1, 8)), cfg, cache=cache)
    with pytest.raises(NotImplementedError, match="sigmoid"):
        moe.route(jnp.zeros((4, cfg.d_model)), p["layers"]["router"][0], None,
                  dataclasses.replace(cfg, moe_scoring="softmax"))


def test_rotated_slice_and_its_shared_key_against_plain_rope():
    cfg = CFG
    p = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(0), cfg)["dense_layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, cfg.d_model))
    pos = jnp.arange(10)[None, :] * 2 + jnp.array([[0], [5]])
    q, k, v = llama.qkv_proj(x, p, cfg, pos)
    nope, rd, kvr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    assert q.shape == k.shape == (2, 10, cfg.n_heads, nope + rd) and v.shape[-1] == cfg.v_head_dim
    h = llama.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    cq = llama.rms_norm(h @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q_plain = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    np.testing.assert_allclose(q[..., :nope], q_plain[..., :nope], atol=1e-6)
    np.testing.assert_allclose(q[..., nope:], llama.rope(q_plain[..., nope:], pos, cfg.rope_theta),
                               atol=1e-6)
    ckv = h @ p["wkv_a"]
    k_rot = llama.rope(ckv[:, :, None, kvr:], pos, cfg.rope_theta)
    for head in range(cfg.n_heads):  # one rotated key, every head's
        np.testing.assert_allclose(k[:, :, head, nope:], k_rot[:, :, 0], atol=1e-6)
    # the published pairing (2i, 2i + 1) on the checkpoint's column order is this rotation
    # on the program's: scores do not see the permutation
    perm = jnp.array(llama.rope_pairs_to_halves(rd))
    assert sorted(perm.tolist()) == list(range(rd)) and perm[:3].tolist() == [0, 2, 4]
    slice_ = q_plain[:1, :, :, nope:]
    published = jnp.zeros_like(slice_).at[..., perm].set(slice_)  # column j lies at perm[j]
    np.testing.assert_allclose(ref._published_order(slice_), published, atol=0)
    turned = ref._rope_pairs(published, cfg.rope_theta)  # positions 0..9
    np.testing.assert_allclose(turned[..., perm], llama.rope(slice_, jnp.arange(10)[None], cfg.rope_theta),
                               atol=1e-6)


def test_flash_kernels_tile_width_256():
    from ray_tpu.ops import flash_attention as fa

    assert fa.supports(8192, 8192, 256) and not fa.supports(8191, 8191, 256)
    assert not fa.supports(8192, 8192, 192)
    # K and V of one head at 8,192 x 256 bf16 are the span budget, exactly: one span
    t = fa._tiling(8192, 8192, 512, 512, 256, 2)
    assert (t.kv_span, t.q_span) == (8192, 4096)
    fwd = fa.tile_counts(8192, 8192, True, 512, 512, head_dim=256)
    assert (fwd.grid_steps, fwd.tiles_computed) == (16, 136) and fwd.tiles_needed == 128.015625
    dkv = fa.tile_counts(8192, 8192, True, 512, 512, head_dim=256, kv_major=True)
    assert (dkv.grid_steps, dkv.tiles_computed) == (32, 136)


@pytest.mark.parametrize("s", [64, 256])
def test_flash_attention_at_unequal_head_parts(s):
    """The kernels (interpreted here) at a head twice the lane width, fed as the latent
    projections feed them: against the reference attention, values and gradients."""
    from ray_tpu.ops.attention import attention_reference
    from ray_tpu.ops.flash_attention import flash_attention

    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(x, (1, s, 2, 256), jnp.float32) for x in ks)

    def run(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) * g), argnums=(0, 1, 2))(q, k, v)

    (l1, g1), (l2, g2) = run(flash_attention), run(attention_reference)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-4)


# ---------------------------------------------------------------- files and entry points

PAIRS = {  # published key -> ModelConfig field
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "moe_intermediate_size": "d_ff_expert",
    "n_shared_experts": "n_shared_experts", "first_k_dense_replace": "n_dense_layers",
    "routed_scaling_factor": "moe_route_scale", "num_nextn_predict_layers": "mtp_depth",
    "num_experts_per_tok": "moe_top_k",
}


def test_configuration_files_program_group_equals_its_published_keys():
    sys.path.insert(0, ROOT)
    from benchmarks.lib import modelcfg

    with open(os.path.join(ROOT, "benchmarks", "configs", "glm-4.7-flash-train-ep8.json")) as f:
        config = json.load(f)
    cfg = modelcfg.model_config(modelcfg.model_keys(config))
    for published, field in PAIRS.items():
        assert getattr(cfg, field) == config[published], (published, field)
    # the router keeps the published width; the file's count is what is held here
    assert cfg.n_experts == config["published"]["n_routed_experts"] == 64
    assert cfg.n_experts_held == config["n_routed_experts"] == 8
    assert config["published"]["vocab_size"] // 8 == cfg.vocab_size == 19360
    assert cfg.n_layers == config["num_hidden_layers"] >= 1 + 4
    assert cfg.head_dim == 256 and cfg.moe_dropless and cfg.moe_select_bias
    assert cfg.moe_scoring == "sigmoid" and config["topk_method"] == "noaux_tc"
    assert sorted(config["reduced"]) == sorted(config["published"])
    assert abs(cfg.n_params - 706.5e6) < 0.1e6  # the issue's arithmetic
    # the same keys through the checkpoint reader give the uncut model of the same widths
    whole = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    whole.update(config["published"])
    hf = _config_from(whole)
    for field in PAIRS.values():
        assert getattr(hf, field) == getattr(cfg, field), field
    assert (hf.n_experts, hf.experts_held, hf.n_layers, hf.vocab_size) == (64, (0, 1), 47, 154880)
    assert hf.moe_dropless and hf.moe_select_bias and hf.latent_attention and hf.head_dim == 256


def _config_from(hf: dict):
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(hf, f)
        return checkpoint.config_from_hf(d)


def test_config_from_hf_refuses_what_the_family_has_and_the_program_lacks():
    base = dict(model_type="glm4_moe_lite", vocab_size=256, hidden_size=64, num_hidden_layers=3,
                num_attention_heads=4, intermediate_size=160, q_lora_rank=48, kv_lora_rank=32,
                qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, n_routed_experts=8,
                num_experts_per_tok=2, moe_intermediate_size=48, n_shared_experts=1,
                first_k_dense_replace=1, topk_method="noaux_tc", routed_scaling_factor=1.8,
                num_nextn_predict_layers=1, rope_theta=1e6)
    cfg = _config_from(base)
    assert dataclasses.replace(cfg, name="glm-tiny", max_seq_len=128, dtype="float32",
                               norm_eps=1e-5) == CFG
    for bad in (dict(n_group=2), dict(rope_scaling={"type": "yarn"}), dict(norm_topk_prob=False)):
        with pytest.raises(ValueError):
            _config_from({**base, **bad})


def test_llm_refuses_the_family_by_name_of_what_is_missing():
    from ray_tpu.llm.config import LLMConfig

    with pytest.raises(NotImplementedError) as e:
        LLMConfig(model_source="glm-tiny").resolve_model_config()
    for what in ("paged cache of latents", "more than one kind", "dropless", "drafts"):
        assert what in str(e.value)
    assert LLMConfig(model_source="moe-tiny").resolve_model_config().n_experts == 4


def test_the_new_cell_rehearses_on_the_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="1")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", "glm47flash-train-ep8share-s8192",
         "--seed", "3000000001", "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=220)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert all(window["checks"].values()), window["checks"]
    assert {"selection_agrees_beyond_margin", "step_losses_match_reference",
            "step_gradients_match_reference", "step_update_follows_its_moments",
            "router_bias_moved_by_the_rule"} <= set(window["checks"])
    assert window["parity"]["gradient"]["rows"] > 50
    setup = next(ln for ln in lines if ln.get("phase") == "setup_split_s")
    assert 0 < setup["of_which_parity"] < setup["warmup_and_parity"]
    assert window["parity"]["positions"] == 2 * (64 + 63)
    values = next(ln for ln in lines if ln.get("phase") == "rehearsal_values")["values"]
    assert values["train_moe_imbalance"]["value"] >= 1.0
    assert lines[-1]["correct"] is False and lines[-1]["metrics"] == {}


def _cell_model():
    sys.path.insert(0, ROOT)
    from benchmarks.lib import modelcfg

    with open(os.path.join(ROOT, "benchmarks", "configs", "glm-4.7-flash-train-ep8.json")) as f:
        config = json.load(f)
    return config, modelcfg.model_keys(config)


def test_the_familys_flops_file_counts_one_chips_share():
    from benchmarks.lib import flops_glm4_moe_lite as flops

    _, model = _cell_model()
    parts = flops.layer_matmul_params(model)
    assert parts["attention_projections"] == 21_757_952  # the issue's 21.76 M
    assert parts["dense_mlp"] == 62_914_560 and parts["shared_experts"] == 9_437_184
    assert parts["routed_experts_expected"] == 4 / 8 * 9_437_184  # 4 x 8/64 of an expert
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    attention = 4 * 20 * 256 * (8192 + 1) / 2 * 6  # both products, six blocks
    assert abs(3 * attention / flops.train_flops_per_token(model, 8192) - 0.42) < 0.01
    assert abs(flops.train_flops_per_token(model, 8192) * 8192 / 1e12 - 29.70) < 0.01
    assert fwd["mtp"] > fwd["head"] and set(fwd) == {"dense_layers", "expert_layers", "head", "mtp"}
    assert flops.grouped_products_flops(model, 4096) == 6 * 4096 * 9_437_184
    assert flops.causal_attention_flops(model, 8192, 1) == 20 * 2 * 256 * 8192 * 8193


def test_the_new_readers_on_a_made_up_result():
    sys.path.insert(0, ROOT)
    from benchmarks.readers import (counter_rows_imbalance, trace_scope_share, train_grouped_mxu,
                                    train_mfu_family)

    config, model = _cell_model()
    rows = [[4096.0] * 5, [4000.0] * 5]
    result = {"series": {"step_s": [0.4, 0.5, 0.6], "held_assignments": rows,
                         "fullest_held_expert_rows": [[1024.0] * 5, [500.0] * 5]},
              "tokens_per_step": 8192, "seq": 8192, "chips": 1, "traced_steps": 5,
              "device": {"kind": "TPU v5 lite"},
              "trace": {"op_seconds": {"%ragged-dot-none.3 = bf16[32768,1536] custom-call(": 0.05,
                                       "%fusion.1 = bf16[8192,2048] fusion(": 1.0}}}
    ctx = {"result": result, "config": config, "model": model, "rehearse": False}
    assert abs(counter_rows_imbalance.read(ctx) - (2.0 + 1.0) / 2) < 1e-9
    mfu = train_mfu_family.read(ctx)
    assert abs(mfu - 100 * 29.70e12 / 0.5 / 197e12) < 0.05
    mxu = train_grouped_mxu.read(ctx, pattern="^%?ragged-dot-none")
    assert abs(mxu - 100 * 5 * 6 * (5 * 4048) * 9_437_184 / (0.05 * 197e12)) < 1e-6
    # the expert layer's share: by scope, and its compiler-made kernels by name
    traced = {"result": {"trace": {**result["trace"], "busy_s": 2.0, "op_scopes": {
        "%fusion.1 = bf16[8192,2048] fusion(": ["closed_call", "mlp", "moe_shared"]}}}}
    assert trace_scope_share.read(traced, pattern="^moe_") == 50.0
    assert trace_scope_share.read(traced, pattern="^moe_", ops="^%?ragged-dot") == 52.5
    assert trace_scope_share.read(traced, pattern="^mla_") is None
    assert trace_scope_share.read({"result": {"trace": {**result["trace"], "busy_s": 2.0}}},
                                  pattern="^moe_") is None  # a driver that made no join
    # nothing to read: a program without the counters, a configuration without a flops file
    bare = {"result": {**result, "series": {"step_s": [0.4]}}, "config": {"trainer": {}},
            "model": {}, "rehearse": False}
    assert counter_rows_imbalance.read(bare) is None
    assert train_mfu_family.read(bare) is None
    assert train_grouped_mxu.read(bare, pattern="x") is None


def test_scopes_are_joined_by_the_instructions_name():
    sys.path.insert(0, ROOT)
    from benchmarks.lib import scope_seconds

    text = """HloModule jit_step

%fused_computation.3 (p: bf16[8,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  ROOT %multiply.1 = bf16[8,4]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/moe_experts/jit(silu)/mul"}
}

ENTRY %main.9 (a: bf16[8,4]) -> bf16[8,4] {
  %a = bf16[8,4]{1,0} parameter(0)
  %fusion.3 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/moe_combine/bsd,dr->bsr/dot_general"}
  %ragged-dot-none.2 = bf16[8,4]{1,0} custom-call(%fusion.3), custom_call_target="x", metadata={op_name="ragged-dot-none"}
  ROOT %add.5 = bf16[8,4]{1,0} add(%fusion.3, %ragged-dot-none.2), metadata={op_name="jit(step)/optimizer/add"}
}
"""
    by = scope_seconds.scopes_by_instruction(text)
    assert by["fusion.3"] >= {"mlp", "moe_combine", "moe_experts", "closed_call"}
    assert "mul" not in by["fusion.3"] and "dot_general" not in by["fusion.3"]
    assert by["ragged-dot-none.2"] == set() and by["add.5"] == {"optimizer"}
    ops = {"%fusion.3 = bf16[8,4]{1,0} fusion(bf16[8,4] %a)": 0.25, "%add.5 = bf16[8,4] add(": 0.5,
           "%not-in-text.1 = f32[] x(": 1.0}
    scopes = scope_seconds.op_scopes(ops, text)
    assert scopes["%not-in-text.1 = f32[] x("] == []
    seconds = scope_seconds.seconds(ops, scopes)
    assert seconds["moe_experts"] == seconds["moe_combine"] == 0.25 and seconds["optimizer"] == 0.5


def test_the_drivers_step_parity_reads_the_steps_own_gradient_and_update():
    """benchmarks/drivers/train_family.py holds the timed step to the reference through
    the state one step from zero moments leaves: Adam's first moment is (1 - b1) x the
    clipped gradient, the second its square, and the parameters moved by AdamW's first
    update. Against optax itself, with a rate above zero, clipped and not."""
    sys.path.insert(0, ROOT)
    import optax
    from benchmarks.drivers import train_family as driver
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    cfg, b1, b2 = CFG, 0.9, 0.95
    for clip in (1.0, 1e3):
        tx = make_optimizer(learning_rate=0.01, warmup_steps=0, grad_clip=clip)
        state0 = init_state(jax.random.PRNGKey(0), cfg, tx)
        batch = {"tokens": _tokens(cfg)}
        state1, m = make_train_step(cfg, tx, donate=False)(state0, batch)
        grads = jax.grad(lambda p: llama.loss_fn(p, batch, cfg)[0])(state0.params)
        mu = optax.tree_utils.tree_get(state1.opt_state, "mu")
        nu = optax.tree_utils.tree_get(state1.opt_state, "nu")
        scale = max(1.0, float(m["grad_norm"]) / clip) / (1 - b1)
        assert (scale > 10.5) == (clip == 1.0)  # the first is clipped
        rows = driver.row_errors(mu, grads, scale)
        assert len(rows) >= 50 and rows["['layers']['w_gate']"][0].shape == (2,)
        for name, (err, ref) in rows.items():
            assert float(err.max()) <= 1e-10 * float(ref.max()) + 1e-20, name
        moments, moved = driver.first_update_errors(
            state0.params, state1.params, mu, nu, 0.01, 0.1, b1, b2)
        assert float(moments) < 1e-5 and float(moved) < 5e-7  # a float32 rounding of a sum near 1
        # a step that moved a leaf otherwise is seen
        off = jax.tree.map(lambda a: a, state1.params)
        off["final_norm"] = off["final_norm"] + 1e-4
        assert float(driver.first_update_errors(state0.params, off, mu, nu, 0.01, 0.1, b1, b2)[1]) > 5e-5
    summary = driver.gradient_summary(
        {"a": (np.array([4.0, 0.0]), np.array([100.0, 0.0])), "b": (np.array([1.0]), np.array([100.0]))},
        {"a": (np.array([1.0, 0.0]), np.array([100.0, 0.0])), "b": (np.array([4.0]), np.array([100.0]))})
    assert summary["rows"] == 2 and summary["ratio_worst"] == 2.0 and summary["ratio_worst_at"] == "a[0]"
    assert summary["ratio_all"] == 1.0 and summary["unreached_rows_are_zero"]
    assert not driver.gradient_summary({"a": (np.array([1.0]), np.array([0.0])), "b": (np.array([1.0]), np.array([1.0]))},
                                       {"a": (np.array([0.0]), np.array([0.0])), "b": (np.array([1.0]), np.array([1.0]))}
                                       )["unreached_rows_are_zero"]
    chosen = [np.array([[0, 1], [0, 2], [0, 3]])]
    ruled, load = driver.balance_rule(np.zeros((1, 4)), chosen, 4, 0.5)
    np.testing.assert_array_equal(load, [[3, 1, 1, 1]])
    np.testing.assert_allclose(ruled, moe.balance_bias(jnp.zeros((1, 4)), jnp.asarray(load, jnp.float32), 0.5))
