"""The lfm2_moe family (LFM2-24B-A2B) on the training path, at a small size on the CPU with
seeded weights: every published layer two parts of a pattern (a mixer, then a feed-forward
part); gated short convolutions three to one with rotated GQA whose q and k are normed a
head, at head width 64 through the flash kernels; a leading dense layer, then
sigmoid-routed SwiGLU experts at 4 of 64 with no shared expert; a tied head; and the share
of a layer's experts a chip holds. The anchor is the plain reference
(ray_tpu/models/reference/)."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import checkpoint, get_config, llama, moe, sconv
from ray_tpu.models.config import ModelConfig
from ray_tpu.models.reference import lfm2_moe as ref

ROOT = os.path.join(os.path.dirname(__file__), "..")
CFG = get_config("lfm2-tiny")
CELL = "lfm2moe-train-ep8share-b4-s8192"
CONFIG = "lfm2-24b-a2b-train-ep8"


def _model(cfg):
    return dataclasses.asdict(cfg)


def _params(cfg, seed=0):
    p = llama.init(jax.random.PRNGKey(seed), cfg)
    if "layers" in p:  # a selection bias that changes who is chosen
        p["layers"]["router_bias"] = 0.05 * jax.random.normal(
            jax.random.PRNGKey(seed + 5), p["layers"]["router_bias"].shape)
    if "q_head_norm" in p.get("attn_layers", {}):  # and norm weights that are not one
        for i, name in enumerate(("q_head_norm", "k_head_norm")):
            p["attn_layers"][name] = 1 + 0.2 * jax.random.normal(
                jax.random.PRNGKey(seed + 6 + i), p["attn_layers"][name].shape)
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
    ("C-*ECECECE", (0, 1)),  # the cell's: a dense layer and one period, everything held
    ("C-*ECECECE", (1, 4)),  # a quarter of the experts
    ("CECE", (0, 2)),        # two periods of CE: the scan over periods
    ("C*C-", (0, 1)),        # no expert part at all
])
def test_loss_and_every_gradient_match_the_reference(pattern, held):
    cfg = dataclasses.replace(CFG, layer_pattern=pattern, n_layers=len(pattern))
    assert llama.pattern_period(pattern)[1] == (2 if pattern == "CECE" else 1)
    p, t = _params(cfg), _tokens(cfg, (3, 41))
    assert "lm_head" not in p  # tied: the head is the embedding, and its gradient both uses'
    if "E" in pattern:
        cfg = dataclasses.replace(cfg, experts_held=held)
        p = _experts_share(p, cfg)
        assert not any(name.startswith("shared_") for name in p["layers"])
    (loss, m), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(p, {"tokens": t}, cfg)
    (r_loss, parts), r_grads = jax.value_and_grad(ref.loss, has_aux=True)(
        p, t, _model(cfg), jnp.float32, None, True)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-6)
    np.testing.assert_allclose(m["ce_loss"], parts["ce_loss"], rtol=1e-6)
    assert "mtp_loss" not in m and parts["position_losses"].shape == (3, 40)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    r_flat = dict(jax.tree_util.tree_flatten_with_path(r_grads)[0])
    assert len(flat) == len(r_flat) >= 10
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
            assert own.shape == (3, 40, 4) and r["margin"].shape == (3, 40)
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
    from benchmarks.lib import reference_lfm2_moe as copy

    with open(ref.__file__) as a, open(copy.__file__) as b:
        assert a.read() == b.read()
    p, t = _params(CFG), _tokens(CFG)
    for mine, theirs in zip(jax.tree.leaves(ref.position_losses(p, t, _model(CFG))),
                            jax.tree.leaves(copy.position_losses(p, t, _model(CFG)))):
        np.testing.assert_array_equal(mine, theirs)


def test_the_coarse_reference_is_the_same_code_rounded_and_a_sequence_at_a_time():
    """bfloat16: the yardstick, near the float32 reference and not equal to it. The batch
    is walked a sequence at a time (`lax.map`): a sequence's numbers do not depend on its
    neighbours, and a selection is cut to each."""
    p, t = _params(CFG), _tokens(CFG, (3, 41))
    exact = ref.loss(p, t, _model(CFG))
    coarse = ref.loss(p, t, _model(CFG), jnp.bfloat16)
    assert 1e-6 < abs(float(coarse - exact)) / float(exact) < 2e-2
    assert ref.next_token_losses(p, t, _model(CFG)).shape == (3, 40)
    whole, _, routings = ref.position_losses(p, t, _model(CFG))
    alone, _, r1 = ref.position_losses(p, t[1:2], _model(CFG))
    np.testing.assert_allclose(whole[1:2], alone, rtol=1e-6)
    np.testing.assert_array_equal(routings[2]["own"][1:2], r1[2]["own"])
    # every token to the layer's own choice rolled by one expert: another loss, the same margins
    rolled = [(r["own"] + 1) % CFG.n_experts for r in routings]
    other, _, again = ref.position_losses(p, t, _model(CFG), jnp.float32, rolled)
    assert float(jnp.abs(other - whole).max()) > 1e-3
    np.testing.assert_array_equal(again[0]["chosen"], rolled[0])
    np.testing.assert_array_equal(again[0]["own"], routings[0]["own"])  # the first layer's own choice stands


# ------------------------------------------------------------------- the mixer

@pytest.mark.parametrize("taps", [3, 4])
def test_the_mixer_is_the_position_at_a_time_loop(taps):
    """c_t = sum_k w_k z_{t-(taps-1)+k} with z = B * x, zeros before the sequence (the first
    taps - 1 positions see them), times C, through W_out: the mixer, the reference's layer and
    a loop over positions that keeps the last taps - 1 values of z, as a decoder would."""
    cfg = dataclasses.replace(CFG, conv_taps=taps)
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
    np.testing.assert_allclose(sconv.mixer(x, lp, cfg), want, atol=2e-5)
    np.testing.assert_allclose(ref.conv_layer(x, lp, _model(cfg)), want, atol=2e-5)
    # position 0 sees only its own z through the LAST tap
    first = (c[:, 0] * (b[:, 0] * v[:, 0]) * lp["sconv_w"][-1]) @ lp["sconv_out"]
    np.testing.assert_allclose(sconv.mixer(x, lp, cfg)[:, 0] - x[:, 0], first, atol=2e-5)
    # causal: what comes later changes nothing earlier
    later = x.at[:, 5:].add(1.0)
    np.testing.assert_array_equal(sconv.mixer(later, lp, cfg)[:, :5], sconv.mixer(x, lp, cfg)[:, :5])


def test_packed_documents_and_a_cache_are_refused_by_the_mixers_name():
    lp = sconv.init(jax.random.PRNGKey(3), CFG)
    x = jnp.zeros((1, 8, 64))
    positions = jnp.arange(8)[None]
    with pytest.raises(NotImplementedError, match="gated short-convolution layer over packed documents"):
        llama._block(x, lp, CFG, positions, jnp.zeros((1, 8), jnp.int32))
    with pytest.raises(NotImplementedError, match="convolution tail"):
        llama._block(x, lp, CFG, positions, None, cache_kv=(x, x), cache_len=jnp.zeros((), jnp.int32))
    with pytest.raises(ValueError, match=r"C \(gated short convolution\)"):
        dataclasses.replace(CFG, layer_pattern="C-*ECECECX")
    with pytest.raises(NotImplementedError, match="latent attention"):
        llama.param_axes(dataclasses.replace(get_config("glm-tiny"), attn_qk_norm=True))


# ------------------------------------------------------------------- attention at width 64

def _attention_cfg(qk_norm):
    return ModelConfig(name="w64", vocab_size=64, d_model=512, n_layers=1, n_heads=8, n_kv_heads=2, d_ff=64,
                       rope_theta=1e6, dtype="float32", layer_pattern="*", attn_qk_norm=qk_norm)


@pytest.mark.parametrize("qk_norm", [True, False])
def test_attention_at_head_width_64_is_the_references(qk_norm):
    """32 / 8 heads of 64 in small: 8 / 2 heads of 64 (groups of 4), q and k normed a head
    BEFORE the rotation where the layer has the weights, rotated at theta 1e6."""
    cfg = _attention_cfg(qk_norm)
    assert cfg.head_dim == 64
    lp = jax.tree.map(lambda a: a[0], _params(cfg)["attn_layers"])
    assert ("q_head_norm" in lp) == qk_norm and lp["wq"].shape == (512, 8, 64) and lp["wk"].shape == (512, 2, 64)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 512))
    got = llama._block(x, lp, cfg, jnp.arange(48)[None], None)[0]
    want = ref.attention_layer(x, lp, _model(cfg))
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    if qk_norm:  # the norm is in front of the rotation: behind it, another number
        plain = ref.attention_layer(x, {n: a for n, a in lp.items() if "head_norm" not in n}, _model(cfg))
        assert float(jnp.abs(plain - want).max()) > 1e-2


def test_the_flash_kernels_run_at_head_width_64_on_padded_lanes():
    """[1, 256, 4 / 1, 64] through Pallas' interpreter in tiles of 128: forward, dQ and
    dK-dV of the SAME three kernels (by name), causal, a group of 4, against the plain
    softmax; `supports` says 64 and the multiples of 128 and nothing between."""
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import Rotation, attention, attention_reference

    assert fa.supports(8192, 8192, 64) and fa.supports(8192, 8192, 128) and fa.supports(8192, 8192, 256)
    assert not fa.supports(8192, 8192, 192) and not fa.supports(8192, 8192, 32) and not fa.supports(8191, 8191, 64)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k, v = (jax.random.normal(key, (1, 256, 1, 64), jnp.float32) for key in ks[1:3])
    w = jax.random.normal(ks[3], (1, 256, 4, 64), jnp.float32)

    def flash(q, k, v):
        return jnp.sum(w * fa.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128))

    def plain(q, k, v):
        return jnp.sum(w * attention_reference(q, k, v, causal=True))

    jaxpr = str(jax.make_jaxpr(jax.grad(flash, argnums=(0, 1, 2)))(q, k, v))
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq", "flash_attention_bwd_dkv"):
        assert f"name={name}" in jaxpr, name
    out = fa.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    assert out.shape == q.shape
    np.testing.assert_allclose(out, attention_reference(q, k, v, causal=True), atol=2e-5)
    for mine, want in zip(jax.grad(flash, argnums=(0, 1, 2))(q, k, v), jax.grad(plain, argnums=(0, 1, 2))(q, k, v)):
        assert mine.shape == want.shape
        np.testing.assert_allclose(mine, want, atol=3e-5 * float(jnp.abs(want).max()))
    # the rotation at 64 is the caller's jax.numpy statement, in front of the kernels
    with pytest.raises(NotImplementedError, match="head width 64"):
        fa.flash_attention(q, k, v, rope=(jnp.arange(256)[None], 1e6))
    rotated = attention(q, k, v, impl="pallas", rotation=Rotation(jnp.arange(256)[None], 1e6, llama.rope))
    want = attention_reference(*(llama.rope(a, jnp.arange(256)[None], 1e6) for a in (q, k)), v, causal=True)
    np.testing.assert_allclose(rotated, want, atol=2e-5)


# ------------------------------------------------------------------- the expert part

@pytest.mark.parametrize("load", ["seeded", "all_on_one_share"])
def test_8_expert_shares_add_up_to_the_uncut_part_with_no_shared_expert(load):
    """What a chip of the deployment holds: 8 of 64 experts. 8 shares add up to the uncut
    expert part, which is nothing beside the routed experts; also where a bias sends every
    token to the first share's experts, which then walks four windows and the others none
    of their own."""
    whole = dataclasses.replace(CFG, n_experts=64, moe_top_k=4)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, CFG.d_model))
    lp = moe.init_expert_weights(jax.random.PRNGKey(3), whole)
    assert set(lp) == {"router", "router_bias", "w_gate", "w_up", "w_down"}
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (64,))
    if load == "all_on_one_share":
        lp["router_bias"] = lp["router_bias"].at[:4].add(10.0)
    want, routing = ref.expert_layer(x, lp, _model(whole))
    parts, windows = [], []
    for i in range(8):
        cfg = dataclasses.replace(whole, experts_held=(i, 8))
        mine = {**lp, **{n: lp[n][8 * i:8 * i + 8] for n in ("w_gate", "w_up", "w_down")}}
        y, aux = moe.expert_layer(x.reshape(-1, CFG.d_model), mine, cfg)
        parts.append(y.reshape(x.shape))
        lo, hi = moe.held_range(cfg)
        windows.append(int(moe.windows_walked(aux["load"][lo:hi].sum().astype(jnp.int32),
                                              moe.window_rows(cfg, 512))))
    assert moe.window_rows(dataclasses.replace(whole, experts_held=(0, 8)), 512) == 512
    np.testing.assert_allclose(sum(parts), want, atol=3e-5 * float(jnp.abs(want).max()))
    if load == "all_on_one_share":
        assert windows == [4] + [1] * 7 and not np.asarray(parts[1]).any()
        assert set(np.asarray(routing["own"]).ravel()) == {0, 1, 2, 3}
    else:
        assert windows == [1] * 8 and float(jnp.abs(parts[1]).max()) > 1e-3


def test_route_normalises_over_the_chosen_with_the_epsilon_a_field():
    """gates = s_chosen / (sum s_chosen + moe_gate_eps) * 1.0: lfm2_moe states 1e-6 where
    every accepted cell has 1e-20; the bias selects and never weights."""
    x = jax.random.normal(jax.random.PRNGKey(0), (96, 64)) * 0.01
    lp = moe.init_expert_weights(jax.random.PRNGKey(1), CFG)
    lp["router"] = lp["router"] - 3.0  # small scores, where an epsilon shows
    bias = 0.02 * jax.random.normal(jax.random.PRNGKey(2), (16,))
    idx, gates = moe.route(x, lp["router"], bias, CFG)
    scores = jax.nn.sigmoid(x @ lp["router"])
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(jax.lax.top_k(scores + bias, 4)[1], -1))
    picked = jnp.take_along_axis(scores, idx, axis=-1)
    np.testing.assert_allclose(gates, picked / (picked.sum(-1, keepdims=True) + 1e-6), rtol=1e-6)
    _, plain = moe.route(x, lp["router"], bias, dataclasses.replace(CFG, moe_gate_eps=1e-20))
    np.testing.assert_allclose(plain.sum(-1), 1.0, rtol=1e-6)
    assert float(jnp.abs(gates.sum(-1) - 1).max()) > 1e-7 and ModelConfig.moe_gate_eps == 1e-20
    _, routing = ref.expert_layer(x[None], {**lp, "router_bias": bias}, _model(CFG))
    np.testing.assert_array_equal(np.sort(idx, -1), np.sort(routing["own"][0], -1))


# ------------------------------------------------------------------- the step

def test_the_step_trains_and_the_bias_moves_by_the_balance_rule():
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    tx = make_optimizer(learning_rate=1e-3, warmup_steps=1, total_steps=10)
    state = init_state(jax.random.PRNGKey(0), CFG, tx)
    assert set(state.params) == {"embed", "sconv_layers", "mlp_layers", "attn_layers", "layers", "final_norm"}
    assert state.params["sconv_layers"]["sconv_in"].shape == (4, 64, 3, 64)
    bias0 = np.asarray(state.params["layers"]["router_bias"])
    state, metrics = make_train_step(CFG, tx)(state, {"tokens": _tokens(CFG, (2, 33))})
    load = np.asarray(metrics["expert_load"])
    assert load.shape == (4, 16) and (load.sum(-1) == 2 * 32 * 4).all()
    want = bias0 + CFG.moe_bias_update_rate * np.sign(load.mean(-1, keepdims=True) - load)
    np.testing.assert_allclose(state.params["layers"]["router_bias"], want, atol=1e-7)
    assert np.isfinite(float(metrics["loss"])) and float(metrics["loss"]) < 2 * np.log(256)
    import optax

    mu = optax.tree_utils.tree_get(state.opt_state, "mu")  # (the schedule's first rate is 0: read the moments)
    assert all(np.abs(np.asarray(a)).max() > 0 for name in ("embed", "sconv_layers", "attn_layers", "mlp_layers")
               for a in jax.tree.leaves(mu[name]))


# ------------------------------------------------------------------- the configuration

PAIRS = {  # published key -> ModelConfig field (norm_eps, rope_theta and n_experts: once more under `program`)
    "hidden_size": "d_model", "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
    "vocab_size": "vocab_size", "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
    "norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings", "max_position_embeddings": "max_seq_len",
    "conv_L_cache": "conv_taps", "norm_topk_prob": "moe_norm_topk", "use_expert_bias": "moe_select_bias",
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


def _published(config):
    hf = {k: v for k, v in config.items() if k not in ("program", "trainer", "published", "reduced")}
    return {**hf, **config["published"]}


def test_configuration_files_program_group_equals_its_published_keys():
    config, _, cfg = _cell_config()
    for published, field in PAIRS.items():
        assert getattr(cfg, field) == config[published], (published, field)
    assert cfg.rope_theta == config["rope_parameters"]["rope_theta"] == 1e6
    assert sorted(config["reduced"]) == sorted(config["published"])
    assert "head_dim" not in config and cfg.head_dim == 64 and cfg.attn_head_dim == 0
    # the published widths, every one
    assert (cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.d_ff, cfg.d_ff_expert, cfg.n_experts, cfg.moe_top_k,
            cfg.conv_taps, cfg.max_seq_len) == (2048, 32, 8, 11776, 1536, 64, 4, 3, 128000)
    # what is held here, and of what: the chip's share of a group that shares each layer
    published = config["published"]
    assert config["layer_types"] == published["layer_types"][1:6] == ["conv", "full_attention"] + ["conv"] * 3
    assert cfg.layer_pattern == "C-*ECECECE" and cfg.n_layers == 2 * config["num_hidden_layers"] == 10
    assert config["num_dense_layers"] == 1 and published["num_dense_layers"] == 2
    assert cfg.n_experts == published["num_experts"] and cfg.n_experts_held == config["num_experts"] == 8
    assert cfg.vocab_size == published["vocab_size"] // 8 and cfg.mtp_depth == 0 and cfg.n_shared_experts == 0
    assert cfg.moe_dropless and cfg.moe_scoring == "sigmoid" and cfg.attn_qk_norm and cfg.tie_embeddings
    assert cfg.moe_gate_eps == 1e-6 and cfg.attention_rotation
    assert abs(cfg.n_params - 469.3e6) < 0.1e6  # the issue's arithmetic
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    held = sum(int(np.prod(a.shape)) for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]
               if "router_bias" not in jax.tree_util.keystr(path))
    assert held == cfg.n_params and "lm_head" not in shapes
    count = lambda stack: sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes[stack]))  # noqa: E731
    assert abs(count("sconv_layers") - 16.79e6) < 0.01e6 and abs(count("attn_layers") - 10.49e6) < 0.01e6
    assert abs(count("mlp_layers") - 72.35e6) < 0.01e6 and abs(count("layers") - 64 - 75.63e6) < 0.01e6
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    trainer = config["trainer"]
    assert len(config["assumed"]) >= 5 and trainer["reference"] == "reference_lfm2_moe"
    assert (trainer["batch"], trainer["seq"], trainer["parity_sequences"], trainer["mesh"]) == (4, 8192, 4, None)
    assert moe.window_rows(cfg, 4 * 8192) == 32768  # a quarter of tokens x k, as in the GLM cell
    # the program's own mapping of the published keys says the same, share apart
    hf = {**_published(config), "num_hidden_layers": 5, "layer_types": config["layer_types"], "num_dense_layers": 1}
    mapped = _config_from(hf)
    assert dataclasses.replace(mapped, name=cfg.name, vocab_size=cfg.vocab_size, experts_held=(0, 8),
                               remat_policy="full", dtype="bfloat16") == cfg


def test_n_params_of_the_published_keys_is_23_84_b():
    cfg = _config_from(_published(_cell_config()[0]))
    assert cfg.n_layers == 80 and cfg.layer_pattern[:12] == "C-C-*ECECECE" and cfg.layer_pattern.count("*") == 10
    assert cfg.layer_pattern.count("C") == 30 and cfg.layer_pattern.count("-") == 2 and cfg.head_dim == 64
    assert abs(cfg.n_params / 23.84e9 - 1) < 0.01
    active = cfg.n_params - 38 * (64 - 4) * 3 * 2048 * 1536  # 4 of 64 experts a token, and everything else
    assert abs(active / 2.3e9 - 1) < 0.03


def test_config_from_hf_maps_the_family_and_refuses_what_is_not_runnable():
    base = dict(model_type="lfm2_moe", vocab_size=256, hidden_size=64, num_attention_heads=4,
                num_key_value_heads=2, intermediate_size=96, num_hidden_layers=5,
                layer_types=["conv", "full_attention", "conv", "conv", "conv"], num_dense_layers=1,
                conv_L_cache=3, conv_bias=False, num_experts=16, num_experts_per_tok=4, moe_intermediate_size=40,
                routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True, norm_eps=1e-5,
                max_position_embeddings=128, rope_parameters={"rope_theta": 1000000, "rope_type": "default"})
    cfg = _config_from(base)
    assert dataclasses.replace(cfg, name="lfm2-tiny", dtype="float32") == CFG
    assert not _config_from({**base, "tie_word_embeddings": False}).tie_embeddings
    for bad, what in ((dict(conv_bias=True), "conv_bias"), (dict(norm_topk_prob=False), "not normalised"),
                      (dict(use_expert_bias=False), "selection bias"), (dict(sliding_window=4096), "window"),
                      (dict(layer_types=["conv"] * 4), "layer_types"),
                      (dict(layer_types=["conv"] * 4 + ["sliding_attention"]), "layer_types"),
                      (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}), "rope_type"),
                      (dict(num_experts=0), "routed experts")):
        with pytest.raises(ValueError, match=what):
            _config_from({**base, **bad})


def test_llm_refuses_the_family_by_name_of_what_is_missing():
    from ray_tpu.llm.config import LLMConfig

    with pytest.raises(NotImplementedError) as e:
        LLMConfig(model_source="lfm2-tiny").resolve_model_config()
    for what in ("gated short convolution's tail of conv_taps - 1 positions", "dropless", "pattern of single-part layers"):
        assert what in str(e.value)


# ------------------------------------------------------------------- the benchmark's files

def test_the_familys_flops_file_counts_one_chips_share():
    from benchmarks.lib import flops_lfm2_moe as flops

    _, model, cfg = _cell_config()
    layer = flops.layer_flops_per_token(model, (8192 + 1) / 2)
    assert layer["C"] == 2 * (2048 * 6144 + 2048 * 2048) and layer["-"] == 2 * 3 * 2048 * 11776
    assert layer["E"] == 2 * (2048 * 64 + 4 / 8 * flops.expert_params(model))  # no shared expert
    assert layer["*"] == 2 * 2048 * 64 * (2 * 32 + 2 * 8) + 2 * 32 * 2 * 64 * 4096.5
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    assert set(fwd) == {"C", "*", "-", "E", "head"}
    total = sum(fwd.values())
    assert abs(3 * total / 1217e6 - 1) < 0.001  # the issue's count, MFLOP a token
    assert abs((fwd["C"] + fwd["*"]) / total - 0.46) < 0.01 and abs(fwd["-"] / total - 0.36) < 0.01
    assert abs(flops.train_flops_per_token(model, 8192) * 32768 / 39.9e12 - 1) < 0.001
    assert flops.grouped_products_flops(model, 4 * 16384) == 3 * 2 * 65536 * 3 * 2048 * 1536
    conv = flops.scan_step_work(model, 32768)
    assert conv["flops"] == 4 * 3 * 32768 * layer["C"]
    assert conv["bytes"] == 4 * 3 * 2 * (4 * 2048 * 2048 + 32768 * 6 * 2048)
    assert conv["flops"] / 197e12 > conv["bytes"] / 819e9  # bound by its products on a v5e
    core = flops.attention_step_work(model, 32768, 8192)
    assert core["flops"] == 3 * 32768 * 2 * 32 * 2 * 64 * 4096.5  # six products of the causal half
    assert core["bytes"] == 2 * 32768 * 64 * (6 * 32 + 6 * 8)
    assert core["flops"] / 197e12 > core["bytes"] / 819e9


def test_the_roofline_readers_read_the_sconv_scope_and_the_flash_kernels():
    sys.path.insert(0, ROOT)
    from benchmarks.lib import flops_lfm2_moe as flops
    from benchmarks.readers import trace_scope_share, train_kernel_roofline, train_scan_roofline

    config, model, _ = _cell_config()
    ops = {"%fusion.1 = bf16[4]": 0.04, "%fusion.2 = bf16[4]": 0.06, "%ragged-dot-none.3 = bf16[4]": 1.7,
           "%flash_attention_fwd.2 = (bf16[4]) custom-call()": 0.03, "%flash_attention_fwd.3 = (bf16[4]) custom-call()": 0.03,
           "%flash_attention_bwd_dq.1 = bf16[4] custom-call()": 0.05, "%flash_attention_bwd_dkv.1 = bf16[4] custom-call()": 0.09}
    scopes = {"%fusion.1 = bf16[4]": ["attn", "sconv", "sconv_in_proj"],
              "%fusion.2 = bf16[4]": ["attn", "sconv", "sconv_gate_conv"], "%ragged-dot-none.3 = bf16[4]": ["moe_experts"]}
    result = {"traced_steps": 5, "tokens_per_step": 32768, "seq": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "trace": {"busy_s": 2.0, "op_seconds": ops, "op_scopes": scopes}}
    ctx = {"result": result, "config": config, "model": model, "rehearse": False}

    def metric(name):
        with open(os.path.join(ROOT, "benchmarks", "metrics", f"{name}.json")) as f:
            return json.load(f)

    conv = flops.scan_step_work(model, 32768)
    assert train_scan_roofline.read(ctx, **metric("train_sconv_roofline_pct")["args"]) == pytest.approx(
        100 * 5 * conv["flops"] / 197e12 / 0.10)
    assert trace_scope_share.read(ctx, **metric("train_sconv_pct")["args"]) == pytest.approx(100 * 0.10 / 2.0)
    core = flops.attention_step_work(model, 32768, 8192)
    assert metric("train_attn_w64_roofline_pct")["reader"] == "train_kernel_roofline"
    assert train_kernel_roofline.read(ctx, **metric("train_attn_w64_roofline_pct")["args"]) == pytest.approx(
        100 * 5 * core["flops"] / 197e12 / 0.20)
    # a program without the scope or the kernels (the parent of this PR, which falls to the
    # XLA path), a flops file without the function, a rehearsal: nothing to read, nothing raised
    result["trace"] = {"busy_s": 2.0, "op_seconds": {"%fusion.9 = f32[4]": 2.0}, "op_scopes": {"%fusion.9 = f32[4]": ["mlp"]}}
    assert train_scan_roofline.read(ctx, "sconv") is None and trace_scope_share.read(ctx, "^sconv") is None
    assert train_kernel_roofline.read(ctx, "flash_attention_", "attention_step_work") is None
    result["trace"]["op_seconds"] = ops
    assert train_kernel_roofline.read(ctx, "flash_attention_", "no_such_work") is None
    assert train_kernel_roofline.read({**ctx, "rehearse": True}, "flash_attention_", "attention_step_work") is None
    solar = {**ctx, "config": {"trainer": {"flops": "flops_solar_open2"}}}
    assert train_kernel_roofline.read(solar, "flash_attention_", "attention_step_work") is None


def test_the_compiled_step_names_the_mixers_scopes_inside_attn():
    """What `train_sconv_pct`, `train_sconv_roofline_pct` and the accepted readers that know
    `attn` read: the compiled program's instructions carry the mixer's scopes, each beside
    `sconv` and `attn`, forward and backward."""
    sys.path.insert(0, ROOT)
    from benchmarks.lib import scope_seconds

    p, t = _params(CFG), _tokens(CFG, (1, 33))

    def loss(p):  # as train/step.py names the model: the outermost scope is the transformations'
        with jax.named_scope("model"):
            return llama.loss_fn(p, {"tokens": t}, CFG)[0]

    text = jax.jit(jax.grad(loss)).lower(p).compile().as_text()
    by_instruction = scope_seconds.scopes_by_instruction(text)
    scopes = set().union(*by_instruction.values())
    names = {"sconv_in_proj", "sconv_gate_conv", "sconv_out_proj"}
    assert names | {"sconv", "moe_router", "moe_experts", "attn", "mlp", "lm_head"} <= scopes, sorted(scopes)
    assert "moe_shared" not in scopes
    assert all({"attn", "sconv"} <= found for found in by_instruction.values() if found & names)


def test_the_manifest_lists_the_cell_and_its_metrics():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    assert [w["name"] for w in manifest["workloads"]][5] == CELL and len(manifest["workloads"]) >= 6
    assert manifest["workloads"][5]["chips"] == 1 and manifest["configs"][5]["name"] == CONFIG
    assert manifest["workloads"][5]["traffic"] == "steady-b4-s8192"
    config = _cell_config()[0]
    assert manifest["configs"][5]["reduced"] == config["reduced"]
    assert manifest["configs"][5]["source"] == config["source"]
    with open(os.path.join(ROOT, "benchmarks", "workloads", f"{CELL}.json")) as f:
        cell = json.load(f)
    assert cell["why"] == manifest["workloads"][5]["why"] and len(cell["why"]) <= 200
    assert cell["traffic_parameters"]["batch"] == config["trainer"]["batch"] == 4
    reported = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
                if CELL in m.get("workloads", [CELL])}
    assert reported == {
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct", "train_sconv_pct", "train_sconv_roofline_pct", "train_attn_w64_roofline_pct",
        "train_mfu_sconv_moe_pct"}
    for name in ("train_sconv_pct", "train_sconv_roofline_pct", "train_attn_w64_roofline_pct", "train_mfu_sconv_moe_pct"):
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics", f"{name}.json"))
        assert [m["workloads"] for m in manifest["per_layer"] if m["name"] == name] == [[CELL]]
    four_chip = [w for w in manifest["workloads"] if w["chips"] == 4]
    assert len(four_chip) == 1  # a quarter of six cells, rounded down


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
    assert window["parity"]["gradient"]["rows"] > 25
    assert set(window["parity"]["losses"]) == {"loss", "ce_loss"}
    assert window["parity"]["positions"] == 2 * 64
    values = next(ln for ln in lines if ln.get("phase") == "rehearsal_values")["values"]
    assert values["train_moe_imbalance"]["value"] >= 1.0
    assert lines[-1]["correct"] is False and lines[-1]["metrics"] == {}
