"""The lfm2_moe family (LFM2-24B-A2B) on the training path, at a small size on the CPU with
seeded weights: every published layer two parts of a pattern (a mixer, then a feed-forward
part); gated short convolutions three to one with rotated GQA whose q and k are normed a
head; a leading dense layer, then sigmoid-routed SwiGLU experts at 4 of 64 with no shared
expert; a tied head; and the share of a layer's experts a chip holds. The contract is
tests/family_contract.py's; here is what the family alone has. (The mixer against its
loop: tests/test_short_conv.py; attention at head width 64: tests/test_llama.py and
tests/test_flash_attention.py.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import Family, config_from, expert_shares, model_of, params, published_keys, tokens
from ray_tpu.models import get_config, llama, moe, sconv
from ray_tpu.models.reference import lfm2_moe as ref

CFG = get_config("lfm2-tiny")


def _pattern(pattern, held):
    return dataclasses.replace(CFG, layer_pattern=pattern, n_layers=len(pattern), experts_held=held)


# ------------------------------------------------------------------- the shares

def _8_expert_shares(load):
    def shares(x):
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
        want, routing, parts, counted = expert_shares(ref, whole, 8, x, lp)
        assert not np.asarray(parts[0]).any()  # no shared expert
        windows = []
        for i, aux in enumerate(counted):
            cfg = dataclasses.replace(whole, experts_held=(i, 8))
            lo, hi = moe.held_range(cfg)
            windows.append(int(moe.windows_walked(aux["load"][lo:hi].sum().astype(jnp.int32),
                                                  moe.window_rows(cfg, 512))))
        assert moe.window_rows(dataclasses.replace(whole, experts_held=(0, 8)), 512) == 512
        if load == "all_on_one_share":
            assert windows == [4] + [1] * 7 and not np.asarray(parts[2]).any()
            assert set(np.asarray(routing["own"]).ravel()) == {0, 1, 2, 3}
            return want, parts, 1
        assert windows == [1] * 8
        return want, parts, 2
    return shares


# ------------------------------------------------------------------- the configuration

def _config_file(config, cfg, config_from):
    assert cfg.rope_theta == config["rope_parameters"]["rope_theta"] == 1e6
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
    assert cfg.attn_qk_norm and cfg.tie_embeddings and cfg.moe_gate_eps == 1e-6 and cfg.attention_rotation
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    assert "lm_head" not in shapes
    count = lambda stack: sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes[stack]))  # noqa: E731
    assert abs(count("sconv_layers") - 16.79e6) < 0.01e6 and abs(count("attn_layers") - 10.49e6) < 0.01e6
    assert abs(count("mlp_layers") - 72.35e6) < 0.01e6 and abs(count("layers") - 64 - 75.63e6) < 0.01e6
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    trainer = config["trainer"]
    assert len(config["assumed"]) >= 5
    assert (trainer["batch"], trainer["seq"], trainer["parity_sequences"], trainer["mesh"]) == (4, 8192, 4, None)
    assert moe.window_rows(cfg, 4 * 8192) == 32768  # a quarter of tokens x k, as in the GLM cell
    # the program's own mapping of the published keys says the same, share apart
    hf = {**published_keys(config), "num_hidden_layers": 5, "layer_types": config["layer_types"], "num_dense_layers": 1}
    assert dataclasses.replace(config_from(hf), name=cfg.name, vocab_size=cfg.vocab_size, experts_held=(0, 8),
                               remat_policy="full", dtype="bfloat16") == cfg


def _published(cfg):
    assert cfg.n_layers == 80 and cfg.layer_pattern[:12] == "C-C-*ECECECE" and cfg.layer_pattern.count("*") == 10
    assert cfg.layer_pattern.count("C") == 30 and cfg.layer_pattern.count("-") == 2 and cfg.head_dim == 64
    active = cfg.n_params - 38 * (64 - 4) * 3 * 2048 * 1536  # 4 of 64 experts a token, and everything else
    assert abs(active / 2.3e9 - 1) < 0.03


HF_BASE = dict(model_type="lfm2_moe", vocab_size=256, hidden_size=64, num_attention_heads=4,
               num_key_value_heads=2, intermediate_size=96, num_hidden_layers=5,
               layer_types=["conv", "full_attention", "conv", "conv", "conv"], num_dense_layers=1,
               conv_L_cache=3, conv_bias=False, num_experts=16, num_experts_per_tok=4, moe_intermediate_size=40,
               routed_scaling_factor=1, norm_topk_prob=True, use_expert_bias=True, norm_eps=1e-5,
               max_position_embeddings=128, rope_parameters={"rope_theta": 1000000, "rope_type": "default"})


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    layer = flops.layer_flops_per_token(model, (8192 + 1) / 2)
    assert layer["C"] == 2 * (2048 * 6144 + 2048 * 2048) and layer["-"] == 2 * 3 * 2048 * 11776
    assert layer["E"] == 2 * (2048 * 64 + 4 / 8 * flops.expert_params(model))  # no shared expert
    assert layer["*"] == 2 * 2048 * 64 * (2 * 32 + 2 * 8) + 2 * 32 * 2 * 64 * 4096.5
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    total = sum(fwd.values())
    assert abs(3 * total / 1217e6 - 1) < 0.001  # the issue's count, MFLOP a token
    assert abs((fwd["C"] + fwd["*"]) / total - 0.46) < 0.01 and abs(fwd["-"] / total - 0.36) < 0.01
    assert flops.grouped_products_flops(model, 4 * 16384) == 3 * 2 * 65536 * 3 * 2048 * 1536
    conv = flops.scan_step_work(model, 32768)
    assert conv["flops"] == 4 * 3 * 32768 * layer["C"]
    assert conv["bytes"] == 4 * 3 * 2 * (4 * 2048 * 2048 + 32768 * 6 * 2048)
    assert conv["flops"] / 197e12 > conv["bytes"] / 819e9  # bound by its products on a v5e
    core = flops.attention_step_work(model, 32768, 8192)
    assert core["flops"] == 3 * 32768 * 2 * 32 * 2 * 64 * 4096.5  # six products of the causal half
    assert core["bytes"] == 2 * 32768 * 64 * (6 * 32 + 6 * 8)
    assert core["flops"] / 197e12 > core["bytes"] / 819e9


def _made_up(flops, config, model):
    ops = {"%fusion.1 = bf16[4]": 0.04, "%fusion.2 = bf16[4]": 0.06, "%ragged-dot-none.3 = bf16[4]": 1.7,
           "%flash_attention_fwd.2 = (bf16[4]) custom-call()": 0.03, "%flash_attention_fwd.3 = (bf16[4]) custom-call()": 0.03,
           "%flash_attention_bwd_dq.1 = bf16[4] custom-call()": 0.05, "%flash_attention_bwd_dkv.1 = bf16[4] custom-call()": 0.09}
    scopes = {"%fusion.1 = bf16[4]": ["attn", "sconv", "sconv_in_proj"],
              "%fusion.2 = bf16[4]": ["attn", "sconv", "sconv_gate_conv"], "%ragged-dot-none.3 = bf16[4]": ["moe_experts"]}
    result = {"traced_steps": 5, "tokens_per_step": 32768, "seq": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "trace": {"busy_s": 2.0, "op_seconds": ops, "op_scopes": scopes}}
    conv = flops.scan_step_work(model, 32768)
    core = flops.attention_step_work(model, 32768, 8192)
    # a program without the scope or the kernels (the parent of the PR that named them, which
    # fell to the XLA path), a flops file without the function, a rehearsal: nothing to read, nothing raised
    mlp_only = {"busy_s": 2.0, "op_seconds": {"%fusion.9 = f32[4]": 2.0}, "op_scopes": {"%fusion.9 = f32[4]": ["mlp"]}}
    bare = {"result": {**result, "trace": mlp_only}}
    unscoped = {"result": {**result, "trace": {**mlp_only, "op_seconds": ops}}}
    kernels = {"pattern": "flash_attention_", "work": "attention_step_work"}
    # the trace of a program whose backward is ONE kernel a call (PR 53; the one above is its parent's)
    one = {"result": {**result, "trace": {"busy_s": 2.0, "op_scopes": scopes, "op_seconds": {
        **{op: s for op, s in ops.items() if "_bwd_" not in op},
        "%transpose_jvp_flash_attention_bwd_dkv_dq__.1 = (bf16[4]) custom-call()": 0.10}}}}
    return result, [
        ("train_kernel_roofline", "train_attn_w64_roofline_pct", one, 100 * 5 * core["flops"] / 197e12 / 0.16),
        ("trace_op_share", "train_attn_bwd_kernel_pct", {}, 100 * (0.05 + 0.09) / 2.0),
        ("trace_op_share", "train_attn_bwd_kernel_pct", one, 100 * 0.10 / 2.0),
        ("train_scan_roofline", "train_sconv_roofline_pct", {}, 100 * 5 * conv["flops"] / 197e12 / 0.10),
        ("trace_scope_share", "train_sconv_pct", {}, 100 * 0.10 / 2.0),
        ("train_kernel_roofline", "train_attn_w64_roofline_pct", {}, 100 * 5 * core["flops"] / 197e12 / 0.20),
        ("train_scan_roofline", {"scope": "sconv"}, bare, None),
        ("trace_scope_share", {"pattern": "^sconv"}, bare, None),
        ("train_kernel_roofline", kernels, bare, None),
        ("train_kernel_roofline", {**kernels, "work": "no_such_work"}, unscoped, None),
        ("train_kernel_roofline", kernels, {**unscoped, "rehearse": True}, None),
        ("train_kernel_roofline", kernels, {**unscoped, "config": {"trainer": {"flops": "flops_solar_open2"}}}, None)]


FAMILY = Family(
    model_type="lfm2_moe", tiny=CFG, cell="lfm2moe-train-ep8share-b4-s8192", config="lfm2-24b-a2b-train-ep8", index=5,
    unsettle=(("attn_layers", "q_head_norm", 0.2, 1.0), ("attn_layers", "k_head_norm", 0.2, 1.0)),  # norm weights that are not one
    cases=(("C-*ECECECE-held0", _pattern("C-*ECECECE", (0, 1)), 1),  # the cell's: a dense layer and one period, everything held
           ("C-*ECECECE-held1", _pattern("C-*ECECECE", (1, 4)), 1),  # a quarter of the experts
           ("CECE-held2", _pattern("CECE", (0, 2)), 2),              # two periods of CE: the scan over periods
           ("C*C--held3", _pattern("C*C-", (0, 1)), 1)),             # no expert part at all
    batch=3, least_leaves=10, float32_leaves=frozenset(), recurrent="gated short-convolution",
    shares={"8_expert_shares_seeded": _8_expert_shares("seeded"),
            "8_expert_shares_all_on_one_share": _8_expert_shares("all_on_one_share")},
    scopes=frozenset({"sconv", "moe_router", "moe_experts", "attn", "mlp", "lm_head", "attn_in_proj", "attn_head_norm", "attn_core", "attn_out_proj", "moe_dispatch", "moe_combine", "layer_stack"}),
    mixer_scopes=frozenset({"sconv_in_proj", "sconv_gate_conv", "sconv_out_proj"}),
    outer=frozenset({"attn", "sconv"}), absent=frozenset({"moe_shared", "attn_gate"}),
    rehearsal=("3000000007", 25, frozenset({"loss", "ce_loss"}), 2 * 64),
    pairs={  # published key -> ModelConfig field (norm_eps, rope_theta and n_experts: once more under `program`)
        "hidden_size": "d_model", "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
        "vocab_size": "vocab_size", "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
        "norm_eps": "norm_eps", "tie_word_embeddings": "tie_embeddings", "max_position_embeddings": "max_seq_len",
        "conv_L_cache": "conv_taps", "norm_topk_prob": "moe_norm_topk", "use_expert_bias": "moe_select_bias",
        "routed_scaling_factor": "moe_route_scale", "num_experts_per_tok": "moe_top_k"},
    cell_params=469.3e6, config_file=_config_file, published_params=23.84e9, published=_published,
    hf_base=HF_BASE, hf_to_tiny=dict(name="lfm2-tiny", dtype="float32"),
    hf_refused=((dict(conv_bias=True), "conv_bias"), (dict(norm_topk_prob=False), "not normalised"),
                (dict(use_expert_bias=False), "selection bias"), (dict(sliding_window=4096), "window"),
                (dict(layer_types=["conv"] * 4), "layer_types"),
                (dict(layer_types=["conv"] * 4 + ["sliding_attention"]), "layer_types"),
                (dict(rope_parameters={"rope_theta": 1e6, "rope_type": "yarn"}), "rope_type"),
                (dict(num_experts=0), "routed experts")),
    llm_refuses=("gated short convolution's tail of conv_taps - 1 positions", "dropless", "pattern of single-part layers"),
    flops_parts=frozenset({"C", "*", "-", "E", "head"}), step_flops=39.9e12, flops_share=_flops_share,
    made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct", "train_sconv_pct", "train_sconv_roofline_pct", "train_attn_w64_roofline_pct",
        "train_mfu_sconv_moe_pct",
        # PR 52: the attention part's pieces, the expert layer's dispatch and combine (the layer
        # loop's own is next to nothing where one period runs unrolled: not listed)
        "train_attn_proj_pct", "train_attn_core_pct", "train_moe_dispatch_pct", "train_moe_combine_pct",
        "train_attn_passes_pct"}),
    own_metrics=("train_sconv_pct", "train_sconv_roofline_pct", "train_attn_w64_roofline_pct", "train_mfu_sconv_moe_pct"),
    # the cell's whole step (`Family.cell_step`). PR 42: four expert parts at 4 of 64 over 32,768 tokens (the pick a slot
    # at a time: 8.4 M mask elements), no shared expert, beside four gated short convolutions, a dense part and attention
    # at head width 64 on padded lanes; arguments 5.63 GB (16 B a parameter less the gradient). PR 43: 5.193 -> 5.568 GB,
    # the one attention part's `out` on its padded lanes [4, 32, 8192, 128] bfloat16 (268 MB) and logsumexp (4 MB) kept,
    # 0.27 GB, and 0.10 GB of the compiler's placing. PR 48 (PR 47's compile): 5.568 -> 6.673 GB, `[B | C | x]` of four
    # conv parts kept from forward to backward, [4, 8192, 3, 2048] bfloat16 = 403 MB a part, 4 x 403 MB = 1.61 GB, of which
    # the compiler places 0.51 GB where the backward pass's float32 intermediates lay before: 1.105 GB more
    cell_step=(4, 2, 6.68),
)


# ------------------------------------------------------------------- the family's own

def test_the_reference_walks_the_batch_a_sequence_at_a_time():
    """The batch is walked a sequence at a time (`lax.map`): a sequence's numbers do not
    depend on its neighbours, and a selection is cut to each."""
    p, t = params(CFG, FAMILY.unsettle), tokens(CFG, (3, 41))
    losses = jax.jit(lambda p, t, selection: ref.position_losses(p, t, model_of(CFG), jnp.float32, selection))
    assert jax.eval_shape(lambda: ref.next_token_losses(p, t, model_of(CFG))).shape == (3, 40)
    whole, _, routings = losses(p, t, None)
    alone, _, r1 = losses(p, t[1:2], None)
    np.testing.assert_allclose(whole[1:2], alone, rtol=1e-6)
    np.testing.assert_array_equal(routings[2]["own"][1:2], r1[2]["own"])
    # every token to the layer's own choice rolled by one expert: another loss, the same margins
    rolled = [(r["own"] + 1) % CFG.n_experts for r in routings]
    other, _, again = losses(p, t, rolled)
    assert float(jnp.abs(other - whole).max()) > 1e-3
    np.testing.assert_array_equal(again[0]["chosen"], rolled[0])
    np.testing.assert_array_equal(again[0]["own"], routings[0]["own"])  # the first layer's own choice stands


def test_the_mixer_is_refused_through_the_block_and_the_pattern_names_it():
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


def test_the_step_trains_every_stack_and_the_head_is_tied(first_step):
    import optax

    state, after, metrics, _, _ = first_step
    assert set(state.params) == {"embed", "sconv_layers", "mlp_layers", "attn_layers", "layers", "final_norm"}
    assert state.params["sconv_layers"]["sconv_in"].shape == (4, 64, 3, 64)
    assert np.asarray(metrics["expert_load"]).shape == (4, 16)
    assert float(metrics["loss"]) < 2 * np.log(256)
    mu = optax.tree_utils.tree_get(after.opt_state, "mu")  # (the schedule's first rate is 0: read the moments)
    assert all(np.abs(np.asarray(a)).max() > 0 for name in ("embed", "sconv_layers", "attn_layers", "mlp_layers")
               for a in jax.tree.leaves(mu[name]))
    assert not config_from({**HF_BASE, "tie_word_embeddings": False}).tie_embeddings
