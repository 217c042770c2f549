"""The xing4_0 family (Xing4.0-29B-A4B) on the training path, at a small size on the CPU with seeded weights:
glm4_moe_lite's block (rotated latent attention with a q latent, a leading dense layer, sigmoid-routed experts
beside a shared one) with v heads narrower than q's and k's, under YaRN, inside a residual stream of n copies
that every part reads and writes through a manifold-constrained hyper-connection (models/hyper.py); and the share
of a layer's heads and experts a chip holds. The contract is tests/family_contract.py's; here is what the family
alone has. (The flash kernels at q/k 192 beside v 128: tests/test_flash_attention.py, tests/test_flash_backward.py;
the dropless layer: tests/test_expert_layer.py.) The cell's whole step, compiled for the described v5e, is a shape of
its own (`-m slow`): what it holds of the stream's layouts a layer of it holds in tier-1, below."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from compiled_step_text import _lines_by_fusion, instructions, kernel_calls, xla_remats
from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import Family, as_batch, config_from, model_of, params, seeded, system, tokens
from ray_tpu.models import attn, get_config, hyper, llama, moe
from ray_tpu.models.reference import glm4_moe_lite as plain_ref
from ray_tpu.models.reference import xing4_0 as ref
from test_attention_scopes import _equations

del test_a_family_cells_step_scores_once_a_layer_and_fits_as_before  # noqa: F821  (this cell's whole step is a shape of its own: below)

CFG = get_config("xing-tiny")


# ------------------------------------------------------------------- the shares

def _block_on(cfg):
    """fn(X, lp) -> the stream behind the parts `lp` holds (llama._block runs the parts whose leaves it is handed)."""
    return jax.jit(lambda X, lp: llama._block(X, lp, cfg, jnp.arange(X.shape[1])[None], None)[0])


def _stream(x, cfg):
    return jax.random.normal(jax.random.PRNGKey(7), (*x.shape[:2], cfg.hc_mult * cfg.d_model))


def _as_copies(X, cfg):
    return X.reshape(*X.shape[:2], cfg.hc_mult, cfg.d_model)


def _8_head_shares(x):
    """Eight chips hold one head each of the same attention part: what every chip computes alike (the stream's own
    term, Hres X: a share whose output product is nothing gives exactly that) counted once, and each share's part
    beyond it (Hpost times its head's output), add up to the uncut reference's part."""
    whole = dataclasses.replace(CFG, n_heads=8, n_kv_heads=8)
    share = dataclasses.replace(whole, attn_heads_held=(1, 1))
    layer = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), whole)["dense_layers"])
    lp = {leaf: a for leaf, a in layer.items() if leaf in attn.AXES or leaf.startswith("attn_hc")}
    X = _stream(x, whole)
    model = model_of(whole)
    want, _ = ref.hyper_connection(_as_copies(X, whole), lambda y: (ref.attention_part(y, lp, model), None), lp, "attn", model)
    block = _block_on(share)
    mine = lambda i: {**lp, "wq_b": lp["wq_b"][:, i:i + 1], "wkv_b": lp["wkv_b"][:, i:i + 1], "wo": lp["wo"][i:i + 1]}  # noqa: E731
    once = block(X, {**mine(0), "wo": jnp.zeros_like(lp["wo"][:1])})
    parts = [once] + [block(X, mine(i)) - once for i in range(8)]
    return want.reshape(X.shape), parts, 1


def _8_expert_shares(x):
    """Eight chips hold one expert each of the same expert part: the stream's own term and the shared expert
    (a share whose routed experts give nothing) counted once, and each share's routed part, add up to the uncut
    reference's part."""
    layer = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), CFG)["layers"])
    lp = {leaf: a for leaf, a in layer.items() if leaf not in attn.AXES and not leaf.startswith("attn_hc")}
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (CFG.n_experts,))
    X, model = _stream(x, CFG), model_of(CFG)

    def feed_forward(y):
        return ref.expert_layer(ref._rms_norm(y, lp["mlp_norm"], CFG.norm_eps), lp, model)

    want, _ = ref.hyper_connection(_as_copies(X, CFG), feed_forward, lp, "mlp", model)
    block = _block_on(dataclasses.replace(CFG, experts_held=(0, 8)))  # (which share is the leaves': the count is the program's)
    blocks = {i: _block_on(dataclasses.replace(CFG, experts_held=(i, 8))) for i in range(8)}
    mine = lambda i: {**lp, **{n: lp[n][i:i + 1] for n in moe.mlp_leaves(CFG)}}  # noqa: E731
    once = block(X, {**mine(0), "w_down": jnp.zeros_like(lp["w_down"][:1])})
    parts = [once] + [blocks[i](X, mine(i)) - once for i in range(8)]
    return want.reshape(X.shape), parts, 1


# ------------------------------------------------------------------- the configuration

PAIRS = {  # published key -> ModelConfig field
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "moe_intermediate_size": "d_ff_expert",
    "n_shared_experts": "n_shared_experts", "first_k_dense_replace": "n_dense_layers",
    "routed_scaling_factor": "moe_route_scale", "num_nextn_predict_layers": "mtp_depth",
    "num_experts_per_tok": "moe_top_k", "hc_mult": "hc_mult", "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "hc_eps": "hc_eps", "mhc_h_res_clamp_max": "hc_res_clamp",
}
YARN = {"factor": "rope_factor", "original_max_position_embeddings": "rope_original_len", "beta_fast": "rope_beta_fast",
        "beta_slow": "rope_beta_slow", "mscale": "rope_mscale", "mscale_all_dim": "rope_mscale_all_dim"}


def _config_file(config, cfg, config_from):
    # the published widths, every one; the heads as published in the file and the share under `program`, as nemotron's
    assert (cfg.d_model, cfg.d_ff, cfg.d_ff_expert, cfg.head_dim, cfg.v_dim, cfg.q_lora_rank, cfg.kv_lora_rank) == (
        3584, 9216, 1024, 192, 128, 768, 512)
    assert config["num_attention_heads"] == cfg.n_heads == 32 and cfg.attn_heads_held == (4, 4)
    assert cfg.n_experts == config["published"]["n_routed_experts"] == 64 and cfg.n_experts_held == config["n_routed_experts"] == 8
    assert config["published"]["vocab_size"] // 8 == cfg.vocab_size == 16384
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.mtp_depth) == (5, 1, 0) and config["topk_method"] == "noaux_tc"
    assert config["mhc_h_res_clamp_min"] == -config["mhc_h_res_clamp_max"]
    for theirs, field in YARN.items():  # the published group at the top level, its fields once more under `program`
        assert config["rope_scaling"][theirs] == getattr(cfg, field), theirs
    assert config["rope_scaling"]["type"] == "yarn"
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    assert len(config["assumed"]) >= 5
    # the same keys through the checkpoint reader give the uncut model of the same widths
    whole = {k: v for k, v in config.items() if k == "rope_scaling" or not isinstance(v, (dict, list))}
    whole.update(config["published"])
    hf = config_from(whole)
    for field in (*PAIRS.values(), *YARN.values()):
        if field not in ("n_dense_layers", "mtp_depth"):
            assert getattr(hf, field) == getattr(cfg, field), field
    assert (hf.n_experts, hf.experts_held, hf.attn_heads_held, hf.n_layers, hf.n_dense_layers, hf.vocab_size, hf.mtp_depth) == (
        64, (0, 1), (0, 0), 40, 2, 131072, 1)
    assert hf.moe_dropless and hf.moe_select_bias and hf.latent_attention and hf.head_dim == 192


def _published(cfg):
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.mtp_depth, cfg.n_experts, cfg.hc_mult) == (40, 2, 1, 64, 4)
    # ISSUE 62 reckoned 29.51 B: the forty layers, embedding and head. config.json states one MTP module
    # (num_nextn_predict_layers 1: an expert layer beside eh_proj and three norms, 0.771 B), which n_params counts
    mtp = cfg.n_params - dataclasses.replace(cfg, mtp_depth=0).n_params
    assert abs((cfg.n_params - mtp) / 29.51e9 - 1) < 0.001 and abs(mtp / 0.771e9 - 1) < 0.01
    layer = dataclasses.replace(cfg, mtp_depth=0, n_layers=3).n_params - dataclasses.replace(cfg, mtp_depth=0, n_layers=2).n_params
    active = cfg.n_params - mtp - 38 * (64 - 4) * 3 * 3584 * 1024  # 4 of 64 experts a token, and everything else
    assert abs(active / 4.40e9 - 1) < 0.01 and abs(layer / (40.35e6 + 64 * 11.01e6) - 1) < 0.001
    assert attn.n_params(cfg) == 28_414_720 and hyper.n_params(cfg) == 344_112  # the issue's 28.41 M and 0.344 M


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    parts = flops.layer_matmul_params(model)
    assert parts["attention_projections"] == 7_766_016  # 4 of 32 heads; the latents' down-projections whole
    assert parts["dense_mlp"] == 99_090_432 and parts["shared_experts"] == 11_010_048
    assert parts["routed_experts_expected"] == 4 / 8 * 11_010_048  # 4 x 8/64 of an expert
    hc = flops.hyper_connection_flops(model)
    assert hc == {"mix": 2 * 14336 * 24, "pre": 2 * 14336, "post": 2 * 20 * 3584}
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    assert fwd["hyper_connections"] == 10 * sum(hc.values())
    assert flops.grouped_products_flops(model, 4096) == 6 * 4096 * 11_010_048
    assert flops.causal_attention_flops(model, 8192, 1) == 4 * (192 + 128) * 8192 * 8193
    work = flops.scan_step_work(model, 8192)
    stream, act = 2 * 8192 * 14336, 2 * 8192 * 3584
    assert work["bytes"] == 5 * (20 * stream + 13 * act) + 4 * stream  # ~28.2 GB: 34.5 ms at 819 GB/s
    assert work["bytes"] / 819e9 > 20 * work["flops"] / 197e12  # the bytes bound it
    cores = flops.attention_step_work(model, 8192, 8192)
    assert cores["flops"] == 5 * 2 * 8193 / 2 * (4 * 192 + 3 * 128) * 4 * 8192


def _made_up(flops, config, model):
    trace = {"op_seconds": {"%fusion.7 = bf16[1,8192,14336] fusion(": 0.2, "%fusion.8 = f32[4,4,8192] fusion(": 0.05,
                            "%fusion.1 = bf16[8192,3584] fusion(": 0.75}}
    result = {"series": {"step_s": [0.3, 0.32, 0.34], "held_assignments": [[4096.0] * 4, [4000.0] * 4],
                         "fullest_held_expert_rows": [[1024.0] * 4, [512.0] * 4]},
              "tokens_per_step": 8192, "seq": 8192, "chips": 1, "traced_steps": 5,
              "device": {"kind": "TPU v5 lite"}, "trace": trace}
    traced = {"result": {**result, "trace": {**trace, "busy_s": 2.0, "op_scopes": {
        "%fusion.7 = bf16[1,8192,14336] fusion(": ["closed_call", "mlp", "hc", "hc_post"],
        "%fusion.8 = f32[4,4,8192] fusion(": ["attn", "hc", "hc_sinkhorn"],
        "%fusion.1 = bf16[8192,3584] fusion(": ["closed_call", "mlp", "moe_shared"]}}}}
    unjoined = {"result": {**result, "trace": {**trace, "busy_s": 2.0}}}  # a driver that made no join
    bare = {"result": {**result, "series": {"step_s": [0.3]}}, "config": {"trainer": {}}, "model": {}}
    need = flops.scan_step_work(model, 8192)["bytes"] / 819e9
    return result, [
        ("train_mfu_family", "train_mfu_mhc_mla_moe_pct", {}, 100 * flops.train_flops_per_token(model, 8192) * 8192 / 0.32 / 197e12),
        ("trace_scope_share", "train_hc_pct", traced, 12.5),
        ("trace_scope_share", "train_hc_sinkhorn_pct", traced, 2.5),
        ("train_scan_roofline", "train_hc_roofline_pct", traced, 100 * 5 * need / 0.25),
        ("counter_rows_imbalance", "train_moe_imbalance", {}, (2.0 + 1.024) / 2),
        ("trace_scope_share", "train_hc_pct", unjoined, None), ("train_scan_roofline", "train_hc_roofline_pct", unjoined, None),
        ("trace_scope_share", {"pattern": "^kda_"}, traced, None),
        ("train_mfu_family", {}, bare, None), ("train_scan_roofline", {"scope": "hc"}, bare, None)]


FAMILY = Family(
    model_type="xing4_0", tiny=CFG, cell="xing4-train-tp8ep8share-s8192", config="xing4.0-29b-a4b-train-tp8-ep8",
    index=10, unsettle=(),
    cases=(("n4-all-held", CFG, 1),
           ("n2-heads-and-experts-held", dataclasses.replace(CFG, hc_mult=2, attn_heads_held=(2, 2), experts_held=(1, 2)), 1)),
    batch=2, least_leaves=36, float32_leaves=frozenset({"attn_hc", "mlp_hc"}), recurrent=None,
    shares={"8_head_shares": _8_head_shares, "8_expert_shares": _8_expert_shares},
    scopes=frozenset({"moe_router", "moe_experts", "moe_shared", "attn", "mlp", "embed", "lm_head", "attn_in_proj", "attn_core",
                      "attn_out_proj", "moe_dispatch", "moe_combine", "layer_stack", hyper.SCOPE, *hyper.SCOPES}),
    mixer_scopes=frozenset({"mla_q", "mla_kv"}), outer=frozenset({"attn"}), absent=frozenset({"attn_head_norm", "attn_gate", "mtp"}),
    rehearsal=("3000000001", 30, frozenset({"loss", "ce_loss"}), 2 * 64),
    pairs=PAIRS, cell_params=656.1e6, config_file=_config_file, published_params=30.28e9, published=_published,
    hf_base=dict(model_type="xing4_0", vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4,
                 intermediate_size=160, q_lora_rank=48, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8,
                 v_head_dim=16, n_routed_experts=8, num_experts_per_tok=2, moe_intermediate_size=48, n_shared_experts=1,
                 first_k_dense_replace=1, topk_method="noaux_tc", scoring_func="sigmoid", routed_scaling_factor=2,
                 num_nextn_predict_layers=0, rope_theta=1e4, rms_norm_eps=1e-6, hc_mult=4, hc_sinkhorn_iters=20, hc_eps=1e-6,
                 mhc_h_res_clamp_min=-30, mhc_h_res_clamp_max=30,
                 rope_scaling={"type": "yarn", "factor": 8, "original_max_position_embeddings": 32, "beta_fast": 4,
                               "beta_slow": 0.5, "mscale": 1, "mscale_all_dim": 1}),
    hf_to_tiny=dict(name="xing-tiny", max_seq_len=128, dtype="float32"),
    hf_refused=((dict(n_group=2), None), (dict(norm_topk_prob=False), None),
                (dict(rope_scaling={"type": "linear", "factor": 4.0}), "rope_scaling"),
                (dict(rope_scaling={"type": "yarn", "factor": 4.0}), "original_max_position_embeddings"),
                (dict(mhc_h_res_clamp_min=-10), "not symmetric"), (dict(moe_layer_freq=2), "moe_layer_freq")),
    llm_refuses=("paged cache of latents", "more than one kind", "dropless", "hc_mult copies in the decode window"),
    flops_parts=frozenset({"dense_layers", "expert_layers", "hyper_connections", "head"}), step_flops=14.457e12,
    flops_share=_flops_share, made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct", "train_scoped_pct",
        "train_attn_proj_pct", "train_attn_core_pct", "train_moe_dispatch_pct", "train_moe_combine_pct",
        "train_layer_stack_pct", "train_mla_proj_pct", "train_attn_mla_roofline_pct", "train_mlp_pct",
        "train_hc_pct", "train_hc_sinkhorn_pct", "train_hc_roofline_pct", "train_mfu_mhc_mla_moe_pct"}),
    own_metrics=("train_hc_pct", "train_hc_sinkhorn_pct", "train_hc_roofline_pct", "train_mfu_mhc_mla_moe_pct"),
    cell_step=None,  # (a shape of its own: `test_the_xing_cells_step_...` below)
)


# ------------------------------------------------------------------- the family's own

def test_hres_is_doubly_stochastic_within_the_rounds_reach_and_the_steps_metrics_say_so(first_step):
    """Twenty rounds from exp of logits with a standard deviation of ~0.7: rows sum to 1 to a rounding (the last
    division is the rows'), columns to a few 1e-6; the program's positions-minor projection is the reference's."""
    logits = 0.7 * jax.random.normal(jax.random.PRNGKey(0), (4, 4, 512))
    res = hyper.sinkhorn(logits, CFG)
    np.testing.assert_allclose(res.sum(1), 1.0, atol=2e-6)
    np.testing.assert_allclose(res.sum(0), 1.0, atol=2e-5)
    assert float(res.min()) > 0
    theirs = ref.sinkhorn(jnp.exp(jnp.moveaxis(logits, -1, 0)), model_of(CFG))
    np.testing.assert_allclose(jnp.moveaxis(res, -1, 0), theirs, rtol=1e-6)
    few = dataclasses.replace(CFG, hc_sinkhorn_iters=2)  # a projection that stopped short shows in the column sums
    assert float(jnp.abs(hyper.sinkhorn(logits, few).sum(0) - 1).max()) > 1e-3
    _, _, m, _, _ = first_step
    assert 0 <= float(m["hc_res_row_err"]) < 5e-6 and 0 < float(m["hc_res_col_err"]) < 1e-4


def test_yarns_range_and_scale_at_the_published_keys():
    """d = 64, theta 1e4, 4,096 original positions, beta 32 and 1: the pairs 10 to 23 are blended; the softmax scale
    is 192^(-1/2) x (1 + 0.1 ln 64)^2 = 192^(-1/2) x 2.0047; cos and sin keep their size (mscale = mscale_all_dim)."""
    cfg = FAMILY.cell_config()[2]
    assert attn.yarn_range(cfg, 64) == ref.yarn_range(model_of(cfg), 64) == (10, 23)
    assert attn.softmax_scale(cfg) == pytest.approx(192 ** -0.5 * 2.0047, rel=1e-4)
    assert attn.softmax_scale(cfg) == pytest.approx(ref.softmax_scale(model_of(cfg)), rel=1e-12)
    freqs, by = attn.yarn(cfg, 64)
    own = 1e4 ** (-np.arange(32) / 32)
    assert by == 1.0 and np.allclose(freqs[:11], own[:11]) and np.allclose(freqs[23:], own[23:] / 64, rtol=1e-6)
    assert np.all(np.diff(freqs) < 0) and own[16] / 64 < freqs[16] < own[16]
    assert attn.yarn(dataclasses.replace(cfg, rope_factor=1.0), 64) is None and attn.softmax_scale(dataclasses.replace(cfg, rope_factor=1.0)) is None
    other = dataclasses.replace(cfg, rope_mscale=0.707, rope_mscale_all_dim=1.0)  # cos and sin scaled where the two differ
    assert attn.yarn(other, 64)[1] == pytest.approx((1 + 0.0707 * np.log(64)) / (1 + 0.1 * np.log(64)))


def test_one_stream_is_the_plain_frame():
    """hc_mult 1 is every other family's program: no leaf of a hyper-connection, no metric of one, and the loss and
    every gradient of glm4_moe_lite's reference (which has neither the streams nor, with rope_factor 1, YaRN)."""
    cfg = dataclasses.replace(CFG, hc_mult=1, rope_factor=1.0)
    p, t = params(cfg), tokens(cfg)
    assert not [leaf for stack in ("dense_layers", "layers") for leaf in p[stack] if "hc" in leaf]
    (loss, m), grads = system(p, t, cfg)
    assert not [name for name in m if name.startswith("hc_")]
    want, r_grads = jax.jit(jax.value_and_grad(lambda p, t: plain_ref.loss(p, t, model_of(cfg))))(p, t)
    np.testing.assert_allclose(loss, want, rtol=1e-6)
    leaves_match(grads, r_grads, least=30)  # noqa: F405
    assert llama.n_params(CFG) - llama.n_params(dataclasses.replace(CFG, hc_mult=1)) == 2 * 3 * hyper.n_params(CFG)


def test_what_n_streams_cannot_do_yet_is_refused_by_name():
    p, t = params(CFG), tokens(CFG, (2, 33))
    hidden = jnp.zeros((2, 32, CFG.hc_mult * CFG.d_model))
    with pytest.raises(NotImplementedError, match="MTP modules over a stream of hc_mult"):
        llama.mtp_logits(p, hidden, t, dataclasses.replace(CFG, mtp_depth=1))
    for field, what in ((dict(pipeline_stages=3), "across pipeline stages"), (dict(loop_steps=2, n_experts=0, n_dense_layers=0), "looped"),
                        (dict(layer_pattern="*-E", n_dense_layers=0), "pattern of single-part layers"),
                        (dict(hc_mult=0), "fewer than one"), (dict(hc_sinkhorn_iters=0), "no round")):
        with pytest.raises(NotImplementedError, match=what):
            dataclasses.replace(CFG, **field)
    # (what it CAN do beside the family's own: n streams around dense layers alone, the projections' error beside the loss)
    dense = dataclasses.replace(CFG, n_experts=0, n_dense_layers=0, n_shared_experts=0, moe_select_bias=False, n_layers=2)
    shapes = jax.eval_shape(lambda: llama.loss_fn(llama.init(jax.random.PRNGKey(0), dense), {"tokens": t}, dense)[1])
    assert {"hc_res_row_err", "hc_res_col_err", "moe_aux_loss", "ce_loss"} <= set(shapes) and "expert_load" not in shapes
    for field in (dict(kv_lora_rank=0), dict(attention_rotation=False), dict(rope_original_len=0), dict(attention_impl="ring")):
        with pytest.raises(NotImplementedError, match="YaRN"):
            dataclasses.replace(CFG, hc_mult=1, **field)


def test_yarn_stays_refused_for_the_family_whose_block_this_one_shares():
    """xing4_0's mapping hands its keys to glm4_moe_lite's with `rope_scaling` taken out: glm4_moe_lite's own still
    refuses a config.json that states one, with its phrase."""
    hf = {**FAMILY.hf_base, "model_type": "glm4_moe_lite"}
    with pytest.raises(ValueError, match="rope_scaling .* is not supported"):
        config_from(hf)
    assert config_from({**hf, "rope_scaling": None}).rope_factor == 1.0


HC_KERNELS = {"hc_read_fwd": "hc_pre", "hc_read_bwd": "hc_pre", "hc_write_fwd": "hc_post", "hc_write_bwd": "hc_post"}  # a kernel's name -> the scope it runs under: that of what it writes


@pytest.mark.parametrize("path", ["plain", "kernels"])
def test_every_equation_of_a_hyper_connection_carries_one_of_its_four_names_inside_its_parts(path):
    """The jaxpr of the loss's gradient, as tests/test_attention_scopes.py reads it: whatever runs under `hc` carries
    exactly one of hyper.SCOPES and lies inside `attn`, `mlp`, `embed` or `lm_head`, forward, made again and backward;
    the rotation under YaRN stays under `mla_q` / `mla_kv`; nothing of an attention part's five pieces is under `hc`.
    By both paths: the tiny configuration's 64 channels tile no kernel (`plain`: XLA's passes); at 128 channels and
    128 positions the four kernels of ops/hyper_mix.py run (`kernels`), the entry's under `hc_pre`, the writing's under
    `hc_post`, forward, made again and backward (the transpose carries the forward's names to the backward kernels)."""
    cfg, positions = (CFG, 32) if path == "plain" else (dataclasses.replace(CFG, d_model=128), 128)
    p = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    batch = {"tokens": jax.ShapeDtypeStruct((2, positions + 1), jnp.int32)}

    def loss(p, batch):
        with jax.named_scope("model"):  # as train/step.py
            return llama.loss_fn(p, batch, cfg)[0]

    equations = list(_equations(jax.make_jaxpr(jax.grad(loss))(p, batch).jaxpr))
    under = [(prim, names) for prim, names in equations if hyper.SCOPE in names]
    assert len(under) > 500
    calls = [names for prim, names in under if prim == "pallas_call"]
    assert (path == "kernels") == bool(calls)
    for kernel, scope in HC_KERNELS.items() if calls else ():
        mine = [names for names in calls if names[-1] == kernel]
        again = sum("rematted_computation" in names for names in mine)
        # two stacks (the dense layer's, the expert layers'), two parts each; a layer's last writing is not made again
        assert (len(mine) - again, again) == {"hc_read_fwd": (4, 4), "hc_write_fwd": (4, 2)}.get(kernel, (4, 0)), kernel
        assert all(scope in names and ("transpose" in names) == (kernel.endswith("bwd") or "rematted_computation" in names)
                   for names in mine), kernel
    assert not [e for e in under if len({n for n in e[1] if n in hyper.SCOPES}) != 1][:5]
    assert not [e for e in under if not {"attn", "mlp", "embed", "lm_head"} & set(e[1])][:5]
    assert not [e for e in under if set(e[1]) & set(attn.SCOPES)][:5]
    passes = {"forward": lambda names: "transpose" not in names, "again": lambda names: "rematted_computation" in names,
              "backward": lambda names: "transpose" in names and "rematted_computation" not in names}
    for which, holds in passes.items():
        for part in hyper.PARTS:
            found = {n for _, names in under if holds(names) and part in names for n in names if n in hyper.SCOPES}
            # (by the kernels a layer's last writing is not made again: its backward kernel reads x, o and the
            # coefficients, not what it wrote)
            spared = {"hc_post"} if (path, which, part) == ("kernels", "again", "mlp") else set()
            assert found == set(hyper.SCOPES) - spared, (which, part, found)
    assert {n for _, names in under if "embed" in names for n in names if n in hyper.SCOPES} == {"hc_pre"}
    assert {n for _, names in under if "lm_head" in names for n in names if n in hyper.SCOPES} == {"hc_post"}
    # the mixtures are sums, not products: no product under `hc` outside `hc_mix` or a kernel (the entry's make the
    # coefficient product and its two transposes themselves)
    products = [names for prim, names in under if prim == "dot_general"]
    assert products and all("hc_mix" in names or {"hc_read_fwd", "hc_read_bwd"} & set(names) for names in products)
    assert any("hc_mix" in names for names in products) == (path == "plain")
    turned = [names for prim, names in equations if prim in ("cos", "sin")]
    assert turned and all({"mla_q", "mla_kv"} & set(names) and "attn_in_proj" in names for names in turned)
    # every attention equation outside `hc` still carries exactly one of the part's five names
    # (but the turns [B, T, C] <-> [B, C, T] of y and of the part's output around the kernels, which `hyper.enter` and
    # `hyper.write` make outside `hc` so that the fusions that take them in stay the part's: changes of names, no passes)
    rest = [(prim, names) for prim, names in equations if "attn" in names and hyper.SCOPE not in names
            and not (path == "kernels" and prim == "transpose")]
    assert rest and not [e for e in rest if len({n for n in e[1] if n in attn.SCOPES}) != 1][:5]


# ------------------------------------------------------------------- the cell's whole step, compiled for the chip

STREAM = r"(?:1,)?8192,14336"
MINOR = r"(?:1,)?(?:14336|4,3584),8192"  # the stream as ops/hyper_mix.py's kernels take it, the positions minor


def _stored(text, pattern):
    """Results of the compiled text's instructions, fused computations' insides left out, whose shape matches."""
    made = re.compile(rf"\s*(?:ROOT )?%[\w.\-]+ = \(?{pattern}")
    return [ln[:160] for ln, in_fusion in _lines_by_fusion(text) if not in_fusion and made.match(ln)]


def _holds_the_streams_layouts(text):
    """The stream is flat bfloat16 [1, 8192, 4 x 3584] and nothing float32 of its size is stored (the coefficient
    product's cotangent leaves its product in the stream's type: `hyper._product`); no array has the copies or the 4 x 4
    as its minor extents behind the positions (a bfloat16 [.., 8192, 4, 3584] tiles its 4 up to 16, a float32
    [.., 8192, 4, 4] pads 64-fold): the projection's rounds are [4, 4, 8192]."""
    assert _stored(text, rf"bf16\[{STREAM}\]") and not _stored(text, rf"f32\[(?:{STREAM}|{MINOR})\]")
    assert not _stored(text, r"\w+\[(?:\d+,)*8192,4,(?:4|3584)\]")
    assert re.search(r"f32\[4,4,8192\]", text)


HC_CALLS = {"hc_read_fwd": (2, 2), "hc_read_bwd": (2, 0), "hc_write_fwd": (2, 1), "hc_write_bwd": (2, 0)}  # (not again, again) a rematerialised layer


def _passes_of_their_own(text):
    """The `copy` and `transpose` instructions that store an array of the stream's extents, in whichever grouping, in a
    pass over HBM of their own (outside fused computations): what a kernel that took the stream in another layout than
    the step holds it in would cost, 0.73 ms a call (PERF.md section 6, PRs 61 and 63)."""
    made = re.compile(rf"\s*(?:ROOT )?%[\w.\-]+ = \w+\[(?:{STREAM}|{MINOR})\]\S* (?:copy|transpose)\(")
    return [ln[:200] for ln, in_fusion in _lines_by_fusion(text) if not in_fusion and made.match(ln)]


def test_a_rematerialised_layer_of_the_cell_stores_no_float32_stream_and_keeps_positions_minor(family, one_chip, on_tpu):
    """One expert layer of the cell under remat `full`, value and gradients, compiled for the described v5e (~60 s):
    what the whole step (`-m slow`, below) holds of the stream's layouts, in tier-1. The layer takes the stream and
    hands back its cotangent the POSITIONS MINOR, as the step's loop over the layers carries them (a program's own
    parameters and results are row-major, which the loop's carry is not): the four kernels of ops/hyper_mix.py run,
    twice each and the forward ones again as the layer is made again (its last writing apart), and no array of the
    stream's extents is copied or transposed in a pass of its own anywhere in the layer."""
    from compiled_step_text import shapes

    _, _, cfg = family.cell_config()
    lp = shapes(jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape[1:], a.dtype),
                             jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))["layers"]), one_chip)
    x = jax.ShapeDtypeStruct((1, cfg.hc_mult * cfg.d_model, 8192), jnp.bfloat16, sharding=one_chip)
    layer = llama._maybe_remat(lambda x, lp: llama._block(x, lp, cfg, jnp.arange(8192)[None], None)[0], cfg)
    loss = lambda x, lp: jnp.sum(jnp.square(layer(x.transpose(0, 2, 1), lp).astype(jnp.float32)))  # noqa: E731
    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, lp).compile().as_text()
    _holds_the_streams_layouts(text)
    assert kernel_calls(text, "flash_attention_fwd") == (1, 0) and not xla_remats(text)
    assert {name: kernel_calls(text, name) for name in HC_CALLS} == HC_CALLS
    assert not _passes_of_their_own(text)


@pytest.mark.slow  # (the whole step, ~2 min of TPU compile: `-m slow -k cells_step`; tier-1 holds a layer of it to the same layouts. ROADMAP.md C13)
def test_the_xing_cells_step_holds_the_streams_layouts_and_fits_the_chip(family, cell_step):
    """The whole step of the cell, compiled for the described v5e. Two bodies (the dense stack's layer, the expert
    stack's): a flash call forward and one backward each, three router products; the hyper-connections' four kernels
    twice a body and the forward ones again where the layer is made again (20 / 10 / 15 / 10 calls a step over one
    dense and four expert layers), and NO array of the stream's extents copied or transposed in a pass of its own (the
    parent's step had two, at the head's end; the ends are made and summed the positions minor since PR 63).

    Memory: `memory_analysis()` reads 7.87 + 9.19 = 17.06 GB (9.62 before the kernels, PR 62), which is NOT what the
    chip holds: the compiler's own buffer assignment of PR 62's program totalled 14.43 GB (preallocated temporaries
    6.41 GB), and on the chip the allocator read 8.09 GB in use beside 6.42 GB reserved = 14.50 of 16.91 GB (15.75 GiB;
    my chip run, PR 62). `temp_size_in_bytes` is held to what it read, as the other cells' records hold theirs: it moves
    when the program's working set does."""
    cfg, text, memory = cell_step.cfg, cell_step.text, cell_step.memory
    assert cfg.remat and cfg.remat_policy == "full" and cfg.hc_mult == 4 and cell_step.trainer["mesh"] is None
    _holds_the_streams_layouts(text)
    bodies = 2
    # 20 / 10 / 15 / 10 calls a step over the two bodies (one dense layer, four expert layers)
    assert {name: kernel_calls(text, name) for name in HC_CALLS} == {name: (bodies * a, bodies * b) for name, (a, b) in HC_CALLS.items()}
    assert kernel_calls(text, "flash_attention_fwd") == (bodies, 0) and kernel_calls(text, "flash_attention_bwd_dkv_dq") == (bodies, 0)
    assert len(instructions(text, "convolution", "moe_router")) == 3 and not instructions(text, "while", "moe_router")
    assert not xla_remats(text) and cell_step.fallbacks == 0
    assert abs(memory.argument_size_in_bytes - 12 * cfg.n_params) < 1e7
    assert memory.temp_size_in_bytes < (9.19 + 0.15) * 1e9
    assert not _passes_of_their_own(text)
    assert family.cell_step is None


def test_a_part_holds_phi_the_bias_and_the_alphas_as_one_leaf(family):
    """Every layer holds ONE leaf a part, [layers, n C + 2, 2n + n^2] = [phi ; b ; alpha_pre alpha_post alpha_res 0 ..]:
    a leaf of 27 numbers was a row of its own in the chip's comparison of gradients, a row a leaf and layer, and read
    1.2 to 4.7 x the bfloat16 reference's error over seven seeds (PERF.md section 6, PR 62). What lies behind the three
    alphas is never read: no gradient reaches it."""
    _, _, cfg = family.cell_config()
    made = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    for stack, layers in (("dense_layers", 1), ("layers", 4)):
        for part in hyper.PARTS:
            assert made[stack][f"{part}_hc"].shape == (layers, 4 * 3584 + 2, 24)
    assert made["layers"]["wq_b"].shape == (4, 768, 4, 192) and made["layers"]["wkv_b"].shape == (4, 512, 4, 256)
    assert made["layers"]["wq_a"].shape == (4, 3584, 768) and made["layers"]["w_gate"].shape == (4, 8, 3584, 1024)
    leaf = seeded(CFG)["layers"]["mlp_hc"][0]
    phi, b, alpha = hyper.parts_of(leaf)
    assert phi.shape == (4 * 64, 24) and b.shape == (24,) and np.allclose(alpha, 0.5) and not np.asarray(leaf[-1, 3:]).any()
    assert 0.3 < float(b.std()) < 0.8 and abs(float(phi.std()) * 16 - 1) < 0.1  # live: the logits' spread is ~0.5 about biases that differ
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 8, 4 * 64))
    g = np.asarray(jax.grad(lambda hc: jnp.sum(jnp.sin(3 * hyper.coefficients(x, hc, CFG)[0])))(leaf))
    assert np.abs(g[-1, :3]).min() > 0 and not g[-1, 3:].any() and np.abs(g[-2]).min() > 0 and np.abs(g[:-2]).max() > 0
    assert as_batch(jnp.zeros((1, 2), jnp.int32)).keys() == {"tokens"}
