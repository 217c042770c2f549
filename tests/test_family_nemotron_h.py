"""The nemotron_h family (Nemotron-3-Super) on the training path, at a small size on the
CPU with seeded weights: a pattern of single-part layers (Mamba-2 mixers, attention without
rotation, latent relu2 experts routed many a token beside a shared one, the dense relu2
MLP), the MTP module, and the shares of a layer's heads and experts a chip holds. The
contract is tests/family_contract.py's; here is what the family alone has. (The chunked
scan itself: tests/test_ssd_scan.py; the expert layer at 22 of 512: tests/test_expert_layer.py.)"""
import dataclasses
import json
import os

import jax
import numpy as np

from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import Family, expert_shares, head_shares, model_of
from ray_tpu.models import get_config, moe, ssm
from ray_tpu.models.reference import nemotron_h as ref

CFG = get_config("nemotron-tiny")


def _pattern(pattern, mtp, held):
    return dataclasses.replace(CFG, layer_pattern=pattern, n_layers=len(pattern), mtp_depth=mtp,
                               mtp_layer_pattern="*E" if mtp else "", experts_held=held)


# ------------------------------------------------------------------- the shares

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


def _mamba_8_head_shares(x):
    whole = dataclasses.replace(CFG, ssm_n_heads=16, ssm_n_groups=8)
    share = dataclasses.replace(whole, ssm_n_heads=2, ssm_n_groups=1)
    lp = ssm.init(jax.random.PRNGKey(3), whole)
    lp["conv_b"] = 0.1 * jax.random.normal(jax.random.PRNGKey(4), lp["conv_b"].shape)
    lp["gate_norm"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), lp["gate_norm"].shape)
    want = ref.mamba_layer(x, lp, model_of(whole)) - x
    mixer = jax.jit(lambda x, mine: ssm.mixer(x, mine, share))  # eight shares, one program
    return want, [mixer(x, _mamba_share(lp, whole, i, 8)) for i in range(8)], 1  # (the mixer's output: `_block` adds it to x)


def _attention_8_head_shares(x):
    whole = dataclasses.replace(CFG, n_heads=8, n_kv_heads=2, layer_pattern="*", n_layers=1, mtp_depth=0,
                                mtp_layer_pattern="")
    _, want, parts = head_shares(ref, whole, x)
    return want, parts, 1


def _64_expert_shares(x):
    whole = dataclasses.replace(CFG, n_experts=64, moe_top_k=5)
    lp = moe.init_expert_weights(jax.random.PRNGKey(3), whole)
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (64,))
    want, _, parts, _ = expert_shares(ref, whole, 64, x, lp)
    return want, parts, 1


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


def _config_file(config, cfg, config_from):
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
    assert not cfg.attention_rotation and cfg.mlp_activation == "relu2"
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
    hf = config_from(whole)
    for published_key, field in PAIRS.items():
        if published_key not in published:
            assert getattr(hf, field) == getattr(cfg, field), field
    assert (hf.n_layers, hf.ssm_n_heads, hf.ssm_n_groups, hf.n_experts, hf.experts_held, hf.mtp_depth,
            hf.attn_heads_held, hf.vocab_size) == (88, 128, 8, 512, (0, 1), 1, (0, 0), 131072)


def _published(cfg):
    # the published size, and the MTP module (an attention and an expert layer, uncut: 2.94 B)
    assert abs(dataclasses.replace(cfg, mtp_depth=0, mtp_layer_pattern="").n_params / 1e9 - 120.67) < 0.01
    assert abs(cfg.n_params / 1e9 - 120.67 - 2.942) < 0.01


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    layer = flops.layer_flops_per_token(model, (8192 + 1) / 2)
    assert layer["M"] - flops.scan_flops_per_token(model) == 2 * (4096 * 2320 + 1024 * 4096)  # 13.70 M weights
    assert layer["E"] == 2 * (4096 * 512 + 2 * 4096 * 1024 + 2 * 4096 * 5376 + 22 / 64 * flops.expert_params(model))
    assert flops.expert_params(model) == 2 * 1024 * 2688
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    assert fwd["-"] == fwd["mtp"] == 0
    total = sum(fwd.values())
    assert 0.15 < fwd["M"] / total < 0.20 and 0.60 < fwd["E"] / total < 0.70 and fwd["*"] / total < 0.04
    assert flops.grouped_products_flops(model, 2816) == 6 * 2816 * 2 * 1024 * 2688
    work = flops.scan_step_work(model, 8192)
    assert work["flops"] == 5 * 3 * 8192 * flops.scan_flops_per_token(model)
    assert work["bytes"] == 5 * 3 * 8192 * (2 * (1024 + 256) + 4 * 16 + 4 * 1024)
    # bound by what it reads and writes on a v5e: 0.12 ms a step against 0.05 ms of products
    assert work["bytes"] / 819e9 > work["flops"] / 197e12


def _made_up(flops, config, model):
    work = flops.scan_step_work(model, 8192)
    needed = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    result = {"traced_steps": 5, "tokens_per_step": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "trace": {"busy_s": 2.0, "op_seconds": {"%a": 0.04, "%b": 0.06, "%c": 1.9},
                        "op_scopes": {"%a": ["ssm_scan"], "%b": ["ssm_conv"], "%c": ["moe_experts"]}}}
    # a program without the scope (the parent of the PR that names it), a run without a
    # trace, a flops file without the function: nothing to read, and nothing raised
    bare = {"result": {**result, "trace": {**result["trace"], "op_scopes": {"%c": ["moe_experts"]}}}}
    other = {"config": {**config, "trainer": {**config["trainer"], "flops": "flops_glm4_moe_lite"}},
             "result": {**result, "trace": {**result["trace"], "op_scopes": {"%a": ["ssm_scan"]}}}}
    return result, [
        ("train_scan_roofline", "train_ssm_scan_roofline_pct", {}, 100 * 5 * needed / 0.04),
        ("trace_scope_share", "train_ssm_pct", {}, 100 * 0.10 / 2.0),
        ("train_scan_roofline", {"scope": "ssm_scan"}, bare, None),
        ("trace_scope_share", {"pattern": "^ssm_"}, bare, None),
        ("train_scan_roofline", {"scope": "ssm_scan"}, {"result": {**result, "trace": None}}, None),
        ("train_scan_roofline", {"scope": "ssm_scan"}, other, None)]


FAMILY = Family(
    model_type="nemotron_h", tiny=CFG, cell="nemotron3super-train-tp8ep64share-s8192",
    config="nemotron-3-super-train-tp8-ep64", index=3,
    unsettle=(("ssm_layers", "conv_b", 0.1, 0.0),),  # a convolution bias that is not zero
    cases=(("MEM*E--1-held0", _pattern("MEM*E-", 1, (0, 1)), 1),  # every character, the MTP module, everything held
           ("MEM*E--0-held1", _pattern("MEM*E-", 0, (1, 4)), 1),  # no MTP term; a quarter of the experts
           ("ME*ME*-1-held2", _pattern("ME*ME*", 1, (0, 2)), 2),  # two periods of ME*: the scan over periods
           ("M*--0-held3", _pattern("M*-", 0, (0, 1)), 1)),       # no expert layer at all
    batch=2, least_leaves=12, float32_leaves=frozenset({"A_log", "dt_bias", "D"}), recurrent="Mamba-2",
    shares={"mamba_8_head_shares": _mamba_8_head_shares, "attention_8_head_shares": _attention_8_head_shares,
            "64_expert_shares": _64_expert_shares},
    scopes=frozenset({"moe_latent", "moe_router", "moe_experts", "moe_shared", "attn", "mlp", "attn_in_proj", "attn_core", "attn_out_proj", "moe_dispatch", "moe_combine", "layer_stack"}),
    mixer_scopes=frozenset({"ssm_in_proj", "ssm_conv", "ssm_scan", "ssm_norm", "ssm_out_proj"}),
    outer=frozenset(), absent=frozenset({"attn_head_norm", "attn_gate"}),
    rehearsal=("3000000007", 40, frozenset({"loss", "ce_loss"}), 2 * 64),  # the cell's cut has no MTP term
    pairs=PAIRS, cell_params=700.9e6, config_file=_config_file,
    published_params=123.612e9, published=_published,
    hf_base=dict(model_type="nemotron_h", vocab_size=256, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=24, intermediate_size=96, hybrid_override_pattern="MEM*E-",
                 num_hidden_layers=6, mamba_num_heads=16, mamba_head_dim=8, expand=2, n_groups=2,
                 ssm_state_size=16, conv_kernel=4, chunk_size=8, n_routed_experts=16, num_experts_per_tok=3,
                 moe_intermediate_size=40, moe_shared_expert_intermediate_size=80, moe_latent_size=32,
                 n_shared_experts=1, routed_scaling_factor=5.0, num_nextn_predict_layers=1,
                 mtp_hybrid_override_pattern="*E", norm_eps=1e-5, max_position_embeddings=128),
    hf_to_tiny=dict(name="nemotron-tiny", dtype="float32", ssm_n_heads=8, rope_theta=CFG.rope_theta),
    hf_refused=((dict(n_group=2), "group-limited"), (dict(norm_topk_prob=False), "not normalised"),
                (dict(mlp_hidden_act="silu"), "mlp_hidden_act"), (dict(use_bias=True), "biases"),
                (dict(time_step_limit=[0.0, 1.0]), "clamp on dt"), (dict(sliding_window=4096), "window"),
                (dict(mtp_hybrid_override_pattern="ME"), "MTP module"),
                (dict(moe_latent_size=None), "full width"), (dict(mamba_num_heads=8), "expand")),
    llm_refuses=("recurrent state", "dropless", "drafts"),
    flops_parts=frozenset({"M", "*", "E", "-", "head", "mtp"}), step_flops=21.08e12, flops_share=_flops_share,
    made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_attn_fwd_kernel_pct",
        "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct", "train_moe_imbalance",
        "train_ssm_pct", "train_ssm_scan_roofline_pct", "train_mfu_ssm_moe_pct",
        # PR 35: the device's own step and the shares of the scopes it made readable
        "train_device_step_ms", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct",
        # PR 52: the attention part's pieces, the expert layer's dispatch and combine (the layer
        # loop's own is next to nothing where one period runs unrolled: not listed)
        "train_attn_proj_pct", "train_attn_core_pct", "train_moe_dispatch_pct", "train_moe_combine_pct"}),
    own_metrics=("train_ssm_pct", "train_ssm_scan_roofline_pct", "train_mfu_ssm_moe_pct"),
    # the cell's whole step (`Family.cell_step`): a period of layers, unrolled: a body each. PR 48: 3.866 -> 4.058 GB,
    # `[z | xBC | dt]` of five Mamba-2 parts kept from forward to backward, [1, 8192, 2320] bfloat16 = 38 MB a part, 0.19 GB
    cell_step=(5, 2, 4.06),
)
