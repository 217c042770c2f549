"""The kimi_linear family (Kimi-Linear-48B-A3B) on the training path, at a small size on the CPU
with seeded weights: every published layer two parts of a pattern (a mixer, then a feed-forward
part); Kimi-Delta-Attention mixers with beta in (0, 1) three to one with latent attention that
has no q latent, no rotation and v heads narrower than q's and k's, as a `*` part of the
pattern; a leading dense layer, then sigmoid-routed SwiGLU experts at 8 of 256 beside a shared
one, the gates scaled by 2.446; and the share of a layer's experts a chip holds. The contract is
tests/family_contract.py's; here is what the family alone has. (The chunked delta rule itself:
tests/test_kda_scan.py; the flash kernels at q/k 192 beside v 128: tests/test_flash_attention.py
and tests/test_flash_backward.py.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import Family, expert_shares, model_of, params
from ray_tpu.models import attn, get_config, llama, moe
from ray_tpu.models.reference import kimi_linear as ref

CFG = get_config("kimi-linear-tiny")
CATALOG_PATTERN = "K-KEKE*E" + 5 * "KEKEKE*E" + "KEKE*E"  # 27 published layers, two characters each


def _pattern(pattern, held):
    return dataclasses.replace(CFG, layer_pattern=pattern, n_layers=len(pattern), experts_held=held)


# ------------------------------------------------------------------- the shares

def _32_expert_shares(x):
    """What a chip of the deployment holds: 8 of 256 experts. 32 shares with the shared expert
    counted once add up to the uncut expert part, gates scaled by routed_scaling_factor."""
    whole = dataclasses.replace(CFG, n_experts=256, moe_top_k=8)
    lp = moe.init_expert_weights(jax.random.PRNGKey(3), whole)
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (256,))
    want, _, parts, _ = expert_shares(ref, whole, 32, x, lp)
    unscaled, _ = ref.expert_layer(x, lp, {**model_of(whole), "moe_route_scale": 1.0})
    assert float(jnp.abs(want - unscaled).max()) > 0.1 * float(jnp.abs(want).max())  # the scale scales
    return want, parts, 1


# ------------------------------------------------------------------- the configuration

def _config_file(config, cfg, config_from):
    linear = config["linear_attn_config"]
    # the published widths, every one
    assert config["head_dim"] * config["num_attention_heads"] == config["hidden_size"] == cfg.d_model == 2304
    assert (cfg.head_dim, cfg.v_dim, cfg.q_lora_rank, cfg.kv_lora_rank) == (192, 128, 0, 512)
    assert config["q_lora_rank"] is None and config["mla_use_nope"] is True and not cfg.attention_rotation
    assert (cfg.n_heads, cfg.kda_n_heads, cfg.kda_head_dim, cfg.kda_conv_taps, cfg.kda_rank, cfg.d_ff, cfg.d_ff_expert,
            cfg.shared_width, cfg.moe_top_k, cfg.n_experts, cfg.moe_route_scale, cfg.norm_eps) == (
        32, linear["num_heads"], linear["head_dim"], linear["short_conv_kernel_size"], 128, 9216, 1024, 1024, 8, 256,
        2.446, 1e-5)
    assert not cfg.kda_neg_eigval and config["moe_router_activation_func"] == "sigmoid" and config["use_grouped_topk"]
    # what is held here, and of what: the chip's share of a group that shares each layer
    published = config["published"]
    assert linear["kda_layers"] == published["linear_attn_config"]["kda_layers"][:4] == [1, 2, 3, 5]
    assert linear["full_attn_layers"] == published["linear_attn_config"]["full_attn_layers"][:1] == [4]
    assert {k: v for k, v in linear.items() if "layers" not in k} == {
        k: v for k, v in published["linear_attn_config"].items() if "layers" not in k}
    assert cfg.layer_pattern == "K-KEKE*EKE" == CATALOG_PATTERN[:10] and cfg.n_layers == 2 * config["num_hidden_layers"] == 10
    assert cfg.n_experts == published["num_experts"] and cfg.n_experts_held == config["num_experts"] == 8
    assert cfg.experts_held == (0, 32) and cfg.vocab_size == published["vocab_size"] // 8 == 20480 and cfg.mtp_depth == 0
    assert config["reduced"] == ["num_hidden_layers", "linear_attn_config", "num_experts", "vocab_size"]
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    count = lambda stack: sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes[stack]))  # noqa: E731
    assert abs(count("kda_layers") - 39.52e6) < 0.01e6 and abs(count("attn_layers") - 29.12e6) < 0.01e6
    assert abs(count("mlp_layers") - 63.70e6) < 0.01e6 and abs(count("layers") - 256 - 64.29e6) < 0.01e6
    assert set(shapes["attn_layers"]) == {"attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert shapes["attn_layers"]["wq"].shape == (1, 2304, 32, 192) and shapes["attn_layers"]["wo"].shape == (1, 32, 128, 2304)
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    assert len(config["assumed"]) >= 6
    # the program's own mapping of the published keys says the same, share apart
    hf = {k: v for k, v in config.items() if k not in ("program", "trainer", "published", "reduced")}
    hf.update(published, num_hidden_layers=5, linear_attn_config=linear)
    assert dataclasses.replace(
        config_from(hf), name=cfg.name, vocab_size=cfg.vocab_size, experts_held=(0, 32), kda_proj_rank=128,
        kda_chunk=cfg.kda_chunk, remat_policy="full", dtype="bfloat16") == cfg


def _published(cfg):
    """The catalog row's config: 54 parts, 49.12 B parameters, about 3 B of them active a token."""
    assert cfg.layer_pattern == CATALOG_PATTERN and cfg.n_layers == 54
    assert (cfg.layer_pattern.count("K"), cfg.layer_pattern.count("*"), cfg.layer_pattern.count("-")) == (20, 7, 1)
    assert abs(cfg.n_params / 49.12e9 - 1) < 0.0002 and cfg.max_seq_len == 1048576
    active = cfg.n_params - 26 * (256 - 8) * 3 * 2304 * 1024  # 8 of 256 experts a token, and everything else
    assert 2.5e9 < active < 3.6e9


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    layer = flops.layer_flops_per_token(model, (8192 + 1) / 2)
    kda_weights = 2304 * 3 * 4096 + 2 * (2304 + 4096) * 128 + 2304 * 32 + 4096 * 2304  # 39.46 M in products
    assert layer["K"] - flops.scan_flops_per_token(model) == 2 * kda_weights
    mla_weights = 2304 * 32 * 192 + 2304 * 576 + 512 * 32 * 256 + 32 * 128 * 2304  # 29.11 M: no q latent
    assert flops.attention_projections(model) == mla_weights
    assert layer["*"] == 2 * mla_weights + 2 * 32 * (192 + 128) * 4096.5  # the scores at 192, the values at 128
    assert layer["-"] == 2 * 3 * 2304 * 9216
    assert layer["E"] == 2 * (2304 * 256 + 3 * 2304 * 1024 + 8 / 32 * flops.expert_params(model))
    assert flops.expert_params(model) == 3 * 2304 * 1024
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    total = sum(fwd.values())
    assert abs(total / 773e6 - 1) < 0.001  # the issue's count, MFLOP a token forward
    for part, share in (("K", 0.43), ("*", 0.18), ("-", 0.16), ("head", 0.12), ("E", 0.10)):
        assert abs(fwd[part] / total - share) < 0.01, part
    # the scan's yardstick is the file's own chunk, whatever the program's scan runs at; all 32 heads held
    assert flops.scan_step_work({**model, "kda_chunk": 32}, 8192) == flops.scan_step_work(model, 8192)
    work = flops.scan_step_work(model, 8192)
    assert work["bytes"] == 4 * 3 * 8192 * 32 * (3 * 2 * 128 + 4 * 128 + 4 + 4 * 128)
    assert work["bytes"] / 819e9 > work["flops"] / 197e12  # bound by what it reads and writes on a v5e
    assert flops.grouped_products_flops(model, 2048) == 3 * 2 * 2048 * 3 * 2304 * 1024
    # the attention core at its two widths: forward 192 + 128 a score, backward 3 x 192 + 2 x 128
    core = flops.attention_step_work(model, 8192, 8192)
    assert core["flops"] == 2 * 4096.5 * ((192 + 128) + (3 * 192 + 2 * 128)) * 32 * 8192
    assert core["bytes"] == 2 * 8192 * 32 * (6 * 192 + 6 * 128)
    assert core["flops"] / 197e12 > core["bytes"] / 819e9  # bound by its products
    padded = 2 * 4096.5 * ((256 + 128) + (3 * 256 + 2 * 128)) * 32 * 8192  # what a kernel on 256 lanes executes
    assert 0.81 < core["flops"] / padded < 0.82  # so a padded kernel at the MXU's peak would read 82, not 100


def _made_up(flops, config, model):
    ops = {"%fusion.1 = bf16[4]": 0.04, "%fusion.2 = bf16[4]": 0.06, "%fusion.3 = bf16[4]": 0.2,
           "%ragged-dot-none.3 = bf16[4]": 1.5, "%flash_attention_fwd.1 = (bf16[4]) custom-call()": 0.03,
           "%transpose_jvp_flash_attention_bwd_dkv_dq__.1 = (bf16[4]) custom-call()": 0.07}
    scopes = {"%fusion.1 = bf16[4]": ["attn", "kda_scan"], "%fusion.2 = bf16[4]": ["attn", "attn_in_proj", "mla_q"],
              "%fusion.3 = bf16[4]": ["mlp"], "%ragged-dot-none.3 = bf16[4]": ["mlp", "moe_experts"]}
    result = {"traced_steps": 5, "tokens_per_step": 8192, "seq": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "series": {"step_s": [0.4, 0.4, 0.5]},
              "trace": {"busy_s": 2.0, "op_seconds": ops, "op_scopes": scopes}}
    scan = flops.scan_step_work(model, 8192)
    core = flops.attention_step_work(model, 8192, 8192)
    # a program without the scopes or the kernels (the parent of the PR that named the cell, were it to run it),
    # a run without a trace, a rehearsal: nothing to read, nothing raised
    other = {"busy_s": 2.0, "op_seconds": {"%fusion.9 = f32[4]": 2.0}, "op_scopes": {"%fusion.9 = f32[4]": ["moe_experts"]}}
    bare = {"result": {**result, "trace": other}}
    untraced = {"result": {k: v for k, v in result.items() if k != "trace"}}
    return result, [
        ("train_kernel_roofline", "train_attn_mla_roofline_pct", {}, 100 * 5 * core["flops"] / 197e12 / 0.10),
        ("train_scan_roofline", "train_kda_scan_roofline_pct", {}, 100 * 5 * scan["bytes"] / 819e9 / 0.04),
        ("trace_scope_share", "train_mlp_pct", {}, 100 * (0.2 + 1.5) / 2.0),
        ("trace_scope_share", "train_mla_proj_pct", {}, 100 * 0.06 / 2.0),
        ("train_mfu_family", "train_mfu_kda_mla_moe_pct", {},
         100 * flops.train_flops_per_token(model, 8192) * 8192 / 0.4 / 197e12),
        ("train_kernel_roofline", "train_attn_mla_roofline_pct", bare, None),
        ("train_kernel_roofline", "train_attn_mla_roofline_pct", untraced, None),
        ("train_kernel_roofline", "train_attn_mla_roofline_pct", {"rehearse": True}, None),
        ("trace_scope_share", "train_mlp_pct", bare, None),
        ("trace_scope_share", "train_mlp_pct", untraced, None),
        ("train_mfu_family", "train_mfu_kda_mla_moe_pct", {"rehearse": True}, None)]


FAMILY = Family(
    model_type="kimi_linear", tiny=CFG, cell="kimilinear-train-ep32share-s8192",
    config="kimi-linear-48b-a3b-train-ep32", index=8,
    unsettle=(("kda_layers", "kda_o_norm", 0.1, 1.0), ("attn_layers", "kv_norm", 0.1, 1.0)),  # norm weights that are not one
    cases=(("K-KEKE*EKE-held0", _pattern("K-KEKE*EKE", (0, 1)), 1),  # the cell's pattern, everything held
           ("KE*EKE*E-held2", _pattern("KE*EKE*E", (1, 2)), 2),      # two periods: latent attention in a scanned period; half the experts
           ("*-K-held3", _pattern("*-K", (0, 1)), 1)),               # no expert part at all
    batch=2, least_leaves=15, float32_leaves=frozenset({"kda_A_log", "kda_dt_bias"}),
    recurrent="Kimi-Delta-Attention", kept_here=False,  # (models/kda.py's `kept` cases run from the solar_open2 file)
    shares={"32_expert_shares": _32_expert_shares},
    scopes=frozenset({"moe_router", "moe_experts", "moe_shared", "attn", "mlp", "attn_in_proj", "attn_core",
                      "attn_out_proj", "mla_q", "mla_kv", "moe_dispatch", "moe_combine", "layer_stack"}),
    mixer_scopes=frozenset({"kda_in_proj", "kda_conv", "kda_scan", "kda_norm_gate", "kda_out_proj"}),
    outer=frozenset({"attn"}), absent=frozenset({"attn_head_norm", "attn_gate"}),
    rehearsal=("3000000007", 40, frozenset({"loss", "ce_loss"}), 2 * 64),
    pairs={  # published key -> ModelConfig field
        "hidden_size": "d_model", "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
        "vocab_size": "vocab_size", "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
        "model_max_length": "max_seq_len", "kv_lora_rank": "kv_lora_rank", "qk_nope_head_dim": "qk_nope_head_dim",
        "qk_rope_head_dim": "qk_rope_head_dim", "v_head_dim": "v_head_dim", "num_shared_experts": "n_shared_experts",
        "moe_renormalize": "moe_norm_topk", "num_expert_group": "moe_n_group", "routed_scaling_factor": "moe_route_scale",
        "num_experts_per_token": "moe_top_k", "num_nextn_predict_layers": "mtp_depth"},
    cell_params=602.4e6, config_file=_config_file, published_params=49.12e9, published=_published,
    hf_base=dict(model_type="kimi_linear", vocab_size=256, hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                 head_dim=16, intermediate_size=96, num_hidden_layers=5, first_k_dense_replace=1,
                 linear_attn_config={"kda_layers": [1, 2, 3, 5], "full_attn_layers": [4], "head_dim": 16, "num_heads": 4,
                                     "short_conv_kernel_size": 4},
                 q_lora_rank=None, kv_lora_rank=32, qk_nope_head_dim=16, qk_rope_head_dim=8, v_head_dim=16,
                 mla_use_nope=True, num_experts=16, num_experts_per_token=3, moe_intermediate_size=40,
                 num_shared_experts=1, routed_scaling_factor=2.446, moe_renormalize=True,
                 moe_router_activation_func="sigmoid", use_grouped_topk=True, num_expert_group=1, topk_group=1,
                 moe_layer_freq=1, num_nextn_predict_layers=0, hidden_act="silu", rms_norm_eps=1e-5, rope_scaling=None,
                 model_max_length=128, rope_theta=10000.0, tie_word_embeddings=False),
    hf_to_tiny=dict(name="kimi-linear-tiny", dtype="float32", kda_chunk=8),
    hf_refused=((dict(num_expert_group=2), "num_expert_group"), (dict(topk_group=2), "group-limited"),
                (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers"),
                (dict(moe_renormalize=False), "not normalised"),
                (dict(moe_router_activation_func="softmax"), "moe_router_activation_func"),
                (dict(moe_layer_freq=2), "moe_layer_freq"), (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
                (dict(num_key_value_heads=2), "num_key_value_heads"), (dict(kv_lora_rank=None), "kv_lora_rank"),
                (dict(linear_attn_config=None), "linear_attn_config"),
                (dict(linear_attn_config={"kda_layers": [1, 2, 3], "full_attn_layers": [4], "head_dim": 16, "num_heads": 4}),
                 "every layer"),
                (dict(num_experts=0), "routed experts")),
    llm_refuses=("delta-rule state", "paged cache of latents", "v heads of another width", "dropless", "convolution tails"),
    flops_parts=frozenset({"K", "*", "-", "E", "head"}), step_flops=18.997e12, flops_share=_flops_share,
    made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_moe_dispatch_pct", "train_moe_combine_pct",
        "train_optimizer_pct", "train_head_loss_pct", "train_scoped_pct", "train_kda_pct", "train_kda_conv_pct",
        "train_kda_scan_roofline_pct", "train_attn_proj_pct", "train_attn_core_pct", "train_mla_proj_pct",
        "train_mfu_kda_mla_moe_pct", "train_attn_mla_roofline_pct", "train_mlp_pct"}),
    own_metrics=("train_mfu_kda_mla_moe_pct", "train_attn_mla_roofline_pct", "train_mlp_pct"),
    # the cell's whole step (`Family.cell_step`). PR 54: [1, 8192]: four delta-rule parts at all 32 heads of 128 (their
    # q | k | v [1, 8192, 12288] kept: 0.2 GB a part), one latent attention part WITHOUT a q latent whose kernels run q/k
    # 192 | v 128 (the kernels under their own names: no XLA fallback), a dense part, four expert parts at 8 of 256 (the
    # pick a slot at a time) beside a shared expert; arguments 7.23 GB; 12.86 of 15.75 GB in all. PR 61: 5.631 -> 5.349 GB,
    # the walk over the scan's chunks in kernels (the loop's stacked states twice over and o's transposed copies gone): 12.58
    cell_step=(4, 2, 5.35),
)


# ------------------------------------------------------------------- the family's own

_the_contracts_whole_step = test_a_family_cells_step_scores_once_a_layer_and_fits_as_before  # noqa: F821


@pytest.mark.slow  # (the largest whole step, ~2.5 min of TPU compile: `-m slow -k cells_step`; tier-1 holds the cell's kernels, names
# and scoped memory in tests/test_tpu_compile.py -k 192_beside. Back into tier-1 when its clock has the room: ROADMAP.md C13)
def test_a_family_cells_step_scores_once_a_layer_and_fits_as_before(family, cell_step):  # noqa: F811
    _the_contracts_whole_step(family, cell_step)


def test_the_stacks_the_direct_q_and_the_published_pattern():
    assert llama._layer_kinds(CFG) == {"kda_layers": (4, "kda", None), "mlp_layers": (1, None, "dense"),
                                       "layers": (4, None, "experts"), "attn_layers": (1, "attn", None)}
    p = params(CFG, FAMILY.unsettle)
    axes = llama.param_axes(CFG)
    assert set(axes) == set(p)
    for name, stack in axes.items():
        if isinstance(stack, dict):
            assert set(stack) == set(p[name]), name
            assert all(len(stack[leaf]) == p[name][leaf].ndim for leaf in stack), name
    # no q latent: one [D, H, nope + rope] product, no `wq_a` / `q_norm` / `wq_b`; v's heads at their own width
    assert set(p["attn_layers"]) == {"attn_norm", "wq", "wkv_a", "kv_norm", "wkv_b", "wo"}
    assert p["attn_layers"]["wq"].shape == (1, 64, 4, 24) and p["attn_layers"]["wkv_a"].shape == (1, 64, 32 + 8)
    assert p["attn_layers"]["wkv_b"].shape == (1, 32, 4, 16 + 16) and p["attn_layers"]["wo"].shape == (1, 4, 16, 64)
    assert (CFG.head_dim, CFG.v_dim) == (24, 16) and get_config("test-tiny").v_dim == get_config("test-tiny").head_dim
    assert attn.n_params(CFG) == sum(int(np.prod(a.shape[1:])) for a in p["attn_layers"].values())
    assert llama.pattern_period(CATALOG_PATTERN) == (CATALOG_PATTERN, 1)  # the last period is short by one K
    with pytest.raises(ValueError, match="kda_n_heads"):
        dataclasses.replace(CFG, kda_n_heads=0)


def test_beta_lies_in_0_1_and_no_position_reaches_the_latent_attention():
    """`kda_neg_eigval` false is beta = sigmoid (the factor 2 changes the loss); the `*` part sees no
    positions: rotating positions changes nothing, and `rope_theta` stands in the config unread."""
    p, t = params(CFG, FAMILY.unsettle), tokens(CFG, (2, 33))
    loss = jax.jit(lambda p, cfg_: llama.loss_fn(p, {"tokens": t}, cfg_)[0], static_argnums=1)
    base = float(loss(p, CFG))
    assert abs(float(loss(p, dataclasses.replace(CFG, kda_neg_eigval=True))) - base) > 1e-4 * base
    assert float(loss(p, dataclasses.replace(CFG, rope_theta=77.0))) == base
    assert abs(float(loss(p, dataclasses.replace(CFG, attention_rotation=True))) - base) > 1e-6 * base  # (rotation would show)


def _latent_qkv_as_it_was(h, lp, cfg, positions):
    """`models/attn.py:_latent_qkv` before this family (PR 53's tree, its statements as they stood):
    q through its latent, both rotated slices rotated whatever the configuration says."""
    dt = h.dtype
    nope, kvr = cfg.qk_nope_head_dim, cfg.kv_lora_rank
    cq = attn.rms_norm(jnp.einsum("bsd,dr->bsr", h, lp["wq_a"].astype(dt)), lp["q_norm"], cfg.norm_eps)
    q = jnp.einsum("bsr,rhk->bshk", cq, lp["wq_b"].astype(dt))
    q = jnp.concatenate([q[..., :nope], attn.rope(q[..., nope:], positions, cfg.rope_theta)], -1)
    ckv = jnp.einsum("bsd,dr->bsr", h, lp["wkv_a"].astype(dt))
    k_rot = attn.rope(ckv[:, :, None, kvr:], positions, cfg.rope_theta)
    kv = jnp.einsum("bsr,rhk->bshk", attn.rms_norm(ckv[..., :kvr], lp["kv_norm"], cfg.norm_eps), lp["wkv_b"].astype(dt))
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_rot, (*kv.shape[:3], k_rot.shape[-1]))], -1)
    return q, k, kv[..., nope:]


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
def test_latent_attention_with_a_q_latent_and_rotation_gives_the_bits_it_gave(dtype):
    """GLM-4.7-Flash's form (a q latent, both slices rotated, v as wide as q) through the function
    that now also runs this family's: the same seeded leaves from the same keys, and q, k, v and the
    part's output bit for bit what the statements it replaced give."""
    glm = get_config("glm-tiny")
    lp = attn.init(jax.random.split(jax.random.PRNGKey(7), 7)[:4], glm)
    assert set(lp) == {"attn_norm", "wq_a", "q_norm", "wq_b", "wkv_a", "kv_norm", "wkv_b", "wo"}
    h = jax.random.normal(jax.random.PRNGKey(1), (2, 32, glm.d_model), dtype)
    positions = jnp.broadcast_to(jnp.arange(32)[None], (2, 32))
    mine = jax.jit(lambda h, lp: attn._latent_qkv(h, lp, glm, positions))(h, lp)
    was = jax.jit(lambda h, lp: _latent_qkv_as_it_was(h, lp, glm, positions))(h, lp)
    for a, b in zip(mine, was):
        assert a.dtype == dtype and float(jnp.abs(a.astype(jnp.float32)).max()) > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))
    part = lambda: jax.jit(lambda x, lp: attn.mixer(x, lp, glm, positions, None, None, None)[0])(h, lp)  # noqa: E731
    out = part()
    with pytest.MonkeyPatch.context() as patch:  # the same part around the statements as they stood
        patch.setattr(attn, "_latent_qkv", _latent_qkv_as_it_was)
        np.testing.assert_array_equal(np.asarray(out, np.float32), np.asarray(part(), np.float32))
    assert out.shape == h.shape and float(jnp.abs(out.astype(jnp.float32)).max()) > 0
