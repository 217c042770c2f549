"""The solar_open2 family (Solar-Open2) on the training path, at a small size on the CPU
with seeded weights: every published layer two parts of a pattern (a mixer, then experts);
Kimi-Delta-Attention mixers, softmax attention without rotation and with an output gate,
sigmoid-routed SwiGLU experts at 8 of 320 beside a shared one, and the shares of a layer's
heads and experts a chip holds. The contract is tests/family_contract.py's; here is what
the family alone has. (The chunked delta rule itself: tests/test_kda_scan.py.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import Family, expert_shares, head_shares, model_of, params
from ray_tpu.models import get_config, kda, llama, moe
from ray_tpu.models.reference import solar_open2 as ref

CFG = get_config("solar-tiny")


def _pattern(pattern, held):
    return dataclasses.replace(CFG, layer_pattern=pattern, n_layers=len(pattern), experts_held=held)


# ------------------------------------------------------------------- the shares

def _kda_8_head_shares(x):
    """8 head shares of a mixer add up to the whole layer through W_o (the low-rank
    down-projections and the norm weight whole in every share)."""
    whole = dataclasses.replace(CFG, kda_n_heads=16)
    share = dataclasses.replace(whole, kda_n_heads=2)
    lp = kda.init(jax.random.PRNGKey(3), whole)
    lp["kda_o_norm"] = 1 + 0.1 * jax.random.normal(jax.random.PRNGKey(5), lp["kda_o_norm"].shape)
    want = ref.kda_layer(x, lp, model_of(whole)) - x
    by_heads = {"kda_qkv": 2, "kda_conv": 2, "kda_f_up": 1, "kda_dt_bias": 0, "kda_A_log": 0, "kda_beta": 1,
                "kda_g_up": 1, "kda_out": 0}  # the axis the heads lie on; every other leaf is whole
    mixer = jax.jit(lambda x, mine: kda.mixer(x, mine, share))  # eight shares, one program
    parts = []
    for i in range(8):
        mine = {name: jnp.take(a, jnp.arange(2 * i, 2 * i + 2), axis=by_heads[name]) if name in by_heads else a
                for name, a in lp.items()}
        assert mine["kda_f_down"].shape == (CFG.d_model, 16) and mine["kda_qkv"].shape == (CFG.d_model, 3, 2, 16)
        parts.append(mixer(x, mine))  # (the mixer's output: `_block` adds it to x)
    return want, parts, 1


def _gated_gqa_8_head_shares(x):
    whole = dataclasses.replace(CFG, n_heads=8, n_kv_heads=2, layer_pattern="*", n_layers=1)
    lp, want, parts = head_shares(ref, whole, x, gated=True)
    ungated = ref.attention_layer(x, {n: a for n, a in lp.items() if n != "wo_gate"}, model_of(whole)) - x
    assert float(jnp.abs(want - ungated).max()) > 0.1 * float(jnp.abs(want).max())  # the gate gates
    return want, parts, 1


def _40_expert_shares(x):
    """40 expert shares with the shared expert counted once add up to the uncut expert part."""
    whole = dataclasses.replace(CFG, n_experts=320, moe_top_k=8)
    lp = moe.init_expert_weights(jax.random.PRNGKey(3), whole)
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (320,))
    want, _, parts, _ = expert_shares(ref, whole, 40, x, lp)
    return want, parts, 1


# ------------------------------------------------------------------- the configuration

def _config_file(config, cfg, config_from):
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
    assert cfg.vocab_size == published["vocab_size"] // 8 and cfg.mtp_depth == 0 and cfg.mlp_activation == "silu_gated"
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    count = lambda stack: sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes[stack]))  # noqa: E731
    assert abs(count("kda_layers") - 18.14e6) < 0.01e6 and abs(count("attn_layers") - 13.64e6) < 0.01e6
    assert abs(count("layers") - 320 - 142.87e6) < 0.01e6
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    assert len(config["assumed"]) >= 6
    # the program's own mapping of the published keys says the same, share apart
    hf = {k: v for k, v in config.items() if k not in ("program", "trainer", "published", "reduced")}
    hf.update(published, num_hidden_layers=4, gqa_layers=[0])
    assert dataclasses.replace(
        config_from(hf), name=cfg.name, vocab_size=cfg.vocab_size, n_heads=cfg.n_heads, kda_n_heads=8,
        attn_heads_held=(8, 1), experts_held=(0, 40),
        kda_proj_rank=128, kda_chunk=cfg.kda_chunk, d_ff_shared=1280, remat_policy="full", dtype="bfloat16") == cfg


def _published(cfg):
    assert cfg.layer_pattern == "*EKEKEKE" * 12 and cfg.n_layers == 96 and cfg.head_dim == 128
    active = cfg.n_params - 48 * (320 - 8) * 3 * 4096 * 1280  # 8 of 320 experts a token, and everything else
    assert abs(active / 14.7e9 - 1) < 0.01


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    layer = flops.layer_flops_per_token(model, (8192 + 1) / 2)
    weights = 4096 * 3 * 1024 + 2 * (4096 + 1024) * 128 + 4096 * 8 + 1024 * 4096  # 18.12 M in products
    assert layer["K"] - flops.scan_flops_per_token(model) == 2 * weights
    assert layer["E"] == 2 * (4096 * 320 + 3 * 4096 * 1280 + 8 / 40 * flops.expert_params(model))
    assert flops.expert_params(model) == 3 * 4096 * 1280
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    total = sum(fwd.values())
    assert 0.38 < fwd["head"] / total < 0.40  # the floors' doing: an eighth of the vocabulary over 4 layers
    assert 0.20 < fwd["K"] / total < 0.23 and 0.30 < fwd["E"] / total < 0.32 and fwd["*"] / total < 0.09
    # the scan's yardstick is the file's own chunk, whatever the program's scan runs at
    assert flops.scan_step_work({**model, "kda_chunk": 32}, 8192) == flops.scan_step_work(model, 8192)
    work = flops.scan_step_work(model, 8192)
    assert work["bytes"] == 3 * 3 * 8192 * 8 * (3 * 2 * 128 + 4 * 128 + 4 + 4 * 128)
    assert work["bytes"] / 819e9 > work["flops"] / 197e12  # bound by what it reads and writes on a v5e
    assert flops.grouped_products_flops(model, 1638) == 3 * 2 * 1638 * 3 * 4096 * 1280


def _made_up(flops, config, model):
    work = flops.scan_step_work(model, 8192)
    needed = max(work["flops"] / 197e12, work["bytes"] / 819e9)
    result = {"traced_steps": 5, "tokens_per_step": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "trace": {"busy_s": 2.0, "op_seconds": {"%a": 0.04, "%b": 0.06, "%c": 1.9},
                        "op_scopes": {"%a": ["attn", "kda_scan"], "%b": ["attn", "kda_conv"], "%c": ["moe_experts"]}}}
    # a program without the scope (the parent of the PR that named it): nothing to read, and nothing raised
    bare = {"result": {**result, "trace": {**result["trace"], "op_scopes": {"%c": ["moe_experts"]}}}}
    return result, [
        ("train_scan_roofline", "train_kda_scan_roofline_pct", {}, 100 * 5 * needed / 0.04),
        ("trace_scope_share", "train_kda_pct", {}, 100 * 0.10 / 2.0),
        ("train_scan_roofline", {"scope": "kda_scan"}, bare, None),
        ("trace_scope_share", {"pattern": "^kda_"}, bare, None)]


FAMILY = Family(
    model_type="solar_open2", tiny=CFG, cell="solaropen2-train-tp8ep40share-s8192",
    config="solar-open2-train-tp8-ep40", index=4,
    unsettle=(("kda_layers", "kda_o_norm", 0.1, 1.0),),  # a norm weight that is not one
    cases=(("*EKEKEKE-held0", _pattern("*EKEKEKE", (0, 1)), 1),  # one period, everything held
           ("*EKEKEKE-held1", _pattern("*EKEKEKE", (1, 4)), 1),  # a quarter of the experts
           ("KEKE-held2", _pattern("KEKE", (0, 2)), 2),          # two periods of KE: the scan over periods
           ("K*K-held3", _pattern("K*K", (0, 1)), 1)),           # no expert part at all
    batch=2, least_leaves=15, float32_leaves=frozenset({"kda_A_log", "kda_dt_bias"}),
    recurrent="Kimi-Delta-Attention",
    shares={"kda_8_head_shares": _kda_8_head_shares, "gated_gqa_8_head_shares": _gated_gqa_8_head_shares,
            "40_expert_shares": _40_expert_shares},
    scopes=frozenset({"moe_router", "moe_experts", "moe_shared", "attn", "mlp", "attn_in_proj", "attn_core", "attn_gate", "attn_out_proj", "moe_dispatch", "moe_combine", "layer_stack"}),
    mixer_scopes=frozenset({"kda_in_proj", "kda_conv", "kda_scan", "kda_norm_gate", "kda_out_proj"}),
    outer=frozenset({"attn"}), absent=frozenset({"attn_head_norm"}),
    rehearsal=("3000000007", 30, frozenset({"loss", "ce_loss"}), 2 * 64),
    pairs={  # published key -> ModelConfig field
        "hidden_size": "d_model", "num_key_value_heads": "n_kv_heads",
        "vocab_size": "vocab_size", "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
        "max_position_embeddings": "max_seq_len", "first_k_dense_replace": "n_dense_layers",
        "use_rope": "attention_rotation", "use_gqa_gate": "attn_output_gate", "kda_allow_neg_eigval": "kda_neg_eigval",
        "n_shared_experts": "n_shared_experts", "norm_topk_prob": "moe_norm_topk",
        "routed_scaling_factor": "moe_route_scale", "num_experts_per_tok": "moe_top_k"},
    cell_params=840.9e6, config_file=_config_file, published_params=250e9, published=_published,
    hf_base=dict(model_type="solar_open2", vocab_size=256, hidden_size=64, num_attention_heads=4,
                 num_key_value_heads=2, head_dim=24, intermediate_size=96, num_hidden_layers=4, gqa_layers=[0],
                 linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16, "num_heads": 4, "num_kv_heads": None},
                 use_rope=False, use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
                 n_routed_experts=20, num_experts_per_tok=3, moe_intermediate_size=40, n_shared_experts=1,
                 routed_scaling_factor=1, norm_topk_prob=True, first_k_dense_replace=0, rms_norm_eps=1e-5,
                 max_position_embeddings=128, rope_theta=500000.0),
    hf_to_tiny=dict(name="solar-tiny", dtype="float32", kda_chunk=8),
    hf_refused=((dict(use_rope=True), "use_rope"), (dict(kda_use_full_proj=True), "kda_use_full_proj"),
                (dict(first_k_dense_replace=1), "dense layers"), (dict(norm_topk_prob=False), "not normalised"),
                (dict(n_group=2), "group-limited"), (dict(sliding_window=4096), "window"),
                (dict(linear_attn_config={"head_dim": 16, "num_heads": 4, "num_kv_heads": 2}), "num_kv_heads"),
                (dict(linear_attn_config=None), "linear_attn_config"),
                (dict(num_nextn_predict_layers=1), "MTP"), (dict(n_routed_experts=0), "routed experts")),
    llm_refuses=("delta-rule state", "output gate", "dropless", "convolution tails"),
    flops_parts=frozenset({"K", "*", "E", "head"}), step_flops=12.75e12, flops_share=_flops_share,
    made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct", "train_kda_pct", "train_kda_scan_roofline_pct", "train_mfu_kda_moe_pct",
        "train_kda_conv_pct",
        # PR 52: the attention part's pieces, the expert layer's dispatch and combine (the layer
        # loop's own is next to nothing where one period runs unrolled: not listed)
        "train_attn_proj_pct", "train_attn_core_pct", "train_moe_dispatch_pct", "train_moe_combine_pct",
        "train_attn_passes_pct"}),
    own_metrics=("train_kda_pct", "train_kda_scan_roofline_pct", "train_mfu_kda_moe_pct", "train_kda_conv_pct"),
    # the cell's whole step (`Family.cell_step`). PR 37: four expert parts at 8 of 320 (the pick a slot at a time, as at
    # 22 of 512), three delta-rule scans whose triangular systems are inverted once each and kept. PR 44: 4.650 ->
    # 4.644 GB, the float32 `[1, 8195, 3072]` padded copies and the taps' products gone. PR 48: 4.644 -> 4.794 GB,
    # q | k | v before the convolution of three delta-rule parts kept, [1, 8192, 3072] bfloat16 = 50 MB a part, 0.15 GB.
    # PR 51: 4.7938 -> 4.7907 GB, 3 MB less: the scan's second half in kernels (its [.., 128, 256] right-hand sides and
    # solutions and the [chunks, B, H, Q, K] copies of q, k, v gone) is not where the step's temporaries peak, so `kk`
    # and `b` (0.2 GB: 4.996) stay unnamed. PR 61: 4.7925 -> 4.7888 GB with the walk over the chunks in kernels (14.879 in all)
    cell_step=(4, 2, 4.80),
)


# ------------------------------------------------------------------- the family's own

def test_the_stacks_and_the_seeded_decays():
    assert llama._layer_kinds(CFG) == {"attn_layers": (1, "attn", None), "layers": (4, None, "experts"),
                                       "kda_layers": (3, "kda", None)}
    p = params(CFG, FAMILY.unsettle)
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
