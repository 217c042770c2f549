"""The glm4_moe_lite family (GLM-4.7-Flash) on the training path, at a small size on the
CPU with seeded weights: latent attention, a leading dense layer, sigmoid-routed experts
served without drops as a share of an expert-parallel group beside a shared one, the
MTP module. The contract is tests/family_contract.py's; here is what the family alone has.
(The dropless layer's window walk: tests/test_expert_layer.py; the rotated slice:
tests/test_llama.py; the kernels at head width 256: tests/test_flash_attention.py.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import Family, expert_shares, model_of, params, tokens
from ray_tpu.models import get_config, llama, moe
from ray_tpu.models.reference import glm4_moe_lite as ref

CFG = get_config("glm-tiny")


# ------------------------------------------------------------------- the shares

def _8_expert_shares(x):
    """Eight chips hold one expert each of the same layer: their routed parts, with the
    shared expert (which every chip computes alike) counted once, are what the uncut
    reference gives for the whole layer."""
    lp = moe.init_expert_weights(jax.random.PRNGKey(3), CFG)
    lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (CFG.n_experts,))
    x = jax.random.normal(jax.random.PRNGKey(5), (1, 96, CFG.d_model))
    want, _, parts, counted = expert_shares(ref, CFG, 8, x, lp)
    for aux in counted:  # a share's counter is over ALL experts: every chip counts the same
        np.testing.assert_array_equal(aux["load"], counted[0]["load"])
    assert float(counted[0]["load"].sum()) == 96 * CFG.moe_top_k
    np.testing.assert_allclose(sum(parts), want, atol=2e-5)
    y_all, _ = moe.expert_layer(x[0], lp, CFG)
    np.testing.assert_allclose(y_all, want[0], atol=2e-5)
    return want, parts, 1


# ------------------------------------------------------------------- the configuration

PAIRS = {  # published key -> ModelConfig field
    "q_lora_rank": "q_lora_rank", "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim", "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim", "moe_intermediate_size": "d_ff_expert",
    "n_shared_experts": "n_shared_experts", "first_k_dense_replace": "n_dense_layers",
    "routed_scaling_factor": "moe_route_scale", "num_nextn_predict_layers": "mtp_depth",
    "num_experts_per_tok": "moe_top_k",
}


def _config_file(config, cfg, config_from):
    # the router keeps the published width; the file's count is what is held here
    assert cfg.n_experts == config["published"]["n_routed_experts"] == 64
    assert cfg.n_experts_held == config["n_routed_experts"] == 8
    assert config["published"]["vocab_size"] // 8 == cfg.vocab_size == 19360
    assert cfg.n_layers == config["num_hidden_layers"] >= 1 + 4
    assert cfg.head_dim == 256 and config["topk_method"] == "noaux_tc"
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    # the same keys through the checkpoint reader give the uncut model of the same widths
    whole = {k: v for k, v in config.items() if not isinstance(v, (dict, list))}
    whole.update(config["published"])
    hf = config_from(whole)
    for field in PAIRS.values():
        assert getattr(hf, field) == getattr(cfg, field), field
    assert (hf.n_experts, hf.experts_held, hf.n_layers, hf.vocab_size) == (64, (0, 1), 47, 154880)
    assert hf.moe_dropless and hf.moe_select_bias and hf.latent_attention and hf.head_dim == 256


def _published(cfg):
    assert (cfg.n_layers, cfg.n_dense_layers, cfg.mtp_depth, cfg.n_experts, cfg.head_dim) == (47, 1, 1, 64, 256)
    active = cfg.n_params - (46 + 1) * (64 - 4) * 3 * 2048 * 1536  # 4 of 64 experts a token, and everything else
    assert abs(active / 3.9e9 - 1) < 0.03


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    parts = flops.layer_matmul_params(model)
    assert parts["attention_projections"] == 21_757_952  # the issue's 21.76 M
    assert parts["dense_mlp"] == 62_914_560 and parts["shared_experts"] == 9_437_184
    assert parts["routed_experts_expected"] == 4 / 8 * 9_437_184  # 4 x 8/64 of an expert
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    attention = 4 * 20 * 256 * (8192 + 1) / 2 * 6  # both products, six blocks
    assert abs(3 * attention / flops.train_flops_per_token(model, 8192) - 0.42) < 0.01
    assert fwd["mtp"] > fwd["head"]
    assert flops.grouped_products_flops(model, 4096) == 6 * 4096 * 9_437_184
    assert flops.causal_attention_flops(model, 8192, 1) == 20 * 2 * 256 * 8192 * 8193


def _made_up(flops, config, model):
    rows = [[4096.0] * 5, [4000.0] * 5]
    trace = {"op_seconds": {"%ragged-dot-none.3 = bf16[32768,1536] custom-call(": 0.05,
                            "%fusion.1 = bf16[8192,2048] fusion(": 1.0}}
    result = {"series": {"step_s": [0.4, 0.5, 0.6], "held_assignments": rows,
                         "fullest_held_expert_rows": [[1024.0] * 5, [500.0] * 5]},
              "tokens_per_step": 8192, "seq": 8192, "chips": 1, "traced_steps": 5,
              "device": {"kind": "TPU v5 lite"}, "trace": trace}
    # the expert layer's share: by scope, and its compiler-made kernels by name
    traced = {"result": {"trace": {**trace, "busy_s": 2.0, "op_scopes": {
        "%fusion.1 = bf16[8192,2048] fusion(": ["closed_call", "mlp", "moe_shared"]}}}}
    unjoined = {"result": {"trace": {**trace, "busy_s": 2.0}}}  # a driver that made no join
    # nothing to read: a program without the counters, a configuration without a flops file
    bare = {"result": {**result, "series": {"step_s": [0.4]}}, "config": {"trainer": {}}, "model": {}}
    return result, [
        ("counter_rows_imbalance", "train_moe_imbalance", {}, (2.0 + 1.0) / 2),
        ("train_mfu_family", "train_mfu_mla_moe_pct", {}, 100 * flops.train_flops_per_token(model, 8192) * 8192 / 0.5 / 197e12),
        ("train_grouped_mxu", {"pattern": "^%?ragged-dot-none"}, {}, 100 * 5 * 6 * (5 * 4048) * 9_437_184 / (0.05 * 197e12)),
        ("trace_scope_share", {"pattern": "^moe_"}, traced, 50.0),
        ("trace_scope_share", {"pattern": "^moe_", "ops": "^%?ragged-dot"}, traced, 52.5),
        ("trace_scope_share", {"pattern": "^mla_"}, traced, None),
        ("trace_scope_share", {"pattern": "^moe_"}, unjoined, None),
        ("counter_rows_imbalance", {}, bare, None), ("train_mfu_family", {}, bare, None),
        ("train_grouped_mxu", {"pattern": "x"}, bare, None)]


FAMILY = Family(
    model_type="glm4_moe_lite", tiny=CFG, cell="glm47flash-train-ep8share-s8192", config="glm-4.7-flash-train-ep8",
    index=2, unsettle=(),
    cases=(("held0", CFG, 1), ("held1", dataclasses.replace(CFG, experts_held=(1, 2)), 1)),
    batch=2, least_leaves=50, float32_leaves=frozenset(), recurrent=None,
    shares={"8_expert_shares": _8_expert_shares},
    scopes=frozenset({"moe_router", "moe_experts", "moe_shared", "attn", "mlp", "mtp", "lm_head", "attn_in_proj", "attn_core", "attn_out_proj", "moe_dispatch", "moe_combine", "layer_stack"}),
    mixer_scopes=frozenset({"mla_q", "mla_kv"}), outer=frozenset({"attn"}), absent=frozenset({"attn_head_norm", "attn_gate"}),
    rehearsal=("3000000001", 50, frozenset({"loss", "ce_loss", "mtp_loss"}), 2 * (64 + 63)),
    pairs=PAIRS, cell_params=706.5e6, config_file=_config_file, published_params=30.59e9, published=_published,
    hf_base=dict(model_type="glm4_moe_lite", vocab_size=256, hidden_size=64, num_hidden_layers=3,
                 num_attention_heads=4, intermediate_size=160, q_lora_rank=48, kv_lora_rank=32,
                 qk_nope_head_dim=24, qk_rope_head_dim=8, v_head_dim=32, n_routed_experts=8,
                 num_experts_per_tok=2, moe_intermediate_size=48, n_shared_experts=1,
                 first_k_dense_replace=1, topk_method="noaux_tc", routed_scaling_factor=1.8,
                 num_nextn_predict_layers=1, rope_theta=1e6),
    hf_to_tiny=dict(name="glm-tiny", max_seq_len=128, dtype="float32", norm_eps=1e-5),
    hf_refused=((dict(n_group=2), None), (dict(rope_scaling={"type": "yarn"}), None), (dict(norm_topk_prob=False), None)),
    llm_refuses=("paged cache of latents", "more than one kind", "dropless", "drafts"),
    flops_parts=frozenset({"dense_layers", "expert_layers", "head", "mtp"}), step_flops=29.70e12,
    flops_share=_flops_share, made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct", "train_mfu_mla_moe_pct",
        # PR 52: the attention part's pieces, the expert layer's dispatch and combine, the layer loop's own
        "train_attn_proj_pct", "train_attn_core_pct", "train_moe_dispatch_pct", "train_moe_combine_pct",
        "train_layer_stack_pct", "train_mla_proj_pct"}),
    own_metrics=("train_mfu_mla_moe_pct", "train_moe_pct", "train_moe_gmm_mxu_pct", "train_moe_imbalance"),
    # the cell's whole step (`Family.cell_step`): the scan over four layers has one body; the MTP module. PR 43: 6.045 ->
    # 6.539 GB, remat `full` keeps the forward flash kernel's `out` [1, 20, 8192, 256] bfloat16 (84 MB) and logsumexp
    # (0.66 MB) of six blocks, 0.51 GB, and runs the kernel 3 times a step where it ran 6
    cell_step=(2, 0, 6.54),
)


# ------------------------------------------------------------------- the family's own

@pytest.mark.parametrize("held", [(0, 1), (1, 2), (3, 4)])
def test_logits_of_both_heads_match_the_reference(held):
    cfg = dataclasses.replace(CFG, experts_held=held)
    p, t = params(cfg), tokens(cfg)

    @jax.jit
    def both_heads(p, t):
        logits, _, aux = llama.forward(p, t[:, :-1], cfg, return_aux=True)
        (mtp, mtp_aux), = llama.mtp_logits(p, aux["hidden"], t, cfg)
        return logits, mtp, [*aux["chosen"], mtp_aux["chosen"]]

    logits, mtp, chosen = both_heads(p, t)
    r_logits, (r_mtp,), routings = jax.jit(lambda p, t: ref.forward(p, t, model_of(cfg)))(p, t[:, :-1])
    np.testing.assert_allclose(logits, r_logits, atol=2e-5)
    np.testing.assert_allclose(mtp, r_mtp, atol=2e-5)
    # what the system chose is what the reference chose, layer by layer
    assert len(chosen) == len(routings) == cfg.n_layers - cfg.n_dense_layers + 1
    for mine, r in zip(chosen, routings):
        own = np.asarray(r["own"])
        mine = np.asarray(mine).reshape(t.shape[0], -1, cfg.moe_top_k)[:, :own.shape[1]]
        np.testing.assert_array_equal(np.sort(mine, -1), np.sort(own, -1))


def test_reference_on_a_given_selection_uses_it():
    """The benchmark evaluates the reference on the system's experts: a selection handed
    in replaces the layer's own top-k, and the layer still reports its own."""
    cfg = dataclasses.replace(CFG, n_layers=2, mtp_depth=0)
    p, t = params(cfg, biased=False), tokens(cfg)
    p.pop("mtp", None)
    forward = jax.jit(lambda p, t, selection: ref.forward(p, t, model_of(cfg), selection=selection))
    plain, _, own = forward(p, t, None)
    other = [(r["chosen"] + 1) % cfg.n_experts for r in own]
    logits, _, routed = forward(p, t, other)
    np.testing.assert_array_equal(routed[0]["chosen"], other[0])
    np.testing.assert_array_equal(routed[0]["own"], own[0]["own"])
    assert float(jnp.abs(logits - plain).max()) > 1e-3
    assert (np.asarray(own[0]["margin"]) >= 0).all()


def test_the_optimizer_never_touches_the_selection_bias(first_step):
    """Weight decay would pull it to zero: a second step moves every entry by the rule's
    rate or not at all."""
    from ray_tpu.train import make_train_step

    _, after, _, tx, _ = first_step
    np.testing.assert_allclose(
        moe.balance_bias(jnp.zeros(4), jnp.array([1.0, 3.0, 2.0, 2.0]), 0.5), [0.5, -0.5, 0, 0])
    state2, _ = make_train_step(CFG, tx, donate=False)(after, {"tokens": tokens(CFG, (2, 33), seed=2)})
    moved = np.abs(np.asarray(state2.params["layers"]["router_bias"] - after.params["layers"]["router_bias"]))
    assert set(np.round(moved / CFG.moe_bias_update_rate, 3).ravel()) <= {0.0, 1.0}
