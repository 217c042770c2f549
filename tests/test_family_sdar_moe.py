"""The sdar_moe family (SDAR-30B-A3B) on the training path, at a small size on the CPU with
seeded weights: the block every layer is (rotated GQA whose q and k are normed a head, then
SOFTMAX-routed SwiGLU experts with no shared expert and no selection bias), an untied head,
and the block-diffusion objective: every sequence through the layers twice, noised and
clean, as one row under the block-diffusion mask, the loss over the masked positions at the
token's own position. The contract is tests/family_contract.py's, on the batch this family
makes (`Family.batch_of`); here is what the family alone has. (The mask's kernels against the
plain softmax: tests/test_flash_attention.py; the cell's whole step compiled for the chip: the last test here.)"""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import ROOT, Family, expert_shares, model_of, params, published_keys, system
from compiled_step_text import instructions, kernel_calls, pallas_grids, xla_remats
from ray_tpu.models import get_config, llama, moe
from ray_tpu.models.reference import sdar_moe as ref
from ray_tpu.train import block_diffusion_noise

del test_a_family_cells_step_scores_once_a_layer_and_fits_as_before  # noqa: F821  (this cell's whole step is a shape of its own: below)

CFG = get_config("sdar-tiny")


def _batch(cfg, t):
    """Seeded ids [B, T] -> the objective's batch over T - 1 positions: ids below the mask token,
    noised by the loader-side function on a generator seeded by the shape."""
    t = np.minimum(np.asarray(t[:, :-1]), cfg.diffusion_mask_token - 1)
    return block_diffusion_noise(np.random.default_rng(t.shape), t)


# ------------------------------------------------------------------- the shares

def _8_expert_shares(x):
    """What a chip of the deployment holds: 16 of 128 experts. 8 shares add up to the uncut
    expert part (no shared expert to count once), each walking one window of its own."""
    whole = dataclasses.replace(CFG, n_experts=128, moe_top_k=8)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, CFG.d_model))
    lp = moe.init_expert_weights(jax.random.PRNGKey(3), whole)
    assert set(lp) == {"router", "w_gate", "w_up", "w_down"}  # no selection bias, no shared expert
    want, routing, parts, counted = expert_shares(ref, whole, 8, x, lp)
    assert not np.asarray(parts[0]).any()  # (`expert_shares`' shared part: nothing)
    for i, aux in enumerate(counted):
        cfg = dataclasses.replace(whole, experts_held=(i, 8))
        lo, hi = moe.held_range(cfg)
        assert hi - lo == 16 and int(moe.windows_walked(
            aux["load"][lo:hi].sum().astype(jnp.int32), moe.window_rows(cfg, 512))) == 1
        np.testing.assert_array_equal(np.sort(aux["chosen"].reshape(2, 256, 8), -1), np.sort(routing["own"], -1))
    return want, parts, 3


# ------------------------------------------------------------------- the configuration

def _config_file(config, cfg, config_from):
    assert cfg.rope_theta == config["rope_theta"] == 1e6 and cfg.norm_eps == config["rms_norm_eps"] == 1e-6
    assert config["head_dim"] == cfg.head_dim == cfg.attn_head_dim == 128
    # the published widths, every one (program.n_heads 16 is for lib/modelcfg.py's check alone: all 32 / 4 heads are held)
    assert (cfg.d_model, cfg.heads_held, cfg.kv_heads_held, cfg.d_ff, cfg.d_ff_expert, cfg.n_experts, cfg.moe_top_k,
            cfg.max_seq_len) == (2048, 32, 4, 6144, 768, 128, 8, 32768)
    assert config["num_attention_heads"] == 32 and cfg.n_heads * cfg.head_dim == cfg.d_model
    published = config["published"]
    assert published == {"num_hidden_layers": 48, "num_experts": 128, "vocab_size": 151936}
    assert cfg.n_layers == config["num_hidden_layers"] == 5 and not cfg.layer_pattern and not cfg.n_dense_layers
    assert cfg.n_experts == published["num_experts"] and cfg.n_experts_held == config["num_experts"] == 16
    assert cfg.vocab_size == published["vocab_size"] // 8 == 18992 and cfg.mtp_depth == 0 and cfg.n_shared_experts == 0
    assert cfg.attn_qk_norm and cfg.attention_rotation and not cfg.tie_embeddings and not cfg.attn_output_gate
    assert (cfg.moe_scoring, cfg.moe_select_bias, cfg.moe_norm_topk, cfg.moe_route_scale) == ("softmax", False, True, 1.0)
    assert (cfg.diffusion_block, cfg.diffusion_mask_token) == (4, cfg.vocab_size - 1)  # the slice's last row
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    layer = sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes["layers"]))
    assert abs(layer - 94.64e6) < 0.01e6 and shapes["layers"]["wq"].shape == (5, 2048, 32, 128)
    assert shapes["layers"]["w_gate"].shape == (5, 16, 2048, 768) and shapes["layers"]["router"].shape == (5, 2048, 128)
    assert set(shapes) == {"embed", "layers", "final_norm", "lm_head"} and "router_bias" not in shapes["layers"]
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    trainer = config["trainer"]
    assert len(config["assumed"]) >= 6
    assert (trainer["batch"], trainer["seq"], trainer["parity_sequences"], trainer["mesh"]) == (1, 8192, 1, None)
    with open(os.path.join(ROOT, "benchmarks", "workloads", f"{FAMILY.cell}.json")) as f:
        assert json.load(f)["driver"] == "train_diffusion"
    # the program's own mapping of the published keys says the same, share apart
    hf = {**published_keys(config), "num_hidden_layers": 5}
    assert dataclasses.replace(config_from(hf), name=cfg.name, vocab_size=cfg.vocab_size, experts_held=(0, 8),
                               n_heads=16, attn_heads_held=(32, 4), remat_policy="full", dtype="bfloat16",
                               diffusion_mask_token=cfg.vocab_size - 1) == cfg


def _published(cfg):
    assert cfg.n_layers == 48 and not cfg.layer_pattern and cfg.head_dim == 128 and cfg.n_experts_held == 128
    assert (cfg.diffusion_block, cfg.diffusion_mask_token) == (4, 151669)
    layer = (cfg.n_params - 2 * 151936 * 2048 - 2048) / 48
    assert abs(layer / 623.1e6 - 1) < 0.001
    active = cfg.n_params - 48 * (128 - 8) * 3 * 2048 * 768  # 8 of 128 experts a token, and everything else
    assert abs(active / 3.35e9 - 1) < 0.03  # "30B-A3B"


HF_BASE = dict(model_type="sdar_moe", vocab_size=256, hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=24,
               intermediate_size=96, num_hidden_layers=3, hidden_act="silu", attention_bias=False, decoder_sparse_step=1,
               mlp_only_layers=[], max_window_layers=3, num_experts=16, num_experts_per_tok=4, moe_intermediate_size=40,
               norm_topk_prob=True, rms_norm_eps=1e-6, rope_theta=1000000, rope_scaling=None, sliding_window=None,
               use_sliding_window=False, max_position_embeddings=128, tie_word_embeddings=False)


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    layer = flops.layer_flops_per_token(model, 8192)
    projections = 2 * 2048 * 128 * (2 * 32 + 2 * 4)
    assert flops.kept_keys(model, 8192) == 8196  # a training token's two rows keep seq + block keys together
    assert layer["attention"] == 2 * projections + 2 * 32 * 2 * 128 * 8196  # both rows' projections: 75.5 + 134.3 MFLOP
    assert layer["experts"] == 2 * 2 * (2048 * 128 + 8 / 8 * flops.expert_params(model))  # router and routed share, both rows
    assert abs((layer["attention"] + layer["experts"] - 2 * 32 * 2 * 128 * 8196) / (2 * 47.71e6) - 1) < 0.001  # ISSUE 50's 2 x 47.7
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    total = sum(fwd.values())
    assert abs(3 * total / 3679e6 - 1) < 0.001  # five layers: 3.68 GFLOP a training token
    six = flops.train_flops_per_token({**model, "n_layers": 6}, 8192)
    assert abs(six / 4.37e9 - 1) < 0.001 and abs(six * 8192 / 35.8e12 - 1) < 0.001  # ISSUE 50's count at six layers
    core = 5 * 2 * 32 * 2 * 128 * 8196
    assert abs(core / total - 0.55) < 0.01 and abs(fwd["head"] / total - 0.063) < 0.005  # the masked attention: 55 % of the products
    assert flops.grouped_products_flops(model, 16384) == 3 * 2 * 16384 * 3 * 2048 * 768
    work = flops.block_diffusion_attention_step_work(model, 8192, 8192)
    assert work["flops"] == 5 * 6 * 2 * 8196 * 128 * 32 * 8192  # six products a head over 8192 x 8196 kept scores
    assert work["bytes"] == 5 * 6 * 2 * (2 * 8192) * 128 * (32 + 4)
    assert work["flops"] / 197e12 > work["bytes"] / 819e9  # bound by its products on a v5e
    # 288 tiles a head each way for 256.1 needed, by the kernels' own count at the cell's size
    from ray_tpu.ops.flash_attention import tile_counts

    counts = tile_counts(16384, 16384, False, 512, 512, block_diffusion=4)
    assert (counts.tiles_computed, round(counts.tiles_needed, 1)) == (288, 256.1)
    assert counts.tiles_needed * 512 * 512 * 4 * 128 * 3 * 32 * 5 == work["flops"]


def _made_up(flops, config, model):
    ops = {"%fusion.1 = bf16[4]": 0.04, "%fusion.2 = bf16[4]": 0.06, "%fusion.3 = s32[4]": 0.01, "%ragged-dot-none.3 = bf16[4]": 1.2,
           "%flash_attention_fwd_bd.4 = (bf16[4]) custom-call()": 0.25,
           "%transpose_jvp_flash_attention_bwd_dq_bd__.4 = bf16[4] custom-call()": 0.35,
           "%flash_attention_bwd_dkv_bd.4 = bf16[4] custom-call()": 0.4,
           "%flash_attention_fwd.1 = (bf16[4]) custom-call()": 0.2}
    scopes = {"%fusion.1 = bf16[4]": ["attn", "attn_bd"], "%fusion.2 = bf16[4]": ["attn"], "%fusion.3 = s32[4]": ["bd_rows", "embed"],
              "%flash_attention_fwd_bd.4 = (bf16[4]) custom-call()": ["attn", "attn_bd"],
              "%ragged-dot-none.3 = bf16[4]": ["moe_experts"]}
    result = {"traced_steps": 5, "tokens_per_step": 8192, "seq": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "series": {"step_s": [0.4, 0.5, 0.4], "masked_tokens": [4096.0, 2048.0, 6144.0]},
              "trace": {"busy_s": 4.0, "op_seconds": ops, "op_scopes": scopes}}
    work = flops.block_diffusion_attention_step_work(model, 8192, 8192)
    # a program without the scopes or the kernels (the parent of the PR that named them), a flops
    # file without the function, a rehearsal, a driver that keeps no such series: nothing to read, nothing raised
    mlp_only = {"busy_s": 2.0, "op_seconds": {"%fusion.9 = f32[4]": 2.0, "%flash_attention_fwd.1 = (bf16[4]) custom-call()": 0.2},
                "op_scopes": {"%fusion.9 = f32[4]": ["mlp"]}}
    bare = {"result": {**result, "trace": mlp_only, "series": {"step_s": [0.4]}}}
    kernels = {"pattern": "^%?\\w*flash_attention_\\w*_bd[\\w.]* = ", "work": "block_diffusion_attention_step_work"}
    # the trace of a program whose backward is ONE kernel a call (PR 53; the one above is its parent's)
    one = {"result": {**result, "trace": {"busy_s": 4.0, "op_scopes": scopes, "op_seconds": {
        **{op: s for op, s in ops.items() if "_bwd_" not in op},
        "%transpose_jvp_flash_attention_bwd_dkv_dq_bd__.4 = (bf16[4]) custom-call()": 0.5}}}}
    return result, [
        ("train_kernel_roofline", "train_attn_bd_roofline_pct", one, 100 * 5 * work["flops"] / 197e12 / 0.75),
        ("trace_op_share", "train_attn_bwd_kernel_pct", {}, 100 * (0.35 + 0.4) / 4.0),
        ("trace_op_share", "train_attn_bwd_kernel_pct", one, 100 * 0.5 / 4.0),
        ("train_kernel_roofline", "train_attn_bd_roofline_pct", {}, 100 * 5 * work["flops"] / 197e12 / 1.0),
        ("trace_scope_share", "train_attn_bd_pct", {}, 100 * (0.04 + 0.25) / 4.0),
        ("trace_scope_share", "train_bd_rows_pct", {}, 100 * 0.01 / 4.0),
        ("series_stat", "train_bd_masked_share_pct", {}, 50.0),
        ("train_mfu_family", "train_mfu_bd_moe_pct", {}, 100 * flops.train_flops_per_token(model, 8192) * 8192 / 0.4 / 197e12),
        ("train_kernel_roofline", kernels, bare, None),
        ("trace_scope_share", {"pattern": "^attn_bd$"}, bare, None),
        ("trace_scope_share", {"pattern": "^bd_rows$"}, bare, None),
        ("series_stat", {"series": "masked_tokens", "stat": "mean"}, bare, None),
        ("train_kernel_roofline", {**kernels, "work": "no_such_work"}, {}, None),
        ("train_kernel_roofline", kernels, {"rehearse": True}, None),
        ("train_kernel_roofline", kernels, {"config": {"trainer": {"flops": "flops_afmoe"}}}, None)]


FAMILY = Family(
    model_type="sdar_moe", tiny=CFG, cell="sdar30b-train-ep8share-s8192", config="sdar-30b-a3b-train-ep8", index=7,
    unsettle=(("layers", "q_head_norm", 0.2, 1.0), ("layers", "k_head_norm", 0.2, 1.0)),  # a head's norm weights that are not one
    cases=(("held0", CFG, 1),  # everything held
           ("held1", dataclasses.replace(CFG, experts_held=(1, 4)), 1),  # a quarter of the experts
           ("blocks-of-8-held2", dataclasses.replace(CFG, diffusion_block=8, experts_held=(0, 2)), 1)),
    batch=3, least_leaves=15, float32_leaves=frozenset(), recurrent=None,
    shares={"8_expert_shares_seeded": _8_expert_shares},
    scopes=frozenset({"attn_bd", "bd_rows", "moe_router", "moe_experts", "attn", "mlp", "lm_head", "embed", "loss", "attn_in_proj", "attn_head_norm", "attn_core", "attn_out_proj", "moe_dispatch", "moe_combine", "layer_stack"}),
    mixer_scopes=frozenset({"attn_bd"}), outer=frozenset({"attn"}),
    absent=frozenset({"sconv", "kda_scan", "attn_window", "attn_full", "moe_shared", "attn_gate"}),
    rehearsal=("3000000007", 20, frozenset({"loss", "ce_loss"}), 2 * 64),
    pairs={  # published key -> ModelConfig field (n_experts: once more under `program`; the heads: _config_file)
        "hidden_size": "d_model", "num_key_value_heads": "n_kv_heads", "head_dim": "attn_head_dim",
        "vocab_size": "vocab_size", "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
        "max_position_embeddings": "max_seq_len", "num_experts_per_tok": "moe_top_k", "norm_topk_prob": "moe_norm_topk",
        "num_hidden_layers": "n_layers"},
    cell_params=550.98e6, config_file=_config_file, published_params=30.53e9, published=_published,
    hf_base=HF_BASE, hf_to_tiny=dict(name="sdar-tiny", dtype="float32"),
    hf_refused=((dict(norm_topk_prob=False), "not normalised"), (dict(attention_bias=True), "attention_bias"),
                (dict(decoder_sparse_step=2), "decoder_sparse_step"), (dict(mlp_only_layers=[0]), "mlp_only_layers"),
                (dict(use_sliding_window=True), "use_sliding_window"),
                (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), "rope_scaling"),
                (dict(hidden_act="gelu"), "hidden_act"), (dict(num_experts=0), "routed experts")),
    llm_refuses=("generation by diffusion over blocks", "dropless"),
    flops_parts=frozenset({"attention", "experts", "head"}), step_flops=30.138e12, flops_share=_flops_share,
    made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct", "train_mfu_bd_moe_pct", "train_attn_bd_roofline_pct", "train_attn_bd_pct",
        "train_bd_rows_pct", "train_bd_masked_share_pct",
        # PR 52: the attention part's pieces, the expert layer's dispatch and combine, the layer loop's own
        "train_attn_proj_pct", "train_attn_core_pct", "train_moe_dispatch_pct", "train_moe_combine_pct",
        "train_layer_stack_pct", "train_attn_passes_pct"}),
    own_metrics=("train_mfu_bd_moe_pct", "train_attn_bd_roofline_pct", "train_attn_bd_pct", "train_bd_rows_pct",
                 "train_bd_masked_share_pct"),
    batch_of=_batch,
)


# ------------------------------------------------------------------- the family's own

def _block_causal_hidden(p, ids, model):
    """A model of the family's layers over ONE plain sequence under a block-causal mask (a
    position sees every block up to and including its own, both ways inside the block), in
    plain jax.numpy with no doubled row and no 2L mask: -> (the last layer's output behind the
    final norm [S, D], [the experts each layer chose [S, k]])."""
    s, block = ids.shape[0], model["diffusion_block"]
    x = p["embed"][ids][None]
    i, j = np.arange(s)[:, None] // block, np.arange(s)[None, :] // block
    chosen = []
    for n in range(p["layers"]["router"].shape[0]):
        lp = jax.tree.map(lambda a: a[n], p["layers"])  # noqa: B023
        u = ref._rms_norm(x, lp["attn_norm"], model["norm_eps"])
        q, k, v = (jnp.einsum("bsd,dhk->bshk", u, lp[name]) for name in ("wq", "wk", "wv"))
        q, k = ref._rms_norm(q, lp["q_head_norm"], model["norm_eps"]), ref._rms_norm(k, lp["k_head_norm"], model["norm_eps"])
        q, k = (ref._rope(a, jnp.arange(s), model["rope_theta"]) for a in (q, k))
        k, v = (jnp.repeat(a, q.shape[2] // a.shape[2], axis=2) for a in (k, v))
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(q.shape[-1])
        probs = jax.nn.softmax(jnp.where(j <= i, scores, -jnp.inf), axis=-1)
        x = x + jnp.einsum("bshk,hkd->bsd", jnp.einsum("bhqk,bkhd->bqhd", probs, v), lp["wo"])
        y, routing = ref.expert_layer(ref._rms_norm(x, lp["mlp_norm"], model["norm_eps"]), lp, model)
        x = x + y
        chosen.append(routing["own"][0])
    return ref._rms_norm(x, p["final_norm"], model["norm_eps"])[0], chosen


@pytest.mark.parametrize("block", [4, 8])
def test_the_doubled_row_is_a_clean_pass_and_a_short_pass_a_noised_block(block):
    """The reference's doubled row against an oracle that never builds the 2L mask: the clean
    half is block-causal attention over x0 alone, and noised block b is the last block of one
    short block-causal pass over [x0's blocks before b ; xt's block b] at positions 0 .. (b + 1)
    Bk - 1: losses at every position of the noised half, and what every row of both halves chose."""
    cfg = dataclasses.replace(CFG, diffusion_block=block)
    model, p = model_of(cfg), params(cfg, FAMILY.unsettle)
    batch = _batch(cfg, jax.random.randint(jax.random.PRNGKey(3), (2, 4 * block + 1), 0, 256))  # four blocks a sequence
    length = batch["tokens"].shape[1]
    losses, _, routings = jax.jit(lambda p, b: ref.position_losses(p, b, model))(p, batch)
    assert losses.shape == (2, length) and routings[0]["own"].shape == (2, 2 * length, cfg.moe_top_k)
    for b in range(2):
        x0 = batch["tokens"][b]
        xt = np.where(batch["masked"][b], cfg.diffusion_mask_token, x0)
        assert 0 < batch["masked"][b].sum() < length
        _, clean = _block_causal_hidden(p, x0, model)
        for layer, mine in enumerate(clean):  # the clean half of the row is the clean pass
            np.testing.assert_array_equal(np.sort(routings[layer]["own"][b, length:], -1), np.sort(mine, -1))
        for at in range(0, length, block):
            hidden, chosen = _block_causal_hidden(p, np.concatenate([x0[:at], xt[at:at + block]]), model)
            logp = jax.nn.log_softmax(hidden[at:] @ p["lm_head"], axis=-1)
            want = -jnp.take_along_axis(logp, jnp.asarray(x0[at:at + block])[:, None], axis=-1)[:, 0]
            np.testing.assert_allclose(losses[b, at:at + block], want, atol=3e-5, rtol=1e-5)
            for layer, mine in enumerate(chosen):
                np.testing.assert_array_equal(np.sort(routings[layer]["own"][b, at:at + block], -1), np.sort(mine[at:], -1))


def test_the_loss_is_the_masked_positions_weighted_and_counted():
    """loss = sum of masked CE / p_mask over B x L; ce_loss their plain mean; masked_tokens
    their count: the system's metrics from the reference's per-position losses, by hand. A
    batch whose noise hides nothing has no loss and no gradient, finitely."""
    p, batch = params(CFG, FAMILY.unsettle), FAMILY.batch_for(CFG, (3, 41))
    every, _, _ = jax.jit(lambda p, b: ref.position_losses(p, b, model_of(CFG)))(p, batch)
    (loss, m), _ = system(p, batch, CFG)
    masked = batch["masked"]
    np.testing.assert_allclose(loss, (np.asarray(every) * masked / batch["p_mask"][:, None]).sum() / masked.size, rtol=1e-6)
    np.testing.assert_allclose(m["ce_loss"], np.asarray(every)[masked].mean(), rtol=1e-6)
    assert float(m["masked_tokens"]) == masked.sum() and float(m["tokens"]) == masked.size
    assert m["experts_chosen"].shape == (CFG.n_layers, 3 * 2 * 40, CFG.moe_top_k)
    nothing = {**batch, "masked": np.zeros_like(masked)}
    (loss, m), grads = system(p, nothing, CFG)
    assert float(loss) == 0.0 == float(m["ce_loss"]) == float(m["masked_tokens"])
    assert all(np.isfinite(np.asarray(g)).all() and not np.asarray(g).any() for g in jax.tree.leaves(grads))


def test_the_loaders_noise_is_the_linear_schedule_from_the_generator():
    """`block_diffusion_noise`: one t a sequence, p = (1 - eps) t + eps, each position masked at
    p; the same generator state gives the same batch; over many sequences half the positions."""
    ids = np.zeros((4096, 64), np.int32)
    a, b = (block_diffusion_noise(np.random.default_rng([7, 1]), ids) for _ in range(2))
    assert set(a) == {"tokens", "masked", "p_mask"} and a["masked"].dtype == bool and a["p_mask"].dtype == np.float32
    for name in a:
        np.testing.assert_array_equal(a[name], b[name])
    assert 1e-3 <= a["p_mask"].min() < 0.01 and 0.99 < a["p_mask"].max() <= 1.0
    assert abs(a["masked"].mean() - 0.5005) < 0.01 and abs(np.mean(a["masked"].mean(-1) - a["p_mask"])) < 0.003
    assert np.corrcoef(a["masked"].mean(-1), a["p_mask"])[0, 1] > 0.97


def test_what_the_objective_cannot_run_under_is_refused_by_name():
    p, batch = params(CFG), FAMILY.batch_for(CFG, (2, 33))
    for bad, what in ((dict(mtp_depth=1), "MTP modules"), (dict(pipeline_stages=2), "pipeline stages"),
                      (dict(diffusion_block=3), "power of two"), (dict(diffusion_mask_token=256), "mask token")):
        with pytest.raises(NotImplementedError, match=what):
            dataclasses.replace(CFG, **bad)
    with pytest.raises(NotImplementedError, match="loss mask"):
        jax.eval_shape(lambda p: llama.loss_fn(p, {**batch, "loss_mask": batch["masked"]}, CFG), p)
    with pytest.raises(NotImplementedError, match="doubled row"):  # 30 positions: not whole blocks twice
        jax.eval_shape(lambda p: llama.forward(p, batch["tokens"][:, :30], CFG), p)
    lp = jax.tree.map(lambda a: a[0], p["layers"])
    x, positions = jnp.zeros((1, 16, 64)), jnp.tile(jnp.arange(8), 2)[None]
    with pytest.raises(NotImplementedError, match="block-diffusion attention"):
        llama._block(x, lp, CFG, positions, jnp.zeros((1, 16), jnp.int32))
    with pytest.raises(NotImplementedError, match="ring / Ulysses"):
        llama._block(x, lp, dataclasses.replace(CFG, attention_impl="ring"), positions, None)
    with pytest.raises(NotImplementedError, match="softmax scores with a selection bias"):
        moe.route(x[0], lp["router"], jnp.zeros((16,)), CFG)
    with pytest.raises(NotImplementedError, match="sigmoid or softmax"):
        moe.route(x[0], lp["router"], None, dataclasses.replace(CFG, moe_scoring="tanh"))


def test_the_softmax_routers_backward_is_the_plain_forms_and_a_rematerialised_layer_chooses_once():
    """`_score_and_pick` with softmax scores: the gradients of x and the router through the
    gates against `jax.grad` of the plain form (softmax over all experts, top-k, the chosen
    normalised), and under remat the layer neither scores nor chooses again: the choice, the
    scores and the picked scores are kept by name."""
    cfg = dataclasses.replace(CFG, n_experts=32, moe_top_k=4)
    x = jax.random.normal(jax.random.PRNGKey(0), (48, 64))
    w = jax.random.normal(jax.random.PRNGKey(1), (64, 32)) * 0.3
    cot = jax.random.normal(jax.random.PRNGKey(2), (48, 4))

    def plain(x, w):
        scores = jax.nn.softmax(x @ w, axis=-1)
        picked, idx = jax.lax.top_k(scores, 4)
        return jnp.sum(picked / picked.sum(-1, keepdims=True) * cot), idx

    def mine(x, w):
        idx, gates = moe.route(x, w, None, cfg)
        return jnp.sum(gates * cot), idx

    (want, idx), grads = jax.value_and_grad(plain, argnums=(0, 1), has_aux=True)(x, w)
    (got, chose), mine_grads = jax.value_and_grad(mine, argnums=(0, 1), has_aux=True)(x, w)
    np.testing.assert_array_equal(chose, idx)
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for a, b in zip(mine_grads, grads):
        assert float(jnp.abs(b).max()) > 1e-3
        np.testing.assert_allclose(a, b, atol=2e-6 * float(jnp.abs(b).max()))
    # a whole expert layer under remat `full`: one top_k and one softmax in the gradient's program
    lp = moe.init_expert_weights(jax.random.PRNGKey(3), cfg)
    layer = llama._maybe_remat(lambda x, lp: moe.expert_layer(x, lp, cfg)[0], cfg)
    jaxpr = jax.make_jaxpr(jax.grad(lambda x, lp: jnp.sum(layer(x, lp)), argnums=(0, 1)))(x, lp).jaxpr

    def count(jaxpr, name):
        return sum((e.primitive.name == name) + sum(count(sub, name) for sub in jax.core.jaxprs_in_params(e.params))
                   for e in jaxpr.eqns)

    assert count(jaxpr, "top_k") == 1 and count(jaxpr, "exp") == 1


def test_the_step_trains_every_leaf_and_no_leaf_is_a_selection_bias(first_step):  # noqa: F811
    import optax

    state, after, metrics, _, batch = first_step
    assert set(state.params) == {"embed", "layers", "final_norm", "lm_head"}
    assert set(state.params["layers"]) == {"attn_norm", "wq", "wk", "wv", "wo", "q_head_norm", "k_head_norm", "mlp_norm",
                                           "router", "w_gate", "w_up", "w_down"}
    assert float(metrics["masked_tokens"]) == batch["masked"].sum() > 0
    assert np.asarray(metrics["expert_load"]).shape == (3, 16) and np.asarray(metrics["experts_chosen"]).shape == (3, 2 * 64, 4)
    mu = optax.tree_utils.tree_get(after.opt_state, "mu")  # (the schedule's first rate is 0: read the moments)
    assert all(np.abs(np.asarray(a)).max() > 0 for a in jax.tree.leaves(mu))


def test_the_drivers_placement_deals_the_mask_tokens_experts_round_the_shares():
    """`benchmarks/drivers/train_diffusion.py:place_hot_experts`: every layer's router columns are a
    permutation of the seeded ones (a relabelling), and the experts the mask token's row scores
    highest get the first labels of the group's shares, dealt round: 8 over 8 shares is one a share,
    so the share this chip holds has exactly one of them whatever the seed."""
    from benchmarks.drivers.train_diffusion import place_hot_experts

    cfg = dataclasses.replace(CFG, n_experts=128, moe_top_k=8, experts_held=(3, 8))
    for seed in range(4):
        p = llama.init(jax.random.PRNGKey(seed), cfg)
        placed, before = place_hot_experts(p, cfg)
        assert len(before) == cfg.n_layers and all(0 <= k <= 8 for k in before)
        row = ref._rms_norm(p["embed"][cfg.diffusion_mask_token], jnp.ones((64,)), cfg.norm_eps)
        for layer in range(cfg.n_layers):
            old, new = np.asarray(p["layers"]["router"][layer]), np.asarray(placed["layers"]["router"][layer])
            np.testing.assert_array_equal(np.sort(old, axis=1), np.sort(new, axis=1))  # the same columns, relabelled
            assert sorted(np.argsort(-np.asarray(row @ new))[:8]) == [16 * share for share in range(8)]
            held = np.argsort(-np.asarray(row @ old))[:8]
            assert before[layer] == ((held >= 48) & (held < 64)).sum()
        assert all(a is b for a, b in zip(jax.tree.leaves({k: v for k, v in placed.items() if k != "layers"}),
                                          jax.tree.leaves({k: v for k, v in p.items() if k != "layers"})))
    assert len({tuple(place_hot_experts(llama.init(jax.random.PRNGKey(s), cfg), cfg)[1]) for s in range(4)}) > 1  # the lottery it removes


def test_the_block_diffusion_cells_step_compiles_inside_its_memory_and_walks_288_tiles_a_head(cell_step, on_tpu):
    """The whole step of `sdar30b-train-ep8share-s8192` as its configuration file states it (five
    layers; the batch a loader makes: tokens, masked, p_mask), compiled for the described chip:
    [1, 16384] rows through the layers under the block-diffusion mask. The two kernels (forward;
    the ONE backward kernel, PR 53) carry `_bd` behind their names, which the accepted kernel metrics and the cell's own roofline
    metric find; the forward kernel runs once a layer (its `out` and logsumexp kept under
    `full`); three router products a layer and the pick's loops, nothing made again by XLA, no
    attention on an XLA path; 15.18 of 15.75 GB (PR 50: six layers were 17.31 and do not fit).
    The grids: a step a q tile forward and backward (K/V of the doubled row are one span, and so
    dK and dV of a kv head stay in VMEM); 288 tiles a head each way by `tile_counts`."""
    from ray_tpu.ops import flash_attention as fa

    cfg, trainer, text, memory = cell_step.cfg, cell_step.trainer, cell_step.text, cell_step.memory
    b, n = trainer["batch"], trainer["seq"]
    assert cfg.remat and cfg.remat_policy == "full" and (cfg.diffusion_block, cfg.n_layers, b, n) == (4, 5, 1, 8192)
    assert set(cell_step.args[1]) == {"tokens", "masked", "p_mask"} and cell_step.args[1]["tokens"].shape == (b, n)
    for path, count in (("train_attn_fwd_kernel_pct", 1), ("train_attn_bwd_kernel_pct", 1), ("train_attn_bd_roofline_pct", 2)):
        with open(os.path.join(ROOT, "benchmarks", "metrics", f"{path}.json")) as f:
            rx = re.compile(json.load(f)["args"]["pattern"])
        kernels = [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln and rx.search(ln.strip())]
        assert len(kernels) == count and all("_bd" in ln.split(" = ")[0] for ln in kernels), (path, kernels)
    assert kernel_calls(text, "flash_attention_fwd_bd") == kernel_calls(text, "flash_attention_bwd_dkv_dq_bd") == (1, 0)
    assert kernel_calls(text, "flash_attention_bwd_dq_bd") == kernel_calls(text, "flash_attention_bwd_dkv_bd") == (0, 0)
    assert kernel_calls(text, "flash_attention_fwd") == (0, 0)
    assert len(instructions(text, "convolution", "moe_router")) == 3 and len(instructions(text, "while", "moe_router")) == 2
    assert not xla_remats(text) and cell_step.fallbacks == 0
    assert abs(memory.argument_size_in_bytes - 12 * cfg.n_params) < 1e7 and cfg.n_params == 550984960
    assert memory.temp_size_in_bytes < (8.57 + 0.15) * 1e9
    assert 0.25 * 15.75e9 < memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9  # what a v5e program may use
    t = fa._tiling(2 * n, 2 * n, 512, 512, 128, 2, 8)
    assert (t.kv_span, t.q_span) == (16384, 1024)
    grids = pallas_grids(jax.make_jaxpr(cell_step.step._jitted)(*cell_step.args).jaxpr)
    assert fa._fuses(t, 2 * n) and grids.count((b, 32, 32, 1)) == 2 and (b, 4, 32, 16) not in grids  # forward; backward (PR 53)
    for kernel, heads in (("fwd", 1), ("dq", 1), ("dkv", 8)):  # ("dq": the one backward kernel's walk too)
        counts = fa.tile_counts(2 * n, 2 * n, False, 512, 512, n_rep=heads, kernel=kernel, block_diffusion=4)
        assert counts.tiles_computed == heads * 288 and round(counts.tiles_needed / heads, 1) == 256.1
