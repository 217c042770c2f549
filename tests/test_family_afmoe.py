"""The afmoe family (Trinity-Mini) on the training path, at a small size on the CPU with
seeded weights: every published layer two parts of a pattern (attention, then a feed-forward
part), each between a norm on its input and one on its output; attention inside a window
and rotated three to one with full attention that is not rotated, both gated a channel and
normed a head; a leading dense layer, then sigmoid-routed SwiGLU experts at 8 of 128 beside
a shared one; the embedding scaled; and the share of a layer's experts a chip holds. The
contract is tests/family_contract.py's; here is what the family alone has. (The windowed
kernels against the plain softmax: tests/test_flash_window.py; compiled for the chip:
tests/test_tpu_compile.py; the whole step: `Family.cell_step` below.)"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import Family, config_from, expert_shares, model_of, params, published_keys, tokens
from ray_tpu.models import attn, get_config, llama, moe
from ray_tpu.models.config import LAYER_KINDS
from ray_tpu.models.reference import afmoe as ref

CFG = get_config("trinity-tiny")


def _pattern(pattern, held):
    return dataclasses.replace(CFG, layer_pattern=pattern, n_layers=len(pattern), experts_held=held)


# ------------------------------------------------------------------- the shares

def _16_expert_shares(load):
    def shares(x):
        """What a chip of the deployment holds: 8 of 128 experts. 16 shares add up to the uncut
        expert part with the shared expert counted once; also where a bias sends every token to
        the first share's experts, which then walks sixteen windows and the others one of their own."""
        whole = dataclasses.replace(CFG, n_experts=128, moe_top_k=8)
        x = jax.random.normal(jax.random.PRNGKey(2), (2, 256, CFG.d_model))
        lp = moe.init_expert_weights(jax.random.PRNGKey(3), whole)
        assert set(lp) == {"router", "router_bias", "w_gate", "w_up", "w_down", "shared_gate", "shared_up", "shared_down"}
        lp["router_bias"] = 0.05 * jax.random.normal(jax.random.PRNGKey(4), (128,))
        if load == "all_on_one_share":
            lp["router_bias"] = lp["router_bias"].at[:8].add(10.0)
        want, routing, parts, counted = expert_shares(ref, whole, 16, x, lp)
        assert float(jnp.abs(parts[0]).max()) > 1e-3  # the shared expert, once
        windows = []
        for i, aux in enumerate(counted):
            cfg = dataclasses.replace(whole, experts_held=(i, 16))
            lo, hi = moe.held_range(cfg)
            windows.append(int(moe.windows_walked(aux["load"][lo:hi].sum().astype(jnp.int32),
                                                  moe.window_rows(cfg, 512))))
        if load == "all_on_one_share":
            assert windows[0] > 1 and windows[1:] == [1] * 15 and not np.asarray(parts[2]).any()
            assert set(np.asarray(routing["own"]).ravel()) == set(range(8))
            return want, parts, 1
        assert windows == [1] * 16
        return want, parts, 2
    return shares


# ------------------------------------------------------------------- the configuration

def _config_file(config, cfg, config_from):
    assert cfg.rope_theta == config["rope_theta"] == 10000 and cfg.norm_eps == config["rms_norm_eps"] == 1e-5
    assert config["head_dim"] == cfg.head_dim == cfg.attn_head_dim == 128
    # the published widths, every one (program.n_heads 16 is for lib/modelcfg.py's check alone: all 32 / 4 heads are held)
    assert (cfg.d_model, cfg.heads_held, cfg.kv_heads_held, cfg.d_ff, cfg.d_ff_expert, cfg.n_experts, cfg.moe_top_k,
            cfg.attn_window, cfg.max_seq_len) == (2048, 32, 4, 6144, 1024, 128, 8, 2048, 131072)
    assert config["num_attention_heads"] == 32 and cfg.n_heads * cfg.head_dim == cfg.d_model
    # what is held here, and of what: the chip's share of a group that shares each layer
    published = config["published"]
    assert config["layer_types"] == [published["layer_types"][i] for i in (1, 4, 5, 6, 7)] == ["sliding_attention"] * 4 + ["full_attention"]
    assert cfg.layer_pattern == "W-WEWEWE*E" and cfg.n_layers == 2 * config["num_hidden_layers"] == 10
    assert config["num_dense_layers"] == 1 and published["num_dense_layers"] == 2
    assert cfg.n_experts == published["num_experts"] and cfg.n_experts_held == config["num_experts"] == 8
    assert cfg.vocab_size == published["vocab_size"] // 8 == 25024 and cfg.mtp_depth == 0 and cfg.n_shared_experts == 1
    assert cfg.attn_qk_norm and cfg.attn_output_gate and cfg.part_post_norm and not cfg.tie_embeddings
    assert not cfg.attention_rotation and cfg.embed_scale == 2048 ** 0.5 and cfg.moe_route_scale == 2.826
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    count = lambda stack: sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes[stack]))  # noqa: E731
    assert abs(count("window_layers") - 27.27e6) < 0.01e6 and abs(count("attn_layers") - 27.27e6) < 0.01e6
    assert abs(count("mlp_layers") - 37.75e6) < 0.01e6 and abs(count("layers") - 128 - 56.89e6) < 0.01e6
    assert shapes["window_layers"]["wq"].shape == (4, 2048, 32, 128) and shapes["attn_layers"]["wk"].shape == (1, 2048, 4, 128)
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    trainer = config["trainer"]
    assert len(config["assumed"]) >= 8
    assert (trainer["batch"], trainer["seq"], trainer["parity_sequences"], trainer["mesh"]) == (2, 16384, 2, None)
    # the program's own mapping of the published keys says the same, share apart
    hf = {**published_keys(config), "num_hidden_layers": 5, "layer_types": config["layer_types"], "num_dense_layers": 1}
    assert dataclasses.replace(config_from(hf), name=cfg.name, vocab_size=cfg.vocab_size, experts_held=(0, 16),
                               n_heads=16, attn_heads_held=(32, 4), remat_policy="full", dtype="bfloat16") == cfg


def _published(cfg):
    assert cfg.n_layers == 64 and cfg.layer_pattern[:16] == "W-W-WE*EWEWEWE*E" and cfg.layer_pattern.count("*") == 8
    assert cfg.layer_pattern.count("W") == 24 and cfg.layer_pattern.count("-") == 2 and cfg.head_dim == 128
    assert cfg.attn_window == 2048 and cfg.embed_scale == 2048 ** 0.5 and cfg.moe_bias_update_rate == 0.001
    active = cfg.n_params - 30 * (128 - 8) * 3 * 2048 * 1024  # 8 of 128 experts a token, and everything else
    assert abs(active / 3.5e9 - 1) < 0.03  # "26B-A3B"


HF_BASE = dict(model_type="afmoe", vocab_size=256, hidden_size=64, num_attention_heads=4, num_key_value_heads=2, head_dim=24,
               intermediate_size=96, num_hidden_layers=5, global_attn_every_n_layers=4, hidden_act="silu",
               layer_types=["sliding_attention"] * 4 + ["full_attention"], sliding_window=11, num_dense_layers=1,
               num_experts=16, num_experts_per_tok=3, num_shared_experts=1, moe_intermediate_size=40, score_func="sigmoid",
               route_norm=True, route_scale=2.826, n_group=1, topk_group=1, num_expert_groups=1, num_limited_groups=1,
               load_balance_coeff=0.001, mup_enabled=True, rms_norm_eps=1e-5, rope_theta=10000, rope_scaling=None,
               max_position_embeddings=128, tie_word_embeddings=False, use_grouped_mm=True)


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    layer = flops.layer_flops_per_token(model, 16384)
    projections = 2 * 2048 * 128 * (3 * 32 + 2 * 4)
    assert flops.band_context(model, 16384) == (2048 * 2049 / 2 + (16384 - 2048) * 2048) / 16384  # 1,920.06 keys a query
    assert layer["W"] == projections + 2 * 32 * 2 * 128 * flops.band_context(model, 16384)
    assert layer["*"] == projections + 2 * 32 * 2 * 128 * 8192.5 and layer["-"] == 2 * 3 * 2048 * 6144
    assert layer["E"] == 2 * (2048 * 128 + (1 + 8 / 16) * flops.expert_params(model))  # the shared expert whole
    fwd = flops.forward_flops_per_token(model, (16384 + 1) / 2)
    total = sum(fwd.values())
    assert abs(3 * total / 2365e6 - 1) < 0.001  # the issue's count, MFLOP a token
    assert abs((fwd["W"] + fwd["*"]) / total - 0.68) < 0.01 and abs(fwd["head"] / total - 0.13) < 0.005
    assert flops.band_context(model, 1024) == 512.5  # a sequence inside the window is the triangle
    assert flops.grouped_products_flops(model, 4 * 16384) == 3 * 2 * 65536 * 3 * 2048 * 1024
    band = flops.window_attention_step_work(model, 32768, 16384)
    assert band["flops"] == 4 * 3 * 32768 * 2 * 32 * 2 * 128 * flops.band_context(model, 16384)
    assert band["bytes"] == 4 * 2 * 32768 * 128 * (6 * 32 + 6 * 4)
    assert band["flops"] / 197e12 > band["bytes"] / 819e9  # bound by its products on a v5e


def _made_up(flops, config, model):
    ops = {"%fusion.1 = bf16[4]": 0.04, "%fusion.2 = bf16[4]": 0.06, "%ragged-dot-none.3 = bf16[4]": 1.2,
           "%flash_attention_fwd_window.4 = (bf16[4]) custom-call()": 0.03, "%flash_attention_fwd_window.5 = (bf16[4]) custom-call()": 0.03,
           "%transpose_jvp_flash_attention_bwd_dq_window__.4 = bf16[4] custom-call()": 0.05,
           "%flash_attention_bwd_dkv_window.4 = bf16[4] custom-call()": 0.09,
           "%flash_attention_fwd.1 = (bf16[4]) custom-call()": 0.2, "%flash_attention_bwd_dq.1 = bf16[4] custom-call()": 0.3}
    scopes = {"%fusion.1 = bf16[4]": ["attn", "attn_window"], "%fusion.2 = bf16[4]": ["attn", "attn_full"],
              "%flash_attention_fwd_window.4 = (bf16[4]) custom-call()": ["attn", "attn_window"],
              "%flash_attention_fwd.1 = (bf16[4]) custom-call()": ["attn", "attn_full"],
              "%ragged-dot-none.3 = bf16[4]": ["moe_experts"]}
    result = {"traced_steps": 5, "tokens_per_step": 32768, "seq": 16384, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "trace": {"busy_s": 4.0, "op_seconds": ops, "op_scopes": scopes}}
    band = flops.window_attention_step_work(model, 32768, 16384)
    # a program without the scopes or the kernels (the parent of the PR that named them), a flops
    # file without the function, a rehearsal: nothing to read, nothing raised
    mlp_only = {"busy_s": 2.0, "op_seconds": {"%fusion.9 = f32[4]": 2.0, "%flash_attention_fwd.1 = (bf16[4]) custom-call()": 0.2},
                "op_scopes": {"%fusion.9 = f32[4]": ["mlp"]}}
    bare = {"result": {**result, "trace": mlp_only}}
    kernels = {"pattern": "^%?\\w*flash_attention_\\w*window", "work": "window_attention_step_work"}
    # the trace of a program whose backward is ONE kernel a call (PR 53; the one above is its parent's)
    one = {"result": {**result, "trace": {"busy_s": 4.0, "op_scopes": scopes, "op_seconds": {
        **{op: s for op, s in ops.items() if "_bwd_" not in op},
        "%transpose_jvp_flash_attention_bwd_dkv_dq_window__.4 = (bf16[4]) custom-call()": 0.10,
        "%transpose_jvp_flash_attention_bwd_dkv_dq__.1 = (bf16[4]) custom-call()": 0.2}}}}
    return result, [
        ("train_kernel_roofline", "train_attn_window_roofline_pct", {}, 100 * 5 * band["flops"] / 197e12 / 0.20),
        ("train_kernel_roofline", "train_attn_window_roofline_pct", one, 100 * 5 * band["flops"] / 197e12 / 0.16),
        ("trace_op_share", "train_attn_bwd_kernel_pct", {}, 100 * (0.05 + 0.09 + 0.3) / 4.0),
        ("trace_op_share", "train_attn_bwd_kernel_pct", one, 100 * (0.10 + 0.2) / 4.0),
        ("trace_scope_share", "train_attn_window_pct", {}, 100 * (0.04 + 0.03) / 4.0),
        ("trace_scope_share", "train_attn_full_pct", {}, 100 * (0.06 + 0.2) / 4.0),
        ("train_kernel_roofline", kernels, bare, None),
        ("trace_scope_share", {"pattern": "^attn_window"}, bare, None),
        ("trace_scope_share", {"pattern": "^attn_full"}, bare, None),
        ("train_kernel_roofline", {**kernels, "work": "no_such_work"}, {}, None),
        ("train_kernel_roofline", kernels, {"rehearse": True}, None),
        ("train_kernel_roofline", kernels, {"config": {"trainer": {"flops": "flops_lfm2_moe"}}}, None)]


_NORMS = tuple((stack, leaf, 0.2, 1.0) for stack, leaves in (
    ("window_layers", ("q_head_norm", "k_head_norm", "attn_post_norm")), ("attn_layers", ("q_head_norm", "k_head_norm", "attn_post_norm")),
    ("layers", ("mlp_post_norm",)), ("mlp_layers", ("mlp_post_norm",))) for leaf in leaves)

FAMILY = Family(
    model_type="afmoe", tiny=CFG, cell="trinitymini-train-ep16share-s16384", config="trinity-mini-train-ep16", index=6,
    unsettle=_NORMS,  # norm weights that are not one: a head's, and those behind the parts
    cases=(("W-WEWEWE*E-held0", _pattern("W-WEWEWE*E", (0, 1)), 1),  # the cell's: a dense layer and one period, everything held
           ("W-WEWEWE*E-held1", _pattern("W-WEWEWE*E", (1, 4)), 1),  # a quarter of the experts
           ("WE*EWE*E-held2", _pattern("WE*EWE*E", (0, 2)), 2),      # two periods of WE*E: the scan over periods
           ("W*W--held3", _pattern("W*W-", (0, 1)), 1)),             # no expert part at all
    batch=3, least_leaves=20, float32_leaves=frozenset(), recurrent=None,
    shares={"16_expert_shares_seeded": _16_expert_shares("seeded"),
            "16_expert_shares_all_on_one_share": _16_expert_shares("all_on_one_share")},
    scopes=frozenset({"attn_window", "attn_full", "moe_router", "moe_experts", "moe_shared", "attn", "mlp", "lm_head", "attn_in_proj", "attn_head_norm", "attn_core", "attn_gate", "attn_out_proj", "moe_dispatch", "moe_combine", "layer_stack"}),
    mixer_scopes=frozenset({"attn_window", "attn_full"}), outer=frozenset({"attn"}), absent=frozenset({"sconv", "kda_scan"}),
    rehearsal=("3000000007", 40, frozenset({"loss", "ce_loss"}), 2 * 64),
    pairs={  # published key -> ModelConfig field (n_experts: once more under `program`; the heads: _config_file)
        "hidden_size": "d_model", "num_key_value_heads": "n_kv_heads", "head_dim": "attn_head_dim",
        "vocab_size": "vocab_size", "intermediate_size": "d_ff", "moe_intermediate_size": "d_ff_expert",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
        "max_position_embeddings": "max_seq_len", "sliding_window": "attn_window", "num_experts_per_tok": "moe_top_k",
        "num_shared_experts": "n_shared_experts", "route_scale": "moe_route_scale", "route_norm": "moe_norm_topk",
        "n_group": "moe_n_group", "load_balance_coeff": "moe_bias_update_rate"},
    cell_params=504.15e6, config_file=_config_file, published_params=26.1e9, published=_published,
    hf_base=HF_BASE, hf_to_tiny=dict(name="trinity-tiny", dtype="float32"),
    hf_refused=((dict(score_func="softmax"), "score_func"), (dict(route_norm=False), "not normalised"),
                (dict(n_group=2), "group-limited"), (dict(num_limited_groups=2), "group-limited"),
                (dict(sliding_window=None), "without a sliding_window"),
                (dict(layer_types=["sliding_attention"] * 4), "layer_types"),
                (dict(layer_types=["sliding_attention"] * 4 + ["conv"]), "layer_types"),
                (dict(rope_scaling={"rope_type": "yarn", "factor": 4.0}), "rope_scaling"),
                (dict(hidden_act="gelu"), "hidden_act"), (dict(num_experts=0), "routed experts")),
    llm_refuses=("a window of the KV cache", "a norm behind each part", "dropless", "pattern of single-part layers",
                 "the attention output gate"),
    flops_parts=frozenset({"W", "*", "-", "E", "head"}), step_flops=77.49e12, flops_share=_flops_share,
    made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_moe_pct", "train_moe_gmm_mxu_pct",
        "train_moe_imbalance", "train_moe_router_pct", "train_optimizer_pct", "train_head_loss_pct",
        "train_scoped_pct", "train_attn_window_pct", "train_attn_full_pct", "train_attn_window_roofline_pct",
        "train_mfu_swa_moe_pct",
        # PR 52: the attention part's pieces, the expert layer's dispatch and combine (the layer
        # loop's own is next to nothing where one period runs unrolled: not listed)
        "train_attn_proj_pct", "train_attn_core_pct", "train_moe_dispatch_pct", "train_moe_combine_pct",
        "train_attn_passes_pct"}),
    own_metrics=("train_attn_window_pct", "train_attn_full_pct", "train_attn_window_roofline_pct", "train_mfu_swa_moe_pct"),
    # the cell's whole step (`Family.cell_step`). PR 46: [2, 16384]: four attention parts inside a window of 2,048 (their
    # kernels under their own names) and one full, gated, normed a head, a norm behind every part; four expert parts at 8
    # of 128 over 32,768 tokens beside a shared expert; arguments 6.05 GB; the f32 logits [2, 16384, 25024] are 3.3 GB of
    # the temporaries
    cell_step=(4, 2, 8.75),
)


# ------------------------------------------------------------------- the family's own

def test_a_window_counts_the_querys_own_position_and_only_w_parts_are_rotated():
    """The reference's band by hand: query i keeps the `attn_window` keys i - window + 1 .. i;
    a `W` part is rotated and windowed, a `*` part neither, from the same leaves."""
    s, window = 40, CFG.attn_window
    x = jax.random.normal(jax.random.PRNGKey(2), (2, s, CFG.d_model))
    lp = jax.tree.map(lambda a: a[0], params(CFG, FAMILY.unsettle)["window_layers"])
    model = model_of(CFG)
    u = ref._rms_norm(x, lp["attn_norm"], CFG.norm_eps)
    q, k, v = (jnp.einsum("bsd,dhk->bshk", u, lp[n]) for n in ("wq", "wk", "wv"))
    q, k = ref._rms_norm(q, lp["q_head_norm"], CFG.norm_eps), ref._rms_norm(k, lp["k_head_norm"], CFG.norm_eps)
    gate = jax.nn.sigmoid(jnp.einsum("bsd,dhk->bshk", u, lp["wo_gate"]))
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]

    def by_hand(q, k, seen):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, 2, axis=2)) / np.sqrt(CFG.head_dim)
        probs = jax.nn.softmax(jnp.where(seen, scores, -jnp.inf), axis=-1)
        return jnp.einsum("bshk,hkd->bsd", jnp.einsum("bhqk,bkhd->bqhd", probs, jnp.repeat(v, 2, axis=2)) * gate, lp["wo"])

    band = (j <= i) & (j > i - window)
    assert band.sum(-1).max() == window == 11 and band[3].sum() == 4
    rotated = [ref._rope(a, CFG.rope_theta) for a in (q, k)]
    np.testing.assert_allclose(ref.attention_part(x, lp, model, True), by_hand(*rotated, band), atol=2e-5)
    np.testing.assert_allclose(ref.attention_part(x, lp, model, False), by_hand(q, k, j <= i), atol=2e-5)
    # the system's two kinds of part from the same leaves: the block's output less its input, behind the norm
    positions = jnp.arange(s)[None]
    for windowed in (True, False):
        mine = llama._block(x, lp, CFG, positions, None, windowed=windowed)[0] - x
        want = ref._rms_norm(ref.attention_part(x, lp, model, windowed), lp["attn_post_norm"], CFG.norm_eps)
        np.testing.assert_allclose(mine, want, atol=3e-5)
    # the reference's blocks of queries: the same numbers a block at a time (a window's slice of the keys)
    whole = ref.attention_part(x, lp, model, True)
    try:
        ref.QUERY_BLOCK = 16
        np.testing.assert_allclose(ref.attention_part(x, lp, model, True), whole, atol=2e-6)
    finally:
        ref.QUERY_BLOCK = 512


def test_what_cannot_take_a_window_refuses_it_by_name_and_the_table_names_the_kind():
    lp = jax.tree.map(lambda a: a[0], params(CFG)["window_layers"])
    x, positions = jnp.zeros((1, 8, 64)), jnp.arange(8)[None]
    cache = (jnp.zeros((1, 16, 2, 24)),) * 2
    with pytest.raises(NotImplementedError, match="window under a KV cache"):
        llama._block(x, lp, CFG, positions, None, cache_kv=cache, cache_len=jnp.zeros((), jnp.int32), windowed=True)
    for impl in ("ring", "ulysses"):
        with pytest.raises(NotImplementedError, match="ring / Ulysses"):
            llama._block(x, lp, dataclasses.replace(CFG, attention_impl=impl), positions, None, windowed=True)
    assert LAYER_KINDS["W"].windowed and LAYER_KINDS["W"].mixer == "attn" and not LAYER_KINDS["*"].windowed
    assert LAYER_KINDS["W"].stack == "window_layers" != LAYER_KINDS["*"].stack
    with pytest.raises(ValueError, match=r"W \(attention inside attn_window, rotated\)"):
        dataclasses.replace(CFG, layer_pattern="W-WEWEWE*X")
    with pytest.raises(ValueError, match="attn_window says how many keys"):
        dataclasses.replace(CFG, attn_window=0)
    # packed documents go through a windowed part: the band inside each document
    t = tokens(CFG, (2, 33))
    seg = jnp.concatenate([jnp.zeros((2, 20), jnp.int32), jnp.ones((2, 13), jnp.int32)], axis=1)
    packed = jax.jit(lambda p: llama.loss_fn(p, {"tokens": t, "segment_ids": seg}, CFG)[0])(params(CFG))
    assert np.isfinite(float(packed))
    assert attn.SCOPE is None  # `attn`, and inside it `attn_window` / `attn_full`, are the mixer's own


def test_the_step_trains_every_stack_and_the_embedding_is_scaled(first_step):
    import optax

    state, after, metrics, _, t = first_step
    assert set(state.params) == {"embed", "window_layers", "mlp_layers", "attn_layers", "layers", "final_norm", "lm_head"}
    assert state.params["window_layers"]["wq"].shape == (4, 64, 4, 24) and state.params["attn_layers"]["wq"].shape == (1, 64, 4, 24)
    assert {"attn_post_norm"} <= set(state.params["window_layers"]) and {"mlp_post_norm"} <= set(state.params["layers"])
    assert np.asarray(metrics["expert_load"]).shape == (4, 16)
    assert float(metrics["loss"]) < 2 * np.log(256)
    mu = optax.tree_utils.tree_get(after.opt_state, "mu")  # (the schedule's first rate is 0: read the moments)
    assert all(np.abs(np.asarray(a)).max() > 0 for name in ("embed", "window_layers", "attn_layers", "mlp_layers", "lm_head")
               for a in jax.tree.leaves(mu[name]))
    x = llama.embed_tokens(state.params, t, CFG)
    np.testing.assert_allclose(x, 8.0 * state.params["embed"][t], rtol=1e-6)
    np.testing.assert_array_equal(llama.embed_tokens(state.params, t, dataclasses.replace(CFG, embed_scale=0.0)),
                                  state.params["embed"][t])
