"""The ouro family (Ouro-2.6B) on the training path, at a small size on the CPU with seeded
weights: the block every layer is (rotated attention with as many key/value heads as query heads,
then the dense SwiGLU MLP, each part between a norm on its input and one on its output) run
`loop_steps` times over the SAME weights, a head and an exit gate behind every recurrence, under
the expected-exit loss with its entropy term. The contract is tests/family_contract.py's; here is
what the family alone has, the cell's whole step at the published widths compiled for the chip among it
(`-m slow -k looped_step`, ~2 min)."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
from family_contract import Family, params, seeded, system, tokens
from compiled_step_text import kernel_calls, lower_cell_step, xla_remats
from ray_tpu.models import get_config, llama
from ray_tpu.models.config import ModelConfig
from ray_tpu.models.reference import ouro as ref

del test_a_family_cells_step_scores_once_a_layer_and_fits_as_before  # noqa: F821  (this cell's whole step is a shape of its own: below)

CFG = get_config("ouro-tiny")
T, L = CFG.loop_steps, CFG.n_layers
PUBLISHED_PARAMS = 2_667_974_657  # 48 x 51,388,416 + 2 x 49,152 x 2,048 + 2,048 (final norm) + 2,049 (the gate)


# ------------------------------------------------------------------- the configuration

def _config_file(config, cfg, config_from):
    # the published widths, every one, the whole vocabulary and every recurrence
    assert config["head_dim"] * config["num_attention_heads"] == config["hidden_size"] == cfg.d_model == 2048
    assert (cfg.n_heads, cfg.n_kv_heads, cfg.head_dim, cfg.d_ff, cfg.vocab_size, cfg.norm_eps, cfg.rope_theta) == (
        16, 16, 128, 5632, 49152, 1e-6, 1e6)
    assert cfg.loop_steps == config["total_ut_steps"] == 4 and cfg.exit_entropy_weight == 0.05
    assert cfg.part_post_norm and not cfg.tie_embeddings and not cfg.n_experts and not cfg.layer_pattern
    assert config["early_exit_threshold"] == 1 and config["use_sliding_window"] is False and config["rope_scaling"] is None
    # the cut is depth alone: at least the guide's floor of four layers
    published = config["published"]
    assert config["reduced"] == ["num_hidden_layers", "layer_types"] and published["num_hidden_layers"] == 48
    assert cfg.n_layers == config["num_hidden_layers"] == len(config["layer_types"]) >= 4
    assert set(config["layer_types"]) == set(published["layer_types"]) == {"full_attention"} and len(published["layer_types"]) == 48
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    layer = sum(int(np.prod(a.shape[1:])) for a in jax.tree.leaves(shapes["layers"]))
    assert layer == 4 * 2048 * 2048 + 3 * 2048 * 5632 + 4 * 2048 == 51_388_416
    assert shapes["exit_gate"].shape == (2049,)  # 2,048 weights and the bias: one leaf
    assert cfg.n_params == cfg.n_layers * layer + 2 * 49152 * 2048 + 2048 + 2049
    for group in ("cut", "deployment"):
        assert len(config[group]) > 200
    assert len(config["assumed"]) >= 4
    # the program's own mapping of the published keys says the same
    hf = {k: v for k, v in config.items() if k not in ("program", "trainer", "published", "reduced")}
    assert dataclasses.replace(config_from(hf), name=cfg.name, remat_policy="full", dtype="bfloat16") == cfg


def _published(cfg):
    """The catalog row's config: 48 layers counted once, however often they run, and the gate."""
    assert (cfg.n_layers, cfg.loop_steps, cfg.max_seq_len) == (48, 4, 65536)
    assert cfg.n_params == PUBLISHED_PARAMS
    assert cfg.n_params - dataclasses.replace(cfg, loop_steps=1, exit_entropy_weight=0.0).n_params == cfg.d_model + 1


# ------------------------------------------------------------------- the benchmark's files

def _flops_share(flops, model):
    layer = flops.layer_flops_per_token(model, (8192 + 1) / 2)
    assert flops.attention_projections(model) == 4 * 2048 * 2048
    assert layer["attention"] == 2 * 4 * 2048 * 2048 + 2 * 16 * 2 * 128 * 4096.5
    assert layer["mlp"] == 2 * 3 * 2048 * 5632
    fwd = flops.forward_flops_per_token(model, (8192 + 1) / 2)
    # every layer and the head loop_steps times, every parameter once: not 6 N a token
    assert fwd["attention"] == 4 * model["n_layers"] * layer["attention"] and fwd["head"] == 4 * 2 * 2048 * 49152
    assert fwd["exit_gate"] == 3 * 2 * 2048
    total = sum(fwd.values())
    for part, share in (("attention", 0.38), ("mlp", 0.39), ("head", 0.23)):
        assert abs(fwd[part] / total - share) < 0.01, part
    whole = flops.forward_flops_per_token({**model, "n_layers": 48}, (8192 + 1) / 2)
    assert abs(whole["head"] / sum(whole.values()) - 0.03) < 0.001  # what the cut makes 23 %
    n = 5 * 51_388_416 + 2 * 49152 * 2048
    assert 2.0 < flops.train_flops_per_token(model, 8192) / (6 * n) < 4.0  # `6 N` is wrong by a factor


def _made_up(flops, config, model):
    ops = {"%fusion.1 = bf16[4]": 0.04, "%fusion.2 = bf16[4]": 0.06, "%fusion.3 = bf16[4]": 0.2,
           "%fusion.4 = f32[4]": 0.01, "%fusion.5 = f32[4]": 0.03, "%fusion.6 = f32[4]": 0.3,
           "%flash_attention_fwd.1 = (bf16[4]) custom-call()": 0.03}
    scopes = {"%fusion.1 = bf16[4]": ["loop_step", "layer_stack", "attn", "attn_in_proj"],
              "%fusion.2 = bf16[4]": ["loop_step"], "%fusion.3 = bf16[4]": ["loop_step", "layer_stack", "mlp"],
              "%fusion.4 = f32[4]": ["exit_gate"], "%fusion.5 = f32[4]": ["exit_loss", "loss"],
              "%fusion.6 = f32[4]": ["lm_head"]}
    result = {"traced_steps": 5, "tokens_per_step": 8192, "seq": 8192, "chips": 1, "device": {"kind": "TPU v5 lite"},
              "series": {"step_s": [0.6, 0.6, 0.7]},
              "trace": {"busy_s": 2.0, "op_seconds": ops, "op_scopes": scopes}}
    # a program without the scopes (the parent of the PR that named them, were it to run the cell), a run
    # without a trace, a rehearsal: nothing to read, nothing raised
    other = {"busy_s": 2.0, "op_seconds": {"%fusion.9 = f32[4]": 2.0}, "op_scopes": {"%fusion.9 = f32[4]": ["layer_stack"]}}
    bare = {"result": {**result, "trace": other}}
    untraced = {"result": {k: v for k, v in result.items() if k != "trace"}}
    return result, [
        ("train_mfu_family", "train_mfu_loop_pct", {}, 100 * flops.train_flops_per_token(model, 8192) * 8192 / 0.6 / 197e12),
        ("trace_scope_share", "train_loop_exit_pct", {}, 100 * (0.01 + 0.03) / 2.0),
        ("trace_scope_share_without", "train_loop_overhead_pct", {}, 100 * 0.06 / 2.0),
        ("trace_scope_share", "train_mlp_pct", {}, 100 * 0.2 / 2.0),
        ("trace_scope_share", "train_loop_exit_pct", bare, None),
        ("trace_scope_share", "train_loop_exit_pct", untraced, None),
        ("trace_scope_share_without", "train_loop_overhead_pct", bare, None),
        ("trace_scope_share_without", "train_loop_overhead_pct", untraced, None),
        ("train_mfu_family", "train_mfu_loop_pct", {"rehearse": True}, None)]


FAMILY = Family(
    model_type="ouro", tiny=CFG, cell="ouro26b-train-loop4-s8192", config="ouro-2.6b-train-loop4", index=9,
    unsettle=(("layers", "attn_post_norm", 0.1, 1.0), ("layers", "mlp_post_norm", 0.1, 1.0)),  # norm weights that are not one
    cases=(("L3xT4", CFG, 1),  # the toy: three layers, four recurrences
           ("L2xT2-gqa-beta0", dataclasses.replace(CFG, n_layers=2, loop_steps=2, n_kv_heads=2, exit_entropy_weight=0.0), 1)),
    batch=2, least_leaves=15, float32_leaves=frozenset({"exit_gate"}), recurrent=None, shares={},
    scopes=frozenset({"loop_step", "exit_gate", "exit_loss", "lm_head", "loss", "embed", "attn", "mlp", "layer_stack"}),
    mixer_scopes=frozenset({"attn_in_proj", "attn_core", "attn_out_proj"}),
    outer=frozenset({"attn"}), absent=frozenset({"attn_head_norm", "attn_gate", "moe_router"}),
    rehearsal=("3000000007", 30, frozenset({"loss", "ce_loss"}), 2 * 64),
    pairs={  # published key -> ModelConfig field
        "hidden_size": "d_model", "num_attention_heads": "n_heads", "num_key_value_heads": "n_kv_heads",
        "head_dim": "head_dim", "vocab_size": "vocab_size", "intermediate_size": "d_ff", "num_hidden_layers": "n_layers",
        "rms_norm_eps": "norm_eps", "rope_theta": "rope_theta", "tie_word_embeddings": "tie_embeddings",
        "max_position_embeddings": "max_seq_len", "total_ut_steps": "loop_steps"},
    cell_params=458.3e6, config_file=_config_file, published_params=2.668e9, published=_published,
    hf_base=dict(model_type="ouro", vocab_size=256, hidden_size=64, num_attention_heads=4, num_key_value_heads=4,
                 head_dim=16, intermediate_size=96, num_hidden_layers=3, layer_types=["full_attention"] * 3,
                 total_ut_steps=4, early_exit_threshold=1, use_sliding_window=False, sliding_window=None,
                 max_window_layers=3, hidden_act="silu", rms_norm_eps=1e-6, rope_scaling=None, rope_theta=1000000,
                 max_position_embeddings=128, tie_word_embeddings=False),
    hf_to_tiny=dict(name="ouro-tiny", dtype="float32"),
    hf_refused=((dict(use_sliding_window=True), "use_sliding_window"), (dict(rope_scaling={"type": "yarn"}), "rope_scaling"),
                (dict(early_exit_threshold=0.5), "early_exit_threshold"), (dict(total_ut_steps=0), "total_ut_steps"),
                (dict(layer_types=["full_attention", "sliding_attention", "full_attention"]), "layer_types"),
                (dict(hidden_act="gelu"), "hidden_act")),
    llm_refuses=("KV cache of loop_steps x n_layers", "exit by the gate's threshold", "a norm behind each part"),
    flops_parts=frozenset({"attention", "mlp", "head", "exit_gate"}), step_flops=86.795e12, flops_share=_flops_share,
    made_up=_made_up,
    metrics=frozenset({
        "setup_s", "train_tokens_per_s", "train_step_ms", "train_device_idle_pct", "train_device_step_ms",
        "train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct", "train_attn_proj_pct", "train_attn_core_pct",
        "train_layer_stack_pct", "train_mlp_pct", "train_optimizer_pct", "train_head_loss_pct", "train_scoped_pct",
        "train_mfu_loop_pct", "train_loop_exit_pct", "train_loop_overhead_pct"}),
    own_metrics=("train_mfu_loop_pct", "train_loop_exit_pct", "train_loop_overhead_pct"),
)


# ------------------------------------------------------------------- the family's own

def _own_params():
    """The seeded tree with a gate that is not centred: a bias, beside the contract's norm weights."""
    p = params(CFG, FAMILY.unsettle)
    return {**p, "exit_gate": p["exit_gate"].at[-1].set(0.3)}


def test_the_leaves_the_gates_key_and_what_a_looped_configuration_refuses():
    p = seeded(CFG)
    axes = llama.param_axes(CFG)
    assert set(axes) == set(p) == {"embed", "layers", "final_norm", "lm_head", "exit_gate"}
    assert all(len(axes["layers"][leaf]) == p["layers"][leaf].ndim for leaf in axes["layers"])
    # ONE leaf: d_model weights and, last, the bias (0 from the seed), held whole on every chip
    assert p["exit_gate"].shape == (CFG.d_model + 1,) and axes["exit_gate"] == (None,) and float(p["exit_gate"][-1]) == 0.0
    assert llama._layer_kinds(CFG) == {"layers": (L, "attn", "dense")}  # ONE stack, held once
    # one recurrence is every other family's stack: no gate, and the seed gives every other leaf the weights it gives here
    once = dataclasses.replace(CFG, loop_steps=1, exit_entropy_weight=0.0)
    plain = seeded(once)
    assert set(plain) == set(p) - {"exit_gate"} and CFG.n_params - once.n_params == CFG.d_model + 1
    for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves({k: v for k, v in p.items() if k in plain})):
        np.testing.assert_array_equal(a, b)
    for what, bad in (("_pattern_layers", dict(layer_pattern="*-*", n_layers=3)), ("_pipeline_layers", dict(pipeline_stages=3)),
                      ("MTP modules", dict(mtp_depth=1)), ("block-diffusion", dict(diffusion_block=4)),
                      ("expert layers", dict(n_experts=4)), ("fewer than one", dict(loop_steps=0))):
        with pytest.raises(NotImplementedError, match=what):
            dataclasses.replace(CFG, **bad)
    assert isinstance(dataclasses.replace(CFG, loop_steps=1, layer_pattern="*-*"), ModelConfig)  # (run once: any stack)


def test_four_recurrences_are_an_unrolled_model_of_four_copies_and_a_shared_leafs_gradient_their_sum(monkeypatch):
    """T recurrences over L layers are T x L layers that hold T copies of the weights, the final norm and a
    head behind every L-th: the same loss, and the gradient of a shared leaf is the sum of its copies'."""
    p, t = _own_params(), tokens(CFG)  # (the contract's shape: `system` is compiled once for it)
    (loss, _), grads = system(p, t, CFG)
    copies = {**p, "layers": jax.tree.map(lambda a: jnp.tile(a, (T,) + (1,) * (a.ndim - 1)), p["layers"])}
    real, calls = llama._stacked_layers, iter(range(T))

    def one_copy(x, held, *rest):  # recurrence i reads rows [i L, (i + 1) L) of the T L rows held
        i = next(calls)
        return real(x, {**held, "layers": jax.tree.map(lambda a: a[i * L:(i + 1) * L], held["layers"])}, *rest)

    monkeypatch.setattr(llama, "_stacked_layers", one_copy)
    (u_loss, _), u_grads = jax.jit(jax.value_and_grad(lambda p: llama.loss_fn(p, {"tokens": t}, CFG), has_aux=True))(copies)
    assert next(calls, None) is None  # every recurrence read a copy of its own
    np.testing.assert_allclose(u_loss, loss, rtol=1e-6)
    for leaf, g in grads["layers"].items():
        mine = u_grads["layers"][leaf].reshape(T, L, *g.shape[1:])
        scale = float(jnp.abs(g).max())
        np.testing.assert_allclose(mine.sum(0), g, atol=2e-5 * scale, err_msg=leaf)
        assert all(float(jnp.abs(mine[i]).max()) > 1e-3 * scale for i in range(T)), leaf  # every copy is reached
    for leaf in ("embed", "final_norm", "lm_head", "exit_gate"):
        np.testing.assert_allclose(u_grads[leaf], grads[leaf], atol=2e-5 * float(jnp.abs(grads[leaf]).max()), err_msg=leaf)


def test_p_sums_to_one_and_the_loss_is_the_formula_of_the_gates_and_ce_by_step():
    a = 3.0 * jax.random.normal(jax.random.PRNGKey(3), (T - 1, 2, 7))
    p, log_p = llama.exit_distribution(a)
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    np.testing.assert_allclose(p, ref.exit_distribution(jax.nn.sigmoid(a)), atol=1e-6)  # the products as written
    np.testing.assert_allclose(jnp.exp(log_p), p, rtol=1e-6)
    far, log_far = llama.exit_distribution(jnp.asarray([[1e4], [-1e4], [0.0]]))  # gates that saturate: no 0 x inf
    assert np.isfinite(np.asarray(far * log_far)).all() and float(far[0, 0]) == 1.0 and float(far[1:].sum()) == 0.0
    # a gate that does not look at the stream leaves everywhere alike: the loss is the formula over the means
    blind = {**_own_params(), "exit_gate": jnp.zeros((CFG.d_model + 1,), jnp.float32).at[-1].set(0.3)}
    (loss, m), grads = system(blind, tokens(CFG), CFG)
    gate = float(jax.nn.sigmoid(0.3))
    want = np.asarray([gate * (1 - gate) ** i for i in range(T - 1)] + [(1 - gate) ** (T - 1)])
    entropy = -(want * np.log(want)).sum()
    assert m["ce_by_step"].shape == (T,)
    np.testing.assert_allclose(m["ce_loss"], (want * np.asarray(m["ce_by_step"])).sum(), rtol=1e-5)
    np.testing.assert_allclose(m["exit_entropy"], entropy, rtol=1e-5)
    np.testing.assert_allclose(m["exit_step_mean"], (want * np.arange(1, T + 1)).sum(), rtol=1e-5)
    np.testing.assert_allclose(loss, m["ce_loss"] - CFG.exit_entropy_weight * m["exit_entropy"], rtol=1e-6)
    assert float(jnp.abs(grads["exit_gate"][-1])) > 0 and float(jnp.abs(grads["exit_gate"][:-1]).max()) > 0  # the gradient runs through p


def test_a_gate_that_always_leaves_behind_the_first_recurrence_makes_the_plain_dense_model_bit_for_bit():
    """One recurrence is the plain dense model: the program is the parent's. And the loop reduces to it: with
    p_1 = 1 exactly the loss is the first recurrence's cross entropy, whose layers are the plain model's."""
    once = dataclasses.replace(CFG, loop_steps=1, exit_entropy_weight=0.0)
    p, t = _own_params(), tokens(CFG)  # (the contract's shape: `system` is compiled once for it)
    plain = {k: v for k, v in p.items() if k != "exit_gate"}
    (loss, m), grads = system(plain, t, once)
    assert set(m) == {"loss", "ce_loss", "tokens", "moe_aux_loss"} and float(m["moe_aux_loss"]) == 0.0
    sure = {**p, "exit_gate": jnp.zeros((CFG.d_model + 1,), jnp.float32).at[-1].set(1e4)}
    (l_loss, l_m), l_grads = system(sure, t, CFG)
    assert float(l_m["exit_step_mean"]) == 1.0 and float(l_m["exit_entropy"]) == 0.0
    assert float(l_loss) == float(l_m["ce_by_step"][0]) == float(loss)
    for leaf, g in grads["layers"].items():
        np.testing.assert_allclose(l_grads["layers"][leaf], g, rtol=0, atol=1e-6 * float(jnp.abs(g).max()), err_msg=leaf)
    assert not np.asarray(l_grads["exit_gate"]).any()


def test_the_steps_metrics_carry_the_exit_counters_and_forward_hands_out_the_last_recurrence(first_step):
    _, _, m, _, t = first_step
    assert {"loss", "ce_loss", "exit_step_mean", "exit_entropy", "ce_by_step", "grad_norm"} <= set(m)
    assert m["ce_by_step"].shape == (T,) and 1.0 < float(m["exit_step_mean"]) < T
    assert 0.0 < float(m["exit_entropy"]) < np.log(T) and float(m["loss"]) < float(m["ce_loss"])
    p = params(CFG, FAMILY.unsettle)
    logits, cache, aux = jax.jit(lambda p: llama.forward(p, t[:, :-1], CFG, return_aux=True))(p)
    assert cache is None and float(aux) == 0.0 and logits.shape == (2, 32, CFG.vocab_size)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    losses = lse - jnp.take_along_axis(logits, t[:, 1:, None], axis=-1)[..., 0]
    want = jax.jit(lambda p: ref.next_token_losses(p, t, dataclasses.asdict(CFG)))(p)
    np.testing.assert_allclose(losses, want, atol=2e-5)


def test_the_compiled_gradient_names_the_loops_scopes_forward_backward_and_rematerialised():
    p, t = params(CFG, FAMILY.unsettle), tokens(CFG, (1, 33))

    def loss(p):  # as train/step.py names the model: the outermost scope is the transformations'
        with jax.named_scope("model"):
            return llama.loss_fn(p, {"tokens": t}, CFG)[0]

    text = jax.jit(jax.grad(loss)).lower(p).compile().as_text()
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in (llama.LOOP_STEP, llama.EXIT_GATE, llama.EXIT_LOSS):
        assert any(f"jvp(model))/{scope}" not in n and f"jvp(model)/{scope}" in n for n in names), scope  # forward
        assert any(f"transpose(jvp(model))/{scope}" in n for n in names), scope  # backward
    # a recurrence's head and loss, made again in the backward pass, keep the names they have
    again = {n for n in names if "transpose(jvp(model))" in n and ("checkpoint" in n or "remat" in n)}
    assert any("/lm_head/" in n for n in again) and any("/loss/" in n for n in again)
    assert any(f"/{llama.LOOP_STEP}/{llama.LAYER_LOOP}/" in n for n in again)  # a layer application's, inside both loops' scopes


@pytest.mark.slow  # (two whole steps compiled, ~2 min: `-m slow -k looped_step`; tier-1 holds the family at a small size. ROADMAP.md C13)
def test_the_ouro_cells_looped_step_compiles_inside_its_memory_at_five_layers_and_not_at_six(cell_step, one_chip, on_tpu):
    """The whole step of `ouro26b-train-loop4-s8192` as its configuration file states it (five layers run
    four times over shared weights, four heads with their exit gates, the expected-exit loss), compiled for
    the described chip. PR 58: arguments 5.500 GB (12 B a parameter, each counted ONCE) + temporaries 9.512 GB
    = 15.01 of 15.75 GB; six layers are 6.116 + 10.148 = 16.26 GB and do not fit (the configuration's `cut`
    says what a layer costs). A forward flash kernel a layer-stack loop (four: one a recurrence, none made
    again: `out` and the logsumexp are kept by name, T x L of them) and ONE backward kernel a loop; XLA
    rematerialises nothing of its own; the loop's three scopes stand in the `op_name`s, forward and backward,
    and a recurrence's rematerialised head and loss keep theirs."""
    cfg, trainer, text, memory = cell_step.cfg, cell_step.trainer, cell_step.text, cell_step.memory
    assert (cfg.loop_steps, cfg.n_layers, cfg.remat_policy, trainer["mesh"]) == (4, 5, "full", None)
    assert abs(memory.argument_size_in_bytes - 12 * cfg.n_params) < 1e7  # a shared leaf is held once
    assert memory.temp_size_in_bytes < (9.52 + 0.15) * 1e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9  # what a v5e program may use
    assert not xla_remats(text) and cell_step.fallbacks == 0
    assert kernel_calls(text, "flash_attention_fwd") == (cfg.loop_steps, 0)
    assert kernel_calls(text, "flash_attention_bwd_dkv_dq") == (cfg.loop_steps, 0)
    assert kernel_calls(text, "flash_attention_bwd_dq") == kernel_calls(text, "flash_attention_bwd_dkv") == (0, 0)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in (llama.LOOP_STEP, llama.EXIT_GATE, llama.EXIT_LOSS):
        assert any(f"/jvp(model)/{scope}" in n for n in names) and any(f"/transpose(jvp(model))/{scope}" in n for n in names), scope
    again = {n for n in names if "transpose(jvp(model))" in n and "rematted_computation" in n}
    assert any("/lm_head/" in n for n in again) and any("/loss/" in n for n in again)
    assert any(f"/{llama.LOOP_STEP}/{llama.LAYER_LOOP}/" in n for n in again)
    deeper = lower_cell_step(dataclasses.replace(cfg, n_layers=6), trainer, one_chip)[1].compile().memory_analysis()
    assert deeper.argument_size_in_bytes + deeper.temp_size_in_bytes > 15.75e9  # the greatest depth that is placed is five
