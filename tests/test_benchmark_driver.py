"""The benchmark's own arithmetic, on made-up inputs and a tiny model: how a traced step's
instructions are joined to the scopes that made them (benchmarks/lib/scope_seconds.py), and how
the train_family driver reads a step's own gradient and update out of the state it left
(benchmarks/drivers/train_family.py). (The readers of each cell: the families' files.)"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import ROOT, _highest, highest, tokens  # noqa: F401  (autouse; and benchmarks/ is importable)
from ray_tpu.models import get_config, llama, moe

GLM = get_config("glm-tiny")


def test_scopes_are_joined_by_the_instructions_name():
    from benchmarks.lib import scope_seconds

    text = """HloModule jit_step

%fused_computation.3 (p: bf16[8,4]) -> bf16[8,4] {
  %p = bf16[8,4]{1,0} parameter(0)
  ROOT %multiply.1 = bf16[8,4]{1,0} multiply(%p, %p), metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/moe_experts/jit(silu)/mul"}
}

ENTRY %main.9 (a: bf16[8,4]) -> bf16[8,4] {
  %a = bf16[8,4]{1,0} parameter(0)
  %fusion.3 = bf16[8,4]{1,0} fusion(%a), kind=kLoop, calls=%fused_computation.3, metadata={op_name="jit(step)/jvp()/while/body/closed_call/mlp/moe_combine/bsd,dr->bsr/dot_general"}
  %ragged-dot-none.2 = bf16[8,4]{1,0} custom-call(%fusion.3), custom_call_target="x", metadata={op_name="ragged-dot-none"}
  ROOT %add.5 = bf16[8,4]{1,0} add(%fusion.3, %ragged-dot-none.2), metadata={op_name="jit(step)/optimizer/add"}
}
"""
    by = scope_seconds.scopes_by_instruction(text)
    assert by["fusion.3"] >= {"mlp", "moe_combine", "moe_experts", "closed_call"}
    assert "mul" not in by["fusion.3"] and "dot_general" not in by["fusion.3"]
    assert by["ragged-dot-none.2"] == set() and by["add.5"] == {"optimizer"}
    ops = {"%fusion.3 = bf16[8,4]{1,0} fusion(bf16[8,4] %a)": 0.25, "%add.5 = bf16[8,4] add(": 0.5,
           "%not-in-text.1 = f32[] x(": 1.0}
    scopes = scope_seconds.op_scopes(ops, text)
    assert scopes["%not-in-text.1 = f32[] x("] == []
    seconds = scope_seconds.seconds(ops, scopes)
    assert seconds["moe_experts"] == seconds["moe_combine"] == 0.25 and seconds["optimizer"] == 0.5


def test_the_drivers_step_parity_reads_the_steps_own_gradient_and_update():
    """benchmarks/drivers/train_family.py holds the timed step to the reference through
    the state one step from zero moments leaves: Adam's first moment is (1 - b1) x the
    clipped gradient, the second its square, and the parameters moved by AdamW's first
    update. Against optax itself, with a rate above zero, clipped and not."""
    import optax
    from benchmarks.drivers import train_family as driver
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    cfg, b1, b2 = GLM, 0.9, 0.95
    batch = {"tokens": tokens(cfg)}
    grads = None
    for clip in (1.0, 1e3):
        tx = make_optimizer(learning_rate=0.01, warmup_steps=0, grad_clip=clip)
        state0 = jax.jit(lambda key: init_state(key, cfg, tx))(jax.random.PRNGKey(0))  # noqa: B023
        state1, m = make_train_step(cfg, tx, donate=False)(state0, batch)
        if grads is None:  # of the same parameters both times: the optimizer's settings do not reach them
            grads = jax.jit(jax.grad(lambda p: llama.loss_fn(p, batch, cfg)[0]))(state0.params)
        mu = optax.tree_utils.tree_get(state1.opt_state, "mu")
        nu = optax.tree_utils.tree_get(state1.opt_state, "nu")
        scale = max(1.0, float(m["grad_norm"]) / clip) / (1 - b1)
        assert (scale > 10.5) == (clip == 1.0)  # the first is clipped
        rows = driver.row_errors(mu, grads, scale)
        assert len(rows) >= 50 and rows["['layers']['w_gate']"][0].shape == (2,)
        for name, (err, ref) in rows.items():
            assert float(err.max()) <= 1e-10 * float(ref.max()) + 1e-20, name
        moments, moved = driver.first_update_errors(
            state0.params, state1.params, mu, nu, 0.01, 0.1, b1, b2)
        assert float(moments) < 1e-5 and float(moved) < 5e-7  # a float32 rounding of a sum near 1
        # a step that moved a leaf otherwise is seen
        off = jax.tree.map(lambda a: a, state1.params)
        off["final_norm"] = off["final_norm"] + 1e-4
        assert float(driver.first_update_errors(state0.params, off, mu, nu, 0.01, 0.1, b1, b2)[1]) > 5e-5
    summary = driver.gradient_summary(
        {"a": (np.array([4.0, 0.0]), np.array([100.0, 0.0])), "b": (np.array([1.0]), np.array([100.0]))},
        {"a": (np.array([1.0, 0.0]), np.array([100.0, 0.0])), "b": (np.array([4.0]), np.array([100.0]))})
    assert summary["rows"] == 2 and summary["ratio_worst"] == 2.0 and summary["ratio_worst_at"] == "a[0]"
    assert summary["ratio_all"] == 1.0 and summary["unreached_rows_are_zero"]
    assert not driver.gradient_summary({"a": (np.array([1.0]), np.array([0.0])), "b": (np.array([1.0]), np.array([1.0]))},
                                       {"a": (np.array([0.0]), np.array([0.0])), "b": (np.array([1.0]), np.array([1.0]))}
                                       )["unreached_rows_are_zero"]
    chosen = [np.array([[0, 1], [0, 2], [0, 3]])]
    ruled, load = driver.balance_rule(np.zeros((1, 4)), chosen, 4, 0.5)
    np.testing.assert_array_equal(load, [[3, 1, 1, 1]])
    np.testing.assert_allclose(ruled, moe.balance_bias(jnp.zeros((1, 4)), jnp.asarray(load, jnp.float32), 0.5))


# ------------------------------------------------------------------- the names of PR 52 in the manifest

# metric -> (what a configuration must have for the pattern to match something there, operations of a
# made-up trace by the scopes they carry -> whether the metric counts them)
_NEW_METRICS = {
    "train_attn_proj_pct": (lambda cfg: True, {("attn", "attn_in_proj"): True, ("attn", "attn_out_proj", "mlp"): True,
                                               ("attn", "attn_core"): False, ("attn", "sconv", "sconv_in_proj"): False}),
    "train_attn_core_pct": (lambda cfg: True, {("attn", "attn_core", "attn_bd"): True, ("attn", "attn_window", "attn_core"): True,
                                               ("attn", "attn_in_proj"): False}),
    "train_attn_passes_pct": (lambda cfg: cfg.attn_qk_norm or cfg.attn_output_gate,
                              {("attn", "attn_head_norm"): True, ("attn", "attn_gate", "attn_core"): True, ("attn", "attn_core"): False}),
    "train_mla_proj_pct": (lambda cfg: cfg.latent_attention, {("attn", "attn_in_proj", "mla_q"): True, ("attn", "attn_in_proj", "mla_kv"): True,
                                                              ("attn", "attn_in_proj"): False}),
    "train_moe_dispatch_pct": (lambda cfg: cfg.n_experts, {("mlp", "moe_dispatch"): True, ("mlp", "moe_combine"): False}),
    "train_moe_combine_pct": (lambda cfg: cfg.n_experts, {("mlp", "moe_combine"): True, ("mlp", "moe_dispatch", "moe_combine"): True,
                                                          ("mlp", "moe_experts"): False}),
    # (a cell whose one period runs as it stands has no loop: 0.00-0.02 ms a step under the name, PR 52's chip runs)
    "train_layer_stack_pct": (lambda cfg: not cfg.layer_pattern or llama.pattern_period(cfg.layer_pattern)[1] > 1, {("model", "layer_stack"): True, ("layer_stack", "while"): True,
                                                             ("layer_stack", "attn", "attn_core"): False, ("layer_stack", "layer_params"): False,
                                                             ("layer_stack", "kda_scan"): False, ("layer_stack", "sconv"): False,
                                                             ("optimizer",): False}),
}


@pytest.mark.parametrize("name", _NEW_METRICS)
def test_a_metric_of_the_new_names_has_its_file_its_reader_and_cells_that_have_the_part(name):
    """Each per-layer metric PR 52 lists: a `metrics/<name>.json` over a reader that exists, a manifest
    entry in a layer of PERF.md's list, cells that exist, whose driver makes the scope join
    (`train_family`, `train_diffusion`) and whose configuration has the part the pattern names; and on a
    made-up trace the reader counts what carries the name and leaves out what does not."""
    import importlib
    import json

    from benchmarks.lib import modelcfg

    has_part, operations = _NEW_METRICS[name]
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    with open(os.path.join(ROOT, "benchmarks", "metrics", f"{name}.json")) as f:
        metric = json.load(f)
    entry = next(m for m in manifest["per_layer"] if m["name"] == name)
    assert (entry["unit"], entry["source"], entry["moves"], entry["better"]) == ("%", "device_trace", "train_tokens_per_s", "lower")
    with open(os.path.join(ROOT, "PERF.md")) as f:  # a layer as PERF.md's list of layers has it, letter for letter
        assert f"\n| {entry['layer']} |" in f.read()
    assert metric["name"] == name and metric["reader"] in ("trace_scope_share", "trace_scope_share_without")
    reader = importlib.import_module(f"benchmarks.readers.{metric['reader']}")
    cells = {w["name"]: w for w in manifest["workloads"]}
    assert entry["workloads"] and set(entry["workloads"]) <= set(cells)
    joined = []
    for cell, w in cells.items():
        with open(os.path.join(ROOT, "benchmarks", "workloads", f"{cell}.json")) as f:
            driver = json.load(f)["driver"]
        with open(os.path.join(ROOT, w["config"] and next(c["file"] for c in manifest["configs"] if c["name"] == w["config"]))) as f:
            cfg = modelcfg.model_config(modelcfg.model_keys(json.load(f)))
        if driver in ("train_family", "train_diffusion") and has_part(cfg):
            joined.append(cell)
    assert entry["workloads"] == joined  # every cell that can read it, in the manifest's order, and no other
    seconds = {f"%op.{i} = x(": 1.0 + i for i in range(len(operations))}
    scopes = {op: sorted(names) for op, names in zip(seconds, operations)}
    trace = {"busy_s": 100.0, "op_seconds": seconds, "op_scopes": scopes}
    want = sum(s for s, counts in zip(seconds.values(), operations.values()) if counts)
    assert reader.read({"result": {"trace": trace}}, **metric["args"]) == pytest.approx(want)
    nothing = {op: ["optimizer"] for op in seconds}  # the parent's program: no such name, nothing to read, nothing raised
    assert reader.read({"result": {"trace": {**trace, "op_scopes": nothing}}}, **metric["args"]) is None
    assert reader.read({"result": {"trace": {**trace, "op_scopes": {}}}}, **metric["args"]) is None
    assert reader.read({"result": {}}, **metric["args"]) is None
