"""The benchmark's own arithmetic, on made-up inputs and a tiny model: how a traced step's
instructions are joined to the scopes that made them (benchmarks/lib/scope_seconds.py), and how
the train_family driver reads a step's own gradient and update out of the state it left
(benchmarks/drivers/train_family.py). (The readers of each cell: the families' files.)"""
import jax
import jax.numpy as jnp
import numpy as np

from family_contract import _highest, highest, tokens  # noqa: F401  (autouse; and benchmarks/ is importable)
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
