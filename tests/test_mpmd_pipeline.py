"""MPMD cross-process pipeline: schedule core (pure functions), bubble-fraction
timeline analysis, and loss/grad BIT-EXACT (f32) parity of the cross-process
runner vs the in-program `pipeline_spmd` on a CPU 2-stage toy model.

The parity contract (train/mpmd_pipeline.py module docstring): per-microbatch
grads fold in REVERSE microbatch order from zeros — the float-add chain
lax.scan's transpose emits — and the last stage seeds each microbatch
cotangent with exactly 1/M (exact in f32 for power-of-two M).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from compiled_step_text import optimised  # noqa: F401  (a fixture)
from ray_tpu.train.mpmd_pipeline import (
    MPMDPipelineConfig,
    build_1f1b_schedule,
    build_gpipe_schedule,
    build_schedule,
    bubble_fraction,
    validate_schedule,
    warmup_len,
)

# the cross-process runner's stages compile in worker processes, which tests/conftest.py does not reach, and are held
# to this process's SPMD program to the bit: both sides under XLA's optimiser, so this file keeps it
pytestmark = pytest.mark.usefixtures("optimised")


# ------------------------------------------------------------- schedule core
@pytest.mark.parametrize("pp", [2, 3, 4])
@pytest.mark.parametrize("m", [1, 3, 4, 7])
def test_1f1b_schedule_shape(pp, m):
    """Every stage touches every microbatch once per direction; warmup depth
    is the fill distance below the stage; cooldown mirrors warmup."""
    for stage in range(pp):
        evs = build_1f1b_schedule(stage, pp, m)
        assert len(evs) == 2 * m
        assert sorted(i for k, i in evs if k == "fwd") == list(range(m))
        assert sorted(i for k, i in evs if k == "bwd") == list(range(m))
        w = warmup_len(stage, pp, m)
        assert w == min(pp - 1 - stage, m)
        # warmup: the first w events are forwards 0..w-1
        assert evs[:w] == [("fwd", i) for i in range(w)]
        # cooldown: the last w events are the final backwards
        assert evs[len(evs) - w:] == [("bwd", i) for i in range(m - w, m)]


@pytest.mark.parametrize("pp,m", [(2, 4), (3, 5), (4, 2), (4, 7)])
def test_1f1b_steady_state_alternates(pp, m):
    """Between warmup and cooldown, events strictly alternate fwd/bwd (the
    one-forward-one-backward invariant that bounds live activations at
    warmup_len + 1 instead of m)."""
    for stage in range(pp):
        evs = build_1f1b_schedule(stage, pp, m)
        w = warmup_len(stage, pp, m)
        steady = evs[w:len(evs) - w]
        kinds = [k for k, _ in steady]
        assert kinds == ["fwd", "bwd"] * ((len(evs) - 2 * w) // 2)


def test_last_stage_has_no_warmup():
    # the last stage can run its first backward immediately after its first
    # forward — depth-0 fill
    for pp in (2, 3, 4):
        assert warmup_len(pp - 1, pp, 8) == 0
        evs = build_1f1b_schedule(pp - 1, pp, 3)
        assert evs == [("fwd", 0), ("bwd", 0), ("fwd", 1), ("bwd", 1),
                       ("fwd", 2), ("bwd", 2)]


def test_gpipe_schedule_shape():
    evs = build_gpipe_schedule(0, 3, 4)
    assert evs == [("fwd", i) for i in range(4)] + [("bwd", i) for i in range(4)]


@pytest.mark.parametrize("schedule", ["1f1b", "gpipe"])
@pytest.mark.parametrize("pp,m", [(2, 1), (2, 4), (3, 5), (4, 3), (4, 8)])
def test_build_schedule_validates(schedule, pp, m):
    scheds = build_schedule(pp, m, schedule)
    assert len(scheds) == pp
    validate_schedule(scheds, pp, m)  # idempotent — already ran inside build


def test_build_schedule_rejects_bad_input():
    with pytest.raises(ValueError, match="unknown pipeline schedule"):
        build_schedule(2, 4, "interleaved")
    with pytest.raises(ValueError, match="pp >= 1"):
        build_schedule(0, 4)
    with pytest.raises(ValueError, match="pp >= 1"):
        build_schedule(2, 0)


def test_validate_schedule_catches_deadlock():
    # stage 1 demands bwd(0) before running fwd(0): cyclic wait
    bad = [[("fwd", 0), ("bwd", 0)], [("bwd", 0), ("fwd", 0)]]
    with pytest.raises(ValueError, match="deadlock"):
        validate_schedule(bad, 2, 1)


def test_validate_schedule_catches_duplicates():
    bad = [[("fwd", 0), ("fwd", 0)], [("fwd", 0), ("bwd", 0)]]
    with pytest.raises(ValueError, match="exactly once"):
        validate_schedule(bad, 2, 1)


def test_pipeline_config_validation():
    with pytest.raises(ValueError, match="schedule"):
        MPMDPipelineConfig(schedule="zigzag")
    with pytest.raises(ValueError, match="transport"):
        MPMDPipelineConfig(transport="carrier-pigeon")
    with pytest.raises(ValueError):
        MPMDPipelineConfig(num_microbatches=0)
    cfg = MPMDPipelineConfig.from_env(num_microbatches=8, prefetch=0)
    assert cfg.num_microbatches == 8 and cfg.prefetch == 0


def test_resolve_stage_transport_cpu_fallback():
    from ray_tpu.dag.accelerator_context import resolve_stage_transport

    assert resolve_stage_transport("host") == "host"
    # no device plane on the CPU test box: auto degrades to host, an explicit
    # device request refuses loudly
    assert resolve_stage_transport("auto") in ("host", "device")
    with pytest.raises(ValueError, match="unknown stage transport"):
        resolve_stage_transport("tcp")


# ------------------------------------------------------------- bubble fraction
def _span(stage, ts, dur):
    return {"name": "train.pipeline_stage", "ph": "X", "ts": ts, "dur": dur,
            "args": {"stage": stage, "kind": "fwd", "mb": 0, "step": 0}}


def test_bubble_fraction_known_gaps():
    # stage 0: busy [0,10] and [30,40] in a [0,40] window -> 50% idle
    events = [_span(0, 0, 10), _span(0, 30, 10),
              # stage 1: back-to-back spans -> 0% idle
              _span(1, 5, 10), _span(1, 15, 10)]
    out = bubble_fraction(events)
    assert out["stage0"] == pytest.approx(0.5)
    assert out["stage1"] == pytest.approx(0.0)
    assert out["mean"] == pytest.approx(0.25)


def test_bubble_fraction_unions_overlaps():
    # nested/overlapping spans must not double-count busy time (which would
    # push the fraction negative)
    events = [_span(0, 0, 20), _span(0, 5, 10), _span(0, 30, 10)]
    out = bubble_fraction(events)
    assert out["stage0"] == pytest.approx(0.25)  # idle [20,30] of [0,40]


def test_bubble_fraction_ignores_foreign_events():
    events = [{"name": "other.span", "ph": "X", "ts": 0, "dur": 5, "args": {"stage": 0}},
              {"name": "train.pipeline_stage", "ph": "X", "ts": 0, "dur": 5, "args": {}}]
    assert bubble_fraction(events) == {}


# ------------------------------------------------------------- parity (2-stage)
def _stage_fn(params, x):
    return x + jnp.tanh(x @ params["w"]) @ params["w2"]


def _stacked_params(pp, d, seed=0):
    k1, k2 = jax.random.split(jax.random.PRNGKey(seed))
    return {
        "w": jax.random.normal(k1, (pp, d, 2 * d)) * 0.1,
        "w2": jax.random.normal(k2, (pp, 2 * d, d)) * 0.1,
    }


def _mb_loss(y):
    return jnp.mean(y ** 2)


def test_cross_process_runner_bit_exact_vs_pipeline_spmd(rt):
    """The acceptance row: one optimizer step of the cross-process MPMD runner
    vs the in-program `pipeline_spmd` — same microbatch decomposition, f32 —
    must agree BITWISE on per-stage grads, the total loss, and the updated
    params. M=4 (power of two) keeps the 1/M cotangent seed exact."""
    from jax.sharding import Mesh

    from ray_tpu.parallel import use_mesh
    from ray_tpu.parallel.pipeline import pipeline
    from ray_tpu.train.mpmd_pipeline import MPMDPipeline

    pp, d, m, mb = 2, 8, 4, 4
    lr = 1e-2
    stacked = _stacked_params(pp, d)
    stage_params = [jax.tree_util.tree_map(lambda p: np.asarray(p[s]), stacked)
                    for s in range(pp)]
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1), (m * mb, d)),
                   np.float32)

    # -- reference: in-program pipeline on a pure-pp mesh, the SAME loss
    # decomposition the runner uses (mean over per-microbatch means)
    mesh = Mesh(np.array(jax.devices()[:pp]), ("pp",))

    def ref_loss(params, xx):
        y = pipeline(_stage_fn, params, xx, num_microbatches=m, mesh=mesh)
        y_mb = y.reshape(m, mb, d)
        return jnp.mean(jnp.stack([_mb_loss(y_mb[i]) for i in range(m)]))

    with use_mesh(mesh):  # the ambient mesh is set outside jit, not while tracing
        _, g_ref = jax.jit(jax.value_and_grad(ref_loss))(stacked, x)
    # loss reference: pipeline_spmd's outputs reduced by the SAME standalone
    # per-microbatch program shape the runner compiles — fusing the reduction
    # into the big traced program instead lets XLA round the mean differently
    # (~1 ulp), which is a harness artifact, not a pipeline difference
    y_ref = jax.jit(lambda params, xx: pipeline(
        _stage_fn, params, xx, num_microbatches=m, mesh=mesh))(stacked, x)
    y_ref_mb = np.asarray(y_ref).reshape(m, mb, d)
    l_ref = jnp.mean(jnp.stack([jax.jit(_mb_loss)(y_ref_mb[i])
                                for i in range(m)]))
    # same jitted update formula the runner compiles, applied to the reference
    # grads — with bit-identical params and grads this must stay bit-identical
    sgd = jax.jit(lambda p, g: jax.tree_util.tree_map(
        lambda pv, gv: pv - jnp.float32(lr) * gv, p, g))
    p_ref = [sgd({k: v[s] for k, v in stacked.items()},
                 {k: v[s] for k, v in g_ref.items()}) for s in range(pp)]

    # -- cross-process runner
    cfg = MPMDPipelineConfig(num_microbatches=m, learning_rate=lr,
                             group_name="mpmd_parity")
    pipe = MPMDPipeline([_stage_fn] * pp, stage_params, loss_fn=_mb_loss,
                        microbatch_spec=((mb, d), np.float32), cfg=cfg)
    try:
        out = pipe.step(0, x)
        grads = pipe.grads_host()
        params_after = pipe.params_host()
        admission = pipe.admission()
        fractions = pipe.bubble_fractions()
    finally:
        pipe.shutdown()

    assert out["loss"] == float(l_ref)
    for s in range(pp):
        for name in ("w", "w2"):
            assert np.array_equal(np.asarray(grads[s][name]),
                                  np.asarray(g_ref[name][s])), \
                f"stage{s}.{name} grads not bit-exact"
            assert np.array_equal(np.asarray(params_after[s][name]),
                                  np.asarray(p_ref[s][name])), \
                f"stage{s}.{name} updated params not bit-exact"
    # a clean step leaves no published-but-unconsumed blocks and no pulls in
    # flight (expected_read_bytes auto-retract did its job)
    for counters in admission:
        assert counters == {"published": 0, "inflight_pulls": 0}
    # both stages produced spans; fractions land in [0, 1]
    assert set(fractions) == {"stage0", "stage1", "mean"}
    assert all(0.0 <= v <= 1.0 for v in fractions.values())


def test_cross_process_runner_multi_step(rt):
    """Steps advance the deterministic block keys: two consecutive steps run
    clean (no cross-step key collisions) and training reduces the loss."""
    from ray_tpu.train.mpmd_pipeline import MPMDPipeline

    pp, d, m, mb = 2, 8, 2, 4
    stacked = _stacked_params(pp, d, seed=3)
    stage_params = [jax.tree_util.tree_map(lambda p: np.asarray(p[s]), stacked)
                    for s in range(pp)]
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(5), (m * mb, d)),
                   np.float32)
    cfg = MPMDPipelineConfig(num_microbatches=m, learning_rate=5e-2,
                             group_name="mpmd_steps")
    pipe = MPMDPipeline([_stage_fn] * pp, stage_params, loss_fn=_mb_loss,
                        microbatch_spec=((mb, d), np.float32), cfg=cfg)
    try:
        losses = [pipe.step(i, x)["loss"] for i in range(3)]
        admission = pipe.admission()
    finally:
        pipe.shutdown()
    assert losses[2] < losses[0]  # SGD on a fixed batch descends
    for counters in admission:
        assert counters == {"published": 0, "inflight_pulls": 0}
