"""Device-plane gradient sync (train/grad_sync.py): bucketed overlapped
allreduce, on-device int8 block-quantized reduction, cross-replica sharded
optimizer update. Runs on the conftest 8-device virtual-CPU mesh."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from ray_tpu.models import get_config
from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
from ray_tpu.parallel.sharding import named_sharding
from ray_tpu.train import (GradSyncConfig, grad_sync, init_state,
                           make_optimizer, make_train_step)


@pytest.fixture(scope="module")
def env():
    """Shared tiny-model dp=8 training setup + the stock-step reference run
    (one compile amortized over every parity test)."""
    cfg = get_config("test-tiny")
    mesh = build_mesh(MeshSpec(dp=-1).resolve(8), jax.devices()[:8])
    tx = make_optimizer(total_steps=100)
    state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (16, 17), 0, cfg.vocab_size)
    with use_mesh(mesh):
        tokens = jax.device_put(tokens, named_sharding(mesh, "batch", None))
    batch = {"tokens": tokens}
    ref_step = make_train_step(cfg, tx, donate=False)
    with use_mesh(mesh):
        ref_state, ref_metrics = ref_step(state, batch)
    return dict(cfg=cfg, mesh=mesh, tx=tx, state=state, batch=batch,
                ref_state=ref_state, ref_metrics=ref_metrics)


def _run(env, sync, state=None):
    step = make_train_step(env["cfg"], env["tx"], donate=False, sync=sync)
    with use_mesh(env["mesh"]):
        new_state, metrics = step(state or env["state"], env["batch"])
    return step, new_state, metrics


def _max_abs_diff(a, b):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x, y: float(jnp.max(jnp.abs(x - y))), a, b)))


# ------------------------------------------------------------ bucketing unit

def test_partition_buckets_bounds_and_coverage():
    tree = {
        "scalar": jnp.zeros(()),               # scalar leaf
        "odd": jnp.zeros((7, 13)),             # odd shape
        "big": jnp.zeros((4096,)),             # larger than the bucket alone
        "mid": [jnp.zeros((100,)), jnp.zeros((101,))],
    }
    buckets = grad_sync.partition_buckets(tree, bucket_bytes=1024)
    leaves = jax.tree_util.tree_leaves(tree)
    flat = [i for b in buckets for i in b]
    assert sorted(flat) == list(range(len(leaves)))  # every leaf exactly once
    for b in buckets:
        nbytes = sum(int(np.prod(leaves[i].shape or (1,))) * 4 for i in b)
        # a bucket only exceeds the bound when a single leaf does
        assert nbytes <= 1024 or len(b) == 1
    # deterministic
    assert buckets == grad_sync.partition_buckets(tree, bucket_bytes=1024)


def test_partition_buckets_single_bucket_when_large():
    tree = [jnp.zeros((8,)), jnp.zeros((8,))]
    assert grad_sync.partition_buckets(tree, bucket_bytes=1 << 30) == [[0, 1]]


def test_sync_payload_bytes_int8_halves():
    tree = {"w": jnp.zeros((4096, 8)), "tiny": jnp.zeros((3,))}
    sync = GradSyncConfig(mode="bucketed", compression="int8")
    p = grad_sync.sync_payload_bytes(tree, sync)
    assert p["compressed_bytes"] * 2 < p["f32_bytes"]
    # the tiny leaf stays f32 (scales would dominate)
    assert p["compressed_bytes"] >= 3 * 4


# ----------------------------------------------------------- f32 parity

def test_bucketed_matches_monolithic_bit_exact(env):
    step, new_state, metrics = _run(env, GradSyncConfig(mode="bucketed",
                                                        bucket_bytes=64 << 10))
    assert len(step.buckets) > 1  # actually bucketed
    assert _max_abs_diff(new_state.params, env["ref_state"].params) == 0.0
    assert float(metrics["loss"]) == float(env["ref_metrics"]["loss"])
    assert float(metrics["tokens"]) == float(env["ref_metrics"]["tokens"])
    env["bucketed_step"] = step  # reused by the HLO overlap test (one compile)


def test_a_sync_step_is_counted_like_the_stock_one(env):
    # the loop's `dispatch` lap and `train_steps_total` (train/session.py) around its call
    from ray_tpu.train import metrics as train_metrics

    step = env.get("bucketed_step") or make_train_step(
        env["cfg"], env["tx"], donate=False, sync=GradSyncConfig(mode="bucketed"))
    before = train_metrics()
    with use_mesh(env["mesh"]):
        step(env["state"], env["batch"])
    after = train_metrics()
    assert after["train_steps_total"] == before["train_steps_total"] + 1
    assert after["train_loop_dispatch_ns_total"] > before["train_loop_dispatch_ns_total"]


def test_bucket_boundaries_do_not_change_result(env):
    # tiny buckets: every leaf its own collective, boundaries cross odd
    # shapes and scalar-adjacent leaves; reference = the monolithic step
    step, tiny, _ = _run(env, GradSyncConfig(mode="bucketed", bucket_bytes=1))
    assert len(step.buckets) == len(jax.tree_util.tree_leaves(tiny.params))
    assert _max_abs_diff(tiny.params, env["ref_state"].params) == 0.0


# ------------------------------------------------------------- int8 path

def test_int8_within_documented_tolerance(env):
    _, new_state, metrics = _run(env, GradSyncConfig(
        mode="bucketed", compression="int8", min_quant_elems=1))
    assert np.isfinite(float(metrics["loss"]))
    # loss of step 1 is computed before the sync touches params
    assert float(metrics["loss"]) == float(env["ref_metrics"]["loss"])
    # params after one update: within the block-quantization contract —
    # per-element error <= mean over ranks of amax_block/254, scaled through
    # Adam; a generous end-to-end envelope is 5% relative on the update
    ref = env["ref_state"].params
    rel = jax.tree_util.tree_map(
        lambda a, b, p: float(jnp.max(jnp.abs(a - b))
                              / (jnp.max(jnp.abs(p - b)) + 1e-12)),
        new_state.params, ref, env["state"].params)
    # updates themselves are tiny (warmup); compare update deltas not params
    assert max(jax.tree_util.tree_leaves(rel)) < 0.25


def test_stochastic_rounding_unbiased():
    x = jnp.full((512,), 0.3)  # 0.3/scale is not representable exactly
    from ray_tpu.ops.quant import dequant_blockwise, quantize_blockwise

    acc = np.zeros((512,), np.float64)
    n = 64
    for i in range(n):
        q, s = quantize_blockwise(x, 128, key=jax.random.PRNGKey(i))
        acc += np.asarray(dequant_blockwise(q, s, 512, jnp.float32))
    mean = acc / n
    # round-nearest would give a constant offset; stochastic converges to x
    assert abs(float(mean.mean()) - 0.3) < 2e-3


def test_quantize_blockwise_roundtrip_tolerance():
    from ray_tpu.ops.quant import dequant_blockwise, quantize_blockwise

    x = jax.random.normal(jax.random.PRNGKey(0), (1000,))
    q, s = quantize_blockwise(x, 256)
    y = dequant_blockwise(q, s, 1000, jnp.float32)
    blocks = jnp.pad(x, (0, 24)).reshape(4, 256)
    amax = jnp.max(jnp.abs(blocks), axis=1)
    bound = np.repeat(np.asarray(amax), 256)[:1000] / 254 + 1e-6
    assert np.all(np.abs(np.asarray(y - x)) <= bound)


# ------------------------------------------------- sharded optimizer update

def test_sharded_update_bit_exact_and_sharded(env):
    sync = GradSyncConfig(sharded_update=True)
    state = init_state(jax.random.PRNGKey(0), env["cfg"], env["tx"],
                       mesh=env["mesh"], sync=sync)
    _, new_state, _ = _run(env, sync, state=state)
    assert _max_abs_diff(new_state.params, env["ref_state"].params) == 0.0
    # the Adam moments actually live sharded over dp
    embed_shape = env["state"].params["embed"].shape
    moment_specs = [leaf.sharding.spec
                    for leaf in jax.tree_util.tree_leaves(new_state.opt_state)
                    if getattr(leaf, "shape", None) == embed_shape]
    assert moment_specs and all("dp" in str(s) for s in moment_specs)


def test_sharded_update_composes_with_bucketed(env):
    sync = GradSyncConfig(mode="bucketed", sharded_update=True)
    state = init_state(jax.random.PRNGKey(0), env["cfg"], env["tx"],
                       mesh=env["mesh"], sync=sync)
    _, new_state, _ = _run(env, sync, state=state)
    assert _max_abs_diff(new_state.params, env["ref_state"].params) == 0.0


def test_build_update_specs(env):
    from jax.sharding import PartitionSpec as P

    mesh = env["mesh"]
    specs = grad_sync.build_update_specs(env["state"].params, mesh, axes=("dp",))
    # embed [256, 64]: dim0 divisible by dp=8 -> gains dp
    assert "dp" in str(specs["embed"])
    # scalars/non-divisible leaves keep their base spec
    tiny = jax.ShapeDtypeStruct((3,), jnp.float32)
    out = grad_sync.build_update_specs({"t": tiny}, mesh, axes=("dp",))
    assert out["t"] == P()


def test_opt_state_bytes_per_shard(env):
    tx, mesh = env["tx"], env["mesh"]
    base = grad_sync.abstract_sharded_opt_state(
        tx, jax.eval_shape(lambda p: p, env["state"].params), mesh, axes=())
    sharded = grad_sync.abstract_sharded_opt_state(
        tx, jax.eval_shape(lambda p: p, env["state"].params), mesh, axes=("dp",))
    b0 = grad_sync.opt_state_bytes_per_shard(base)
    b1 = grad_sync.opt_state_bytes_per_shard(sharded)
    assert b1 * 2 <= b0  # dp=8 sharding cuts the dominant moments >= 2x


# ------------------------------------------------------------ HLO overlap

def test_bucketed_reductions_not_sunk_to_end(env):
    step = env.get("bucketed_step")
    if step is None:  # parity test not run first (e.g. -k selection)
        step = make_train_step(env["cfg"], env["tx"], donate=False,
                               sync=GradSyncConfig(mode="bucketed",
                                                   bucket_bytes=64 << 10))
    with use_mesh(env["mesh"]):
        lowered = step.lower(env["state"], env["batch"])
        rep = grad_sync.overlap_report(lowered.compile())
    # the program asks for one reduction per bucket; XLA's combiner may merge
    # neighbours afterwards, so count before it runs and check placement after
    assert lowered.as_text().count("stablehlo.all_reduce") >= len(step.buckets)
    assert rep["n_reductions"] >= 1
    assert not rep["all_sunk_to_end"]
    assert rep["n_compute_after_first_reduction"] > 0


# ----------------------------------------------------------- config plumbing

def test_config_env_roundtrip():
    sync = GradSyncConfig(mode="bucketed", compression="int8",
                          stochastic_rounding=True, sharded_update=True,
                          bucket_bytes=123456, telemetry=True,
                          quant_block_elems=512, min_quant_elems=64,
                          update_axes=("dp",))
    env = sync.to_env()
    import os

    old = {k: os.environ.get(k) for k in env}
    os.environ.update(env)
    try:
        back = GradSyncConfig.from_env()
    finally:
        for k, v in old.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
    assert back == sync  # every field round-trips (frozen dataclass equality)


def test_monolithic_alias_and_default():
    assert GradSyncConfig(mode="monolithic").mode == "gspmd"
    assert GradSyncConfig().is_default
    assert not GradSyncConfig(mode="bucketed").is_default
    with pytest.raises(ValueError):
        GradSyncConfig(mode="nope")
    with pytest.raises(ValueError):
        GradSyncConfig(mode="bucketed", compression="fp4")
    with pytest.raises(ValueError, match="bucketed"):
        GradSyncConfig(compression="int8")  # silently-ignored int8 forbidden


def test_incompatible_model_rejected(env):
    cfg = get_config("test-tiny", attention_impl="ring")
    step = make_train_step(cfg, env["tx"], donate=False,
                           sync=GradSyncConfig(mode="bucketed"))
    with pytest.raises(ValueError, match="ring"):
        with use_mesh(env["mesh"]):
            step(env["state"], env["batch"])


# ------------------------------------------------------- telemetry phases

def test_instrumented_step_records_phases(env):
    from ray_tpu.util import telemetry

    telemetry.enable()
    try:
        sync = GradSyncConfig(mode="bucketed", bucket_bytes=64 << 10,
                              telemetry=True)
        step = make_train_step(env["cfg"], env["tx"], donate=False, sync=sync)
        with use_mesh(env["mesh"]):
            state, metrics = step(env["state"], env["batch"])
            state, metrics = step(state, env["batch"])
        assert float(metrics["loss"]) > 0
        from ray_tpu.util import metrics as M

        snap = M.merge_snapshots([M._registry.snapshot()])
        hist = snap.get("train_grad_sync_seconds", {}).get("values", {})
        phases = {dict(k).get("phase") for k in hist}
        assert "grad_sync.forward_backward" in phases
        assert "grad_sync.bucket_wait" in phases
        assert "grad_sync.optimizer" in phases
        # the generic step-phase histogram carries the spans too
        sp = snap.get("train_step_phase_seconds", {}).get("values", {})
        assert any(dict(k).get("phase", "").startswith("grad_sync.")
                   for k in sp)
    finally:
        telemetry.disable()


def test_cluster_status_exposes_grad_sync_phases(rt):
    from ray_tpu.util import state as state_api

    status = state_api.cluster_status()
    assert "grad_sync_phases_s" in status["train"]


# ------------------------------------------------------ jax_backend satellites

def test_pick_port_race_retries_once(monkeypatch):
    from ray_tpu.train import jax_backend as jb

    assert jb._is_bind_failure(OSError(98, "Address already in use"))
    assert jb._is_bind_failure(RuntimeError("Failed to bind to port 4242"))
    assert not jb._is_bind_failure(RuntimeError("NCCL timeout"))
    # an unrelated OSError (dead worker, broken pipe) must NOT look like a
    # port race — the retry would bury the real failure
    assert not jb._is_bind_failure(OSError(32, "Broken pipe"))


def test_shutdown_warning_throttled(caplog):
    import logging

    from ray_tpu.train import jax_backend as jb

    jb._last_shutdown_warning[0] = 0.0
    with caplog.at_level(logging.WARNING, logger=jb.__name__):
        jb._warn_shutdown_failure("test path", RuntimeError("boom"))
        jb._warn_shutdown_failure("test path", RuntimeError("boom2"))  # throttled
    msgs = [r for r in caplog.records if "on_shutdown" in r.getMessage()]
    assert len(msgs) == 1
    assert "boom" in msgs[0].getMessage()
