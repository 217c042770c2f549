"""Model-level parallelism correctness: losses under pp/sp/tp sharded configs must match
the plain single-config forward bit-for-bit-ish (f32 tolerances)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ray_tpu.models import llama
from ray_tpu.models.config import get_config
from ray_tpu.parallel import local_mesh, use_mesh
from ray_tpu.train import init_state, make_optimizer, make_train_step


def _loss_for(cfg, mesh, tokens):
    tx = make_optimizer(total_steps=10)
    state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
    step = make_train_step(cfg, tx, donate=False)
    with use_mesh(mesh):
        _, metrics = step(state, {"tokens": tokens})
    return float(metrics["loss"]), float(metrics["grad_norm"])


def test_pp_ring_tp_matches_plain():
    tokens = jax.random.randint(jax.random.PRNGKey(7), (4, 33), 0, 256)

    plain_cfg = get_config("test-tiny", dtype="float32")
    plain_mesh = local_mesh(dp=8)
    loss_plain, gn_plain = _loss_for(plain_cfg, plain_mesh, tokens)

    sharded_cfg = get_config(
        "test-tiny", dtype="float32", attention_impl="ring", pipeline_stages=2,
        pipeline_microbatches=2,
    )
    sharded_mesh = local_mesh(pp=2, sp=2, tp=2)
    loss_sharded, gn_sharded = _loss_for(sharded_cfg, sharded_mesh, tokens)

    np.testing.assert_allclose(loss_sharded, loss_plain, rtol=1e-5)
    np.testing.assert_allclose(gn_sharded, gn_plain, rtol=1e-4)


def test_ulysses_in_model():
    tokens = jax.random.randint(jax.random.PRNGKey(8), (4, 33), 0, 256)
    plain_cfg = get_config("test-tiny", dtype="float32")
    loss_plain, _ = _loss_for(plain_cfg, local_mesh(dp=8), tokens)
    uly_cfg = get_config("test-tiny", dtype="float32", attention_impl="ulysses")
    loss_uly, _ = _loss_for(uly_cfg, local_mesh(dp=2, sp=2, tp=2), tokens)
    np.testing.assert_allclose(loss_uly, loss_plain, rtol=1e-5)


def test_pp_moe_matches_plain(monkeypatch):
    """MoE composes with pipeline parallelism: the stage-threaded aux loss (bubble
    ticks masked, psum over stages, mean over microbatches) reproduces the plain
    run's loss exactly. Group size pinned to 32 so microbatch boundaries align
    with dispatch-group boundaries — the two paths then partition tokens
    identically and every capacity decision matches."""
    monkeypatch.setenv("RAY_TPU_MOE_GROUP_SIZE", "32")
    tokens = jax.random.randint(jax.random.PRNGKey(9), (4, 33), 0, 256)

    plain_cfg = get_config("moe-tiny", dtype="float32")
    loss_plain, gn_plain = _loss_for(plain_cfg, local_mesh(dp=4, ep=2), tokens)

    pp_cfg = get_config("moe-tiny", dtype="float32", pipeline_stages=2,
                        pipeline_microbatches=2)
    loss_pp, gn_pp = _loss_for(pp_cfg, local_mesh(pp=2, ep=2, tp=2), tokens)

    np.testing.assert_allclose(loss_pp, loss_plain, rtol=1e-5)
    np.testing.assert_allclose(gn_pp, gn_plain, rtol=1e-4)

    # the aux loss is genuinely nonzero (the fence used to drop it silently)
    cfg = get_config("moe-tiny", dtype="float32", pipeline_stages=2,
                     pipeline_microbatches=2)
    mesh = local_mesh(pp=2, ep=2, tp=2)
    state = init_state(jax.random.PRNGKey(0), cfg, make_optimizer(total_steps=10),
                       mesh=mesh)
    step = make_train_step(cfg, make_optimizer(total_steps=10), donate=False)
    with use_mesh(mesh):
        _, metrics = step(state, {"tokens": tokens})
    assert float(metrics["moe_aux_loss"]) > 0.0


def test_pp_segment_ids_matches_plain():
    """Packed sequences (segment_ids) ride the pipeline as microbatched side
    inputs; pp2(ring)/sp2/tp2 loss matches the plain run on the same packed
    batch. Exercises both the side-input plumbing and local-chunk slicing of
    the packing mask under sp."""
    import jax.numpy as jnp

    tokens = jax.random.randint(jax.random.PRNGKey(11), (4, 33), 0, 256)
    # two packed documents per row: ids 1 then 2
    seg = jnp.concatenate([jnp.full((4, 17), 1, jnp.int32),
                           jnp.full((4, 16), 2, jnp.int32)], axis=1)

    def loss_with_seg(cfg, mesh):
        tx = make_optimizer(total_steps=10)
        state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
        step = make_train_step(cfg, tx, donate=False)
        with use_mesh(mesh):
            _, metrics = step(state, {"tokens": tokens, "segment_ids": seg})
        return float(metrics["loss"])

    plain = loss_with_seg(get_config("test-tiny", dtype="float32"), local_mesh(dp=8))
    pp = loss_with_seg(
        get_config("test-tiny", dtype="float32", attention_impl="ring",
                   pipeline_stages=2, pipeline_microbatches=2),
        local_mesh(pp=2, sp=2, tp=2))
    np.testing.assert_allclose(pp, plain, rtol=1e-5)
    # packing must actually matter (the mask isn't being dropped somewhere)
    tx = make_optimizer(total_steps=10)
    cfg = get_config("test-tiny", dtype="float32")
    state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh=local_mesh(dp=8))
    step = make_train_step(cfg, tx, donate=False)
    with use_mesh(local_mesh(dp=8)):
        _, m_noseg = step(state, {"tokens": tokens})
    assert abs(float(m_noseg["loss"]) - plain) > 1e-7


def test_pp_moe_token_mask_matches_plain(monkeypatch):
    """token_mask (MoE capacity masking for padded batches) rides the pipeline
    as a side input: pp2/ep2 logits match the plain forward bit-for-bit when
    dispatch groups align, and masked tokens genuinely change routing."""
    import jax.numpy as jnp

    from ray_tpu.models import llama as L

    monkeypatch.setenv("RAY_TPU_MOE_GROUP_SIZE", "32")
    cfg_plain = get_config("moe-tiny", dtype="float32", remat=False)
    cfg_pp = get_config("moe-tiny", dtype="float32", remat=False,
                        pipeline_stages=2, pipeline_microbatches=2)
    params = L.init(jax.random.PRNGKey(3), cfg_plain)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (4, 32), 0, 256)
    mask = jnp.ones((4, 32), jnp.float32).at[:, 24:].set(0.0)  # tail padding

    with use_mesh(local_mesh(dp=4, ep=2)):
        ref, _, aux_ref = L.forward(params, tokens, cfg_plain,
                                    token_mask=mask, return_aux=True)
        ref, aux_ref = np.asarray(ref), float(aux_ref)
    with use_mesh(local_mesh(pp=2, ep=2, tp=2)):
        got, _, aux_got = L.forward(params, tokens, cfg_pp,
                                    token_mask=mask, return_aux=True)
        got, aux_got = np.asarray(got), float(aux_got)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(aux_got, aux_ref, rtol=1e-5)


def test_pp_positions_honored():
    """Caller-supplied RoPE position offsets reach pipeline stages (they ride as
    a side input); pp logits match the plain forward at the same offsets."""
    import jax.numpy as jnp

    from ray_tpu.models import llama as L

    cfg_plain = get_config("test-tiny", dtype="float32", remat=False)
    cfg_pp = get_config("test-tiny", dtype="float32", remat=False,
                        pipeline_stages=2, pipeline_microbatches=2)
    params = L.init(jax.random.PRNGKey(5), cfg_plain)
    tokens = jax.random.randint(jax.random.PRNGKey(6), (4, 16), 0, 256)
    # numpy (not jax) array: eager forwards under two different mesh contexts
    # would otherwise pin the first mesh's sharding onto the array. Non-uniform
    # spacing (x2), not a constant offset — RoPE is shift-invariant, so a
    # uniform offset would leave causal attention unchanged and prove nothing.
    pos = np.broadcast_to(np.arange(16, dtype=np.int32)[None, :] * 2,
                          (4, 16)).copy()

    with use_mesh(local_mesh(dp=4, tp=2)):
        ref, _ = L.forward(params, tokens, cfg_plain, positions=pos)
        ref = np.asarray(ref)
        base, _ = L.forward(params, tokens, cfg_plain)
        base = np.asarray(base)
    with use_mesh(local_mesh(pp=2, tp=2, dp=2)):
        got, _ = L.forward(params, tokens, cfg_pp, positions=pos)
        got = np.asarray(got)
    np.testing.assert_allclose(got, ref, rtol=2e-5, atol=2e-5)
    # the offset genuinely changes the result (otherwise this test proves nothing)
    assert np.abs(ref - base).max() > 1e-3


@pytest.mark.parametrize("axes", [dict(dp=2, fsdp=2), dict(fsdp=2, tp=2), dict(dp=1)])
def test_step_compiles_once_for_a_state_placed_on_a_mesh(axes):
    """The state init_state places and the state a step returns are keyed
    alike by jit: the second step must not compile again (on the chip that
    was 16 s at step 2 of a dp=2 x fsdp=2 run)."""
    from ray_tpu.parallel import MeshSpec, build_mesh
    from ray_tpu.parallel.sharding import named_sharding

    n = int(np.prod(list(axes.values())))
    mesh = build_mesh(MeshSpec(**axes), jax.devices()[:n])
    cfg = get_config("test-tiny", dtype="float32")
    tx = make_optimizer(total_steps=10)
    state = init_state(jax.random.PRNGKey(0), cfg, tx, mesh=mesh)
    step = make_train_step(cfg, tx)
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(7), (4, 33), 0, 256))
    with use_mesh(mesh):
        batch = {"tokens": jax.device_put(tokens, named_sharding(mesh, "batch", None))}
        for _ in range(3):
            state, _ = step(state, batch)
    assert step._cache_size() == 1
