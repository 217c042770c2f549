"""Model correctness tests on CPU (8 virtual devices)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import highest, model_of, params, tokens  # noqa: F401  (highest: a fixture)
from ray_tpu.models import get_config, llama
from ray_tpu.models.config import ModelConfig
from ray_tpu.models.reference import glm4_moe_lite as glm_ref
from ray_tpu.models.reference import lfm2_moe as lfm2_ref
from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
from ray_tpu.parallel.sharding import TRAIN_RULES, shard_pytree

CFG = get_config("test-tiny")


def _params():
    return llama.init(jax.random.PRNGKey(0), CFG)


def test_forward_shapes():
    params = _params()
    tokens = jnp.arange(32, dtype=jnp.int32).reshape(2, 16) % CFG.vocab_size
    logits, cache = llama.forward(params, tokens, CFG)
    assert logits.shape == (2, 16, CFG.vocab_size)
    assert cache is None
    assert logits.dtype == jnp.float32


def test_loss_and_grad_finite():
    params = _params()
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, CFG.vocab_size)
    (loss, aux), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(
        params, {"tokens": tokens}, CFG
    )
    assert np.isfinite(float(loss))
    flat = jax.tree.leaves(grads)
    assert all(np.all(np.isfinite(np.asarray(g))) for g in flat)
    assert float(loss) > 0


def test_decode_matches_full_forward():
    """Prefill+decode through KV cache must reproduce the full-sequence logits."""
    params = _params()
    tokens = jax.random.randint(jax.random.PRNGKey(2), (1, 12), 0, CFG.vocab_size)
    full_logits, _ = llama.forward(params, tokens, CFG)

    cache = llama.init_kv_cache(CFG, batch=1, max_len=16, dtype=jnp.float32)
    prefill_logits, cache = llama.forward(params, tokens[:, :8], CFG, cache=cache)
    np.testing.assert_allclose(
        np.asarray(prefill_logits), np.asarray(full_logits[:, :8]), rtol=2e-4, atol=2e-4
    )
    # Decode one token at a time.
    for i in range(8, 12):
        step_logits, cache = llama.forward(params, tokens[:, i : i + 1], CFG, cache=cache)
        np.testing.assert_allclose(
            np.asarray(step_logits[:, 0]), np.asarray(full_logits[:, i]), rtol=2e-4, atol=2e-4
        )


def test_segment_ids_isolate_packed_sequences():
    """Packed sequences must not attend across segment boundaries."""
    params = _params()
    a = jax.random.randint(jax.random.PRNGKey(3), (1, 6), 0, CFG.vocab_size)
    b = jax.random.randint(jax.random.PRNGKey(4), (1, 6), 0, CFG.vocab_size)
    packed = jnp.concatenate([a, b], axis=1)
    seg = jnp.concatenate([jnp.zeros((1, 6), jnp.int32), jnp.ones((1, 6), jnp.int32)], axis=1)
    packed_logits, _ = llama.forward(params, packed, CFG, segment_ids=seg)
    solo_logits, _ = llama.forward(params, a, CFG)
    # Segment a inside the pack must match running a alone (positions restart not modeled;
    # use same positions explicitly).
    np.testing.assert_allclose(
        np.asarray(packed_logits[:, :6]), np.asarray(solo_logits), rtol=2e-4, atol=2e-4
    )


def test_sharded_train_step():
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2, tp=2))
    params = _params()
    axes = llama.param_axes(CFG)
    params = shard_pytree(params, axes, mesh, TRAIN_RULES)
    tokens = jax.random.randint(jax.random.PRNGKey(5), (8, 17), 0, CFG.vocab_size)

    @jax.jit
    def step(p, batch):
        (loss, _), grads = jax.value_and_grad(llama.loss_fn, has_aux=True)(p, batch, CFG)
        new_p = jax.tree.map(lambda w, g: w - 1e-3 * g, p, grads)
        return loss, new_p

    with use_mesh(mesh):
        loss, new_params = step(params, {"tokens": tokens})
    assert np.isfinite(float(loss))
    # sgd-updated params keep the parameter shardings (grads get resharded to match)
    w = new_params["layers"]["w_gate"]
    assert w.sharding.spec == params["layers"]["w_gate"].sharding.spec


def test_a_restored_state_runs_the_step_it_compiled():
    """A seeded state is committed to its device as a state that comes back from the
    host (a checkpoint restored, the benchmark's comparison with its reference) is:
    the step compiled for the first serves the second. It was compiled twice."""
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    tx = make_optimizer()
    state = init_state(jax.random.PRNGKey(0), CFG, tx)
    shardings = jax.tree.map(lambda a: a.sharding, state)
    step = make_train_step(CFG, tx, donate=False)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(5), (2, 17), 0, CFG.vocab_size)}
    state, _ = step(state, batch)
    restored = jax.device_put(jax.device_get(state), shardings)
    step(restored, batch)
    assert step._cache_size() == 1


def test_n_params_reasonable():
    cfg8b = get_config("llama3-8b")
    assert 7.5e9 < cfg8b.n_params < 8.6e9


def test_remat_policies_agree():
    """remat_policy changes scheduling, never math: losses must match exactly."""
    import dataclasses

    import jax

    from ray_tpu.models import get_config
    from ray_tpu.models import llama as ll

    losses = {}
    for pol in ("full", "dots", "dots_no_batch"):
        cfg = dataclasses.replace(get_config("test-tiny"), remat_policy=pol)
        params = ll.init(jax.random.PRNGKey(0), cfg)
        tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 17), 0, cfg.vocab_size)
        loss, _ = ll.loss_fn(params, {"tokens": tokens}, cfg)
        losses[pol] = float(loss)
    assert losses["full"] == losses["dots"] == losses["dots_no_batch"], losses


# ------------------------------------------------- one block, every program (PR 29)

def _serving_inputs(cfg, layout, slots=4, max_len=32, block_size=8, seed=3):
    """A decode state of `layout` with distinct per-slot histories (prefilled
    through the model, so the cache holds real K/V), the last token of every
    slot, and one inactive slot."""
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm import paged

    params = llama.init(jax.random.PRNGKey(seed), cfg)
    rng = np.random.default_rng(seed)
    lens = [5, 9, 3, 7][:slots]
    if layout == "slot":
        mesh = jax.sharding.Mesh(np.asarray(jax.devices()[:1]).reshape(1, 1), ("dp", "tp"))
        state = mr.init_state(cfg, slots, max_len, mesh)
    else:
        n_per_slot = max_len // block_size
        state = paged.init_paged_state(cfg, slots, max_len, slots * n_per_slot, block_size)
    for s, n in enumerate(lens):
        toks = np.zeros((1, 16), np.int32)
        toks[0, :n] = rng.integers(1, cfg.vocab_size, n)
        k, v, _ = mr.prefill_detached(params, jnp.asarray(toks), jnp.int32(n), cfg)
        if layout == "slot":
            state = mr.install_kv(state, k, v, jnp.int32(n), jnp.int32(s))
        else:
            # the slot's whole table, so a window past the prompt has blocks to land in
            row = jnp.arange(s * n_per_slot, (s + 1) * n_per_slot, dtype=jnp.int32)
            state = paged.install_with_prefix(
                state, k, v, row[:16 // block_size], row, jnp.int32(n), jnp.int32(s),
                n_new=16 // block_size)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, slots), jnp.int32)
    active = jnp.asarray([True, True, False, True][:slots])
    return params, state, tokens, active


def _copy(state):
    return jax.tree.map(jnp.copy, state)  # the programs donate their state


def _slot_view(state, s, n):
    """Slot s's first n cached positions as [L, n, KV, HD], either layout."""
    if hasattr(state, "block_tables"):
        bs = state.k.shape[2]
        blocks = state.block_tables[s]
        k = state.k[:, blocks].reshape(state.k.shape[0], -1, *state.k.shape[3:])
        v = state.v[:, blocks].reshape(state.v.shape[0], -1, *state.v.shape[3:])
        assert k.shape[1] == blocks.shape[0] * bs
        return k[:, :n], v[:, :n]
    return state.k[:, s, :n], state.v[:, s, :n]


SEAM_PROGRAMS = ["forward", "prefill_detached", "decode_step", "decode_step_paged",
                 "spec_verify_step", "spec_verify_step_paged"]


@pytest.mark.parametrize("program", SEAM_PROGRAMS)
def test_every_program_goes_through_the_one_feed_forward(program, monkeypatch):
    """The block is written once: tracing any program that runs the model calls
    llama.feed_forward (and, with it, the other parts beside it). A fourth copy
    of the block in a serving program would pass every parity test and fail this."""
    import dataclasses

    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm import paged

    calls = []
    real = llama.feed_forward

    def marked(x, lp, cfg, *a, **kw):
        calls.append(x.shape)
        return real(x, lp, cfg, *a, **kw)

    monkeypatch.setattr(llama, "feed_forward", marked)
    # a cfg of its own: the jitted programs key their traces on it
    cfg = dataclasses.replace(CFG, name=f"seam-{program}")
    layout = "paged" if program.endswith("paged") else "slot"
    params, state, tokens, active = _serving_inputs(cfg, layout)
    calls.clear()  # _serving_inputs prefilled through the model
    s = tokens.shape[0]
    sample = (jax.random.PRNGKey(0), jnp.zeros((s,)), jnp.ones((s,)),
              jnp.zeros((s,), jnp.int32))
    window = jnp.stack([tokens, tokens, tokens], axis=1)
    if program == "forward":
        llama.forward(params, window, cfg)
    elif program == "prefill_detached":
        mr.prefill_detached(params, jnp.zeros((1, 32), jnp.int32), jnp.int32(4), cfg)
    elif program == "decode_step":
        mr.decode_step(params, state, tokens, active, cfg)
    elif program == "decode_step_paged":
        paged.decode_step_paged(params, state, tokens, active, cfg)
    elif program == "spec_verify_step":
        mr.spec_verify_step(params, state, window, jnp.full((s,), 2, jnp.int32),
                            active, cfg, *sample)
    else:
        paged.spec_verify_step_paged(params, state, window, jnp.full((s,), 2, jnp.int32),
                                     active, cfg, *sample)
    assert calls, f"{program} traced no call of llama.feed_forward"


@pytest.mark.parametrize("layout", ["slot", "paged"])
@pytest.mark.parametrize("model", ["test-tiny", "moe-tiny"])
def test_decode_is_a_window_of_one(model, layout):
    """A decode step is the verify window at W=1, and both are the block that
    prefill runs: for every slot, decode_step's logits and the K/V it wrote equal
    (a) a one-token verify step's token, cache and lengths, and (b) llama.forward
    of that token against the slot's own cache (the train/prefill body, scalar
    offsets, ops.attention), which holds the window core to _block."""
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm import paged

    cfg = get_config(model)
    params, state, tokens, active = _serving_inputs(cfg, layout)
    s = tokens.shape[0]
    lens = np.asarray(state.lengths)
    step, verify = ((mr.decode_step, mr.spec_verify_step) if layout == "slot"
                    else (paged.decode_step_paged, paged.spec_verify_step_paged))
    before = _copy(state)
    dec, logits = step(params, _copy(state), tokens, active, cfg)
    ver, greedy, n_acc = verify(
        params, _copy(state), tokens[:, None], jnp.zeros((s,), jnp.int32), active, cfg,
        jax.random.PRNGKey(0), jnp.zeros((s,)), jnp.ones((s,)), jnp.zeros((s,), jnp.int32))

    # (a) the one-token window: same token, same cache, same lengths
    np.testing.assert_array_equal(np.asarray(greedy[:, 0]), np.asarray(jnp.argmax(logits, -1)))
    np.testing.assert_array_equal(np.asarray(n_acc), 0)
    np.testing.assert_array_equal(np.asarray(ver.lengths), np.asarray(dec.lengths))
    np.testing.assert_array_equal(np.asarray(dec.lengths), lens + np.asarray(active))
    for i in np.flatnonzero(np.asarray(active)):
        for a, b in zip(_slot_view(ver, i, lens[i] + 1), _slot_view(dec, i, lens[i] + 1)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    # (b) the prefill body on each slot alone
    for i in np.flatnonzero(np.asarray(active)):
        n = int(lens[i])
        k, v = _slot_view(before, i, n)
        pad = ((0, 0), (0, 0), (0, 1), (0, 0), (0, 0))
        cache = llama.KVCache(k=jnp.pad(k[:, None], pad), v=jnp.pad(v[:, None], pad),
                              length=jnp.int32(n))
        want, cache = llama.forward(params, tokens[i][None, None], cfg, cache=cache)
        np.testing.assert_allclose(np.asarray(logits[i]), np.asarray(want[0, 0]),
                                   rtol=2e-5, atol=2e-5)
        got_k, got_v = _slot_view(dec, i, n + 1)
        np.testing.assert_allclose(np.asarray(got_k), np.asarray(cache.k[:, 0]),
                                   rtol=1e-6, atol=1e-6)
        np.testing.assert_allclose(np.asarray(got_v), np.asarray(cache.v[:, 0]),
                                   rtol=1e-6, atol=1e-6)


# ------------------------------------- RoPE in front of the flash kernels (PR 30)

def _eqns(jaxpr, out=None):
    """Every equation of a program, nested calls included."""
    out = [] if out is None else out
    for eqn in jaxpr.eqns:
        out.append(eqn)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _eqns(sub, out)
    return out


def _kernel_names(jaxpr):
    return [e.params["name"] for e in _eqns(jaxpr) if e.primitive.name == "pallas_call"]


def _pallas_cfg(**kw):
    """test-tiny at the lane width the kernels tile (head_dim 128), forced onto the
    Pallas path (interpreted on the CPU)."""
    import dataclasses

    return dataclasses.replace(CFG, **{"name": "rope-in-front", "d_model": 256, "n_heads": 2,
                                       "n_kv_heads": 1, "attention_impl": "pallas", **kw})


def test_train_step_rotates_in_the_kernel():
    """On the Pallas path the train step's loss holds the rotate kernel once a phase
    (the rotated q and k are kept for the backward under remat `dots`) and no
    concatenate of q's or k's halves: `rope`'s split-and-join is not in the program."""
    cfg = _pallas_cfg(remat_policy="dots")
    params = llama.init(jax.random.PRNGKey(0), cfg)
    tokens = jnp.zeros((2, 33), jnp.int32)
    grad = jax.make_jaxpr(jax.grad(lambda p: llama.loss_fn(p, {"tokens": tokens}, cfg)[0]))(params)
    names = _kernel_names(grad.jaxpr)
    assert names.count("rope_fwd") == 1 and names.count("rope_bwd") == 1, names
    assert names.count("flash_attention_fwd") == 2, names  # once more in the backward
    joins = [e.outvars[0].aval.shape for e in _eqns(grad.jaxpr) if e.primitive.name == "concatenate"]
    assert joins and all(len(shape) == 3 for shape in joins), joins  # the angles' tables only
    # `full` keeps the flash kernel's output and logsumexp and no q or k: the rotation
    # runs again in the backward, the forward flash kernel does not (PR 43)
    full = _pallas_cfg(remat_policy="full")
    names = _kernel_names(jax.make_jaxpr(jax.grad(
        lambda p: llama.loss_fn(p, {"tokens": tokens}, full)[0]))(params).jaxpr)
    assert names.count("rope_fwd") == 2 and names.count("rope_bwd") == 1, names
    assert names.count("flash_attention_fwd") == 1, names


@pytest.mark.parametrize("program", ["prefill_detached", "decode_step", "decode_step_paged",
                                     "spec_verify_step"])
def test_serving_programs_hold_no_kernel(program):
    """Cached prefill and the decode window take the XLA attention path and the
    jax.numpy `rope` (PERF.md, PR 28 (a)): no Pallas kernel in any of them, also with
    the model forced onto the Pallas path where it can take it."""
    from ray_tpu.llm import model_runner as mr
    from ray_tpu.llm import paged

    cfg = _pallas_cfg(attention_impl="auto", name=f"no-kernel-{program}")
    layout = "paged" if program.endswith("paged") else "slot"
    params, state, tokens, active = _serving_inputs(cfg, layout)
    s = tokens.shape[0]
    if program == "prefill_detached":
        jaxpr = jax.make_jaxpr(lambda p, t: mr.prefill_detached(p, t, jnp.int32(20), cfg))(
            params, jnp.zeros((1, 512), jnp.int32))
    elif program == "decode_step":
        jaxpr = jax.make_jaxpr(lambda p, st: mr.decode_step(p, st, tokens, active, cfg))(params, state)
    elif program == "decode_step_paged":
        jaxpr = jax.make_jaxpr(lambda p, st: paged.decode_step_paged(p, st, tokens, active, cfg))(
            params, state)
    else:
        sample = (jax.random.PRNGKey(0), jnp.zeros((s,)), jnp.ones((s,)), jnp.zeros((s,), jnp.int32))
        window = jnp.stack([tokens, tokens, tokens], axis=1)
        jaxpr = jax.make_jaxpr(lambda p, st: mr.spec_verify_step(
            p, st, window, jnp.full((s,), 2, jnp.int32), active, cfg, *sample))(params, state)
    assert _kernel_names(jaxpr.jaxpr) == []
    assert any(e.primitive.name == "concatenate" and len(e.outvars[0].aval.shape) == 4
               for e in _eqns(jaxpr.jaxpr)), "rope's join of the halves is in the program"


@pytest.mark.parametrize("policy", ["dots", "dots_no_batch"])
def test_remat_keeps_the_rotated_pair(policy):
    """Under the `dots` policies a layer's residuals hold the rotated q and k (as the
    kernel wrote them, by name) and v, and not the q and k projections' own outputs,
    which nothing in the backward reads."""
    from jax._src.ad_checkpoint import saved_residuals

    cfg = _pallas_cfg(remat_policy=policy)
    params = llama.init(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda x: x[0], params["layers"])
    b, s = 2, 32
    x = jnp.zeros((b, s, cfg.d_model), jnp.float32)
    pos = jnp.arange(s)[None, :]
    body = llama._maybe_remat(lambda x, lp: llama._block(x, lp, cfg, pos, None)[0], cfg)
    kept = [(aval.shape, why) for aval, why in saved_residuals(body, x, lp)]
    named = {why.split("'")[1]: shape for shape, why in kept if why.startswith("named")}
    assert named == {"rope_q": (b, cfg.n_heads, s, cfg.head_dim),
                     "rope_k": (b, cfg.n_kv_heads, s, cfg.head_dim)}, kept
    from_proj = [shape for shape, why in kept if "(qkv_proj)" in why]
    assert from_proj == [(b, s, cfg.n_kv_heads, cfg.head_dim)], kept  # v alone


@pytest.mark.parametrize("case", ["plain", "packed", "mesh", "packed-mesh"])
def test_rotating_in_the_kernel_is_the_model(case):
    """The forced-Pallas forward (rotation in the kernel, interpreted) against the same
    model on the XLA path (`rope`, then attention_reference): logits and the gradients
    of every parameter; packed rows restart their positions; under a dp x fsdp mesh the
    kernels run per shard with their shard's positions."""
    import contextlib

    pallas, xla = _pallas_cfg(), _pallas_cfg(attention_impl="reference")
    params = llama.init(jax.random.PRNGKey(0), pallas)
    b, s = 4, 64
    tokens = jax.random.randint(jax.random.PRNGKey(1), (b, s), 0, pallas.vocab_size)
    g = jax.random.normal(jax.random.PRNGKey(2), (b, s, pallas.vocab_size), jnp.float32)
    kw = {}
    if case.startswith("packed"):
        cuts = np.asarray([20, 31, 40, 7])[:, None]
        at = np.arange(s)[None, :]
        kw = {"segment_ids": jnp.asarray((at >= cuts).astype(np.int32)),
              "positions": jnp.asarray(np.where(at < cuts, at, at - cuts).astype(np.int32))}

    def run(cfg):
        def loss(p):
            logits, _ = llama.forward(p, tokens, cfg, **kw)
            return jnp.sum(logits * g), logits
        (_, logits), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
        return logits, grads

    mesh = build_mesh(MeshSpec(dp=2, fsdp=2), jax.devices()[:4]) if case.endswith("mesh") else None
    with use_mesh(mesh) if mesh is not None else contextlib.nullcontext():
        got, want = run(pallas), run(xla)
    np.testing.assert_allclose(np.asarray(got[0]), np.asarray(want[0]), rtol=2e-4, atol=2e-4)
    for a, r in zip(jax.tree.leaves(got[1]), jax.tree.leaves(want[1])):
        scale = max(1.0, float(jnp.max(jnp.abs(r))))
        np.testing.assert_allclose(np.asarray(a) / scale, np.asarray(r) / scale, rtol=0, atol=5e-4)


# ------------------------------------------------- the families' stacks, patterns and attention layers
# (at a small size against the plain references, ray_tpu/models/reference/; the families' own
# contract is tests/family_contract.py)

GLM, NEMOTRON = get_config("glm-tiny"), get_config("nemotron-tiny")


@pytest.mark.usefixtures("highest")
def test_llama_and_glm_keep_their_stacks_and_blocks():
    """Every other family is the pattern 'attention + feed-forward' of period 1: the same
    stacks under the same names, and a block with both parts."""
    assert llama._layer_kinds(get_config("test-tiny")) == {"layers": (2, "attn", "dense")}
    assert llama._layer_kinds(get_config("glm-tiny")) == {
        "dense_layers": (1, "attn", "dense"), "layers": (2, "attn", "experts")}
    assert llama._layer_kinds(NEMOTRON) == {"ssm_layers": (2, "ssm", None), "layers": (2, None, "experts"),
                                       "attn_layers": (1, "attn", None), "mlp_layers": (1, None, "dense")}
    p = params(NEMOTRON)
    assert set(llama.param_axes(NEMOTRON)) == set(p)
    for name, stack in llama.param_axes(NEMOTRON).items():
        if isinstance(stack, dict):
            assert set(stack) == set(p[name]), name


@pytest.mark.parametrize("pattern,unit,n", [
    ("ME", "ME", 1), ("MEME*EMEME*E", "MEME*E", 2), ("MMMM", "M", 4), ("MEMEMEM*EME", "MEMEMEM*EME", 1)])
def test_a_patterns_period(pattern, unit, n):
    assert llama.pattern_period(pattern) == (unit, n)


@pytest.mark.usefixtures("highest")
def test_a_pattern_is_checked_against_the_depth_and_the_kinds_it_names():
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(NEMOTRON, layer_pattern="MEM")
    with pytest.raises(ValueError, match="layer_pattern"):
        dataclasses.replace(NEMOTRON, layer_pattern="MEMXE-")
    with pytest.raises(NotImplementedError, match="mtp_layer_pattern"):
        dataclasses.replace(NEMOTRON, mtp_layer_pattern="ME")
    with pytest.raises(NotImplementedError, match="KV cache"):
        llama.forward(params(NEMOTRON), tokens(NEMOTRON), NEMOTRON, cache=llama.init_kv_cache(NEMOTRON, 2, 64))


@pytest.mark.usefixtures("highest")
def test_leading_and_following_stacks():
    cfg = dataclasses.replace(GLM, n_layers=4, n_dense_layers=2)
    p = jax.jit(llama.init, static_argnums=1)(jax.random.PRNGKey(0), cfg)
    axes = llama.param_axes(cfg)
    assert p["dense_layers"]["w_gate"].shape == (2, cfg.d_model, cfg.d_ff)
    assert p["layers"]["w_gate"].shape == (2, cfg.n_experts, cfg.d_model, cfg.d_ff_expert)
    assert "router" not in p["dense_layers"] and "router" in p["layers"]
    assert p["mtp"]["eh_proj"].shape == (1, 2 * cfg.d_model, cfg.d_model)
    same = jax.tree.map(lambda a, ax: a.ndim == len(ax), p, axes,
                        is_leaf=lambda x: isinstance(x, tuple))
    assert all(jax.tree.leaves(same))
    t = tokens(cfg)
    logits, _, aux = jax.jit(lambda p, t: llama.forward(p, t, cfg, return_aux=True))(p, t)
    assert aux["load"].shape == (2, cfg.n_experts)
    np.testing.assert_allclose(logits, jax.jit(lambda p, t: glm_ref.forward(p, t, model_of(cfg))[0])(p, t), atol=2e-5)
    # a one-kind model keeps its one stack and the keys it always drew
    dense = get_config("test-tiny")
    assert set(jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), dense))) == {
        "embed", "layers", "final_norm", "lm_head"}
    assert cfg.n_params == sum(a.size for a in jax.tree.leaves(p)) - sum(
        p[n]["router_bias"].size for n in ("layers", "mtp"))


@pytest.mark.usefixtures("highest")
def test_a_cache_over_two_stacks_is_refused():
    """llm/ refuses the family (llm/config.py:_served), so nothing walks a cache over a
    leading and a following stack: forward says so instead of carrying the code."""
    cfg = dataclasses.replace(GLM, mtp_depth=0)
    cache = llama.init_kv_cache(cfg, 1, 16, dtype=jnp.float32)
    with pytest.raises(NotImplementedError, match="more than one kind"):  # (while it is traced: nothing has to run)
        jax.eval_shape(lambda: llama.forward(llama.init(jax.random.PRNGKey(0), cfg), tokens(cfg, (1, 8)), cfg, cache=cache))


@pytest.mark.usefixtures("highest")
def test_rotated_slice_and_its_shared_key_against_plain_rope():
    cfg = GLM
    p = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(0), cfg)["dense_layers"])
    x = jax.random.normal(jax.random.PRNGKey(1), (2, 10, cfg.d_model))
    pos = jnp.arange(10)[None, :] * 2 + jnp.array([[0], [5]])
    q, k, v = llama.qkv_proj(x, p, cfg, pos)
    nope, rd, kvr = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.kv_lora_rank
    assert q.shape == k.shape == (2, 10, cfg.n_heads, nope + rd) and v.shape[-1] == cfg.v_head_dim
    h = llama.rms_norm(x, p["attn_norm"], cfg.norm_eps)
    cq = llama.rms_norm(h @ p["wq_a"], p["q_norm"], cfg.norm_eps)
    q_plain = jnp.einsum("bsr,rhk->bshk", cq, p["wq_b"])
    np.testing.assert_allclose(q[..., :nope], q_plain[..., :nope], atol=1e-6)
    np.testing.assert_allclose(q[..., nope:], llama.rope(q_plain[..., nope:], pos, cfg.rope_theta),
                               atol=1e-6)
    ckv = h @ p["wkv_a"]
    k_rot = llama.rope(ckv[:, :, None, kvr:], pos, cfg.rope_theta)
    for head in range(cfg.n_heads):  # one rotated key, every head's
        np.testing.assert_allclose(k[:, :, head, nope:], k_rot[:, :, 0], atol=1e-6)
    # the published pairing (2i, 2i + 1) on the checkpoint's column order is this rotation
    # on the program's: scores do not see the permutation
    perm = jnp.array(llama.rope_pairs_to_halves(rd))
    assert sorted(perm.tolist()) == list(range(rd)) and perm[:3].tolist() == [0, 2, 4]
    slice_ = q_plain[:1, :, :, nope:]
    published = jnp.zeros_like(slice_).at[..., perm].set(slice_)  # column j lies at perm[j]
    np.testing.assert_allclose(glm_ref._published_order(slice_), published, atol=0)
    turned = glm_ref._rope_pairs(published, cfg.rope_theta)  # positions 0..9
    np.testing.assert_allclose(turned[..., perm], llama.rope(slice_, jnp.arange(10)[None], cfg.rope_theta),
                               atol=1e-6)


def _attention_cfg(qk_norm):
    return ModelConfig(name="w64", vocab_size=64, d_model=512, n_layers=1, n_heads=8, n_kv_heads=2, d_ff=64,
                       rope_theta=1e6, dtype="float32", layer_pattern="*", attn_qk_norm=qk_norm)


@pytest.mark.usefixtures("highest")
@pytest.mark.parametrize("qk_norm", [True, False])
def test_attention_at_head_width_64_is_the_references(qk_norm):
    """32 / 8 heads of 64 in small: 8 / 2 heads of 64 (groups of 4), q and k normed a head
    BEFORE the rotation where the layer has the weights, rotated at theta 1e6."""
    cfg = _attention_cfg(qk_norm)
    assert cfg.head_dim == 64
    lp = jax.tree.map(lambda a: a[0], params(cfg)["attn_layers"])
    assert ("q_head_norm" in lp) == qk_norm and lp["wq"].shape == (512, 8, 64) and lp["wk"].shape == (512, 2, 64)
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 48, 512))
    got = llama._block(x, lp, cfg, jnp.arange(48)[None], None)[0]
    want = lfm2_ref.attention_layer(x, lp, model_of(cfg))
    np.testing.assert_allclose(got, want, atol=2e-5 * float(jnp.abs(want).max()))
    if qk_norm:  # the norm is in front of the rotation: behind it, another number
        plain = lfm2_ref.attention_layer(x, {n: a for n, a in lp.items() if "head_norm" not in n}, model_of(cfg))
        assert float(jnp.abs(plain - want).max()) > 1e-2
