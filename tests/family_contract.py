"""What every model family with a plain reference (ray_tpu/models/reference/) is held to on
the training path, at a small size on the CPU with seeded weights: each behaviour once, as
a test over the `family` of the module that imports it. A family's file
(tests/test_family_<model_type>.py) is

    from family_contract import *  # noqa: F401,F403  (the contract's tests, bound to FAMILY)
    FAMILY = Family(...)

and below that only what the family alone has. pytest does not collect this module by
itself (its name); conftest.py registers it for assertion rewriting. The file stays the
unit the driver's `--dist loadfile` hands to a worker, and a family's whole cost in tier-1
is its file's: the cell's whole step, compiled for the described v5e, is `Family.cell_step`
and the `cell_step` fixture (once a file), not a case of tests/test_tpu_compile.py.

The system under test runs as its users run it: loss and gradient under one `jax.jit`
(the configuration static), the references likewise, and a seeded tree is made once a
configuration (`seeded`)."""
import dataclasses
import functools
import importlib
import json
import os
import re
import subprocess
import sys
import tempfile
import types
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from compiled_step_text import (  # noqa: F401  (`topo`, `one_chip`, `on_tpu`: fixtures a family's file gets with the contract)
    CONV_PADDED_COPY, KEPT_PRODUCTS, OVERLAPS_INTERMEDIATES, PARTS_INTERMEDIATES, ROOT, cell_config, instructions, kept_copies,
    kernel_calls, lower_cell_step, on_tpu, one_chip, products, topo, tpu_side, xla_remats)
from ray_tpu.models import checkpoint, llama, moe
from ray_tpu.models.config import LAYER_KINDS, ModelConfig

sys.path.insert(0, ROOT)  # benchmarks/ is read as its users read it, from the repository's root


@dataclasses.dataclass(frozen=True)
class Family:
    """What differs between families; everything else is the contract's."""
    model_type: str  # its reference is models/reference/<model_type>.py, the copy benchmarks/lib/reference_<model_type>.py
    tiny: ModelConfig  # the registry's toy of the family
    cell: str  # the benchmark's cell, its configuration file, and where the manifest lists both
    config: str
    index: int
    # how `params` unsettles the seeded tree beside the selection bias: (stack, leaf, scale of
    # the noise, what it is added to), e.g. a norm weight that is not one
    unsettle: Tuple[Tuple[str, str, float, float], ...]
    # the configurations whose loss and every gradient are held to the reference: (id, cfg,
    # periods the pattern is scanned in); their batch, and the fewest leaves a gradient has
    cases: Tuple[Tuple[str, ModelConfig, int], ...]
    batch: int
    least_leaves: int
    float32_leaves: frozenset  # the reference's leaves that stay float32 in every type
    recurrent: Optional[str]  # the name packed documents are refused by, or None
    # the parts whose shares add up to the uncut layer: id -> fn(x) -> (want, parts, index of a share that is not nothing)
    shares: Dict[str, Callable]
    # the compiled step: scopes it must name, the mixer's own and the scopes each of those lies under, scopes it must not have
    scopes: frozenset
    mixer_scopes: frozenset
    outer: frozenset
    absent: frozenset
    # the rehearsal of the cell: seed, more gradient rows than, the losses compared, positions
    rehearsal: Tuple[str, int, frozenset, int]
    pairs: Dict[str, str]  # published key -> ModelConfig field, equal in the configuration file
    cell_params: float  # n_params of the cell's configuration, to 0.1 M
    config_file: Callable  # fn(config, cfg, config_from): what the family's file alone says
    published_params: float  # n_params of the published keys, to a hundredth
    published: Callable  # fn(cfg): what else the published model is
    hf_base: Dict[str, Any]  # a config.json of the family that maps onto `tiny` ...
    hf_to_tiny: Dict[str, Any]  # ... with these fields replaced
    hf_refused: Tuple[Tuple[Dict[str, Any], Optional[str]], ...]  # (keys, the phrase it is refused with)
    llm_refuses: Tuple[str, ...]
    flops_parts: frozenset  # the forward pass's parts in the flops file
    step_flops: float  # a step's training FLOPs in the cell, to a thousandth
    flops_share: Callable  # fn(flops, model): the chip's share the file counts
    made_up: Callable  # fn(flops, config, model) -> (result, [(reader, metric file or args, ctx overrides, value or None)])
    metrics: frozenset  # what the manifest reports for the cell
    own_metrics: Tuple[str, ...]  # those that came with the family: a file each
    # the batch the family's objective takes, made of seeded token ids [B, T]: fn(cfg, tokens) -> what
    # `llama.loss_fn` and the reference's `loss` are handed (None: the ids themselves, next-token prediction
    # over T - 1 positions; a family of another objective keeps T - 1 positions too)
    batch_of: Optional[Callable] = None
    # whether the `kept` cases (what a rematerialised recurrent mixer keeps) run from this family's file: a second
    # family of one mixer module leaves them to the first's
    kept_here: bool = True
    # the cell's whole step compiled for the described v5e (`cell_step`): (bodies the layers' loop unrolls to: a period
    # of the pattern's layers, or the scan's one and the MTP module's; the forward pick's loops under `moe_router` a
    # body; the temporaries in GB, held to + 0.15). None: the cell's whole step is a test of its own shape in the
    # family's file, which unbinds the contract's (`del test_a_family_cells_step_...`)
    cell_step: Optional[Tuple[int, int, float]] = None

    @property
    def ref(self):
        return importlib.import_module(f"ray_tpu.models.reference.{self.model_type}")

    def cell_config(self):
        return cell_config(self.config)

    def batch_for(self, cfg, shape=None, seed=1):
        """The family's batch of seeded ids; `[batch, 41]` wherever a test is not about the shape, so that a
        file compiles one program a (configuration, dtype)."""
        t = tokens(cfg, shape or (self.batch, 41), seed)
        return t if self.batch_of is None else self.batch_of(cfg, t)


def pytest_generate_tests(metafunc):
    family = metafunc.module.FAMILY
    if "case" in metafunc.fixturenames:
        metafunc.parametrize("case", family.cases, ids=[case[0] for case in family.cases])
    if "part" in metafunc.fixturenames:
        metafunc.parametrize("part", list(family.shares))
    if "kept" in metafunc.fixturenames:  # the family's recurrent mixer (its module), where it has one
        mixers = {kind: mixer for kind, (mixer, _) in llama.MIXERS.items()
                  if family.recurrent and family.kept_here and mixer.RECURRENT == family.recurrent}
        metafunc.parametrize("kept", list(mixers.values()), ids=list(mixers))


@pytest.fixture(scope="module")
def family(request):
    return request.module.FAMILY


@pytest.fixture
def highest():
    """Every product at the highest precision: what a comparison with a plain reference wants."""
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(autouse=True)
def _highest(highest):  # (autouse in every module that imports it)
    yield


# ------------------------------------------------------------- made once, run compiled

def model_of(cfg):
    return dataclasses.asdict(cfg)


@functools.lru_cache(maxsize=None)
def seeded(cfg, seed=0):
    """llama.init's tree of a configuration, made once (a file's tests share it: copy, never write)."""
    return jax.jit(llama.init, static_argnums=1)(jax.random.PRNGKey(seed), dataclasses.replace(cfg, dtype="float32"))


def params(cfg, unsettle=(), seed=0, biased=True):
    """The seeded tree, unsettled: a selection bias that changes who is chosen, and what
    the family says beside it (`Family.unsettle`)."""
    p = jax.tree.map(lambda a: a, seeded(dataclasses.replace(cfg, experts_held=(0, 1)), seed))
    for name in ("layers", "mtp"):
        if biased and "router_bias" in p.get(name, {}):
            p[name]["router_bias"] = 0.05 * jax.random.normal(
                jax.random.PRNGKey(seed + 5), p[name]["router_bias"].shape)
    for i, (stack, leaf, scale, base) in enumerate(unsettle):
        if leaf in p.get(stack, {}):
            p[stack][leaf] = base + scale * jax.random.normal(jax.random.PRNGKey(seed + 6 + i), p[stack][leaf].shape)
    lo, hi = moe.held_range(cfg)  # the routed experts `cfg.experts_held` says, of a tree that holds all
    for name in ("layers", "mtp"):
        if "router" in p.get(name, {}):
            p[name] = {leaf: a[:, lo:hi] if leaf in moe.mlp_leaves(cfg) else a for leaf, a in p[name].items()}
    return p


def tokens(cfg, shape=(2, 41), seed=1):
    return jax.random.randint(jax.random.PRNGKey(seed), shape, 0, cfg.vocab_size)


def as_batch(t):
    """What `llama.loss_fn` takes: token ids as they are, or a batch a family made (`Family.batch_for`)."""
    return t if isinstance(t, dict) else {"tokens": t}


@functools.partial(jax.jit, static_argnums=2)
def system(p, t, cfg):
    """((loss, metrics), gradients) of the system, as one program."""
    return jax.value_and_grad(llama.loss_fn, has_aux=True)(p, as_batch(t), cfg)


@functools.lru_cache(maxsize=None)
def reference(ref, cfg, dtype=jnp.float32, parts=False):
    """fn(p, t, chosen) -> (loss or (loss, parts), gradients) of a reference module, as one program, made
    once for the same arguments (a file's tests share it, as they share `system`)."""
    model = model_of(cfg)
    return jax.jit(jax.value_and_grad(
        lambda p, t, chosen: ref.loss(p, t, model, dtype, chosen, parts), has_aux=parts))


def leaves_match(grads, r_grads, atol=2e-5, least=20):
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    r_flat = dict(jax.tree_util.tree_flatten_with_path(r_grads)[0])
    assert len(flat) == len(r_flat) >= least
    for path, g in flat:
        name = jax.tree_util.keystr(path)
        if "router_bias" in name:  # selects, never weights: no gradient reaches it
            assert not np.asarray(g).any() and not np.asarray(r_flat[path]).any(), name
            continue
        scale = float(jnp.abs(r_flat[path]).max())
        assert scale > 0, name
        np.testing.assert_allclose(g, r_flat[path], atol=atol * scale + 1e-9, err_msg=name)


def config_from(hf: dict):
    with tempfile.TemporaryDirectory() as d:
        with open(os.path.join(d, "config.json"), "w") as f:
            json.dump(hf, f)
        return checkpoint.config_from_hf(d)


def published_keys(config):
    hf = {k: v for k, v in config.items() if k not in ("program", "trainer", "published", "reduced")}
    return {**hf, **config["published"]}


def head_shares(ref, whole, x, gated=False):
    """8 query heads, one a share, and the key/value head each reads (4 share one): (the
    uncut reference's layer, each share's part through `_block`)."""
    d = whole.d_model
    share = dataclasses.replace(whole, attn_heads_held=(1, 1))
    lp = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), whole)["attn_layers"])
    held = jax.tree.map(lambda a: a[0], llama.init(jax.random.PRNGKey(3), share)["attn_layers"])
    assert lp["wq"].shape == (d, 8, 24) and lp["wk"].shape == (d, 2, 24)
    assert held["wq"].shape == (d, 1, 24) and held["wk"].shape == (d, 1, 24)
    assert ("wo_gate" in lp) == gated and ("wo_gate" in held) == gated
    want = ref.attention_layer(x, lp, model_of(whole)) - x
    positions = jnp.arange(x.shape[1])[None]
    block = jax.jit(lambda x, mine: llama._block(x, mine, share, positions, None)[0])  # eight shares, one program
    parts = []
    for i in range(8):
        mine = {"attn_norm": lp["attn_norm"], "wq": lp["wq"][:, i:i + 1], "wo": lp["wo"][i:i + 1],
                "wk": lp["wk"][:, i // 4:i // 4 + 1], "wv": lp["wv"][:, i // 4:i // 4 + 1],
                **({"wo_gate": lp["wo_gate"][:, i:i + 1]} if gated else {})}
        parts.append(block(x, mine) - x)
    return lp, want, parts


def expert_shares(ref, whole, n_shares, x, lp):
    """n_shares equal shares of an expert part's routed experts, what all compute alike (the
    shared expert) counted once: (the uncut reference's part, the routing, [shared, each share's routed part], counters)."""
    want, routing = ref.expert_layer(x, lp, model_of(whole))
    shared_leaves = moe.mlp_leaves(whole, "shared_")
    shared = moe._mlp(x, tuple(lp[n] for n in shared_leaves)) if shared_leaves[0] in lp else jnp.zeros_like(x)
    held = whole.n_experts // n_shares
    parts, counted = [shared], []
    for i in range(n_shares):
        cfg = dataclasses.replace(whole, experts_held=(i, n_shares))
        mine = {**lp, **{n: lp[n][held * i:held * (i + 1)] for n in moe.mlp_leaves(whole)}}
        y, aux = moe.expert_layer(x.reshape(-1, whole.d_model), mine, cfg)
        parts.append(y.reshape(x.shape) - shared)
        counted.append(aux)
    return want, routing, parts, counted


# ---------------------------------------------------------------- against the reference

def test_loss_and_every_gradient_match_the_reference(family, case):
    _, cfg, periods = case
    if cfg.layer_pattern:
        assert llama.pattern_period(cfg.layer_pattern)[1] == periods
    p, t = params(cfg, family.unsettle), family.batch_for(cfg)
    assert ("mtp" in p) == bool(cfg.mtp_depth) and ("lm_head" in p) != cfg.tie_embeddings
    (loss, m), grads = system(p, t, cfg)
    (r_loss, parts), r_grads = reference(family.ref, cfg, parts=True)(p, t, None)
    np.testing.assert_allclose(loss, r_loss, rtol=1e-6)
    np.testing.assert_allclose(m["ce_loss"], parts["ce_loss"], rtol=1e-6)
    assert ("mtp_loss" in m) == bool(cfg.mtp_depth)
    if cfg.mtp_depth:
        np.testing.assert_allclose(m["mtp_loss"], parts["mtp_loss"], rtol=1e-6)
        np.testing.assert_allclose(loss, m["ce_loss"] + cfg.mtp_loss_weight * m["mtp_loss"], rtol=1e-6)
    assert parts["position_losses"].shape == (family.batch, sum(40 - m for m in range(cfg.mtp_depth + 1)))
    leaves_match(grads, r_grads, least=family.least_leaves)
    # a row an expert layer in the pattern's order, the MTP modules' last: what the step
    # chose is what the reference chose, layer by layer
    stack = cfg.layer_pattern.count("E") if cfg.layer_pattern else (cfg.n_layers - cfg.n_dense_layers) * (cfg.n_experts > 0)
    expert_layers = stack + cfg.mtp_depth
    if expert_layers:
        assert m["expert_load"].shape == (expert_layers, cfg.n_experts)
        assert len(parts["routings"]) == expert_layers
        for mine, r in zip(m["experts_chosen"], parts["routings"]):
            own = np.asarray(r["own"])
            assert own.shape[::2] == (family.batch, cfg.moe_top_k) and r["margin"].shape == own.shape[:2]
            mine = np.asarray(mine).reshape(family.batch, -1, cfg.moe_top_k)[:, :own.shape[1]]
            np.testing.assert_array_equal(np.sort(mine, -1), np.sort(own, -1))
    else:
        assert "expert_load" not in m


def test_bfloat16_activations_err_as_the_rounded_reference_does(family):
    """The benchmark's comparison at a small size: the system with bfloat16 activations
    against the float32 reference, loss and every leaf's gradient, in multiples of the
    error the same plain reference makes in bfloat16, on the experts the system chose."""
    cfg = dataclasses.replace(family.tiny, dtype="bfloat16")
    p, t = params(cfg, family.unsettle), family.batch_for(cfg)
    (loss, m), grads = system(p, t, cfg)
    # 40 rows a sequence, or 2 x 40 (a family that routes nothing has no selection to hand over)
    chosen = [np.asarray(c).reshape(family.batch, -1, c.shape[-1]) for c in m["experts_chosen"]] if cfg.n_experts else None
    exact, e_grads = reference(family.ref, cfg)(p, t, chosen)
    coarse, c_grads = reference(family.ref, cfg, jnp.bfloat16)(p, t, chosen)
    assert abs(float(loss - exact)) < 3 * abs(float(coarse - exact)) + 1e-3 * float(exact)
    square = lambda a, b: sum(float(jnp.sum(jnp.square(x - y))) for x, y in zip(  # noqa: E731
        jax.tree.leaves(a), jax.tree.leaves(b)))
    mine, yardstick = square(grads, e_grads), square(c_grads, e_grads)
    assert 0 < mine < 1.5 ** 2 * yardstick, (mine, yardstick)
    assert yardstick < 0.05 ** 2 * square(e_grads, jax.tree.map(jnp.zeros_like, e_grads))


def test_the_reference_and_the_benchmarks_copy_agree(family):
    """benchmarks/lib/ keeps its own copy, so that no PR that claims a gain can change
    the yardstick by editing the program's tree: the two say the same."""
    ref, copy = family.ref, importlib.import_module(f"benchmarks.lib.reference_{family.model_type}")
    with open(ref.__file__) as a, open(copy.__file__) as b:
        assert a.read() == b.read()
    cfg = dataclasses.replace(family.tiny, experts_held=(1, 2))
    p, t = params(cfg, family.unsettle), family.batch_for(cfg)
    mine, theirs = (jax.jit(lambda p, t, fn=module.position_losses: fn(p, t, model_of(cfg)))(p, t)
                    for module in (ref, copy))
    assert len(jax.tree.leaves(mine)) == len(jax.tree.leaves(theirs)) > 3
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(theirs)):
        np.testing.assert_array_equal(a, b)


def test_the_coarse_reference_is_the_same_code_rounded(family):
    """bfloat16: the yardstick. Near the float32 reference, not equal to it; the decays'
    own leaves stay float32."""
    cfg, ref = family.tiny, family.ref
    p, t = params(cfg, family.unsettle), family.batch_for(cfg)
    exact, coarse = (jax.jit(lambda p, t, dtype=dtype: ref.loss(p, t, model_of(cfg), dtype))(p, t)
                     for dtype in (jnp.float32, jnp.bfloat16))
    assert 1e-6 < abs(float(coarse - exact)) / float(exact) < 2e-2
    assert frozenset(getattr(ref, "FLOAT32_LEAVES", ())) == family.float32_leaves
    one_a_position = getattr(ref, "next_token_losses", lambda *a: ref.position_losses(*a)[0])
    assert jax.eval_shape(lambda: one_a_position(p, t, model_of(cfg))).shape == (family.batch, 40)


def test_packed_documents_and_a_cache_are_refused_by_name(family):
    cfg = family.tiny
    p, t = params(cfg, family.unsettle), tokens(cfg, (2, 33))
    if family.recurrent:  # (refused while the program is traced: nothing has to run)
        with pytest.raises(NotImplementedError, match=f"{family.recurrent} layer over packed documents"):
            jax.eval_shape(lambda p: llama.loss_fn(p, {"tokens": t, "segment_ids": jnp.ones_like(t)}, cfg), p)
    if cfg.diffusion_block:  # (the objective's own refusals: its row is doubled, its batch carries the noise)
        packed = {**family.batch_for(cfg, (2, 33)), "segment_ids": jnp.ones((2, 32))}
        with pytest.raises(NotImplementedError, match="block-diffusion objective over packed documents"):
            jax.eval_shape(lambda p: llama.loss_fn(p, packed, cfg), p)
    refused = ("block-diffusion attention .* under a KV cache" if cfg.diffusion_block else
               "a looped stack under a KV cache" if cfg.loop_steps > 1 else
               "a stream of hc_mult .* copies under a KV cache" if cfg.hc_mult > 1 else "KV cache over layers of more than one kind")
    with pytest.raises(NotImplementedError, match=refused):
        jax.eval_shape(lambda p: llama.forward(p, t[:, :32], cfg, cache=llama.init_kv_cache(cfg, 2, 64)), p)


# ------------------------------------------------------------------- the shares

def test_the_shares_add_up_to_the_uncut_layer(family, part):
    """What every share of a layer computes, summed, with what all compute alike (the
    shared expert) counted once, is the uncut reference's layer: the system given a share
    runs the published layer's part and nothing stands in for the rest."""
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 24, family.tiny.d_model))
    want, parts, a_share = family.shares[part](x)
    np.testing.assert_allclose(sum(parts), want, atol=3e-5 * float(jnp.abs(want).max()))
    assert float(jnp.abs(parts[a_share]).max()) > 1e-3  # a share is a part, not nothing


# ------------------------------------------------------------------- what a rematerialised recurrent mixer keeps

def _mixer_part(cfg, mixer, policy, dtype=jnp.bfloat16):
    """(a mixer's loss under `policy` as `llama._maybe_remat` runs the part, x, lp) at [2, 32, d_model]."""
    cfg = dataclasses.replace(cfg, remat=policy != "none", remat_policy=policy)
    part = llama._maybe_remat(lambda x, lp: mixer.mixer(x, lp, cfg), cfg)
    loss = lambda x, lp: jnp.sum(jnp.square(part(x, lp).astype(jnp.float32)))  # noqa: E731
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 32, cfg.d_model), dtype)
    return loss, x, mixer.init(jax.random.PRNGKey(1), cfg)


def _value_and_grads(loss):
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1)))


def _equations(jaxpr):
    """Every equation, nested jaxprs' too, in order."""
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _equations(sub)


def _without_the_product(monkeypatch, mixer, named=True):
    """The mixer as it was before its input product was kept: `KEPT` without the name (and, with
    `named` false, the product without its name)."""
    monkeypatch.setattr(mixer, "KEPT", {policy: tuple(n for n in names if n != mixer.IN_PROJ_NAME)
                                        for policy, names in mixer.KEPT.items()})
    if not named:
        monkeypatch.setattr(mixer, "checkpoint_name", lambda x, name: x)


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
def test_a_mixer_that_keeps_its_input_product_gives_the_bits_of_one_that_keeps_everything(family, kept, dtype):
    """Under `full` the backward pass reads the input product's result as the forward pass wrote it,
    where it read an equal recomputation: the value and every gradient are those of
    `remat_policy="none"`, bit for bit. (An operation a program, not one `jit`: the same operations
    on the same values give the same bits, where XLA's fusions on the CPU sum a scan's float32
    intermediates in another order in two programs that differ anywhere.)"""
    assert kept.KEPT["full"] == (kept.IN_PROJ_NAME,)
    full, x, lp = _mixer_part(family.tiny, kept, "full", dtype)
    none, _, _ = _mixer_part(family.tiny, kept, "none", dtype)
    mine, plain = (jax.value_and_grad(loss, argnums=(0, 1))(x, lp) for loss in (full, none))
    assert len(jax.tree.leaves(mine)) == 2 + len(lp)
    for a, b in zip(jax.tree.leaves(mine), jax.tree.leaves(plain)):
        assert np.abs(np.asarray(a, np.float32)).max() > 0
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_a_rematerialised_mixer_keeps_its_inputs_and_the_named_product_and_runs_it_once(family, kept, capsys, monkeypatch):
    """What `full` keeps of a recurrent mixer: the layer's inputs, the ONE array its input product
    wrote, rounded once where it is made, and what the part keeps under every policy. The gradient's
    program holds one product fewer than that of a part whose `KEPT` lacks the name: the input
    product again in the rematerialised layer."""
    loss, x, lp = _mixer_part(family.tiny, kept, "full")
    program = lambda part: list(_equations(jax.make_jaxpr(_value_and_grads(part))(x, lp).jaxpr))  # noqa: E731
    count = lambda eqns, p: sum(e.primitive.name == p for e in eqns)  # noqa: E731
    eqns = program(loss)
    product, = [e for e in eqns if e.primitive.name == "name" and e.params["name"] == kept.IN_PROJ_NAME]
    shape = ",".join(map(str, product.outvars[0].aval.shape))
    assert product.outvars[0].aval.shape[:2] == (2, 32) and product.outvars[0].aval.dtype == jnp.bfloat16
    jax.ad_checkpoint.print_saved_residuals(loss, x, lp)
    saved = [ln for ln in capsys.readouterr().out.strip().splitlines() if "_mixer_part" not in ln]  # (less the loss's own square)
    inputs = [ln for ln in saved if "from the argument" in ln]
    assert len(inputs) == 1 + len(lp) and inputs[0].startswith(f"bf16[2,32,{family.tiny.d_model}] from the argument x")
    named = [ln for ln in saved if ln not in inputs]
    assert len(named) == 1 + len(kept.KEPT.get("every", ())), named
    (mine,) = [ln for ln in named if ln.startswith(f"bf16[{shape}] output of reduce_precision")]
    assert os.path.basename(kept.__file__) in mine
    _without_the_product(monkeypatch, kept)  # the name alone keeps nothing: the part says what a policy keeps
    before = program(_mixer_part(family.tiny, kept, "full")[0])
    assert count(before, "dot_general") == count(eqns, "dot_general") + 1
    assert count(before, "reduce_precision") == count(eqns, "reduce_precision") - 1
    assert count(before, "name") == count(eqns, "name") + 1  # (made twice, named twice)


def _lines_up_to_order(text):
    """A lowered program's lines with value numbers blanked, a barrier's types sorted, sorted: what two
    programs that list the same residuals in another order share."""
    def line(ln):
        ln = re.sub(r"%\w+(#\d+)?", "%", ln)
        if "optimization_barrier" in ln:
            head, _, types = ln.partition(" : ")
            ln = head + " : " + ", ".join(sorted(types.split(", ")))
        return ln
    return sorted(line(ln) for ln in text.splitlines())


@pytest.mark.parametrize("policy", ["dots", "dots_no_batch", "none"])
def test_the_products_name_changes_nothing_of_a_mixer_under_the_other_policies(family, kept, policy, monkeypatch):
    """`checkpoint_dots` keeps the product already, and nothing is rematerialised under `none`: the
    part's program, value and gradients, lowers to the text it had before the product carried a name.
    (Under the `dots` policies jax may list a layer's residuals in another order, a named one first:
    then the lines are the same up to the values' numbers.)"""
    # (the number behind a private function's name is the lowering's own counter, and a name takes one)
    text = lambda loss: re.sub(r"(@[A-Za-z_]\w*?)_\d+\b", r"\1", _value_and_grads(loss).lower(x, lp).as_text())  # noqa: E731
    loss, x, lp = _mixer_part(family.tiny, kept, policy)
    with_name = text(loss)
    _without_the_product(monkeypatch, kept, named=False)
    before = text(_mixer_part(family.tiny, kept, policy)[0])
    assert before == with_name or (policy != "none" and _lines_up_to_order(before) == _lines_up_to_order(with_name))
    assert "dot_general" in with_name


def test_what_the_mixer_keeps_by_name_no_other_part_names(family, kept):
    """`llama._maybe_remat` gathers every part's `KEPT` into one `save_only_these_names`: a name two
    parts shared would keep under one part's policy what the other's made."""
    parts = {**llama.MIXERS, **llama.FEED_FORWARD}
    names = lambda part: {name for names in part.KEPT.values() for name in names}  # noqa: E731
    assert all(set(part.KEPT) <= {"full", "dots", "dots_no_batch", "every"} for part, _ in parts.values())
    others = set().union(*(names(part) for part, _ in parts.values() if part is not kept))
    assert names(kept) and not names(kept) & others


# ------------------------------------------------------------------- the step

@pytest.fixture(scope="module")
def first_step(family):
    """(the state before, the state after one step of train/step.py's, its metrics, the optimizer, the tokens)"""
    from ray_tpu.train import init_state, make_optimizer, make_train_step

    cfg = family.tiny
    with jax.default_matmul_precision("highest"):
        tx = make_optimizer(learning_rate=1e-3, warmup_steps=1, total_steps=10)
        state = jax.jit(lambda key: init_state(key, cfg, tx))(jax.random.PRNGKey(0))
        t = family.batch_for(cfg, (2, 33))
        after, m = make_train_step(cfg, tx, donate=False)(state, as_batch(t))
    return state, after, m, tx, t


def test_the_bias_moves_by_the_balance_rule_a_row_an_expert_part_in_pattern_order(family, first_step):
    cfg = family.tiny
    state, after, m, _, _ = first_step
    if not cfg.n_experts:  # a family that routes nothing: the step has no counts and no rule to follow
        assert np.isfinite(float(m["loss"])) and not {"expert_load", "experts_chosen", "mtp_loss"} & set(m)
        assert int(after.step) == int(state.step) + 1
        return
    load = np.asarray(m["expert_load"])
    assert load.shape == (after.params["layers"]["router"].shape[0] + cfg.mtp_depth, cfg.n_experts)
    rows = 2 * 32 * (2 if cfg.diffusion_block else 1)  # (a doubled row: both halves choose)
    assert (load.sum(-1) == rows * cfg.moe_top_k).all() and m["experts_chosen"].shape[1] == rows
    assert all(("router_bias" in stack) == cfg.moe_select_bias
               for stack in after.params.values() if isinstance(stack, dict) and "router" in stack)
    rule = lambda b, rows: b + cfg.moe_bias_update_rate * np.sign(rows.mean(-1, keepdims=True) - rows)  # noqa: E731
    n = load.shape[0] - cfg.mtp_depth  # the pattern's expert parts, then the MTP modules'
    assert ("mtp" in after.params) == bool(cfg.mtp_depth)
    for name, rows in (("layers", load[:n]), ("mtp", load[n:])):
        if cfg.moe_select_bias and name in after.params:  # (a family without the bias has nothing for the rule to move)
            before = np.asarray(state.params[name]["router_bias"])
            np.testing.assert_allclose(after.params[name]["router_bias"], rule(before, rows), atol=1e-7)
            assert np.abs(rule(before, rows) - before).max() > 0
    for layer in range(load.shape[0]):  # the counters are of what each part chose, in that order
        np.testing.assert_array_equal(
            load[layer], np.bincount(np.asarray(m["experts_chosen"][layer]).ravel(), minlength=cfg.n_experts))
    assert np.isfinite(float(m["loss"])) and ("mtp_loss" in m) == bool(cfg.mtp_depth)


def test_the_compiled_step_names_the_mixers_scopes(family):
    """What the cell's per-layer metrics and the accepted readers that know `attn` read:
    the compiled program's instructions carry the mixer's scopes, each beside the scopes
    it lies under, forward and backward."""
    from benchmarks.lib import scope_seconds

    cfg = family.tiny
    p, t = params(cfg, family.unsettle), family.batch_for(cfg, (1, 33))

    def loss(p):  # as train/step.py names the model: the outermost scope is the transformations'
        with jax.named_scope("model"):
            return llama.loss_fn(p, as_batch(t), cfg)[0]

    text = jax.jit(jax.grad(loss)).lower(p).compile().as_text()
    by_instruction = scope_seconds.scopes_by_instruction(text)
    scopes = set().union(*by_instruction.values())
    assert family.mixer_scopes | family.scopes <= scopes, sorted(scopes)
    assert not family.absent & scopes
    assert all(family.outer <= found for found in by_instruction.values() if found & family.mixer_scopes)


# ------------------------------------------------------------------- the cell's whole step, compiled for the chip

@pytest.fixture(scope="module")
def cell_step(family, one_chip):
    """The whole step of the family's cell as its configuration file states it, lowered and compiled for the
    described v5e ONCE a file, for every case that reads it: cfg, trainer, the compiled text, its
    `memory_analysis()`, the attention calls that fell to an XLA path while it was traced, the step and
    the shapes it was lowered for."""
    attention_ops = importlib.import_module("ray_tpu.ops.attention")  # (the package re-exports the function under this name)
    file, _, cfg = family.cell_config()
    fallbacks = attention_ops.xla_fallback_count
    with tpu_side():
        step, lowered, args = lower_cell_step(cfg, file["trainer"], one_chip)
        compiled = lowered.compile()
    return types.SimpleNamespace(cfg=cfg, trainer=file["trainer"], text=compiled.as_text(), memory=compiled.memory_analysis(),
                                 fallbacks=attention_ops.xla_fallback_count - fallbacks, step=step, args=args)


def test_a_family_cells_step_scores_once_a_layer_and_fits_as_before(family, cell_step):
    """The whole step of the family's cell, compiled for the described chip: three router products an
    expert layer and the forward pass's loops only (tests/test_tpu_compile_experts.py holds a layer alone to the
    same); the temporaries within 0.15 GB of what `Family.cell_step` says they were, and XLA
    rematerialises nothing of its own to fit (PERF.md section 7, after PR 26 (2)). The record's comment
    says which PR moved the figure and by what."""
    assert family.cell_step is not None, "a cell whose whole step is a test of its own shape unbinds this one (`del`)"
    bodies, loops, temp_gb = family.cell_step
    cfg, trainer, text, memory = cell_step.cfg, cell_step.trainer, cell_step.text, cell_step.memory
    assert cfg.remat and cfg.remat_policy == "full" and trainer["mesh"] is None
    assert len(instructions(text, "convolution", "moe_router")) == 3 * bodies
    assert len(instructions(text, "while", "moe_router")) == loops * bodies
    assert not xla_remats(text)
    # under `full` a rematerialised layer keeps the forward flash kernel's results: a call an
    # attention block forward, none made again (PR 43), ONE backward kernel a call (PR 53)
    blocks = kernel_calls(text, "flash_attention_bwd_dkv_dq")[0]
    assert blocks >= 1 and kernel_calls(text, "flash_attention_fwd") == (blocks, 0)
    # a windowed part runs the windowed kernels, as often; no attention falls to an XLA path
    windowed = sum(LAYER_KINDS[c].windowed for c in cfg.layer_pattern)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv_dq"):
        assert kernel_calls(text, f"{kernel}_window") == (windowed, 0)
    for suffix in ("", "_window"):  # the two kernels of a sequence longer than a span of K and V run nowhere
        assert kernel_calls(text, f"flash_attention_bwd_dq{suffix}") == kernel_calls(text, f"flash_attention_bwd_dkv{suffix}") == (0, 0)
    assert blocks + windowed == sum(LAYER_KINDS[c].mixer == "attn" for c in cfg.layer_pattern) + cfg.mtp_depth or not cfg.layer_pattern
    assert cell_step.fallbacks == 0
    # a recurrent mixer keeps its input product's result under `full` (PR 48): the product once a part
    # forward, none made again in the rematerialised layer, two backward; one stored copy a part,
    # rounded in the product's own epilogue
    period = llama.pattern_period(cfg.layer_pattern)[0] if cfg.layer_pattern else ""
    for kind, (scope, einsum, extents) in KEPT_PRODUCTS.items():
        parts = period.count(kind)
        assert products(text, scope, einsum) == (parts, 0, 2 * parts), kind
        assert kept_copies(text, extents) == (parts, parts), kind
    assert memory.temp_size_in_bytes < (temp_gb + 0.15) * 1e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9  # what a v5e program may use
    if cfg.kda_n_heads:  # a delta-rule part substitutes once, a call a block: the backward pass keeps the inverse
        from ray_tpu.ops.kda import _SOLVE

        parts = cfg.layer_pattern.count("K")
        assert text.count('custom_call_target="InvertDiagBlocksLowerTriangular"') == parts * (cfg.kda_chunk // _SOLVE)
        # the overlaps' kernels a part: forward, again in the rematerialised layer, backward (PR 38)
        assert kernel_calls(text, "kda_overlaps_fwd") == (parts, parts)
        assert kernel_calls(text, "kda_overlaps_bwd") == (parts, 0)
        assert not re.search(OVERLAPS_INTERMEDIATES, text)
        # the chunks' four matrices likewise (PR 51): [W | U0] and what it is made from stay in fast memory
        assert kernel_calls(text, "kda_parts_fwd") == (parts, parts)
        assert kernel_calls(text, "kda_parts_bwd") == (parts, 0)
        assert not re.search(PARTS_INTERMEDIATES, text)
        # and the walk over the chunks (PR 61): the join's loop is in no pass
        assert kernel_calls(text, "kda_walk_fwd") == (parts, parts)
        assert kernel_calls(text, "kda_walk_bwd") == (parts, 0)
        assert not instructions(text, "while", "kda_scan")
        # and the running sum G (PR 64): no `reduce-window` under the scan in any pass
        assert kernel_calls(text, "kda_prefix_fwd") == (parts, parts)
        assert kernel_calls(text, "kda_prefix_bwd") == (parts, 0)
        assert not instructions(text, "reduce-window", "kda_scan")
        # the convolution, silu and norms of q, k and v: ONE call a part and pass whatever the three
        # (9 a step; a call for each of q, k, v was 27, and 3 s of every first step: PR 44), and the
        # plain form's float32 copy of q|k|v padded by the taps is gone with its shifted products
        assert kernel_calls(text, "short_conv_fwd") == (parts, parts)
        assert kernel_calls(text, "short_conv_bwd") == (parts, 0)
        assert not re.search(CONV_PADDED_COPY, text)
        assert abs(memory.argument_size_in_bytes - 12 * cfg.n_params) < 1e7


# ------------------------------------------------------------------- the configuration

def test_configuration_files_program_group_equals_its_published_keys(family):
    config, _, cfg = family.cell_config()
    for published, field in family.pairs.items():
        assert getattr(cfg, field) == config[published], (published, field)
    assert sorted(config["reduced"]) == sorted(config["published"])
    assert (cfg.n_experts > 0) == (family.tiny.n_experts > 0)
    if cfg.n_experts:
        assert cfg.moe_dropless and (cfg.moe_scoring, cfg.moe_select_bias) == (family.tiny.moe_scoring, family.tiny.moe_select_bias)
        assert (cfg.moe_scoring, cfg.moe_select_bias) in (("sigmoid", True), ("softmax", False))
    assert abs(cfg.n_params - family.cell_params) < 0.1e6  # the issue's arithmetic
    shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    held = sum(int(np.prod(a.shape)) for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]
               if "router_bias" not in jax.tree_util.keystr(path))
    assert held == cfg.n_params
    assert config["trainer"]["reference"] == f"reference_{family.model_type}"
    assert config["trainer"]["flops"] == f"flops_{family.model_type}"
    family.config_file(config, cfg, config_from)


def test_n_params_counts_what_is_held_and_the_published_keys_their_size(family):
    tiny = family.tiny
    share = dataclasses.replace(tiny, experts_held=(1, 4), mtp_depth=0, mtp_layer_pattern="",
                                attn_heads_held=(0, 0) if tiny.latent_attention else (2, 1))
    for cfg in (tiny, share):
        shapes = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))  # noqa: B023
        held = sum(int(np.prod(a.shape)) for path, a in jax.tree_util.tree_flatten_with_path(shapes)[0]
                   if "router_bias" not in jax.tree_util.keystr(path))
        assert held == cfg.n_params
    cfg = config_from(published_keys(family.cell_config()[0]))
    assert abs(cfg.n_params / family.published_params - 1) < 0.01
    family.published(cfg)


def test_config_from_hf_maps_the_family_and_refuses_what_is_not_runnable(family):
    cfg = config_from(family.hf_base)
    assert dataclasses.replace(cfg, **family.hf_to_tiny) == family.tiny
    for bad, what in family.hf_refused:
        with pytest.raises(ValueError, match=what):
            config_from({**family.hf_base, **bad})


def test_llm_refuses_the_family_by_name_of_what_is_missing(family):
    from ray_tpu.llm.config import LLMConfig

    with pytest.raises(NotImplementedError) as e:
        LLMConfig(model_source=family.tiny.name).resolve_model_config()
    for what in family.llm_refuses:
        assert what in str(e.value)
    assert LLMConfig(model_source="moe-tiny").resolve_model_config().n_experts == 4  # the capacity-based experts are served


# ------------------------------------------------------------------- the benchmark's files

def test_the_familys_flops_file_counts_one_chips_share(family):
    flops = importlib.import_module(f"benchmarks.lib.flops_{family.model_type}")
    config, model, _ = family.cell_config()
    trainer = config["trainer"]
    assert set(flops.forward_flops_per_token(model, (trainer["seq"] + 1) / 2)) == family.flops_parts
    step = flops.train_flops_per_token(model, trainer["seq"]) * trainer["batch"] * trainer["seq"]
    assert abs(step / family.step_flops - 1) < 0.001
    family.flops_share(flops, model)


def test_the_readers_on_a_made_up_result(family):
    """The cell's per-layer readers on a result written by hand: the value where there is
    something to read, None (and nothing raised) where there is not: a program without the
    scope, a run without a trace, a flops file without the function, a rehearsal."""
    flops = importlib.import_module(f"benchmarks.lib.flops_{family.model_type}")
    config, model, _ = family.cell_config()
    result, reads = family.made_up(flops, config, model)
    assert len(reads) >= 4
    for reader, args, overrides, want in reads:
        ctx = {"result": result, "config": config, "model": model, "rehearse": False, **overrides}
        if isinstance(args, str):  # the metric's file says which reader and how
            with open(os.path.join(ROOT, "benchmarks", "metrics", f"{args}.json")) as f:
                metric = json.load(f)
            assert metric["reader"] == reader, args
            args = metric.get("args", {})
        got = importlib.import_module(f"benchmarks.readers.{reader}").read(ctx, **args)
        assert (got is None) if want is None else (got == pytest.approx(want, rel=1e-6)), (reader, args, got, want)


def test_the_manifest_lists_the_cell_and_its_metrics(family):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    i, cell = family.index, family.cell
    assert [w["name"] for w in manifest["workloads"]][i] == cell and manifest["configs"][i]["name"] == family.config
    assert manifest["workloads"][i]["chips"] == 1 and manifest["workloads"][i]["config"] == family.config
    config = family.cell_config()[0]
    assert manifest["configs"][i]["reduced"] == config["reduced"]
    assert manifest["configs"][i]["source"] == config["source"]
    with open(os.path.join(ROOT, "benchmarks", "workloads", f"{cell}.json")) as f:
        work = json.load(f)
    assert work["why"] == manifest["workloads"][i]["why"] and len(work["why"]) <= 200
    assert work["traffic"] == manifest["workloads"][i]["traffic"]
    assert work["traffic_parameters"]["batch"] == config["trainer"]["batch"]
    reported = {m["name"] for m in manifest["per_layer"] + manifest["end_to_end"]
                if cell in m.get("workloads", [cell])}
    assert reported == family.metrics
    for name in family.own_metrics:
        assert os.path.exists(os.path.join(ROOT, "benchmarks", "metrics", f"{name}.json"))
    assert len([w for w in manifest["workloads"] if w["chips"] == 4]) == 1  # a quarter of six cells, rounded down


def test_the_new_cell_rehearses_on_the_cpu(family):
    seed, rows, losses, positions = family.rehearsal
    env = dict(os.environ, JAX_PLATFORMS="cpu", RAY_TPU_NUM_TPUS="1")
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload", family.cell,
         "--seed", seed, "--seconds", "2", "--trace", "1", "--rehearse"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=220)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = [json.loads(ln) for ln in out.stdout.splitlines() if ln.startswith("{")]
    window = next(ln for ln in lines if ln.get("phase") == "window")
    assert all(window["checks"].values()), window["checks"]
    routed = {"selection_agrees_beyond_margin",
              "router_bias_moved_by_the_rule" if family.tiny.moe_select_bias else "masked_tokens_are_the_batchs"}
    assert {"step_losses_match_reference", "step_gradients_match_reference", "step_update_follows_its_moments",
            *(routed if family.tiny.n_experts else ())} <= set(window["checks"])
    assert family.tiny.n_experts or not routed & set(window["checks"])
    assert window["parity"]["gradient"]["rows"] > rows
    assert set(window["parity"]["losses"]) == losses
    assert window["parity"]["positions"] == positions
    setup = next(ln for ln in lines if ln.get("phase") == "setup_split_s")
    assert 0 < setup["of_which_parity"] < setup["warmup_and_parity"]
    values = next(ln for ln in lines if ln.get("phase") == "rehearsal_values")["values"]
    if family.tiny.n_experts:
        assert values["train_moe_imbalance"]["value"] >= 1.0
    assert values["train_step_ms"]["value"] > 0
    assert lines[-1]["correct"] is False and lines[-1]["metrics"] == {}


__all__ = [name for name in dir() if name.startswith("test_")] + [
    "Family", "pytest_generate_tests", "family", "highest", "_highest", "first_step", "topo", "one_chip", "on_tpu", "cell_step", "ROOT", "cell_config", "model_of", "seeded", "params",
    "tokens", "system", "as_batch", "reference", "leaves_match", "config_from", "published_keys", "head_shares", "expert_shares"]
