"""The names a reader of the device trace tells an attention part's pieces and the layer
loop by (models/attn.py:SCOPES, models/llama.py:LAYER_LOOP), held on the jaxpr of the
loss's gradient of a tiny model of each attention kind: `eqn.source_info.name_stack` is
what lowering writes into an instruction's `op_name`, a sub-jaxpr's equations under their
caller's. No compile; the compiled text's side is tests/test_tpu_compile_parts.py's.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest

from ray_tpu.models import attn, llama
from ray_tpu.models.config import LAYER_KINDS, get_config

# a tiny model of each kind of attention part, by what its layers hold
KINDS = {
    "plain-gqa": "test-tiny",                              # a scan over layers, remat `dots`
    "qk-normed-gated-windowed-beside-full": "trinity-tiny",  # a pattern's period, a norm behind every part
    "latent": "glm-tiny",                                  # a leading dense stack, a scan, the MTP module's block
    "block-diffusion": "sdar-tiny",                        # the doubled row under `attn_bd`
    "latent-without-a-q-latent-in-a-pattern": "kimi-linear-tiny",  # a `*` part beside delta-rule mixers; no rotation
}
RECURRENT = re.compile(r"^(ssm_|kda_|sconv)")


def _sub_jaxprs(eqn):
    for value in eqn.params.values():
        for v in value if isinstance(value, (tuple, list)) else (value,):
            inner = getattr(v, "jaxpr", v)
            if hasattr(inner, "eqns"):
                yield inner


def _equations(jaxpr, outer=()):
    """(primitive, names) of every equation, those of a jaxpr an equation holds behind it:
    `names` the plain scope names of its own stack behind its callers' (`jvp(model)` gives
    `model`)."""
    for eqn in jaxpr.eqns:
        names = outer + tuple(re.findall(r"[A-Za-z_]\w*", str(eqn.source_info.name_stack)))
        yield eqn.primitive.name, names
        for sub in _sub_jaxprs(eqn):
            yield from _equations(sub, names)


@functools.lru_cache(maxsize=None)
def _gradient_equations(kind):
    cfg = get_config(KINDS[kind])
    params = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    b, s = 2, 32
    batch = {"tokens": jax.ShapeDtypeStruct((b, s + 1), jnp.int32)}
    if cfg.diffusion_block:
        batch = {"tokens": jax.ShapeDtypeStruct((b, s), jnp.int32), "masked": jax.ShapeDtypeStruct((b, s), jnp.bool_),
                 "p_mask": jax.ShapeDtypeStruct((b,), jnp.float32)}

    def loss(params, batch):
        with jax.named_scope("model"):  # as train/step.py
            return llama.loss_fn(params, batch, cfg)[0]

    return cfg, list(_equations(jax.make_jaxpr(jax.grad(loss))(params, batch).jaxpr))


@pytest.mark.parametrize("kind", KINDS)
def test_every_equation_of_an_attention_part_carries_exactly_one_of_the_five_names(kind):
    """Forward, made again under remat and backward (`transpose(jvp(..))`): whatever runs under
    `attn` and under no recurrent mixer's scope is one of the part's five pieces, and every piece
    the configuration has is there in all three."""
    cfg, equations = _gradient_equations(kind)
    under = [(p, names) for p, names in equations
             if "attn" in names and not any(RECURRENT.match(n) for n in names)]
    assert under
    stray = [(p, names) for p, names in under if len({n for n in names if n in attn.SCOPES}) != 1]
    # (beside attention parts a pattern's recurrent mixers add their output to the stream under `attn` and no
    # piece's name, `llama._block`: one `add` a part, which is not an attention part's)
    recurrent = sum(llama.MIXERS[LAYER_KINDS[c].mixer][0].RECURRENT is not None
                    for c in cfg.layer_pattern if LAYER_KINDS[c].mixer)
    residuals = [e for e in stray if e[0] == "add" and e[1][-1] == "attn" and not set(e[1]) & set(attn.SCOPES)]
    assert len(residuals) == recurrent and len(stray) == recurrent, stray[:5]
    wanted = {"attn_in_proj", "attn_core", "attn_out_proj"}
    wanted |= {"attn_head_norm"} if cfg.attn_qk_norm else set()
    wanted |= {"attn_gate"} if cfg.attn_output_gate else set()
    passes = {"forward": lambda names: "transpose" not in names,
              "backward": lambda names: "transpose" in names and "rematted_computation" not in names}
    if cfg.remat:
        passes["again"] = lambda names: "rematted_computation" in names
    for which, holds in passes.items():
        found = {n for _, names in under if holds(names) for n in names if n in attn.SCOPES}
        # (a head norm's scope also holds the `jax.numpy` rotation, so it may be there without the norm)
        need = wanted
        if which == "again" and cfg.layer_pattern and not cfg.part_post_norm:
            # a part alone in its layer, nothing behind its output but the residual: the backward pass reads
            # no result of the output product, and the rematerialised layer does not make it again
            need = wanted - {"attn_out_proj"}
        assert need <= found <= wanted | {"attn_head_norm"}, (which, found)
    if cfg.latent_attention:  # the latent path keeps its own names inside the input's
        assert all("attn_in_proj" in names for _, names in under if "mla_q" in names or "mla_kv" in names)
        assert any("mla_q" in names for _, names in under) and any("mla_kv" in names for _, names in under)
    if cfg.diffusion_block:  # the block-diffusion core's own name stays inside the core's
        assert all("attn_core" in names for _, names in under if "attn_bd" in names)
        assert any("attn_bd" in names for _, names in under)
    for prefix in ("attn_window", "attn_full", "attn_bd"):  # the accepted metrics' patterns are prefixes
        assert not any(n.startswith(prefix) for n in attn.SCOPES)


@pytest.mark.parametrize("kind", KINDS)
def test_every_equation_of_a_layer_carries_the_layer_loops_name(kind):
    """Whatever a layer runs (under `attn`, `mlp`, `layer_params`; the MTP module's one block
    apart, which no loop runs) lies under `layer_stack`, and so does the loop's own
    equation; what lies under it and under no part's name is the loop's own and is there.
    (Differentiating a `lax.scan` moves what no iteration changes, the rotation's tables, out in
    front of the loop: such an equation keeps the names it had inside the body and loses the
    loop's and every caller's, `model` too, and is the loop's no more.)"""
    cfg, equations = _gradient_equations(kind)
    parts = ("attn", "mlp", "layer_params")
    inside = [(p, names) for p, names in equations
              if any(n in parts for n in names) and "mtp" not in names and "model" in names]
    assert inside and not [e for e in inside if llama.LAYER_LOOP not in e[1]][:5]
    if "-" in cfg.layer_pattern:  # a pattern's dense part lies under `mlp`, as a block's does
        assert any(p == "dot_general" and "mlp" in names and not any(n.startswith("moe_") for n in names)
                   for p, names in inside)
    own = [(p, names) for p, names in equations
           if llama.LAYER_LOOP in names and not any(n in parts for n in names)]
    # the loop's own: the `scan` (whose lowering stores and reads the stacks), or in a period run as it
    # stands each layer's rematerialisation and the counts its expert layers hand up, stacked
    assert {p for p, _ in own} & ({"scan"} if not cfg.layer_pattern else {"checkpoint", "remat2", "concatenate"})
    assert not any("embed" in names or "lm_head" in names or "loss" in names for _, names in own)
