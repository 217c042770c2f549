"""The dropless expert layer at the two expert cells' shapes (a chip's share of GLM-4.7-Flash and of
Nemotron-3-Super, written by hand: tests/compiled_step_text.py `glm_share`, `nemotron_share`), value and
every gradient at 8,192 tokens, compiled for a described TPU v5e: what its text holds and does not hold.
Nothing runs (tests/test_tpu_compile.py says why that guards the chip); a layer's text is made once a
configuration for the cases that read it."""
import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from compiled_step_text import (  # noqa: F401  (`on_tpu`, `one_chip` and the `topo` it is made of: this module's fixtures)
    glm_share, instructions, nemotron_share, on_tpu, one_chip, products, shapes, topo)


_LAYER_TEXTS = {}


def _expert_layer_text(cfg, one_chip, remat=False):
    """The compiled text of an expert layer's value and every gradient at 8,192 tokens
    (`remat`: rematerialised under the configuration's policy, as a model's layer is),
    made once a configuration (under `on_tpu`, which every caller has)."""
    from ray_tpu.models import llama, moe

    if (cfg.name, remat) not in _LAYER_TEXTS:
        lp = shapes(jax.eval_shape(lambda: moe.init_expert_weights(jax.random.PRNGKey(0), cfg)),
                     one_chip)
        x = jax.ShapeDtypeStruct((8192, cfg.d_model), jnp.bfloat16, sharding=one_chip)

        def layer(x, lp):
            return moe.expert_layer(x, lp, cfg)[0]

        def loss(x, lp, cot):
            with jax.named_scope("model"):  # as train/step.py: the first name inside `grad` is written jvp(..)
                y = (llama._maybe_remat(layer, cfg) if remat else layer)(x, lp)
                return jnp.sum((y * cot).astype(jnp.float32))

        _LAYER_TEXTS[cfg.name, remat] = (
            set(lp), jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, lp, x).compile().as_text())
    return _LAYER_TEXTS[cfg.name, remat]


def _grouped_kernels(text):
    return [ln for ln in text.splitlines() if re.match(r"\s*%ragged-dot-none[\w.]* = ", ln)]


def test_expert_layer_compiles_to_the_grouped_kernels(one_chip, on_tpu):
    """The dropless expert layer at the cell's shape (8,192 tokens x 4 assignments, 8
    held experts of 2048 x 1536, a window of 8,192 rows): its grouped products are the
    TPU compiler's own ragged-dot kernels, not a dense product an expert over the whole
    buffer. 21 of them: a window is 3 products forward and 9 in its backward pass (the
    3 again, since only the walk's inputs are kept, and 2 transposes each), and the
    window's body is in the program twice, for the first window (9: XLA shares its
    forward products with the backward's, no rematerialisation standing between them
    here) and in the loops that only an overflowing step enters (3 + 9); and since PR 34
    the combine's own, 3 a body. No scatter in either direction, and nothing of tokens x
    k rows by either width is left."""
    from ray_tpu.models import moe

    cfg = glm_share()
    assert moe.window_rows(cfg, 8192) == 8192
    _, text = _expert_layer_text(cfg, one_chip)
    kernels = _grouped_kernels(text)
    assert len(kernels) == 21 + 6, len(kernels)
    assert sum("bf16[8192," in ln.split(" custom-call(")[0] for ln in kernels) == 15
    assert not re.search(r" scatter\(", text)
    full = [ln.strip()[:160] for ln in text.splitlines()
            if re.search(r"\[32768,(1536|2048)\]", ln) and re.search(r'op_name="[^"]*moe_', ln)]
    assert not full, full[:4]
    assert not re.search(r"\[32768,(1536|2048)\]", text)  # nor anywhere else in the layer


def test_latent_expert_layer_at_22_of_512_compiles_without_a_tokens_by_k_by_experts_operand(one_chip, on_tpu):
    """The expert layer of the Nemotron-3-Super cell (8,192 tokens x 22 assignments over a
    router of 512, 8 experts of 1024 x 2688 held in a latent, a window of 5,632 rows): two
    grouped products an expert MLP (`ragged-dot-none`: 2 forward and 6 in the backward of a
    window, the window's body in the program twice: 14; and since PR 34 the combine's own,
    3 a body: the window's rows summed onto their tokens forward, for dx, and the gates'
    gradient), no scatter, and no operand with the extents of tokens, k and experts
    together: a mask `[8192, 22, 512]` is 92 M elements a layer, forward and again in the
    backward pass."""
    from ray_tpu.models import moe

    cfg = nemotron_share()
    assert moe.window_rows(cfg, 8192) == 5632
    names, text = _expert_layer_text(cfg, one_chip)
    assert names == {"router", "router_bias", "w_up", "w_down", "shared_up", "shared_down",
                     "latent_down", "latent_up"}
    kernels = _grouped_kernels(text)
    assert len(kernels) == 14 + 6, len(kernels)
    assert not re.search(r" scatter\(", text)
    assert not re.search(r"\[(8192,22,512|22,8192,512|8192,512,22|180224,512)\]", text)
    assert not re.search(r"\[180224,(1024|2688|4096)\]", text)  # nor tokens x k rows of any width


def _gathers_under(text, scope):
    """Result shapes of the gather instructions (fused or not) traced under `scope`."""
    return [m.group(1) for ln in text.splitlines()
            if (m := re.match(r"\s*(?:ROOT )?%[\w.\-]+ = (\S+) gather\(", ln))
            and re.search(rf'op_name="[^"]*/{scope}/', ln)]


@pytest.mark.parametrize("cell,ratio,sums", [
    ("nemotron", 32, ["f32[16,512,1024]"] * 4 + ["f32[3,512,128]"] * 2),
    ("glm", 4, ["f32[1,512,128]"] * 2 + ["f32[16,512,2048]"] * 4)])
def test_the_combine_follows_the_windows_rows_in_both_cells(one_chip, on_tpu, cell, ratio, sums):
    """`moe.combine_from_rows` at the two cells' shapes, and the program it makes (PERF.md
    sections 3 and 6, PR 34): Nemotron-3-Super sums 22 x 8,192 assignments over a window
    of 5,632 rows (32 to 1), GLM-4.7-Flash 4 x 8,192 over 8,192 (4 to 1), both from the
    window's side: no gather under `moe_combine` has a row a token or an assignment (the
    sorted rows and the gates are gathered, a window's rows each), the sums are grouped
    products by tile of 512 tokens (`[16, 512, width]`, forward and for dx in each body;
    the gates' scalars 128 to a row), no scatter, no operand of tokens x k rows. A layer
    that holds a quarter of its experts or more keeps a gather a slot."""
    from ray_tpu.models import moe

    cfg = {"nemotron": nemotron_share, "glm": glm_share}[cell]()
    k, rows = cfg.moe_top_k, moe.window_rows(cfg, 8192)
    assert 8192 * k == ratio * rows
    assert moe.combine_from_rows(8192, k, rows) and moe.combine_from_rows(8192 * k, 1, rows)
    for held in ((0, 4), (0, 2), (0, 1)):  # the same layer with a quarter, half or all of its experts
        assert not moe.combine_from_rows(
            8192, k, moe.window_rows(dataclasses.replace(cfg, experts_held=held), 8192))
    _, text = _expert_layer_text(cfg, one_chip)
    combined = [ln.split(" custom-call(")[0].split(" = ")[1].split("{")[0] for ln in _grouped_kernels(text)
                if re.search(r"= f32\[\d+,512,\d+\]", ln)]
    assert sorted(combined) == sums
    # one gather a sum, the window's rows into the tokens' order (a gather a slot: k a sum)
    gathered = [s.split("{")[0] for s in _gathers_under(text, "moe_combine")]
    assert gathered.count(f"bf16[{rows},{cfg.moe_latent_dim or cfg.d_model}]") == 4, gathered
    if rows != 8192:  # nor has any a row a token or an assignment
        assert not [s for s in gathered if re.match(rf"\w+\[({8192 * k}|8192)[,\]]", s)], gathered
    assert not re.search(r" scatter\(", text)
    assert not re.search(rf"\[{8192 * k},\d+\]", text)


@pytest.mark.parametrize("cell,loops", [("nemotron", 2), ("glm", 0)])
def test_a_rematerialised_expert_layer_scores_once_in_both_cells(one_chip, on_tpu, cell, loops):
    """An expert layer of each family cell under remat `full` (as the cells run it), value
    and every gradient at 8,192 tokens, compiled for the described chip (PERF.md section
    6, PR 36): the router's products are three (the scores once, `[T, E]`; dx; the
    weight's gradient), where a backward pass that scores again has four; at 22 of 512
    the only loops under `moe_router` are the forward pick's and the count's (the pick
    made again, its backward slot by slot into an accumulator and the count made again
    were three more) and no operand has the extents of tokens, k and experts together
    (4 of 64 picks by one fused mask of 2 M elements in the forward pass, as it did)."""
    from ray_tpu.models import moe

    cfg = {"nemotron": nemotron_share, "glm": glm_share}[cell]()
    assert cfg.remat and cfg.remat_policy == "full"
    _, text = _expert_layer_text(cfg, one_chip, remat=True)
    t, k, e = 8192, cfg.moe_top_k, cfg.n_experts
    products = instructions(text, "convolution", "moe_router")
    assert len(products) == 3, products
    assert sum(f" = f32[{t},{e}]" in ln for ln in products) == 1, products
    assert len(instructions(text, "while", "moe_router")) == loops
    if t * k * e > moe._MASK_ELEMENTS:
        assert not re.search(rf"\[({t},{k},{e}|{k},{t},{e}|{t},{e},{k}|{t * k},{e})\]", text)
    assert not re.search(r" scatter\(", text)
