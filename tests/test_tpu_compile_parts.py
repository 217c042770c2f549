"""A cell's parts alone, at the cell's shape and under the cell's remat, compiled for a described TPU
v5e: the attention part of three cells, and the three recurrent mixers (Mamba-2, the gated short
convolution, Kimi-Delta-Attention), value and every gradient. Nothing runs (tests/test_tpu_compile.py
says why that guards the chip). The whole steps are the families' (`Family.cell_step`)."""
import re

import jax
import jax.numpy as jnp
import pytest

from compiled_step_text import (  # noqa: F401  (`on_tpu`, `one_chip` and the `topo` it is made of: this module's fixtures)
    CONV_PADDED_COPY, KEPT_PRODUCTS, OVERLAPS_INTERMEDIATES, PARTS_INTERMEDIATES, WALK_OUTPUT, cell_config, instructions,
    kept_copies, kernel_calls, nemotron_share, on_tpu, one_chip, products, shapes, stored_alone, topo, xla_remats)


@pytest.mark.parametrize("config,forward", [
    ("glm-4.7-flash-train-ep8", (1, 0)),  # `full`, [1, 8192, 20 / 20, 256] behind the latent projections
    ("lfm2-24b-a2b-train-ep8", (1, 0)),   # `full`, [4, 8192, 32 / 8, 64] on padded lanes
    ("mistral-7b-train", (1, 1))])        # `dots`, the control: the forward kernel again in the backward pass
def test_a_rematerialised_attention_part_runs_the_forward_kernel_once_under_full(one_chip, on_tpu, config, forward):
    """A cell's attention part (norm, projections, rotation, the flash kernels, the output
    projection, the residual) under the cell's remat, value and every gradient at the cell's
    shape, compiled for the described chip: under `full` the forward kernel's `out` and
    logsumexp are kept by name (`ops.attention.FLASH_NAMES`) and the program holds ONE
    `flash_attention_fwd`, where it held a second in the rematerialised layer (PR 43); under
    `dots`, which keeps neither (PERF.md section 7, after PR 26 (1)), it holds two as before.
    One backward kernel, `_bwd_dkv_dq`, either way (PR 53)."""
    from ray_tpu.models import attn, llama

    file, _, cfg = cell_config(config)
    trainer = file["trainer"]
    assert cfg.remat and cfg.remat_policy == ("full" if forward == (1, 0) else "dots")
    stacks = jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg))
    stack = next(st for st in stacks.values() if isinstance(st, dict) and "attn_norm" in st)
    lp = {n: jax.ShapeDtypeStruct(stack[n].shape[1:], stack[n].dtype, sharding=one_chip)
          for n in llama._layer_axes(cfg, "attn", None)}
    b, s = trainer["batch"], trainer["seq"]
    x = jax.ShapeDtypeStruct((b, s, cfg.d_model), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)

    def loss(x, lp, pos):
        with jax.named_scope("model"):  # as train/step.py
            part = llama._maybe_remat(
                lambda x, lp: attn.mixer(x, lp, cfg, pos, None, None, None)[0], cfg)
            return jnp.sum(part(x, lp).astype(jnp.float32))

    text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, lp, pos).compile().as_text()
    assert kernel_calls(text, "flash_attention_fwd") == forward
    assert kernel_calls(text, "flash_attention_bwd_dkv_dq") == (1, 0)
    assert kernel_calls(text, "flash_attention_bwd_dq") == kernel_calls(text, "flash_attention_bwd_dkv") == (0, 0)
    assert not xla_remats(text)
    _every_instruction_of_the_part_carries_a_piece(text, cfg)


def _every_instruction_of_the_part_carries_a_piece(text, cfg):
    """In an attention part's compiled text every instruction whose `op_name` lies under `attn`
    carries one of the part's five names (models/attn.py:SCOPES; a fusion its parts'), in the
    forward pass, made again (`rematted_computation`) and in the backward pass
    (`transpose(jvp(..))`): a `custom_vjp`'s backward rule is traced under its call site's names,
    so the backward flash kernel and the rotate kernel's lie under `attn_core` (PR 52)."""
    from ray_tpu.models import attn

    paths = [p for p in re.findall(r'op_name="([^"]*)"', text) if "/attn/" in p]
    assert paths
    stray = [p for p in paths if sum(f"/{piece}/" in p for piece in attn.SCOPES) != 1]
    assert not stray, stray[:5]
    passes = {"forward": [p for p in paths if "transpose(" not in p],
              "again": [p for p in paths if "rematted_computation" in p],
              "backward": [p for p in paths if "transpose(" in p and "rematted_computation" not in p]}
    wanted = {"attn_in_proj", "attn_core", "attn_out_proj"} | ({"attn_head_norm"} if cfg.attn_qk_norm else set())
    for which, found in passes.items():
        assert wanted <= {piece for piece in attn.SCOPES for p in found if f"/{piece}/" in p}, which
    kernels = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and "op_name=" in ln]
    assert kernels and all("/attn_core/" in ln for ln in kernels)
    assert {k for k in ("fwd", "bwd_dkv_dq") for ln in kernels if f"flash_attention_{k}/" in ln} == {"fwd", "bwd_dkv_dq"}
    if cfg.latent_attention:
        assert all("/attn_in_proj/" in p for p in paths if "/mla_q/" in p or "/mla_kv/" in p)
    elif cfg.head_dim >= 128:  # (narrower heads rotate in `jax.numpy`, in front of the padded kernels)
        assert any("rope_fwd/" in ln for ln in kernels) and any("rope_bwd/" in ln for ln in kernels)


def test_mamba2_mixer_compiles_and_fits_at_the_cells_shape(one_chip, on_tpu):
    """A Mamba-2 layer's share of the Nemotron-3-Super cell (16 heads of 64, 1 group, state
    128, 8,192 positions in 64 chunks of 128), value and every gradient under the cell's remat:
    plain XLA, no kernel, the chunked scan's float32 intermediates beside the projections' under
    2 GB; `[z | xBC | dt]` `[1, 8192, 2320]` kept by name (`_rematerialised_mixer`, PR 48)."""
    from ray_tpu.models import ssm

    cfg = nemotron_share()
    lp = shapes(jax.eval_shape(lambda: ssm.init(jax.random.PRNGKey(0), cfg)), one_chip)
    assert lp["in_proj"].shape == (4096, 2 * 1024 + 2 * 128 + 16) and lp["out_proj"].shape == (1024, 4096)
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16, sharding=one_chip)
    compiled = _rematerialised_mixer(ssm, "M", cfg, x, lp)
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


def test_gated_short_convolution_compiles_and_fits_at_the_cells_shape(one_chip, on_tpu):
    """A gated short-convolution part of the LFM2 cell ([4, 8192] tokens, 2048 wide, 3 taps),
    value and every gradient under the cell's remat: plain XLA, no kernel, the float32
    convolution beside the projections' outputs under 1.95 GB (1.880 by the compile, as before
    PR 47: alone, the part's kept array lives no longer than the one made again did). Under
    `full` the input product's result `[B | C | x]` is kept by name (`sconv.IN_PROJ_NAME`, PRs 47, 48):
    the product runs once forward, not again in the rematerialised part (it did), twice backward;
    the rounding jax.checkpoint gives a named residual is in the product's own epilogue; and the
    one stored array `[4, 8192, 3, 2048]` is what forward and backward both read, through bitcasts."""
    from ray_tpu.models import sconv

    cfg = cell_config("lfm2-24b-a2b-train-ep8")[2]
    lp = shapes(jax.eval_shape(lambda: sconv.init(jax.random.PRNGKey(0), cfg)), one_chip)
    assert lp["sconv_in"].shape == (2048, 3, 2048) and lp["sconv_w"].shape == (3, 2048)
    assert lp["sconv_out"].shape == (2048, 2048)
    x = jax.ShapeDtypeStruct((4, 8192, 2048), jnp.bfloat16, sharding=one_chip)
    compiled = _rematerialised_mixer(sconv, "C", cfg, x, lp)
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 1.95e9


def _rematerialised_mixer(mixer, kind, cfg, x, lp):
    """A recurrent mixer under remat `full` as `llama._maybe_remat` runs a part, value and every
    gradient, compiled: its input product once forward, not again in the rematerialised part, twice
    backward; ONE stored copy of the kept result, rounded in the product's own fusion; no `.remat`."""
    from ray_tpu.models import llama

    assert cfg.remat and cfg.remat_policy == "full" and mixer.KEPT["full"] == (mixer.IN_PROJ_NAME,)
    part = llama._maybe_remat(lambda x, lp: mixer.mixer(x, lp, cfg), cfg)

    def loss(x, lp):
        with jax.named_scope("model"):  # as train/step.py
            return jnp.sum(part(x, lp).astype(jnp.float32))

    compiled = jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(x, lp).compile()
    text = compiled.as_text()
    scope, einsum, extents = KEPT_PRODUCTS[kind]
    assert products(text, scope, einsum) == (1, 0, 2)
    assert kept_copies(text, extents) == (1, 1)
    assert not xla_remats(text)
    return compiled


def test_kda_mixer_compiles_and_fits_at_the_cells_shape(one_chip, on_tpu):
    """A Kimi-Delta-Attention part's share of the Solar-Open2 cell (8 heads of 128, 8,192
    positions in 64 chunks of 128), value and every gradient under the cell's remat: the
    overlaps are the two Pallas kernels by name (forward, forward again in the
    rematerialised layer, backward: ISSUE 38's item 5 was not taken, PERF.md section 6), the
    chunks' four matrices two more with the same three calls (PR 51), the walk over the chunks two more
    (PR 61: twelve custom calls a part, no `while` left under the scan and no stored transpose of o or of its
    cotangent: the walk writes o and reads d o a head's positions together, the order XLA gives the norm, the gate and
    the output product behind the scan), the running sum G two more (PR 64: fifteen custom calls a part, no
    `reduce-window` under the scan, and no copy or transpose of g, G or their gradients in a pass of its own: the
    kernels read g and write dg a head's positions together, the order XLA gives the decay's product),
    convolution, silu and norms of q, k and v likewise two kernels and three calls (PR 44), the
    inverse the compiler's own triangular kernel once a block, no float32 array with the
    extents of the differences, the sub-chunks' factors or the second half's right-hand
    sides and solutions of all chunks, the scan's float32 intermediates beside the
    projections' under 2 GB; q | k | v before the convolution
    `[1, 8192, 3072]` kept by name in the layout the convolution's kernels read
    (`_rematerialised_mixer`, PR 48: named before the reshape it was stored positions-minor and
    copied for the kernels, forward and backward)."""
    from ray_tpu.models import kda
    from ray_tpu.ops.kda import _SOLVE, takes_kernels

    cfg = cell_config("solar-open2-train-tp8-ep40")[2]
    lp = shapes(jax.eval_shape(lambda: kda.init(jax.random.PRNGKey(0), cfg)), one_chip)
    assert lp["kda_qkv"].shape == (4096, 3, 8, 128) and lp["kda_out"].shape == (8, 128, 4096)
    assert lp["kda_f_down"].shape == lp["kda_g_down"].shape == (4096, 128)
    assert cfg.kda_chunk == 128 and takes_kernels(cfg.kda_chunk, cfg.kda_head_dim)
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16, sharding=one_chip)
    compiled = _rematerialised_mixer(kda, "K", cfg, x, lp)
    text = compiled.as_text()
    assert kernel_calls(text, "kda_overlaps_fwd") == (1, 1) and kernel_calls(text, "kda_overlaps_bwd") == (1, 0)
    assert kernel_calls(text, "short_conv_fwd") == (1, 1) and kernel_calls(text, "short_conv_bwd") == (1, 0)
    assert kernel_calls(text, "kda_parts_fwd") == (1, 1) and kernel_calls(text, "kda_parts_bwd") == (1, 0)
    assert kernel_calls(text, "kda_walk_fwd") == (1, 1) and kernel_calls(text, "kda_walk_bwd") == (1, 0)
    assert kernel_calls(text, "kda_prefix_fwd") == (1, 1) and kernel_calls(text, "kda_prefix_bwd") == (1, 0)
    assert text.count("tpu_custom_call") == text.count('custom_call_target="tpu_custom_call"') == 15
    assert not instructions(text, "while", "kda_scan") and not instructions(text, "reduce-window", "kda_scan")
    # (g, G and their gradients have o's extents: none of them is copied or transposed in a pass of its own either)
    assert not stored_alone(text, WALK_OUTPUT, "kda_scan")
    assert not re.search(CONV_PADDED_COPY, text)
    assert text.count('custom_call_target="InvertDiagBlocksLowerTriangular"') == cfg.kda_chunk // _SOLVE
    assert not re.search(OVERLAPS_INTERMEDIATES, text) and not re.search(PARTS_INTERMEDIATES, text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9
