"""The main path's kernels and whole programs that belong to no family's cell compile for a TPU v5e:
the flash and rotate kernels at the cells' shapes, the Mistral step on four chips, the serving programs.

Nothing runs: the TPU compiler is installed here and compiles for a chip that
is described, not attached. What it refuses here (a slice not aligned to the
tiling, too much fast memory, a program that does not fit 16 GB) it would
refuse on the chip, so these cases guard every later PR at no chip time.

A cell's parts at the cell's shape are tests/test_tpu_compile_parts.py's (mixers, the attention part) and
tests/test_tpu_compile_experts.py's (the expert layer); a family cell's WHOLE step is its family's
(`Family.cell_step`, tests/family_contract.py; tests/test_family_<model_type>.py), compiled once in the
family's file. The fixtures and the readers of a compiled program's text are tests/compiled_step_text.py's;
compile in the test's own process (no children).
"""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from compiled_step_text import (  # noqa: F401  (`on_tpu`, `one_chip`, `topo`: this module's fixtures)
    on_tpu, one_chip, pallas_grids, scalar, shapes, topo)


@pytest.mark.parametrize("b,s,h,kv,segments", [
    (6, 2048, 32, 8, False),   # llama8b-geom2, the smoke's train batch; mistral7b-train-1chip
    (4, 2048, 32, 8, False),   # a chip's shard of mistral7b-train-fsdp4
    (8, 2048, 12, 6, False),   # llama-500m
    (2, 4096, 32, 8, True),    # packed documents
    (2, 200, 32, 8, True),     # one tile, not a multiple of 128: the lane vectors are padded
    (1, 32768, 32, 8, False),  # K and V of a kv head do not fit a grid step: two spans of 16,384
])
def test_flash_attention_compiles(one_chip, on_tpu, b, s, h, kv, segments):
    from ray_tpu.ops.flash_attention import _fuses, _tiling, flash_attention, tile_counts

    d = 128
    one_backward = _fuses(_tiling(s, s, 512, 512, d, 2, h // kv), s)  # K and V of a kv head are one span (PR 53)
    assert one_backward == (s <= 16384)
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=one_chip)
    seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip) if segments else None

    def loss(q, k, v, seg):
        return jnp.sum(flash_attention(q, k, v, causal=True, segment_ids=seg)
                       .astype(jnp.float32))

    fwd = jax.jit(lambda q, k, v, seg: flash_attention(
        q, k, v, causal=True, segment_ids=seg)).lower(q, k, k, seg).compile()
    bwd = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k, seg).compile()
    assert "tpu_custom_call" in fwd.as_text()
    # the forward kernel and the backward's one (PR 53), or dQ's and dK/dV's where K and V are two spans
    assert bwd.as_text().count("tpu_custom_call") == (2 if one_backward else 3)
    # a grid step owns a span of K/V and walks its 512-wide tiles in the kernel: the
    # forward program's grid is the short one, not a step a (q tile, kv tile)
    grid, = pallas_grids(jax.make_jaxpr(
        lambda q, k, v: flash_attention(q, k, v, causal=True))(q, k, k).jaxpr)
    tiles = -(-s // 512)
    steps = tile_counts(s, s, True, 512, 512).grid_steps
    assert grid == (b, h, tiles, steps // tiles) and grid[2] * grid[3] == steps
    assert steps == {200: 1, 2048: 4, 4096: 8, 32768: 128}[s] and steps <= tiles * tiles
    # the kernels' names are their instructions' names, which the device trace
    # shows (benchmarks/metrics/train_attn_{fwd,bwd}_kernel_pct.json select by them)
    # (a transformation may wrap the name: %jvp_flash_attention_fwd_.1)
    for path in ("train_attn_fwd_kernel_pct", "train_attn_bwd_kernel_pct"):
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "metrics",
                               f"{path}.json")) as f:
            rx = re.compile(json.load(f)["args"]["pattern"])
        kernels = [ln.strip() for ln in bwd.as_text().splitlines()
                   if "tpu_custom_call" in ln and rx.search(ln.strip())]
        assert len(kernels) == (1 if "fwd" in path or one_backward else 2), (path, kernels)
        assert "fwd" in path or all(("bwd_dkv_dq" in ln.split(" = ")[0]) == one_backward for ln in kernels), kernels


def test_windowed_flash_attention_compiles_at_the_cells_shape_and_visits_the_band_alone(one_chip, on_tpu):
    """[2, 16384, 32 / 4, 128] inside a window of 2,048 with the rotation deferred into the
    rotate kernel (trinitymini-train-ep16share-s16384's four `W` parts): the forward kernel
    and the ONE backward kernel (PR 53: K and V are one 16,384-row span, so dK and dV of a kv
    head stay in VMEM beside them, 64 MiB asked for and granted) compile under their own names,
    which the accepted kernel metrics still find; and the kernels' own bands, steps and index
    maps over every grid step: what is computed is the band's tiles, by the backward kernel
    (dQ's walk, q tile by q tile) the two an edge crosses in their 256-row pieces (135 tiles'
    worth a head for 120.0 needed; forward 150, as all three before PR 49). The two kernels
    that run where K and V are longer than a span keep their own walks: a group's Q/dO in spans
    of 1,024 of which the dK/dV grid holds the 3 a kv tile's band reaches, and no step of it
    walks nothing but the steps past the sequence's end."""
    import numpy as np

    from ray_tpu.ops import flash_attention as fa

    b, s, h, kv, d, window, tile = 2, 16384, 32, 4, 128, 2048, 512
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)

    def loss(q, k, v, pos):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, window=window, rope=(pos, 1e4)).astype(jnp.float32))

    grad = jax.grad(loss, argnums=(0, 1, 2))
    compiled = jax.jit(grad).lower(q, k, k, pos).compile()
    text = compiled.as_text()
    for path, name in (("train_attn_fwd_kernel_pct", "fwd"), ("train_attn_bwd_kernel_pct", "bwd_dkv_dq")):
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "metrics", f"{path}.json")) as f:
            rx = re.compile(json.load(f)["args"]["pattern"])
        kernels = [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln and rx.search(ln.strip())]
        assert len(kernels) == 1 and f"flash_attention_{name}_window_" in kernels[0].split(" = ")[0], (path, kernels)
    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "metrics", "train_attn_window_roofline_pct.json")) as f:
        rx = re.compile(json.load(f)["args"]["pattern"])
    assert len([ln for ln in text.splitlines() if "tpu_custom_call" in ln and rx.search(ln.strip())]) == 2
    # the backward kernel's VMEM request is derived from the shapes and granted: 16 MiB for the compute tile
    # and 3 x 16 for K, V, dK, dV's blocks and the f32 sums, of which the compiled kernel uses 34 MiB
    asked, used = re.search(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"\}\].*?'
                            r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"\}\]', kernels[0]).groups()
    assert int(asked) == 64 << 20 and (32 << 20) < int(used) < int(asked)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 1.0e9  # the kernels keep no scores
    t = fa._tiling(s, s, tile, tile, d, 2, h // kv)
    assert (t.kv_span, t.q_span) == (16384, 1024)
    n = s // tile
    q_spans = fa._q_spans(s, s, t, window)
    assert (fa._kv_spans(s, s, t, window), q_spans, s // t.q_span) == (1, 3, 16)
    grids = pallas_grids(jax.make_jaxpr(grad)(q, k, k, pos).jaxpr)
    assert fa._fuses(t, s) and grids.count((b, h, n, 1)) == 2 and not any(g[1] == kv for g in grids if len(g) == 4)
    # the band, by brute force over the tiles: tile (qi, kj) holds a kept score; the share of each computed
    first, last = np.arange(n) * tile, np.arange(n) * tile + tile - 1
    band = (last[:, None] - first[None, :] >= 0) & (first[:, None] - last[None, :] < window)
    assert band.sum() == 150 == fa.tile_counts(s, s, True, tile, tile, window=window).tiles_computed
    assert fa.tile_counts(s, s, True, tile, tile, window=window, kernel="dq").tiles_computed == 135
    kv_spec = fa._q_major_specs(d, h // kv, True, t, False, window)[1]
    for kernel, span_tiles, steps in (("fwd", t.kv_span // tile, 1), ("dq", t.kv_span // tile, 1), ("dkv", t.q_span // tile, q_spans)):
        walk = "q" if kernel == "dkv" else "kv"
        share = np.zeros((n, n))  # [qi, kj]
        idle = []
        for own in range(n):  # the grid's third dimension: a q tile (forward, dQ) or a kv tile (dK/dV)
            mine = fa._q_band(own, n, tile, tile, window) if walk == "q" else fa._kv_band(own, tile, tile, window, kernel == "dq")
            first_used, last_used = int(mine.first) // span_tiles, int(mine.last) // span_tiles
            for step in range(steps):
                sp = first_used + step  # a windowed grid counts its spans from the band's first
                lo, hi, (t1, in1), (t2, in2) = fa._band_steps(mine, sp * span_tiles, span_tiles)
                done = [(sp * span_tiles + x, 1.0) for x in range(int(lo), int(hi))]
                for at, inside, pieces in ((t1, in1, mine.pieces_first), (t2, in2, mine.pieces_last)):
                    if bool(inside):
                        done.append((sp * span_tiles + int(at), sum(p.q[1] * p.kv[1] for p in pieces) / tile**2))
                for other, part in done:
                    share[(own, other) if walk == "kv" else (other, own)] += part
                # only a span past the band's last walks nothing, and it names the last one used: no copy is issued
                assert bool(done) == (sp <= last_used), (walk, own, sp)
                if not done:
                    idle.append((own, step))
                if walk == "kv":  # the forward and dQ kernels' own index map for K and V
                    assert int(kv_spec.index_map(0, 0, own, step)[2]) == min(sp, last_used), (own, step)
        # whole tiles, but backward on the two edges, where 3 of a tile's 4 pieces hold a kept score
        assert set(np.unique(share[band])) == ({1.0} if kernel == "fwd" else {0.75, 1.0}) and not share[~band].any(), kernel
        assert share.sum() == (150 if kernel == "fwd" else 135), kernel
        # dK/dV: the band of a kv tile in the sequence's last 2,048 positions ends with the sequence
        assert idle == ([] if walk == "kv" else [(kj, st) for kj in range(n) for st in range(3) if kj // 2 + st > 15])
        assert len(idle) in (0, 6)  # of 96 steps a (batch, kv head); 416 of 512 before


def test_an_unwindowed_call_lowers_to_the_kernels_it_lowered_to(one_chip, on_tpu):
    """[2, 16384, 32 / 4, 128] without a window (Trinity-Mini's full part; the six other
    cells pass none either): the Mosaic bodies of the forward kernel and of the ONE backward
    kernel, decoded and printed without source locations, are the text they were (sha256 of
    it), with and without segment ids: the forward kernel's at the parent of PR 49, the
    backward's as PR 53 wrote it in place of dQ's and dK/dV's (`6addad1f9a3a3bd3`,
    `82b7f2e4eb1f2f2a`; packed `385dfe5649f5a98c`, `3b5d8d4e375bb10e`). At [1, 32768, 32 / 8,
    128] K and V of a kv head are two spans and those two kernels run (`_fuses`): all three
    bodies are the text they were at PR 53's parent. A change that means to move the
    un-windowed kernels replaces the digests, and says so."""
    import base64
    import hashlib

    from jax._src.interpreters import mlir
    from jax._src.lib.mlir import ir

    from ray_tpu.ops.flash_attention import flash_attention

    d = 128
    was = {  # (b, s, h, kv, packed): forward, then the backward's
        (2, 16384, 32, 4, False): ["80956e8bb4c0098a", "831a9af23037739b"],
        (2, 16384, 32, 4, True): ["33f3a81fd5eb83bb", "4d92c7fd04a254d4"],
        (1, 32768, 32, 8, False): ["d2bec9831c724fb3", "cdc19bd57b229996", "cf085ce9dc4181d6"],
        (1, 32768, 32, 8, True): ["c6d29eb38e065aa6", "1c5043421b33c712", "32d43e1f9c72e4cc"],
    }
    for (b, s, h, kv, packed), digests in was.items():
        q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
        k = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=one_chip)
        seg = jax.ShapeDtypeStruct((b, s), jnp.int32, sharding=one_chip)

        def loss(q, k, v, seg):
            return jnp.sum(flash_attention(q, k, v, causal=True, segment_ids=seg if packed else None).astype(jnp.float32))

        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k, seg).as_text()
        bodies = re.findall(r"body\\22: \\22([A-Za-z0-9+/=]+)\\22", text)
        got = []
        for body in bodies:
            ctx = mlir.make_ir_context()
            ctx.allow_unregistered_dialects = True
            with ctx:
                asm = ir.Module.parse(base64.b64decode(body)).operation.get_asm(enable_debug_info=False)
            got.append(hashlib.sha256(asm.encode()).hexdigest()[:16])
        assert got == digests, (s, packed, got)


def test_flash_attention_compiles_at_width_256(one_chip, on_tpu):
    """[1, 8192, 20/20, 256], the latent-attention cell's shape (glm47flash-train-
    ep8share-s8192): heads twice the lane width, no grouping, K and V of a head exactly
    the span budget, dK and dV of a head as much again in f32; the forward kernel and the one
    backward kernel (PR 53) under the names the trace metrics select by."""
    from ray_tpu.ops.flash_attention import flash_attention, tile_counts

    b, s, h, d = 1, 8192, 20, 256
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, q, q).compile().as_text()
    calls = [ln.strip().split(" = ")[0] for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    for name in ("flash_attention_fwd", "flash_attention_bwd_dkv_dq"):
        assert sum(name in c for c in calls) == 1, (name, calls)
    grids = pallas_grids(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr)
    steps = tile_counts(s, s, True, 512, 512, head_dim=d).grid_steps
    assert grids == [(b, h, 16, steps // 16)] * 2, grids  # one kv span, forward and backward


def test_flash_attention_compiles_at_q_k_192_beside_v_128(one_chip, on_tpu):
    """[1, 8192, 32/32, 192 | 128], the kimilinear-train-ep32share-s8192 cell's shape (latent attention
    without a q latent: q and k 128 + 64 wide, v 128): q and k on 256 lanes beside v on its own 128, so
    K and V of a head are one span (three quarters of the budget) and the backward is the ONE kernel,
    dK resident at 256 lanes and dV at 128: it asks Mosaic for 16 MiB + 8,192 rows x 384 lanes x 12 B =
    52 MiB of VMEM and the compiled kernel uses under 40 (GLM's 256 | 256 asks for 64); the kernels
    carry the names the trace metrics select by; dq and dk come back 192 wide, dv and the output 128."""
    from ray_tpu.ops.flash_attention import _fuses, _tiling, flash_attention

    b, s, h, d, dv = 1, 8192, 32, 192, 128
    assert _fuses(_tiling(s, s, 512, 512, 256, 2, 1, dv), s)
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    v = jax.ShapeDtypeStruct((b, s, h, dv), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        return jnp.sum(flash_attention(q, k, v, causal=True).astype(jnp.float32))

    grad = jax.jit(jax.grad(loss, argnums=(0, 1, 2)))
    assert [a.shape[-1] for a in jax.eval_shape(grad, q, q, v)] == [d, d, dv]
    compiled = grad.lower(q, q, v).compile()
    text = compiled.as_text()
    calls = [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2
    for path, name in (("train_attn_fwd_kernel_pct", "fwd"), ("train_attn_bwd_kernel_pct", "bwd_dkv_dq"),
                       ("train_attn_mla_roofline_pct", None)):
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "metrics", f"{path}.json")) as f:
            rx = re.compile(json.load(f)["args"]["pattern"])
        found = [ln for ln in calls if rx.search(ln)]
        assert len(found) == (2 if name is None else 1), (path, found)
        assert name is None or f"flash_attention_{name}" in found[0].split(" = ")[0]
    backward, = [ln for ln in calls if "bwd_dkv_dq" in ln.split(" = ")[0]]
    asked, used = re.search(r'"scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"\}\].*?'
                            r'"used_scoped_memory_configs":\[\{"memory_space":"1","offset":"0","size":"(\d+)"\}\]', backward).groups()
    assert int(asked) == (16 << 20) + s * (256 + 128) * 12 == 52 << 20 and (32 << 20) < int(used) < (40 << 20)
    memory = compiled.memory_analysis()
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 1.0e9  # the kernels keep no scores


def test_flash_attention_compiles_at_head_width_64(one_chip, on_tpu):
    """[4, 8192, 32 / 8, 64], the LFM2 cell's shape (lfm2moe-train-ep8share-b4-s8192): heads
    half the lane width run the same kernels (forward; the backward's one, PR 53) on zero-padded lanes (Mosaic refuses a
    block 64 lanes wide: "must be aligned to tiling (128)"), under the names the trace
    metrics select by; the rotation in front of them is jax.numpy's (no rotate kernel)."""
    from ray_tpu.models.llama import rope
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.ops.attention import Rotation, attention

    b, s, h, kv, d = 4, 8192, 32, 8, 64
    assert fa.supports(s, s, d)
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((1, s), jnp.int32, sharding=one_chip)

    def loss(q, k, v, pos):
        return jnp.sum(attention(q, k, v, causal=True, rotation=Rotation(pos, 1e6, rope)).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k, pos).compile().as_text()
    calls = [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln]
    assert len(calls) == 2 and not any("rope_" in ln.split(" = ")[0] for ln in calls)
    for path, n in (("train_attn_fwd_kernel_pct", 1), ("train_attn_bwd_kernel_pct", 1),
                    ("train_attn_w64_roofline_pct", 2)):
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "metrics", f"{path}.json")) as f:
            rx = re.compile(json.load(f)["args"]["pattern"])
        assert sum(bool(rx.search(ln)) for ln in calls) == n, (path, calls)
    # the kernels see whole vregs: [4, 32, 8192, 128] and [4, 8, 8192, 128]
    assert all("bf16[4,32,8192,128]" in ln and "bf16[4,8,8192,128]" in ln for ln in calls)
    grid, = pallas_grids(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(q, k, v, causal=True))(q, k, k).jaxpr)
    assert grid == (b, h, 16, 1)  # K and V of a kv head, 8,192 rows of 128 lanes, are one span


@pytest.mark.parametrize("b,s,h,kv,per_row", [
    (6, 2048, 32, 8, False),   # mistral7b-train-1chip: one row of positions for the batch
    (4, 2048, 32, 8, False),   # a chip's shard of mistral7b-train-fsdp4
    (2, 4096, 32, 8, True),    # packed documents: positions a row
    (2, 200, 32, 8, True),     # one block, not a multiple of 16
    (8, 2048, 12, 6, False),   # llama-500m
])
def test_rope_kernel_compiles(one_chip, on_tpu, b, s, h, kv, per_row):
    """The rotate kernel in front of the flash kernels, forward and backward, at the
    cells' widths: two custom calls by their trace names, 256 positions of all 40 heads
    a grid step at Mistral's widths."""
    from ray_tpu.ops import flash_attention as fa

    d = 128
    q = jax.ShapeDtypeStruct((b, s, h, d), jnp.bfloat16, sharding=one_chip)
    k = jax.ShapeDtypeStruct((b, s, kv, d), jnp.bfloat16, sharding=one_chip)
    pos = jax.ShapeDtypeStruct((b if per_row else 1, s), jnp.int32, sharding=one_chip)

    def loss(q, k, v, pos):
        return jnp.sum(fa.flash_attention(q, k, v, causal=True, rope=(pos, 1e6)).astype(jnp.float32))

    text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(q, k, k, pos).compile().as_text()
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln]
    for name in ("rope_fwd", "rope_bwd"):
        assert sum(name in ln.split(" = ")[0] for ln in calls) == 1, (name, len(calls))
    grids = pallas_grids(jax.make_jaxpr(
        lambda q, k, pos: fa.rope_to_heads(q, k, pos, 1e6))(q, k, pos).jaxpr)
    rows = {2048: 256 if h == 32 else 512, 4096: 256, 200: 200}[s]
    assert grids == [(b, s // rows)], grids


def test_train_step_compiles_for_four_chips(topo, on_tpu):
    """make_train_step on llama8b-geom2 under dp=2 x fsdp=2, the four-chip
    smoke's shape: the kernel is in the program (GSPMD cannot partition a
    Mosaic kernel; ops/attention.py calls it per shard), and parameters and
    optimizer state are split over fsdp, not stacked whole on every device."""
    import optax

    from ray_tpu.models import get_config, llama
    from ray_tpu.parallel import MeshSpec, build_mesh, use_mesh
    from ray_tpu.parallel.sharding import named_sharding
    from ray_tpu.train import make_optimizer, make_train_step
    from ray_tpu.train.step import TrainState

    cfg = dataclasses.replace(get_config("llama8b-geom2"), remat_policy="dots")
    mesh = build_mesh(MeshSpec(dp=2, fsdp=2), topo.devices)
    params = jax.tree.map(
        lambda x, axes: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=named_sharding(mesh, *axes)),
        jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg)),
        llama.param_axes(cfg), is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    tx = make_optimizer()
    replicated = named_sharding(mesh)
    opt_state = optax.tree_map_params(
        tx, lambda x, p: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=p.sharding),
        jax.eval_shape(tx.init, params), params,
        transform_non_params=lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=replicated))
    state = TrainState(step=jax.ShapeDtypeStruct((), jnp.int32, sharding=replicated),
                       params=params, opt_state=opt_state)
    batch = {"tokens": jax.ShapeDtypeStruct(
        (4, 2049), jnp.int32, sharding=named_sharding(mesh, "batch", None))}
    with use_mesh(mesh):
        compiled = make_train_step(cfg, tx).lower(state, batch).compile()
    text = compiled.as_text()
    assert text.count("tpu_custom_call") >= 3  # forward, dQ, dK/dV
    # the rotation runs in its kernel, once a phase (the rotated pair is kept under
    # `dots`), on q and k as the projections' epilogues wrote them: no layout copy of
    # either in front of it (PERF.md, PR 30: a [B, S, H*D] view cost one on four chips)
    calls = {ln.split(" = ")[0].strip(): ln for ln in text.splitlines() if "tpu_custom_call" in ln}
    (fwd,) = [ln for name, ln in calls.items() if "rope_fwd" in name]
    assert sum("rope_bwd" in name for name in calls) == 1, list(calls)
    q, k = re.search(r"custom-call\((%[\w.\-]+), (%[\w.\-]+),", fwd).groups()
    assert not any(x.startswith(("%copy", "%transpose")) for x in (q, k)), (q, k)
    # 3 x f32 x 698M parameters (params, mu, nu) is 8.4 GB on one device
    assert compiled.memory_analysis().argument_size_in_bytes < 0.55 * 8.4e9


@pytest.fixture(scope="module")
def serving(one_chip):
    """llama3-8b widths, full vocabulary, bf16 weights, at the depth and KV
    geometry chip_smoke.py's serve phase runs (shapes only)."""
    import chip_smoke
    from ray_tpu.models import get_config, llama

    slots, max_len = 8, 2048
    depth = chip_smoke.serve_depth(slots, max_len, "llama3-8b")["depth"]
    cfg = get_config("llama3-8b", n_layers=depth)
    params = jax.eval_shape(lambda: jax.tree.map(
        lambda x: x.astype(jnp.bfloat16), llama.init(jax.random.PRNGKey(0), cfg)))
    return dict(cfg=cfg, slots=slots, max_len=max_len, block=16,
                params=shapes(params, one_chip))


def _vec(n, sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct((n,), dtype, sharding=sharding)


def _lower_slot(sv, sh, program):
    from ray_tpu.llm import model_runner

    cfg, slots = sv["cfg"], sv["slots"]
    kv = jax.ShapeDtypeStruct(
        (cfg.n_layers, slots, sv["max_len"], cfg.n_kv_heads, cfg.head_dim),
        jnp.bfloat16, sharding=sh)
    state = model_runner.DecodeState(k=kv, v=kv, lengths=_vec(slots, sh))
    if program == "prefill":
        tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32, sharding=sh)
        return model_runner.prefill.lower(
            sv["params"], state, tokens, scalar(sh), scalar(sh), cfg)
    return model_runner.decode_step.lower(
        sv["params"], state, _vec(slots, sh), _vec(slots, sh, jnp.bool_), cfg)


def _lower_paged(sv, sh, program):
    from ray_tpu.llm import model_runner, paged

    cfg, slots, block = sv["cfg"], sv["slots"], sv["block"]
    n_blocks = slots * sv["max_len"] // block
    if program == "prefill":
        tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32, sharding=sh)
        return model_runner.prefill_detached.lower(
            sv["params"], tokens, scalar(sh), cfg)
    pool = jax.ShapeDtypeStruct(
        (cfg.n_layers, n_blocks + 1, block, cfg.n_kv_heads, cfg.head_dim),
        jnp.bfloat16, sharding=sh)
    state = paged.PagedState(
        k=pool, v=pool, lengths=_vec(slots, sh),
        block_tables=jax.ShapeDtypeStruct(
            (slots, sv["max_len"] // block), jnp.int32, sharding=sh))
    if program == "install":
        kv = jax.ShapeDtypeStruct((cfg.n_layers, 1, 64, cfg.n_kv_heads, cfg.head_dim),
                                  jnp.bfloat16, sharding=sh)
        return paged.install_prefill.lower(
            state, kv, kv, _vec(64 // block, sh), scalar(sh), scalar(sh),
            n_blocks=64 // block)
    k_steps = 4  # one fused decode+sample burst
    rngs = jax.ShapeDtypeStruct((k_steps, 2), jnp.uint32, sharding=sh)
    return paged.decode_multi_paged.lower(
        sv["params"], state, _vec(slots, sh), _vec(slots, sh, jnp.bool_), cfg, rngs,
        _vec(slots, sh, jnp.float32), _vec(slots, sh, jnp.float32),
        _vec(slots, sh), _vec(slots, sh))


@pytest.mark.parametrize("layout,program", [
    ("slot", "prefill"), ("slot", "decode"),
    ("paged", "prefill"), ("paged", "install"), ("paged", "decode"),
])
def test_serving_program_compiles_and_fits(one_chip, on_tpu, serving, layout, program):
    import chip_smoke

    lower = _lower_slot if layout == "slot" else _lower_paged
    compiled = lower(serving, one_chip, program).compile()
    ma = compiled.memory_analysis()
    # weights and the KV state are arguments (the state aliased to the output);
    # with the temporaries they are what this program needs of the chip
    need = ma.argument_size_in_bytes + ma.temp_size_in_bytes + (
        ma.output_size_in_bytes - ma.alias_size_in_bytes)
    assert need < chip_smoke.V5E_BYTES_LIMIT, (layout, program, need)


def test_depth_cut_preset_keeps_its_own_init_cache_entry():
    """Two depths of one preset share a name; the engine's seeded-init cache
    must not hand one the other's weights, and serves in the dtype asked for."""
    from ray_tpu.llm.engine import _INIT_CACHE, llama_init_cached
    from ray_tpu.models import get_config

    two = get_config("test-tiny")
    one = dataclasses.replace(two, n_layers=1)
    assert one.name == two.name
    p2, p1 = llama_init_cached(two, "bfloat16"), llama_init_cached(one, "bfloat16")
    assert p2["layers"]["wq"].shape[0] == 2 and p1["layers"]["wq"].shape[0] == 1
    assert p1["layers"]["wq"].dtype == jnp.bfloat16
    assert llama_init_cached(two, "float32")["embed"].dtype == jnp.float32
    assert llama_init_cached(two, "bfloat16") is p2
    assert (two, "bfloat16", None) in _INIT_CACHE and (one, "bfloat16", None) in _INIT_CACHE
