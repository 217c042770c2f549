"""The main path's kernels and serving programs compile for a TPU v5e.

Nothing runs: the TPU compiler is installed here and compiles for a chip that
is described, not attached. What it refuses here (a slice not aligned to the
tiling, too much fast memory, a program that does not fit 16 GB) it would
refuse on the chip, so these cases guard every later PR at no chip time.

The topology is described inside the module-scoped `topo` fixture and nowhere
else: only one process may load the TPU's library, and xdist workers all
import this file. Keep every such test in THIS file, and compile in the test's
own process (no children).
"""
import dataclasses
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding


@pytest.fixture(scope="module")
def topo():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")  # or the compiler logs under /tmp
    from jax.experimental import topologies

    try:
        return topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Steer the code's own backend probes to their TPU side: they ask the
    attached backend, which here is the CPU."""
    from ray_tpu.ops import flash_attention as fa

    monkeypatch.setattr(fa, "_interpret", lambda: False)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _shapes(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding), tree)


def _pallas_grids(jaxpr):
    """The grid of every Pallas kernel in a program, nested calls included."""
    grids = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            grids.append(eqn.params["grid_mapping"].grid)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            grids += _pallas_grids(sub)
    return grids


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
    grid, = _pallas_grids(jax.make_jaxpr(
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
    grids = _pallas_grids(jax.make_jaxpr(grad)(q, k, k, pos).jaxpr)
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
    grids = _pallas_grids(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, q, q).jaxpr)
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


def _glm_share():
    from ray_tpu.models.config import ModelConfig

    return ModelConfig(
        name="glm-shape", vocab_size=19360, d_model=2048, n_layers=5, n_heads=20, n_kv_heads=20,
        d_ff=10240, n_experts=64, moe_top_k=4, moe_capacity_factor=0.0, d_ff_expert=1536,
        n_shared_experts=1, moe_scoring="sigmoid", moe_route_scale=1.8, moe_select_bias=True,
        experts_held=(0, 8))


_LAYER_TEXTS = {}


def _expert_layer_text(cfg, one_chip, remat=False):
    """The compiled text of an expert layer's value and every gradient at 8,192 tokens
    (`remat`: rematerialised under the configuration's policy, as a model's layer is),
    made once a configuration (under `on_tpu`, which every caller has)."""
    from ray_tpu.models import llama, moe

    if (cfg.name, remat) not in _LAYER_TEXTS:
        lp = _shapes(jax.eval_shape(lambda: moe.init_expert_weights(jax.random.PRNGKey(0), cfg)),
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

    cfg = _glm_share()
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


def _nemotron_share():
    from ray_tpu.models.config import ModelConfig

    return ModelConfig(
        name="nemotron-shape", vocab_size=16384, d_model=4096, n_layers=11, n_heads=32, n_kv_heads=2,
        d_ff=2688, layer_pattern="MEMEMEM*EME", ssm_n_heads=16, ssm_head_dim=64, ssm_n_groups=1,
        ssm_state=128, ssm_chunk=128, attn_heads_held=(4, 1), attention_rotation=False, n_experts=512,
        moe_top_k=22, moe_capacity_factor=0.0, d_ff_expert=2688, n_shared_experts=1, d_ff_shared=5376,
        moe_latent_dim=1024, mlp_activation="relu2", moe_scoring="sigmoid", moe_route_scale=5.0,
        moe_select_bias=True, experts_held=(0, 64))


def _cell_file(config):
    """(ModelConfig, file) of a family cell's configuration as its file states it."""
    import sys

    sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))
    from benchmarks.lib import modelcfg

    with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "configs", f"{config}.json")) as f:
        file = json.load(f)
    return modelcfg.model_config(modelcfg.model_keys(file)), file


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

    cfg = _nemotron_share()
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

    cfg = {"nemotron": _nemotron_share, "glm": _glm_share}[cell]()
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


def _xla_remats(text):
    """The instructions XLA made again by itself to fit the program (`.remat` in their names)."""
    return re.findall(r"^\s*(?:ROOT )?%[\w.\-]*\.remat\S*", text, re.M)


def _instructions(text, op, scope=None):
    """The program's instructions of kind `op`, fused or not (under `scope`, by `op_name`)."""
    return [ln for ln in text.splitlines() if re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = .*?[\])}}] {op}\(", ln)
            and (scope is None or re.search(rf'op_name="[^"]*/{scope}/', ln))]


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

    cfg = {"nemotron": _nemotron_share, "glm": _glm_share}[cell]()
    assert cfg.remat and cfg.remat_policy == "full"
    _, text = _expert_layer_text(cfg, one_chip, remat=True)
    t, k, e = 8192, cfg.moe_top_k, cfg.n_experts
    products = _instructions(text, "convolution", "moe_router")
    assert len(products) == 3, products
    assert sum(f" = f32[{t},{e}]" in ln for ln in products) == 1, products
    assert len(_instructions(text, "while", "moe_router")) == loops
    if t * k * e > moe._MASK_ELEMENTS:
        assert not re.search(rf"\[({t},{k},{e}|{k},{t},{e}|{t},{e},{k}|{t * k},{e})\]", text)
    assert not re.search(r" scatter\(", text)


@pytest.mark.parametrize("config,bodies,loops,temp_gb", [
    # (a period of layers, unrolled: a body each). PR 48: 3.866 -> 4.058 GB, `[z | xBC | dt]` of five
    # Mamba-2 parts kept from forward to backward, [1, 8192, 2320] bfloat16 = 38 MB a part, 0.19 GB
    ("nemotron-3-super-train-tp8-ep64", 5, 2, 4.06),
    # (the scan over four layers has one body; the MTP module). PR 43: 6.045 -> 6.539 GB, remat `full`
    # keeps the forward flash kernel's `out` [1, 20, 8192, 256] bfloat16 (84 MB) and logsumexp (0.66 MB)
    # of six blocks, 0.51 GB, and runs the kernel 3 times a step where it ran 6
    ("glm-4.7-flash-train-ep8", 2, 0, 6.54),
    # PR 37: four expert parts at 8 of 320 (the pick a slot at a time, as at 22 of 512), three
    # delta-rule scans whose triangular systems are inverted once each and kept. PR 44: 4.650 ->
    # 4.644 GB, the float32 `[1, 8195, 3072]` padded copies and the taps' products gone. PR 48: 4.644 ->
    # 4.794 GB, q | k | v before the convolution of three delta-rule parts kept, [1, 8192, 3072] bfloat16 =
    # 50 MB a part, 0.15 GB. PR 51: 4.7938 -> 4.7907 GB, 3 MB less: the scan's second half in kernels
    # (its [.., 128, 256] right-hand sides and solutions and the [chunks, B, H, Q, K] copies of q, k, v
    # gone) is not where the step's temporaries peak, so `kk` and `b` (0.2 GB: 4.996) stay unnamed
    ("solar-open2-train-tp8-ep40", 4, 2, 4.80),
    # PR 42: four expert parts at 4 of 64 over 32,768 tokens (the pick a slot at a time: 8.4 M mask
    # elements), no shared expert, beside four gated short convolutions, a dense part and attention
    # at head width 64 on padded lanes; arguments 5.63 GB (16 B a parameter less the gradient).
    # PR 43: 5.193 -> 5.568 GB, the one attention part's `out` on its padded lanes [4, 32, 8192, 128]
    # bfloat16 (268 MB) and logsumexp (4 MB) kept, 0.27 GB, and 0.10 GB of the compiler's placing
    # PR 48 (PR 47's compile): 5.568 -> 6.673 GB, `[B | C | x]` of four conv parts kept from forward to
    # backward, [4, 8192, 3, 2048] bfloat16 = 403 MB a part, 4 x 403 MB = 1.61 GB, of which the compiler
    # places 0.51 GB where the backward pass's float32 intermediates lay before: 1.105 GB more
    ("lfm2-24b-a2b-train-ep8", 4, 2, 6.68),
    # PR 46: [2, 16384]: four attention parts inside a window of 2,048 (their kernels under their own
    # names) and one full, gated, normed a head, a norm behind every part; four expert parts at 8 of 128
    # over 32,768 tokens beside a shared expert; arguments 6.05 GB; the f32 logits [2, 16384, 25024] are
    # 3.3 GB of the temporaries
    ("trinity-mini-train-ep16", 4, 2, 8.75),
    # PR 54: [1, 8192]: four delta-rule parts at all 32 heads of 128 (their q | k | v [1, 8192, 12288] kept:
    # 0.2 GB a part), one latent attention part WITHOUT a q latent whose kernels run q/k 192 | v 128 (the
    # kernels under their own names: no XLA fallback), a dense part, four expert parts at 8 of 256 (the
    # pick a slot at a time) beside a shared expert; arguments 7.23 GB; 12.86 of 15.75 GB in all
    # (marked slow: this file is tier-1's longest, one worker's from its start to the run's end, and a seventh
    # whole step is two minutes more of it than the run's limit leaves: `-m slow -k kimi` runs it, ~2.5 min; tier-1
    # holds the cell's kernels, names and scoped memory in `test_flash_attention_compiles_at_q_k_192_beside_v_128`)
    pytest.param("kimi-linear-48b-a3b-train-ep32", 4, 2, 5.64, marks=pytest.mark.slow)])
def test_a_family_cells_step_scores_once_a_layer_and_fits_as_before(one_chip, on_tpu, config, bodies,
                                                                    loops, temp_gb):
    """The whole step of each family cell as its configuration file states it, compiled for
    the described chip: three router products an expert layer and the forward pass's loops
    only (the test above, in the step); what the router keeps from forward to backward
    (88 MB in the Nemotron cell, 10.5 MB in GLM's) leaves the temporaries within 0.15 GB of
    what they were before it did (PR 35's programs: 3.83 and 6.01 GB), and XLA
    rematerialises nothing of its own to fit (PERF.md section 7, after PR 26 (2))."""
    import importlib

    from ray_tpu.models import llama
    from ray_tpu.models.config import LAYER_KINDS
    from ray_tpu.train import make_optimizer, make_train_step
    from ray_tpu.train.step import TrainState

    attention_ops = importlib.import_module("ray_tpu.ops.attention")  # (the package re-exports the function under this name)
    cfg, file = _cell_file(config)
    trainer = file["trainer"]
    assert cfg.remat and cfg.remat_policy == "full" and trainer["mesh"] is None
    fallbacks = attention_ops.xla_fallback_count
    tx = make_optimizer(**trainer["optimizer"])
    params = _shapes(jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg)), one_chip)
    opt_state = _shapes(jax.eval_shape(tx.init, params), one_chip)
    state = TrainState(step=_scalar(one_chip), params=params, opt_state=opt_state)
    batch = {"tokens": jax.ShapeDtypeStruct((trainer["batch"], trainer["seq"] + 1), jnp.int32, sharding=one_chip)}
    compiled = make_train_step(cfg, tx).lower(state, batch).compile()
    text = compiled.as_text()
    assert len(_instructions(text, "convolution", "moe_router")) == 3 * bodies
    assert len(_instructions(text, "while", "moe_router")) == loops * bodies
    assert not _xla_remats(text)
    # under `full` a rematerialised layer keeps the forward flash kernel's results: a call an
    # attention block forward, none made again (PR 43), ONE backward kernel a call (PR 53)
    blocks = _kernel_calls(text, "flash_attention_bwd_dkv_dq")[0]
    assert blocks >= 1 and _kernel_calls(text, "flash_attention_fwd") == (blocks, 0)
    # a windowed part runs the windowed kernels, as often; no attention falls to an XLA path
    windowed = sum(LAYER_KINDS[c].windowed for c in cfg.layer_pattern)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd_dkv_dq"):
        assert _kernel_calls(text, f"{kernel}_window") == (windowed, 0)
    for suffix in ("", "_window"):  # the two kernels of a sequence longer than a span of K and V run nowhere
        assert _kernel_calls(text, f"flash_attention_bwd_dq{suffix}") == _kernel_calls(text, f"flash_attention_bwd_dkv{suffix}") == (0, 0)
    assert blocks + windowed == sum(LAYER_KINDS[c].mixer == "attn" for c in cfg.layer_pattern) + cfg.mtp_depth or not cfg.layer_pattern
    assert attention_ops.xla_fallback_count == fallbacks
    # a recurrent mixer keeps its input product's result under `full` (PR 48): the product once a part
    # forward, none made again in the rematerialised layer, two backward; one stored copy a part,
    # rounded in the product's own epilogue
    period = llama.pattern_period(cfg.layer_pattern)[0] if cfg.layer_pattern else ""
    for kind, (scope, einsum, extents) in _KEPT_PRODUCTS.items():
        parts = period.count(kind)
        assert _products(text, scope, einsum) == (parts, 0, 2 * parts), kind
        assert _kept_copies(text, extents) == (parts, parts), kind
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < (temp_gb + 0.15) * 1e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9  # what a v5e program may use
    if cfg.kda_n_heads:  # a delta-rule part substitutes once, a call a block: the backward pass keeps the inverse
        from ray_tpu.ops.kda import _SOLVE

        parts = cfg.layer_pattern.count("K")
        assert text.count('custom_call_target="InvertDiagBlocksLowerTriangular"') == parts * (cfg.kda_chunk // _SOLVE)
        # the overlaps' kernels a part: forward, again in the rematerialised layer, backward (PR 38)
        assert _kernel_calls(text, "kda_overlaps_fwd") == (parts, parts)
        assert _kernel_calls(text, "kda_overlaps_bwd") == (parts, 0)
        assert not re.search(_OVERLAPS_INTERMEDIATES, text)
        # the chunks' four matrices likewise (PR 51): [W | U0] and what it is made from stay in fast memory
        assert _kernel_calls(text, "kda_parts_fwd") == (parts, parts)
        assert _kernel_calls(text, "kda_parts_bwd") == (parts, 0)
        assert not re.search(_PARTS_INTERMEDIATES, text)
        # the convolution, silu and norms of q, k and v: ONE call a part and pass whatever the three
        # (9 a step; a call for each of q, k, v was 27, and 3 s of every first step: PR 44), and the
        # plain form's float32 copy of q|k|v padded by the taps is gone with its shifted products
        assert _kernel_calls(text, "short_conv_fwd") == (parts, parts)
        assert _kernel_calls(text, "short_conv_bwd") == (parts, 0)
        assert not re.search(_CONV_PADDED_COPY, text)
        assert abs(memory.argument_size_in_bytes - 12 * cfg.n_params) < 1e7


@pytest.mark.slow  # (beside Kimi-Linear's whole step: `-m slow -k ouro`, ~1 min; tier-1 holds the family at a small size)
def test_the_ouro_cells_looped_step_compiles_inside_its_memory_at_five_layers_and_not_at_six(one_chip, on_tpu):
    """The whole step of `ouro26b-train-loop4-s8192` as its configuration file states it (five layers run
    four times over shared weights, four heads with their exit gates, the expected-exit loss), compiled for
    the described chip. PR 58: arguments 5.500 GB (12 B a parameter, each counted ONCE) + temporaries 9.512 GB
    = 15.01 of 15.75 GB; six layers are 6.116 + 10.148 = 16.26 GB and do not fit (the configuration's `cut`
    says what a layer costs). A forward flash kernel a layer-stack loop (four: one a recurrence, none made
    again: `out` and the logsumexp are kept by name, T x L of them) and ONE backward kernel a loop; XLA
    rematerialises nothing of its own; the loop's three scopes stand in the `op_name`s, forward and backward,
    and a recurrence's rematerialised head and loss keep theirs."""
    import importlib

    from ray_tpu.models import llama
    from ray_tpu.train import make_optimizer, make_train_step
    from ray_tpu.train.step import TrainState

    attention_ops = importlib.import_module("ray_tpu.ops.attention")
    cfg, file = _cell_file("ouro-2.6b-train-loop4")
    trainer = file["trainer"]
    assert (cfg.loop_steps, cfg.n_layers, cfg.remat_policy, trainer["mesh"]) == (4, 5, "full", None)
    fallbacks = attention_ops.xla_fallback_count
    tx = make_optimizer(**trainer["optimizer"])

    def compiled_step(cfg):
        params = _shapes(jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg)), one_chip)
        state = TrainState(step=_scalar(one_chip), params=params,
                           opt_state=_shapes(jax.eval_shape(tx.init, params), one_chip))
        batch = {"tokens": jax.ShapeDtypeStruct((trainer["batch"], trainer["seq"] + 1), jnp.int32, sharding=one_chip)}
        return make_train_step(cfg, tx).lower(state, batch).compile()

    compiled = compiled_step(cfg)
    text, memory = compiled.as_text(), compiled.memory_analysis()
    assert abs(memory.argument_size_in_bytes - 12 * cfg.n_params) < 1e7  # a shared leaf is held once
    assert memory.temp_size_in_bytes < (9.52 + 0.15) * 1e9
    assert memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9  # what a v5e program may use
    assert not _xla_remats(text) and attention_ops.xla_fallback_count == fallbacks
    assert _kernel_calls(text, "flash_attention_fwd") == (cfg.loop_steps, 0)
    assert _kernel_calls(text, "flash_attention_bwd_dkv_dq") == (cfg.loop_steps, 0)
    assert _kernel_calls(text, "flash_attention_bwd_dq") == _kernel_calls(text, "flash_attention_bwd_dkv") == (0, 0)
    names = set(re.findall(r'op_name="([^"]*)"', text))
    for scope in (llama.LOOP_STEP, llama.EXIT_GATE, llama.EXIT_LOSS):
        assert any(f"/jvp(model)/{scope}" in n for n in names) and any(f"/transpose(jvp(model))/{scope}" in n for n in names), scope
    again = {n for n in names if "transpose(jvp(model))" in n and "rematted_computation" in n}
    assert any("/lm_head/" in n for n in again) and any("/loss/" in n for n in again)
    assert any(f"/{llama.LOOP_STEP}/{llama.LAYER_LOOP}/" in n for n in again)
    deeper = compiled_step(dataclasses.replace(cfg, n_layers=6)).memory_analysis()
    assert deeper.argument_size_in_bytes + deeper.temp_size_in_bytes > 15.75e9  # the greatest depth that is placed is five


def test_the_block_diffusion_cells_step_compiles_inside_its_memory_and_walks_288_tiles_a_head(one_chip, on_tpu):
    """The whole step of `sdar30b-train-ep8share-s8192` as its configuration file states it (five
    layers; the batch a loader makes: tokens, masked, p_mask), compiled for the described chip:
    [1, 16384] rows through the layers under the block-diffusion mask. The two kernels (forward;
    the ONE backward kernel, PR 53) carry `_bd` behind their names, which the accepted kernel metrics and the cell's own roofline
    metric find; the forward kernel runs once a layer (its `out` and logsumexp kept under
    `full`); three router products a layer and the pick's loops, nothing made again by XLA, no
    attention on an XLA path; 15.18 of 15.75 GB (PR 50: six layers were 17.31 and do not fit).
    The grids: a step a q tile forward and backward (K/V of the doubled row are one span, and so
    dK and dV of a kv head stay in VMEM); 288 tiles a head each way by `tile_counts`."""
    import importlib

    from ray_tpu.models import llama
    from ray_tpu.ops import flash_attention as fa
    from ray_tpu.train import make_optimizer, make_train_step
    from ray_tpu.train.step import TrainState

    attention_ops = importlib.import_module("ray_tpu.ops.attention")
    cfg, file = _cell_file("sdar-30b-a3b-train-ep8")
    trainer = file["trainer"]
    b, n = trainer["batch"], trainer["seq"]
    assert cfg.remat and cfg.remat_policy == "full" and (cfg.diffusion_block, cfg.n_layers, b, n) == (4, 5, 1, 8192)
    fallbacks = attention_ops.xla_fallback_count
    tx = make_optimizer(**trainer["optimizer"])
    params = _shapes(jax.eval_shape(lambda: llama.init(jax.random.PRNGKey(0), cfg)), one_chip)
    state = TrainState(step=_scalar(one_chip), params=params, opt_state=_shapes(jax.eval_shape(tx.init, params), one_chip))
    batch = {"tokens": jax.ShapeDtypeStruct((b, n), jnp.int32, sharding=one_chip),
             "masked": jax.ShapeDtypeStruct((b, n), jnp.bool_, sharding=one_chip),
             "p_mask": jax.ShapeDtypeStruct((b,), jnp.float32, sharding=one_chip)}
    step = make_train_step(cfg, tx)
    compiled = step.lower(state, batch).compile()
    text = compiled.as_text()
    for path, count in (("train_attn_fwd_kernel_pct", 1), ("train_attn_bwd_kernel_pct", 1), ("train_attn_bd_roofline_pct", 2)):
        with open(os.path.join(os.path.dirname(__file__), "..", "benchmarks", "metrics", f"{path}.json")) as f:
            rx = re.compile(json.load(f)["args"]["pattern"])
        kernels = [ln.strip() for ln in text.splitlines() if "tpu_custom_call" in ln and rx.search(ln.strip())]
        assert len(kernels) == count and all("_bd" in ln.split(" = ")[0] for ln in kernels), (path, kernels)
    assert _kernel_calls(text, "flash_attention_fwd_bd") == _kernel_calls(text, "flash_attention_bwd_dkv_dq_bd") == (1, 0)
    assert _kernel_calls(text, "flash_attention_bwd_dq_bd") == _kernel_calls(text, "flash_attention_bwd_dkv_bd") == (0, 0)
    assert _kernel_calls(text, "flash_attention_fwd") == (0, 0)
    assert len(_instructions(text, "convolution", "moe_router")) == 3 and len(_instructions(text, "while", "moe_router")) == 2
    assert not _xla_remats(text) and attention_ops.xla_fallback_count == fallbacks
    memory = compiled.memory_analysis()
    assert abs(memory.argument_size_in_bytes - 12 * cfg.n_params) < 1e7 and cfg.n_params == 550984960
    assert memory.temp_size_in_bytes < (8.57 + 0.15) * 1e9
    assert 0.25 * 15.75e9 < memory.argument_size_in_bytes + memory.temp_size_in_bytes < 15.75e9  # what a v5e program may use
    t = fa._tiling(2 * n, 2 * n, 512, 512, 128, 2, 8)
    assert (t.kv_span, t.q_span) == (16384, 1024)
    grids = _pallas_grids(jax.make_jaxpr(step._jitted)(state, batch).jaxpr)
    assert fa._fuses(t, 2 * n) and grids.count((b, 32, 32, 1)) == 2 and (b, 4, 32, 16) not in grids  # forward; backward (PR 53)
    for kernel, heads in (("fwd", 1), ("dq", 1), ("dkv", 8)):  # ("dq": the one backward kernel's walk too)
        counts = fa.tile_counts(2 * n, 2 * n, False, 512, 512, n_rep=heads, kernel=kernel, block_diffusion=4)
        assert counts.tiles_computed == heads * 288 and round(counts.tiles_needed / heads, 1) == 256.1


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

    cfg, file = _cell_file(config)
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
    assert _kernel_calls(text, "flash_attention_fwd") == forward
    assert _kernel_calls(text, "flash_attention_bwd_dkv_dq") == (1, 0)
    assert _kernel_calls(text, "flash_attention_bwd_dq") == _kernel_calls(text, "flash_attention_bwd_dkv") == (0, 0)
    assert not _xla_remats(text)
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

    cfg = _nemotron_share()
    lp = _shapes(jax.eval_shape(lambda: ssm.init(jax.random.PRNGKey(0), cfg)), one_chip)
    assert lp["in_proj"].shape == (4096, 2 * 1024 + 2 * 128 + 16) and lp["out_proj"].shape == (1024, 4096)
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16, sharding=one_chip)
    compiled = _rematerialised_mixer(ssm, "M", cfg, x, lp)
    assert "tpu_custom_call" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


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
    grid, = _pallas_grids(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(q, k, v, causal=True))(q, k, k).jaxpr)
    assert grid == (b, h, 16, 1)  # K and V of a kv head, 8,192 rows of 128 lanes, are one span


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

    cfg, _ = _cell_file("lfm2-24b-a2b-train-ep8")
    lp = _shapes(jax.eval_shape(lambda: sconv.init(jax.random.PRNGKey(0), cfg)), one_chip)
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
    scope, einsum, extents = _KEPT_PRODUCTS[kind]
    assert _products(text, scope, einsum) == (1, 0, 2)
    assert _kept_copies(text, extents) == (1, 1)
    assert not _xla_remats(text)
    return compiled


# a recurrent mixer's pattern character -> (the scope of its input product, the product's einsum, the
# kept result at its cell's shape in whichever order of its extents): `[B | C | x]` of the LFM2 cell,
# `[z | xBC | dt]` of the Nemotron cell, q | k | v before the convolution of the Solar-Open2 cell
_KEPT_PRODUCTS = {
    "C": ("sconv_in_proj", "btd,dpe->btpe", r"bf16\[(?:4,8192,3,2048|3,4,8192,2048|4,3,8192,2048)\]"),
    "M": ("ssm_in_proj", "btd,de->bte", r"bf16\[(?:1,)?8192,2320\]"),
    "K": ("kda_in_proj", "btd,dphk->btphk", r"bf16\[(?:1,)?8192,(?:3072|3,8,128|12288|3,32,128)\]"),
}


def _products(text, scope, einsum):
    """The compiled program's products (XLA's `convolution`) of `einsum` under `scope`, by what ran
    them: forward, the forward made again by a rematerialised layer, backward."""
    products = [ln for ln in _instructions(text, "convolution", scope) if f"/{scope}/{einsum}/" in ln]
    again = sum("rematted_computation" in ln for ln in products)
    backward = sum("transpose(jvp(" in ln for ln in products) - again
    return len(products) - again - backward, again, backward


def _kept_copies(text, extents):
    """(arrays with the `extents` of a mixer's kept product that the program makes and stores: results
    of fusions, products, copies and transposes outside fused computations (bitcasts, a loop's tuple
    plumbing and the asynchronous copies between fast memory and HBM, which change no layout, apart);
    how many of them the named residual's `reduce-precision` is fused behind the product itself).
    Equal, and one a part: ONE copy, rounded where the product wrote it, no pass of its own, none
    transposed."""
    stored, fused, in_fusion = 0, 0, False
    for ln in text.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.\-]+) \(", ln)
        if head:
            in_fusion = head.group(1).startswith("fused_computation")
        made = re.match(rf"\s*(?:ROOT )?%[\w.\-]+ = {extents}\S* ([\w\-]+)\((%[\w.\-]+)", ln)
        if not made:
            continue
        if made.group(1) == "reduce-precision":
            assert in_fusion and made.group(2).startswith("%convolution"), ln  # not stand-alone
            fused += 1
        elif not in_fusion and made.group(1) in ("fusion", "convolution", "copy", "transpose"):
            stored += 1
    return stored, fused


def _kernel_calls(text, name):
    """The compiled program's calls of the Pallas kernel `name`, by what ran them: forward,
    the forward made again by a rematerialised layer, backward."""
    calls = [ln for ln in text.splitlines() if "tpu_custom_call" in ln and f"/{name}/" in ln]
    again = sum("rematted_computation" in ln for ln in calls)
    return len(calls) - again, again


# q|k|v `[1, 8192, 3072]` in float32 with the taps' 3 rows of zeros in front: `ssm._causal_conv`'s copy
_CONV_PADDED_COPY = r"f32\[1,8195,(3072|12288)\]"

# float32 arrays of every chunk with the extents of a sub-chunk's differences [.., 32, 32, 128]
# or of the sub-chunks' factors [.., 4, 128, 128]: what `_decayed_overlaps` wrote to HBM
_OVERLAPS_INTERMEDIATES = r"f32\[(\d+,)+(32,32,128|4,128,128)\]"


# float32 arrays of every chunk with the extents of `_chunk_parts`' right-hand sides and solutions,
# beta [k exp G | v] and [W | U0], [.., 128, 256]: what the second half wrote to HBM before its kernels
_PARTS_INTERMEDIATES = r"f32\[(\d+,)+128,256\]"


def test_kda_mixer_compiles_and_fits_at_the_cells_shape(one_chip, on_tpu):
    """A Kimi-Delta-Attention part's share of the Solar-Open2 cell (8 heads of 128, 8,192
    positions in 64 chunks of 128), value and every gradient under the cell's remat: the
    overlaps are the two Pallas kernels by name (forward, forward again in the
    rematerialised layer, backward: ISSUE 38's item 5 was not taken, PERF.md section 6), the
    chunks' four matrices two more with the same three calls (PR 51: nine custom calls a part),
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

    cfg, _ = _cell_file("solar-open2-train-tp8-ep40")
    lp = _shapes(jax.eval_shape(lambda: kda.init(jax.random.PRNGKey(0), cfg)), one_chip)
    assert lp["kda_qkv"].shape == (4096, 3, 8, 128) and lp["kda_out"].shape == (8, 128, 4096)
    assert lp["kda_f_down"].shape == lp["kda_g_down"].shape == (4096, 128)
    assert cfg.kda_chunk == 128 and takes_kernels(cfg.kda_chunk, cfg.kda_head_dim)
    x = jax.ShapeDtypeStruct((1, 8192, 4096), jnp.bfloat16, sharding=one_chip)
    compiled = _rematerialised_mixer(kda, "K", cfg, x, lp)
    text = compiled.as_text()
    assert _kernel_calls(text, "kda_overlaps_fwd") == (1, 1) and _kernel_calls(text, "kda_overlaps_bwd") == (1, 0)
    assert _kernel_calls(text, "short_conv_fwd") == (1, 1) and _kernel_calls(text, "short_conv_bwd") == (1, 0)
    assert _kernel_calls(text, "kda_parts_fwd") == (1, 1) and _kernel_calls(text, "kda_parts_bwd") == (1, 0)
    assert text.count("tpu_custom_call") == text.count('custom_call_target="tpu_custom_call"') == 9
    assert not re.search(_CONV_PADDED_COPY, text)
    assert text.count('custom_call_target="InvertDiagBlocksLowerTriangular"') == cfg.kda_chunk // _SOLVE
    assert not re.search(_OVERLAPS_INTERMEDIATES, text) and not re.search(_PARTS_INTERMEDIATES, text)
    assert compiled.memory_analysis().temp_size_in_bytes < 2e9


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
    grids = _pallas_grids(jax.make_jaxpr(
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
                params=_shapes(params, one_chip))


def _scalar(sharding, dtype=jnp.int32):
    return jax.ShapeDtypeStruct((), dtype, sharding=sharding)


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
            sv["params"], state, tokens, _scalar(sh), _scalar(sh), cfg)
    return model_runner.decode_step.lower(
        sv["params"], state, _vec(slots, sh), _vec(slots, sh, jnp.bool_), cfg)


def _lower_paged(sv, sh, program):
    from ray_tpu.llm import model_runner, paged

    cfg, slots, block = sv["cfg"], sv["slots"], sv["block"]
    n_blocks = slots * sv["max_len"] // block
    if program == "prefill":
        tokens = jax.ShapeDtypeStruct((1, 64), jnp.int32, sharding=sh)
        return model_runner.prefill_detached.lower(
            sv["params"], tokens, _scalar(sh), cfg)
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
            state, kv, kv, _vec(64 // block, sh), _scalar(sh), _scalar(sh),
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
