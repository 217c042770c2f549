"""Flash attention kernels vs the XLA reference, in Pallas' interpreter on the CPU: the causal
triangle and the full square over tilings, the block-diffusion mask, the rotation in front of the
kernels, remat, head widths. (A sliding window's band: tests/test_flash_window.py; the one backward
kernel: tests/test_flash_backward.py; what the three share: tests/flash_cases.py.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from family_contract import highest  # noqa: F401  (a fixture)
from flash_cases import flash_names, kernel_names, packed_segments, rand
from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.flash_attention import flash_attention, tile_counts


@pytest.mark.parametrize("causal", [True, False])
def test_fwd_matches_reference(causal):
    b, s, h, d = 2, 128, 4, 64
    q, k, v = rand((b, s, h, d), 0), rand((b, s, h, d), 1), rand((b, s, h, d), 2)
    out = flash_attention(q, k, v, causal=causal, block_q=64, block_kv=64)
    ref = attention_reference(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_fwd_gqa():
    b, s, h, hkv, d = 1, 128, 8, 2, 64
    q = rand((b, s, h, d), 0)
    k, v = rand((b, s, hkv, d), 1), rand((b, s, hkv, d), 2)
    out = flash_attention(q, k, v, causal=True, block_q=64, block_kv=64)
    ref = attention_reference(q, k, v, causal=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


def test_fwd_segment_ids():
    b, s, h, d = 1, 128, 2, 64
    q, k, v = rand((b, s, h, d), 0), rand((b, s, h, d), 1), rand((b, s, h, d), 2)
    seg = jnp.concatenate(
        [jnp.zeros((b, 64), jnp.int32), jnp.ones((b, 64), jnp.int32)], axis=1
    )
    out = flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=64, block_kv=64)
    ref = attention_reference(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-3, atol=2e-3)


@pytest.mark.parametrize("gqa", [False, True])
def test_grads_match_reference(gqa):
    b, s, h, d = 1, 128, 4, 64
    hkv = 2 if gqa else h
    q = rand((b, s, h, d), 0)
    k, v = rand((b, s, hkv, d), 1), rand((b, s, hkv, d), 2)

    def loss_flash(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_kv=64).sum()

    def loss_ref(q, k, v):
        return attention_reference(q, k, v, causal=True).sum()

    g_flash = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b_ in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), rtol=5e-3, atol=5e-3)


# Tilings of the (q, kv) plane: every case runs the forward kernel and the backward (ONE
# kernel where K and V are a span, PR 53; dQ's and dK/dV's two under a budget that cuts them
# in spans) against the f32 reference on f32 copies of the same inputs, with a random
# cotangent. Under `causal` a tiling with more than one block a side has tiles below
# the diagonal, on it, and above it (not visited). A grid step fetches a span of the
# other side's sequence, all of it where it fits `SPAN_VMEM_BYTES`, and walks the
# compute tiles inside the kernel; a case with an eleventh field runs under that
# budget in bytes, so that a step's span is shorter than the sequence.
TILINGS = {
    # name: (b, s, h, hkv, d, block_q, block_kv, causal, segment cuts, dtype[, span budget])
    "causal-4x4": (1, 256, 2, 2, 64, 64, 64, True, None, jnp.float32),
    "causal-wide-kv-2x4": (1, 256, 2, 2, 64, 128, 64, True, None, jnp.float32),
    "causal-wide-q-4x2": (1, 256, 2, 2, 64, 64, 128, True, None, jnp.float32),
    "causal-one-tile": (1, 128, 2, 2, 64, 128, 128, True, None, jnp.float32),
    "causal-block-larger-than-seq": (1, 128, 2, 1, 64, 512, 512, True, None, jnp.float32),
    "noncausal-2x2": (1, 128, 4, 4, 64, 64, 64, False, None, jnp.float32),
    "noncausal-gqa-2x4": (1, 256, 4, 2, 64, 128, 64, False, None, jnp.float32),
    "segments-causal-4x4": (1, 256, 2, 2, 64, 64, 64, True, (40, 150, 200), jnp.float32),
    "segments-causal-gqa-2x4": (1, 256, 4, 2, 64, 128, 64, True, (100, 130), jnp.float32),
    "segments-noncausal-2x2": (1, 128, 2, 2, 64, 64, 64, False, (70,), jnp.float32),
    "gqa4-causal-4x4": (1, 256, 4, 1, 64, 64, 64, True, None, jnp.float32),
    "gqa4-causal-batch2-2x4": (2, 256, 8, 2, 64, 128, 64, True, None, jnp.float32),
    "bf16-causal-4x4": (1, 256, 2, 2, 64, 64, 64, True, None, jnp.bfloat16),
    "bf16-gqa4-causal-2x2": (1, 256, 8, 2, 128, 128, 128, True, None, jnp.bfloat16),
    "bf16-segments-causal-4x2": (1, 256, 4, 2, 64, 64, 128, True, (90, 180), jnp.bfloat16),
    "bf16-noncausal-2x2": (1, 128, 4, 4, 64, 64, 64, False, None, jnp.bfloat16),
    # the span is the sequence: one grid step a q block (a kv block), 2, 4 and 8 tiles walked
    "span-2-tiles-causal": (1, 128, 2, 2, 64, 64, 64, True, None, jnp.float32),
    "span-4-tiles-causal-gqa2": (1, 256, 4, 2, 64, 64, 64, True, None, jnp.float32),
    "span-4-tiles-segments-gqa4": (2, 256, 4, 1, 64, 64, 64, True, (40, 150, 200), jnp.float32),
    "span-8-tiles-causal-gqa2": (1, 512, 4, 2, 64, 64, 64, True, None, jnp.float32),
    "span-8-tiles-segments-noncausal": (1, 512, 2, 2, 64, 64, 64, False, (100, 300), jnp.float32),
    "span-4x2-unequal-tiles-gqa2": (1, 256, 4, 2, 64, 64, 128, True, None, jnp.float32),
    "span-2x4-unequal-tiles-segments-gqa2": (1, 256, 4, 2, 64, 128, 64, True, (100, 130), jnp.float32),
    "bf16-span-4-tiles-segments-gqa4": (1, 512, 8, 2, 128, 128, 128, True, (90, 300), jnp.bfloat16),
    # the budget forced down: K/V take 1 KB a row here, a group's Q/dO n_rep x 1,152 B
    "two-spans-of-2-causal-gqa2": (1, 256, 4, 2, 64, 64, 64, True, None, jnp.float32, 128 << 10),
    "two-spans-of-2-segments-gqa2": (1, 256, 4, 2, 64, 64, 64, True, (40, 150, 200), jnp.float32,
                                     128 << 10),
    "four-spans-of-2-causal": (1, 512, 2, 2, 64, 64, 64, True, None, jnp.float32, 128 << 10),
    "kv-spans-of-4-q-spans-of-1-gqa4": (1, 512, 4, 1, 64, 64, 64, True, None, jnp.float32, 256 << 10),
    "two-spans-unequal-tiles-segments": (1, 512, 4, 2, 64, 64, 128, True, (70, 260, 400), jnp.float32,
                                         256 << 10),
    "two-spans-noncausal-gqa2": (1, 256, 4, 2, 64, 64, 64, False, None, jnp.float32, 128 << 10),
    "bf16-two-spans-segments-gqa4": (1, 512, 8, 2, 128, 128, 128, True, (90, 300), jnp.bfloat16,
                                     256 << 10),
    # the block-diffusion mask (a twelfth field: the block length; `s` is the doubled row [noised ;
    # clean], a half of it whole tiles of whole blocks): a noised q tile walks its own noised kv
    # tile and the clean ones before its last block, a clean one the clean ones up to itself
    "bd4-4-tiles-a-half": (1, 512, 2, 2, 64, 64, 64, False, None, jnp.float32, None, 4),
    "bd16-gqa8-w128": (1, 512, 8, 1, 128, 64, 64, False, None, jnp.float32, None, 16),
    "bd4-unequal-tiles-gqa2": (1, 512, 4, 2, 64, 64, 128, False, None, jnp.float32, None, 4),
    "bd4-wide-q-gqa2": (1, 512, 4, 2, 64, 128, 64, False, None, jnp.float32, None, 4),
    "bd64-a-block-a-tile": (1, 512, 2, 1, 64, 64, 64, False, None, jnp.float32, None, 64),
    "bd8-batch2-one-tile-a-half": (2, 256, 2, 2, 64, 128, 128, False, None, jnp.float32, None, 8),
    "bd4-a-half-shorter-than-a-tile": (1, 128, 2, 1, 64, 512, 512, False, None, jnp.float32, None, 4),
    "bd4-spans-of-2-gqa8": (1, 512, 8, 1, 64, 64, 64, False, None, jnp.float32, 128 << 10, 4),
    "bd16-spans-of-4-and-1-gqa4": (1, 1024, 4, 1, 64, 64, 64, False, None, jnp.float32, 256 << 10, 16),
    "bf16-bd4-the-chips-tiles-gqa8": (1, 2048, 8, 1, 128, 512, 512, False, None, jnp.bfloat16, None, 4),
}


@pytest.mark.parametrize("case", list(TILINGS))
def test_fwd_and_grads_over_tilings(case, monkeypatch):
    b, s, h, hkv, d, bq, bkv, causal, cuts, dtype, budget, bd = (*TILINGS[case], None, None)[:12]
    if budget:
        monkeypatch.setattr(fa, "SPAN_VMEM_BYTES", budget)
    t = fa._tiling(s, s, bq, bkv, d, jnp.dtype(dtype).itemsize, h // hkv)
    if budget:  # both sides' spans are whole tiles, and shorter than the sequence
        assert bkv <= t.kv_span < s and bq <= t.q_span < s, t
    elif case != "bf16-bd4-the-chips-tiles-gqa8":  # (the cell's own: K/V one span, a group of 8 query heads' Q/dO two)
        assert (t.kv_span, t.q_span) == (s, s), t
    q = rand((b, s, h, d), 0, dtype)
    k, v = rand((b, s, hkv, d), 1, dtype), rand((b, s, hkv, d), 2, dtype)
    g = rand((b, s, h, d), 3, dtype)
    seg = None if cuts is None else packed_segments(b, s, cuts)

    def run(fn, *xs, **kw):
        def loss(q, k, v):
            o = fn(q, k, v, causal=causal, segment_ids=seg, block_diffusion=bd, **kw)
            return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o
        (_, o), grads = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(*xs)  # one program, as its users run it
        return (o, *grads)

    got = run(flash_attention, q, k, v, block_q=bq, block_kv=bkv)
    names = kernel_names(jax.make_jaxpr(jax.grad(lambda q: flash_attention(
        q, k, v, causal=causal, segment_ids=seg, block_diffusion=bd, block_q=bq, block_kv=bkv).sum()))(q).jaxpr)
    assert sorted(names) == flash_names("_bd" if bd else "", one_backward=not budget), names
    want = run(attention_reference, *(x.astype(jnp.float32) for x in (q, k, v)))
    if bd:  # the mask by hand, once: query r keeps key c as the objective states it
        r, c = np.arange(s)[:, None], np.arange(s)[None, :]
        blk = lambda x: (x % (s // 2)) // bd  # noqa: E731
        kept = ((c >= s // 2) & (blk(c) < blk(r))) | (((c >= s // 2) == (r >= s // 2)) & (blk(c) == blk(r)))
        from ray_tpu.ops.attention import block_diffusion_keep
        np.testing.assert_array_equal(np.asarray(block_diffusion_keep(jnp.asarray(r), jnp.asarray(c), s // 2, bd)), kept)
        assert kept.sum() == (s // 2) * (s // 2 + bd) and not kept[s // 2:, :s // 2].any()
    # bf16: inputs, p and ds reach the MXU with 8 bits of mantissa, and the results
    # are rounded to bf16; statistics and accumulators are f32 either way
    tol = 5e-3 if dtype == jnp.float32 else 3e-2
    for name, a, ref in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == ref.shape, name
        scale = max(1.0, float(jnp.max(jnp.abs(ref))))
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale, np.asarray(ref) / scale,
                                   rtol=0, atol=tol, err_msg=f"{case}: {name}")


@pytest.mark.parametrize("s,causal,steps,computed,needed,dkv_steps", [
    # 512 x 512 tiles, Mistral's heads: K/V and a group's Q/dO of 2,048 rows fit a span
    (2048, True, 4, 10, 8.00390625, 4),
    (2048, False, 4, 16, 16.0, 4),
    # at 4,096 rows a group's Q/dO (4 heads x 1,152 B a row, two buffers) take two spans
    (4096, True, 8, 36, 32.0078125, 16),
    (4096, False, 8, 64, 64.0, 16),
])
def test_tile_counts(s, causal, steps, computed, needed, dkv_steps):
    """What the kernels' grids are built from, at the cells' shape and twice it: a
    (batch, head) of the forward and dQ kernels is one grid step a q tile where the
    tile-a-step grid made (s / 512)^2, the diagonal's tiles and those below it are
    computed, and dK/dV's figures are a kv head's with its four query heads."""
    nq = s // 512
    assert tile_counts(s, s, causal, 512, 512) == (steps, computed, needed)
    assert tile_counts(s, s, causal, 512, 512, n_rep=4, kernel="dkv") == (
        dkv_steps, 4 * computed, 4 * needed)
    assert steps == nq < nq * nq


def test_tile_counts_under_the_block_diffusion_mask():
    """The doubled row of the block-diffusion cell, [2 x 8192] in 512-row tiles, blocks of 4:
    288 tiles a head each way (2 x 136 + 16) where the kept scores are 8192 x 8196 = 256.1
    tiles' worth, the triangle over the doubled row computes 528 and a dense walk 1,024; K/V
    are one span (a grid step a q tile), a group of 8 query heads' Q/dO spans of 1,024 rows."""
    s = 16384
    for kernel in ("fwd", "dq"):
        assert tile_counts(s, s, False, 512, 512, kernel=kernel, block_diffusion=4) == (32, 288, 8192 * 8196 / 512**2)
    assert tile_counts(s, s, False, 512, 512, n_rep=8, kernel="dkv", block_diffusion=4) == (
        32 * 16, 8 * 288, 8 * 8192 * 8196 / 512**2)
    assert tile_counts(s, s, True, 512, 512).tiles_computed == 528 and tile_counts(s, s, False, 512, 512).tiles_computed == 1024
    with pytest.raises(ValueError, match="block_diffusion 3"):
        tile_counts(s, s, False, 512, 512, block_diffusion=3)  # no power of two
    with pytest.raises(ValueError, match="block_diffusion 4"):
        tile_counts(s, s, True, 512, 512, block_diffusion=4)  # not beside `causal`
    assert fa.supports(s, s, 128, block_diffusion=4) and not fa.supports(s, s, 128, block_diffusion=1024)
    assert not fa.supports(1000, 1000, 128, block_diffusion=4) and fa.supports(96, 96, 128, block_diffusion=4)


@pytest.mark.parametrize("half,bq,bkv,block,span", [
    (8192, 512, 512, 4, 32), (2048, 512, 512, 512, 8), (1024, 128, 256, 8, 2), (1024, 256, 128, 128, 4),
    (512, 64, 64, 64, 1), (512, 64, 64, 2, 3)])
def test_the_block_diffusion_walk_computes_every_needed_tile_once(half, bq, bkv, block, span):
    """The kernels' own ranges, steps and index maps by brute force over the doubled row: a
    tile is walked iff the mask keeps one of its scores, once, by the grid step whose span
    holds it; a step whose span holds none of its tile's ranges fetches the nearest span that
    does. Forward and dQ (a q tile's kv tiles, spans of `span` kv tiles) and dK/dV (a kv
    tile's q tiles, the same spans of q tiles)."""
    from ray_tpu.ops.attention import block_diffusion_keep

    bd = fa.BlockDiffusion(block, half)
    r, c = np.arange(2 * half)[:, None], np.arange(2 * half)[None, :]
    kept = np.asarray(block_diffusion_keep(jnp.asarray(r), jnp.asarray(c), half, block))
    nq, nk = 2 * half // bq, 2 * half // bkv
    needed = kept.reshape(nq, bq, nk, bkv).any(axis=(1, 3))  # [q tile, kv tile]
    assert tile_counts(2 * half, 2 * half, False, bq, bkv, block_diffusion=block).tiles_computed == needed.sum()
    assert tile_counts(2 * half, 2 * half, False, bq, bkv, block_diffusion=block).tiles_needed == kept.sum() / (bq * bkv)
    for major, n_own, n_other in (("q", nq, nk), ("kv", nk, nq)):
        if n_other % span:
            continue
        walked = np.zeros((n_own, n_other), int)
        for own in range(n_own):
            ranges = fa._bd_kv_ranges(own, bq, bkv, bd) if major == "q" else fa._bd_q_ranges(own, bq, bkv, bd)
            spans_used = {t // span for lo, hi in ranges for t in range(lo, hi)}
            first = (ranges[0] if major == "q" else tuple(a + b for a, b in zip(ranges[0], ranges[1])))
            for step in range(n_other // span):
                for lo, hi in ranges:
                    for t in range(int(np.clip(lo - step * span, 0, span)), int(np.clip(hi - step * span, 0, span))):
                        walked[own, step * span + t] += 1
                fetched = int(fa._nearest_span(step, span, first, ranges[-1]))
                assert fetched in spans_used and (fetched == step) == (step in spans_used), (major, own, step, fetched)
        np.testing.assert_array_equal(walked, needed if major == "q" else needed.T)



def test_span_is_derived():
    """Whole compute tiles, a divisor of the sequence, inside the budget."""
    assert fa._span(4096, 512, 1024) == 4096
    assert fa._span(4096, 512, 1024, budget=3 << 20) == 2048  # 3,072 rows fit; 6 tiles do not divide 8
    assert fa._span(4096, 512, 1024, budget=1) == 512  # never less than the compute tile
    assert fa._span(200, 200, 1 << 30) == 200


def test_products_take_the_inputs_dtype():
    """Every matrix product of the kernels is fed the call's own dtype and accumulates in
    f32; nothing else in the kernels is bf16 (but the transposed dS, which is the fifth
    product's operand). The backward kernel makes five products a tile: S, dV, dP, dK, dQ."""
    q = rand((1, 128, 4, 64), 0, jnp.bfloat16)
    k = rand((1, 128, 2, 64), 1, jnp.bfloat16)

    def loss(q, k, v):
        return flash_attention(q, k, v, causal=True, block_q=64, block_kv=64).astype(
            jnp.float32).sum()

    def dots(jaxpr, found):
        for eqn in jaxpr.eqns:
            if eqn.primitive.name == "dot_general":
                found.append((tuple(v.aval.dtype for v in eqn.invars), eqn.outvars[0].aval.dtype))
            for sub in jax.core.jaxprs_in_params(eqn.params):
                dots(sub, found)
        return found

    found = dots(jax.make_jaxpr(jax.grad(loss, argnums=(0, 1, 2)))(q, k, k).jaxpr, [])
    assert len(found) == 2 + 5  # forward; backward (the two kernels' 3 + 4 before PR 53)
    for ins, out in found:
        assert ins == (jnp.bfloat16, jnp.bfloat16) and out == jnp.float32, (ins, out)


# ------------------------------------------------ the rotate-and-lay-out kernel (PR 30)

def _positions(kind, b, s):
    """offset: every row starts at its own offset and strides by two (RoPE is shift-
    invariant under attention, not here: the kernel's output is the rotated q itself);
    restart: documents packed into a row, each counting from zero, cut differently a row;
    shared: one row of positions for the whole batch."""
    if kind == "shared":
        return (jnp.arange(s, dtype=jnp.int32) * 3 + 7)[None, :]
    if kind == "offset":
        return (jnp.arange(s, dtype=jnp.int32)[None, :] * 2
                + 1000 * (1 + jnp.arange(b, dtype=jnp.int32))[:, None])
    cuts = [max(1, s // 3) + r for r in range(b)]
    return jnp.stack([jnp.where(jnp.arange(s) < c, jnp.arange(s), jnp.arange(s) - c)
                      for c in cuts]).astype(jnp.int32)


@pytest.mark.parametrize("positions", ["offset", "restart", "shared"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["f32", "bf16"])
@pytest.mark.parametrize("h,hkv", [(2, 2), (4, 1)], ids=["mha", "gqa4"])
@pytest.mark.parametrize("s", [8, 64, 512, 1536])
def test_rope_kernel_is_rope_then_transpose(s, h, hkv, dtype, positions):
    """`rope_to_heads` against models/llama.py:rope followed by the transpose the flash
    kernels' layout wants: values, and both gradients under a random cotangent. Both
    compute in f32 and round once, so they differ by a rounding of the last place at
    most (a fused multiply-add on one side)."""
    from ray_tpu.models.llama import rope

    b, d, theta = 2, 128, 1e4
    q, k = rand((b, s, h, d), 0, dtype), rand((b, s, hkv, d), 1, dtype)
    cts = rand((b, h, s, d), 2, dtype), rand((b, hkv, s, d), 3, dtype)
    pos = _positions(positions, b, s)

    def want(q, k):
        return tuple(rope(x, pos, theta).transpose(0, 2, 1, 3) for x in (q, k))

    got, got_vjp = jax.vjp(lambda q, k: fa.rope_to_heads(q, k, pos, theta), q, k)
    ref, ref_vjp = jax.vjp(want, q, k)
    ulp = 2.0 ** (-7 if dtype == jnp.bfloat16 else -22)
    for name, a, r in zip(("q", "k", "dq", "dk"), got + got_vjp(cts), ref + ref_vjp(cts)):
        assert a.dtype == dtype and a.shape == r.shape, name
        np.testing.assert_allclose(np.asarray(a, np.float32), np.asarray(r, np.float32),
                                   rtol=ulp, atol=ulp, err_msg=name)


def test_rope_rows_are_derived():
    """A grid step moves as many positions as fit the budget in whole blocks: 256 of
    Mistral's 40 heads in bf16 (10.5 MB with both buffers), the whole of a short or odd
    sequence, fewer of f32."""
    row = 2 * 40 * 128 * 2 + 2 * 128 * 4  # q and k in and out, the two angle tiles
    assert fa._rope_rows(2048, row) == 256 and fa._rope_rows(1536, row) == 384
    assert fa._rope_rows(200, row) == 200 and fa._rope_rows(8, row) == 8
    assert fa._rope_rows(2048, 2 * row) == 128


@pytest.mark.parametrize("segments", [False, True], ids=["plain", "packed"])
def test_flash_attention_rotates_in_front(segments):
    """flash_attention(rope=...) on un-rotated q and k is flash_attention on rotated
    ones: output and the three gradients."""
    from ray_tpu.models.llama import rope

    b, s, h, hkv, d, theta = 2, 128, 4, 2, 128, 1e4
    q, k, v = rand((b, s, h, d), 0), rand((b, s, hkv, d), 1), rand((b, s, hkv, d), 2)
    g = rand((b, s, h, d), 3)
    seg = packed_segments(b, s, (50,)) if segments else None
    pos = _positions("restart" if segments else "offset", b, s)

    def run(rotate_in_kernel):
        def loss(q, k, v):
            if rotate_in_kernel:
                o = flash_attention(q, k, v, causal=True, segment_ids=seg, rope=(pos, theta),
                                    block_q=64, block_kv=64)
            else:
                o = flash_attention(rope(q, pos, theta), rope(k, pos, theta), v, causal=True,
                                    segment_ids=seg, block_q=64, block_kv=64)
            return jnp.sum(o * g), o
        (_, o), grads = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)(q, k, v)
        return (o, *grads)

    for name, a, r in zip(("out", "dq", "dk", "dv"), run(True), run(False)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(r), rtol=1e-5, atol=1e-5, err_msg=name)


# ------------------------------- the forward kernel's results kept under remat `full` (PR 43)

def _attention_block(seg):
    """A layer's attention part in small: three projections, the flash kernels, the output
    projection. x [B, S, M], w a dict of the four weights."""
    def block(x, w):
        q, k, v = (jnp.einsum("bsm,mhd->bshd", x, w[n]) for n in ("q", "k", "v"))
        o = flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=64, block_kv=64)
        return jnp.einsum("bshd,hdm->bsm", o, w["o"])
    return block


def _remat_cfg(policy):
    import dataclasses

    from ray_tpu.models import get_config

    return dataclasses.replace(get_config("test-tiny"), remat=True, remat_policy=policy)


@pytest.mark.parametrize("segments", [False, True], ids=["plain", "packed"])
@pytest.mark.parametrize("d", [128, 256, 64], ids=["w128", "w256", "w64-padded"])
def test_a_block_under_remat_full_is_the_block_bit_for_bit(d, segments):
    """Value and every gradient of an attention block rematerialised under `full` (which
    keeps the forward kernel's `out` and logsumexp by name, so the backward kernels read
    what the forward pass wrote) are bit-equal to the same block without remat, which
    hands the backward rule those arrays as plain residuals; the rematerialised program
    holds the forward kernel once, where under `dots` (which keeps neither) it holds it
    twice."""
    from ray_tpu.models import llama

    b, s, m, h, hkv = 2, 128, 64, 4, 2
    x = rand((b, s, m), 0, jnp.bfloat16)
    w = {n: rand((m, heads, d), i + 1, jnp.bfloat16) * m ** -0.5
         for i, (n, heads) in enumerate((("q", h), ("k", hkv), ("v", hkv)))}
    w["o"] = rand((h, d, m), 4, jnp.bfloat16) * (h * d) ** -0.5
    g = rand((b, s, m), 5, jnp.bfloat16)
    block = _attention_block(packed_segments(b, s, (50,)) if segments else None)

    def run(body):
        def loss(x, w):
            y = body(x, w)
            return jnp.sum((y * g).astype(jnp.float32)), y
        fn = jax.value_and_grad(loss, argnums=(0, 1), has_aux=True)
        return jax.jit(fn)(x, w), kernel_names(jax.make_jaxpr(fn)(x, w).jaxpr)

    plain, names = run(block)
    assert sorted(names) == flash_names()
    full, names_full = run(llama._maybe_remat(block, _remat_cfg("full")))
    assert sorted(names_full) == sorted(names), names_full  # the forward kernel once
    for a, r in zip(jax.tree.leaves(full), jax.tree.leaves(plain)):
        assert a.dtype == r.dtype and np.array_equal(np.asarray(a, np.float32), np.asarray(r, np.float32))
    _, names_dots = run(llama._maybe_remat(block, _remat_cfg("dots")))
    assert names_dots.count("flash_attention_fwd") == 2, names_dots


@pytest.mark.parametrize("policy,named", [("full", True), ("dots", False), ("dots_no_batch", False)])
def test_remat_full_alone_keeps_the_kernels_results(policy, named):
    """What a rematerialised attention block keeps by `FLASH_NAMES`: under `full` the
    forward kernel's output [B, H, S, D] (the padded 128 lanes at width 64: the pad and
    the cut lie outside the rule) and its logsumexp; under the `dots` policies neither."""
    from jax._src.ad_checkpoint import saved_residuals

    from ray_tpu.models import llama
    from ray_tpu.ops.attention import FLASH_NAMES

    b, s, m, h, hkv, d = 2, 128, 64, 4, 2, 64
    x = jnp.zeros((b, s, m), jnp.bfloat16)
    w = {n: jnp.zeros((m, heads, d), jnp.bfloat16) for n, heads in (("q", h), ("k", hkv), ("v", hkv))}
    w["o"] = jnp.zeros((h, d, m), jnp.bfloat16)
    body = llama._maybe_remat(_attention_block(None), _remat_cfg(policy))
    kept = {why.split("'")[1]: aval for aval, why in saved_residuals(body, x, w) if why.startswith("named")}
    assert set(kept) == (set(FLASH_NAMES) if named else set()), kept
    if named:
        out, lse = (kept[name] for name in FLASH_NAMES)
        assert out.shape == (b, h, s, 128) and lse.shape[:2] == (b, h) and lse.size >= b * h * s, kept
        assert (out.dtype, lse.dtype) == (jnp.uint16, jnp.uint32)  # their bits: the policy rounds nothing


# ------------------------------------------------- the kernels at the families' head widths: 64 on padded lanes, 256

@pytest.mark.usefixtures("highest")
def test_the_flash_kernels_run_at_head_width_64_on_padded_lanes():
    """[1, 256, 4 / 1, 64] through Pallas' interpreter in tiles of 128: forward, dQ and
    dK-dV of the SAME three kernels (by name), causal, a group of 4, against the plain
    softmax; `supports` says 64 and the multiples of 128 and nothing between."""
    from ray_tpu.models import llama
    from ray_tpu.ops.attention import Rotation, attention

    assert fa.supports(8192, 8192, 64) and fa.supports(8192, 8192, 128) and fa.supports(8192, 8192, 256)
    assert not fa.supports(8192, 8192, 96) and not fa.supports(8192, 8192, 32) and not fa.supports(8191, 8191, 64)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 256, 4, 64), jnp.float32)
    k, v = (jax.random.normal(key, (1, 256, 1, 64), jnp.float32) for key in ks[1:3])
    w = jax.random.normal(ks[3], (1, 256, 4, 64), jnp.float32)

    def flash(q, k, v):
        return jnp.sum(w * fa.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128))

    def plain(q, k, v):
        return jnp.sum(w * attention_reference(q, k, v, causal=True))

    jaxpr = str(jax.make_jaxpr(jax.grad(flash, argnums=(0, 1, 2)))(q, k, v))
    for name in flash_names():
        assert f"name={name} " in jaxpr or f"name={name}\n" in jaxpr, name
    out = fa.flash_attention(q, k, v, causal=True, block_q=128, block_kv=128)
    assert out.shape == q.shape
    np.testing.assert_allclose(out, attention_reference(q, k, v, causal=True), atol=2e-5)
    for mine, want in zip(jax.grad(flash, argnums=(0, 1, 2))(q, k, v), jax.grad(plain, argnums=(0, 1, 2))(q, k, v)):
        assert mine.shape == want.shape
        np.testing.assert_allclose(mine, want, atol=3e-5 * float(jnp.abs(want).max()))
    # the rotation at 64 is the caller's jax.numpy statement, in front of the kernels
    with pytest.raises(NotImplementedError, match="head width 64"):
        fa.flash_attention(q, k, v, rope=(jnp.arange(256)[None], 1e6))
    rotated = attention(q, k, v, impl="pallas", rotation=Rotation(jnp.arange(256)[None], 1e6, llama.rope))
    want = attention_reference(*(llama.rope(a, jnp.arange(256)[None], 1e6) for a in (q, k)), v, causal=True)
    np.testing.assert_allclose(rotated, want, atol=2e-5)


@pytest.mark.usefixtures("highest")
@pytest.mark.parametrize("group,packed", [(1, False), (2, True)], ids=["mha-one-document", "gqa2-packed"])
def test_the_flash_kernels_run_q_and_k_192_wide_beside_v_128_wide(group, packed, monkeypatch):
    """[1, 256, 4 / (4 or 2), 192 | 128] through Pallas' interpreter in tiles of 128 (latent attention
    without a q latent: Kimi Linear's heads): the SAME kernels by name, causal, with and without
    segment ids, forward and dq, dk, dv against the plain softmax scaled by 192^-1/2; the output and dv
    come back 128 wide, dq and dk 192; and the two kernels that run where K and V are longer than a span
    say the same. `supports` says which pairs tile: each width whole halves of a vreg."""
    from ray_tpu.ops.attention import attention

    assert fa.supports(8192, 8192, 192, v_head_dim=128) and fa.supports(8192, 8192, 192) and fa.supports(8192, 8192, 128, v_head_dim=64)
    assert not fa.supports(8192, 8192, 192, v_head_dim=96) and not fa.supports(8192, 8192, 96, v_head_dim=128)
    assert not fa.supports(8191, 8191, 192, v_head_dim=128)
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (1, 256, 4, 192), jnp.float32)
    k = jax.random.normal(ks[1], (1, 256, 4 // group, 192), jnp.float32)
    v = jax.random.normal(ks[2], (1, 256, 4 // group, 128), jnp.float32)
    w = jax.random.normal(ks[3], (1, 256, 4, 128), jnp.float32)
    seg = packed_segments(1, 256, (70, 150, 201)) if packed else None

    def flash(q, k, v):
        return jnp.sum(w * fa.flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=128, block_kv=128))

    def plain(q, k, v):
        return jnp.sum(w * attention_reference(q, k, v, causal=True, segment_ids=seg))

    names = kernel_names(jax.make_jaxpr(jax.grad(flash, argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert sorted(names) == flash_names(), names
    out = fa.flash_attention(q, k, v, causal=True, segment_ids=seg, block_q=128, block_kv=128)
    assert out.shape == (1, 256, 4, 128)
    want = attention_reference(q, k, v, causal=True, segment_ids=seg)
    np.testing.assert_allclose(out, want, atol=2e-5)
    np.testing.assert_allclose(attention(q, k, v, causal=True, segment_ids=seg, impl="pallas"), want, atol=2e-5)
    np.testing.assert_allclose(attention(q, k, v, causal=True, segment_ids=seg, impl="chunked"), want, atol=2e-5)
    # the scale is q's and k's width's, not the padded lanes' and not v's
    scaled = attention_reference(q, k, v, causal=True, segment_ids=seg, scale=192 ** -0.5)
    np.testing.assert_array_equal(np.asarray(want), np.asarray(scaled))
    one = jax.grad(flash, argnums=(0, 1, 2))(q, k, v)
    for mine, ref in zip(one, jax.grad(plain, argnums=(0, 1, 2))(q, k, v)):
        assert mine.shape == ref.shape
        np.testing.assert_allclose(mine, ref, atol=3e-5 * float(jnp.abs(ref).max()))
    with pytest.raises(NotImplementedError, match="head width 192"):
        fa.flash_attention(q, k, v, rope=(jnp.arange(256)[None], 1e6))
    # K and V (256 + 128 lanes) in two spans of a tile each: dQ's and dK/dV's kernels, the same gradients
    t = fa._tiling(256, 256, 128, 128, 256, 4, group, 128)
    assert fa._fuses(t, 256) and (t.kv_span, t.q_span) == (256, 256)
    monkeypatch.setattr(fa, "SPAN_VMEM_BYTES", 128 * 2 * (256 + 128) * 4)  # a tile of K and V, both pipeline buffers
    assert not fa._fuses(fa._tiling(256, 256, 128, 128, 256, 4, group, 128), 256)
    names = kernel_names(jax.make_jaxpr(jax.grad(flash, argnums=(0, 1, 2)))(q, k, v).jaxpr)
    assert sorted(names) == flash_names(one_backward=False), names
    for mine, ref in zip(jax.grad(flash, argnums=(0, 1, 2))(q, k, v), one):
        np.testing.assert_allclose(mine, ref, atol=2e-5 * float(jnp.abs(ref).max()))


def test_flash_kernels_tile_width_256():
    assert fa.supports(8192, 8192, 256) and not fa.supports(8191, 8191, 256)
    assert not fa.supports(8192, 8192, 160)
    # K and V of one head at 8,192 x 256 bf16 are the span budget, exactly: one span
    t = fa._tiling(8192, 8192, 512, 512, 256, 2)
    assert (t.kv_span, t.q_span) == (8192, 4096)
    fwd = fa.tile_counts(8192, 8192, True, 512, 512, head_dim=256)
    assert (fwd.grid_steps, fwd.tiles_computed) == (16, 136) and fwd.tiles_needed == 128.015625
    dkv = fa.tile_counts(8192, 8192, True, 512, 512, head_dim=256, kernel="dkv")
    assert (dkv.grid_steps, dkv.tiles_computed) == (32, 136)
    # beside v 128 wide (q and k of 192 on their 256 lanes) K and V are three quarters of the budget: one span,
    # and a kv head's group of up to 5 query heads' Q and dO are one span too
    t = fa._tiling(8192, 8192, 512, 512, 256, 2, dv=128)
    assert (t.kv_span, t.q_span) == (8192, 8192) and fa._fuses(t, 8192)
    assert fa.tile_counts(8192, 8192, True, 512, 512, head_dim=256, v_head_dim=128) == fwd


@pytest.mark.usefixtures("highest")
@pytest.mark.parametrize("s", [64, 256])
def test_flash_attention_at_unequal_head_parts(s):
    """The kernels (interpreted here) at a head twice the lane width, fed as the latent
    projections feed them: against the reference attention, values and gradients."""
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q, k, v, g = (jax.random.normal(x, (1, s, 2, 256), jnp.float32) for x in ks)

    def run(fn):
        return jax.value_and_grad(
            lambda q, k, v: jnp.sum(fn(q, k, v, causal=True) * g), argnums=(0, 1, 2))(q, k, v)

    (l1, g1), (l2, g2) = run(flash_attention), run(attention_reference)
    np.testing.assert_allclose(l1, l2, rtol=1e-5)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(a, b, atol=2e-4)
