"""A sliding window in the flash kernels, in Pallas' interpreter on the CPU: key j is kept for
query i where 0 <= i - j < window. The windowed kernels against the plain softmax with both edges in
its mask, the band on every path, `tile_counts` and the kernels' own walk against a brute-force
count. (The triangle, the block-diffusion mask and the rotation: tests/test_flash_attention.py; what the
files share: tests/flash_cases.py.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from flash_cases import flash_names, kernel_names, packed_segments, rand
from ray_tpu.ops import flash_attention as fa
from ray_tpu.ops.attention import attention_reference
from ray_tpu.ops.flash_attention import flash_attention, tile_counts


# Tiles of 64 here where the chip's are 512: a window of 256 over 4 tiles is then what 2,048 is
# over 512-row tiles; one case runs the chip's own 512 x 512 tiles under a window of 2,048. Tiles
# of 64 are computed whole (`_edge_rows`); the `pieces-` cases (PR 49) run the chip's tiles, whose
# two edge tiles are cut in 256-row pieces, or small tiles cut in the pieces the case names.
WINDOWS = {
    # name: (s, h, hkv, d, block, window, segment cuts, rotate, dtype[, span budget[, rows of a piece]])
    "shorter-than-a-tile": (512, 2, 2, 64, 64, 24, None, False, jnp.float32),
    "a-tile": (512, 2, 1, 64, 64, 64, None, False, jnp.float32),
    "four-tiles-gqa8-w128": (512, 8, 1, 128, 64, 256, None, False, jnp.float32),
    "not-a-multiple-of-a-tile-gqa4": (512, 4, 1, 64, 64, 200, None, False, jnp.float32),
    "one-less-than-two-tiles": (512, 2, 2, 64, 64, 127, None, False, jnp.float32),
    "as-long-as-the-sequence": (256, 2, 1, 64, 64, 256, None, False, jnp.float32),
    "segments-gqa2": (512, 4, 2, 64, 64, 200, (90, 300, 310), False, jnp.float32),
    "segments-w128-shorter-than-a-tile": (512, 2, 1, 128, 64, 40, (100, 260), False, jnp.float32),
    "rotated-w128-gqa4": (512, 4, 1, 128, 64, 200, None, True, jnp.float32),
    "rotated-segments-w128": (512, 2, 2, 128, 64, 256, (70, 400), True, jnp.float32),
    "bf16-rotated-w128-gqa8": (512, 8, 1, 128, 128, 256, None, True, jnp.bfloat16),
    "bf16-segments-gqa2": (512, 4, 2, 64, 64, 100, (200,), False, jnp.bfloat16),
    # spans shorter than the sequence: a span wholly outside the band names one inside it
    "spans-of-2-gqa2": (512, 4, 2, 64, 64, 100, None, False, jnp.float32, 128 << 10),
    "spans-of-2-segments-not-a-multiple": (512, 2, 2, 64, 64, 200, (150, 333), False, jnp.float32, 128 << 10),
    "pieces-the-chips-tiles-2048": (4096, 1, 1, 128, 512, 2048, None, False, jnp.float32),
    # a row on each side of every piece's boundary: one, two and five tiles; windows that are and are not
    # whole pieces; a group of 1 and of 8 query heads
    "pieces-five-tiles-w2048-gqa8": (2560, 8, 1, 128, 512, 2048, None, False, jnp.float32),
    "pieces-five-tiles-w1536": (2560, 2, 2, 128, 512, 1536, None, False, jnp.float32),
    "pieces-five-tiles-w640-segments-gqa2": (2560, 2, 1, 128, 512, 640, (700, 1500, 1537), False, jnp.float32),
    "pieces-two-tiles-w640-gqa8": (1024, 8, 1, 128, 512, 640, None, False, jnp.float32),
    "pieces-two-tiles-w257-bf16-rotated": (1024, 2, 2, 128, 512, 257, None, True, jnp.bfloat16),
    "pieces-one-tile-w200": (512, 2, 2, 128, 512, 200, None, False, jnp.float32),
    "pieces-one-tile-w300-segments-gqa8": (512, 8, 1, 128, 512, 300, (255, 257), False, jnp.float32),
    # spans shorter than the sequence (K/V two tiles, Q/dO one): the edge tiles lie in the spans' own counts
    "pieces-spans-w700-gqa2": (3072, 2, 1, 128, 512, 700, None, False, jnp.float32, 2 << 20),
    "pieces-spans-w1024-segments": (3072, 2, 2, 128, 512, 1024, (1000, 2049), False, jnp.float32, 2 << 20),
    # small tiles in halves and in quarters
    "pieces-of-32-w200-gqa4": (512, 4, 1, 64, 64, 200, None, False, jnp.float32, None, 32),
    "pieces-of-16-w256-segments-gqa2": (512, 4, 2, 64, 64, 256, (90, 300, 310), False, jnp.float32, None, 16),
    "pieces-of-16-w1": (256, 2, 2, 64, 64, 1, None, False, jnp.float32, None, 16),
    "pieces-of-16-spans-w100-gqa2": (512, 4, 2, 64, 64, 100, None, False, jnp.float32, 128 << 10, 16),
    "pieces-of-128-w2048-rotated-gqa2": (2560, 2, 1, 128, 512, 2048, None, True, jnp.float32, None, 128),
}


@pytest.mark.parametrize("case", list(WINDOWS))
def test_windowed_fwd_and_grads_match_the_reference(case, monkeypatch):
    """The three windowed kernels (by name) in the interpreter against the plain softmax
    with both edges in its mask: the output and all three gradients under a random
    cotangent, one jitted program as its users run it."""
    from ray_tpu.models.llama import rope

    s, h, hkv, d, block, window, cuts, rotate, dtype, budget, piece = (*WINDOWS[case], None, None)[:11]
    if piece:
        monkeypatch.setattr(fa, "EDGE_PIECE", piece)
    assert (fa._edge_rows(block, block, window) is not None) == case.startswith("pieces-"), case
    if budget:
        monkeypatch.setattr(fa, "SPAN_VMEM_BYTES", budget)
        t = fa._tiling(s, s, block, block, d, jnp.dtype(dtype).itemsize, h // hkv)
        assert t.kv_span < s and t.q_span < s, t
    b, theta = 2, 1e4
    q, k, v, g = (rand((b, s, heads, d), i, dtype) for i, heads in enumerate((h, hkv, hkv, h)))
    seg = None if cuts is None else packed_segments(b, s, cuts)
    pos = jnp.arange(s, dtype=jnp.int32)[None] * 2 + 5

    def run(fn, *xs, **kw):
        def loss(q, k, v):
            o = fn(q, k, v, causal=True, segment_ids=seg, window=window, **kw)
            return jnp.sum(o.astype(jnp.float32) * g.astype(jnp.float32)), o
        fn_ = jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True)
        (_, o), grads = jax.jit(fn_)(*xs)
        return (o, *grads), kernel_names(jax.make_jaxpr(fn_)(*xs).jaxpr)

    got, names = run(flash_attention, q, k, v, block_q=block, block_kv=block,
                     rope=(pos, theta) if rotate else None)
    suffix = "" if window >= s else "_window"
    assert sorted(n for n in names if n.startswith("flash")) == flash_names(suffix, one_backward=not budget)

    def plain(q, k, v, **kw):
        if rotate:
            q, k = rope(q, pos, theta), rope(k, pos, theta)
        return attention_reference(q, k, v, **kw)

    want, _ = run(plain, *(x.astype(jnp.float32) for x in (q, k, v)))
    tol = 5e-3 if dtype == jnp.float32 else 3e-2
    for name, a, ref in zip(("out", "dq", "dk", "dv"), got, want):
        assert a.dtype == dtype and a.shape == ref.shape, name
        scale = max(1.0, float(jnp.max(jnp.abs(ref))))
        np.testing.assert_allclose(np.asarray(a, np.float32) / scale, np.asarray(ref) / scale,
                                   rtol=0, atol=tol, err_msg=f"{case}: {name}")


def test_the_window_is_a_band_on_every_path_and_none_is_todays_program():
    """`attention_reference` and `attention_chunked` mask both edges (by hand here: a row
    of the band's width); without a window, or with one no shorter than the sequence, the
    Pallas path traces the program it always traced; what cannot take a window says so."""
    from ray_tpu.ops.attention import attention, attention_chunked

    b, s, h, d, window = 1, 96, 2, 16, 20
    q, k, v = (rand((b, s, h, d), i) for i in range(3))
    i, j = np.arange(s)[:, None], np.arange(s)[None, :]
    band = (j <= i) & (i - j < window)
    assert band.sum(-1).max() == window and band[5].sum() == 6
    scores = np.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    probs = jax.nn.softmax(jnp.where(band, scores, -jnp.inf), axis=-1)
    want = np.einsum("bhqk,bkhd->bqhd", probs, v)
    np.testing.assert_allclose(attention_reference(q, k, v, window=window), want, atol=2e-6)
    np.testing.assert_allclose(attention_chunked(q, k, v, window=window, block_kv=32), want, atol=2e-6)
    np.testing.assert_allclose(attention(q, k, v, window=window, impl="reference"), want, atol=2e-6)

    def text(**kw):
        fn = jax.grad(lambda q, k, v: flash_attention(q, k, v, block_q=32, block_kv=32, **kw).sum(), argnums=(0, 1, 2))
        return str(jax.make_jaxpr(fn)(q, k, v))

    assert text() == text(window=None) == text(window=s) == text(window=10 * s)
    assert text(window=window) != text() and "flash_attention_fwd_window" in text(window=window)
    with pytest.raises(ValueError, match="a causal band"):
        flash_attention(q, k, v, causal=False, window=window)
    with pytest.raises(NotImplementedError, match="window under a KV cache"):
        attention(q, k, v, window=window, q_offset=0)


def _pieces_kept(block, rows, ahead0, window):
    """Of the tile whose corner score has q position - kv position = `ahead0`: the share that
    lies in `rows` x `rows` pieces holding a kept score, counted piece by piece."""
    at = np.arange(0, block, rows)
    most, least = ahead0 + at[:, None] + rows - 1 - at[None, :], ahead0 + at[:, None] - at[None, :] - (rows - 1)
    return float(((most >= 0) & (least < window)).sum()) * rows**2 / block**2


@pytest.mark.parametrize("s,block,window,n_rep", [
    (16384, 512, 2048, 8), (8192, 512, 2048, 8), (4096, 512, 1000, 4), (2048, 512, 100, 1),
    (1024, 256, 512, 2), (2048, 512, 513, 1), (2048, 512, 2048, 4), (2048, 512, 4096, 4),
    (2560, 512, 1536, 1), (2560, 512, 640, 8), (1024, 512, 257, 8), (512, 512, 200, 1), (4096, 512, 1, 2)])
def test_tile_counts_count_the_band(s, block, window, n_rep):
    """`tile_counts(..., window=)` against a count by brute force over the tiles of the
    (q, kv) plane: a tile is computed if any of its scores is kept, and by the backward
    kernels the diagonal's tile and the one the window's far edge crosses (`depth` tiles
    below it) in the 256-row pieces that hold one. With 512 x 512 tiles and a window of
    2,048 a q tile meets 5 kv tiles forward and 4.5 backward (5 before PR 49) where the band
    needs 4.0; the grids count the spans a band reaches."""
    n = s // block
    first, last = np.arange(n) * block, np.arange(n) * block + block - 1
    # some (i, j) of the tile has 0 <= i - j < window: the largest i - j >= 0, the smallest < window
    whole = (last[:, None] - first[None, :] >= 0) & (first[:, None] - last[None, :] < window)
    rows = fa._edge_rows(block, block, window if window < s else None)
    depth = (window + block - 2) // block
    tiles = computed = float(whole.sum())
    if rows is not None:
        assert rows == fa.EDGE_PIECE == 256 and block == 512
        for qi, kj in zip(*np.nonzero(whole)):
            if qi - kj in (0, depth):
                computed += _pieces_kept(block, rows, (qi - kj) * block, window) - 1
    i = np.arange(s)
    needed = float(np.minimum(i + 1, window).sum()) / block**2
    fwd, dq, dkv = (tile_counts(s, s, True, block, block, window=window, n_rep=n_rep, kernel=kernel)
                    for kernel in ("fwd", "dq", "dkv"))
    assert (fwd.tiles_computed, fwd.tiles_needed) == (tiles, needed)
    assert (dq.tiles_computed, dq.tiles_needed, dq.grid_steps) == (computed, needed, fwd.grid_steps)
    assert (dkv.tiles_computed, dkv.tiles_needed) == (n_rep * computed, n_rep * needed)
    full, full_dkv = (tile_counts(s, s, True, block, block, n_rep=n_rep, kernel=kernel) for kernel in ("fwd", "dkv"))
    # K and V are one span at these lengths; a kv tile's band ends `depth` q tiles on, in spans of `q_span`
    t = fa._tiling(s, s, block, block, 128, 2, n_rep)
    assert t.kv_span == s and fwd.grid_steps == full.grid_steps == n
    if window >= s:
        assert fwd == tile_counts(s, s, True, block, block) and dkv == full_dkv
    else:
        per = t.q_span // block
        spans = max(min(kj + depth, n - 1) // per - kj // per + 1 for kj in range(n))
        assert dkv.grid_steps == n * spans <= full_dkv.grid_steps == n * (s // t.q_span)
    if (s, window) == (16384, 2048):  # the cell's: 5 and 4.5 tiles a q tile where it needs 4.0 (all fewer at the start)
        assert tiles == 5 * 32 - 10 and computed == 4.5 * 32 - 9 == 135 and needed == 4.0 * 32 - 8 + 1 / 256
        assert needed / full.tiles_needed == pytest.approx(0.2344, abs=1e-3)
        assert (t.q_span, dkv.grid_steps, full_dkv.grid_steps) == (1024, 32 * 3, 32 * 16)


@pytest.mark.parametrize("block,rows", [(64, 32), (64, 16), (32, 8), (48, 16), (64, 64)])
def test_a_bands_walk_computes_every_kept_score_once(block, rows, monkeypatch):
    """The three kernels' own walk (`_kv_band` whole and in pieces, `_q_band`, `_band_steps`,
    the grids' lengths) over every grid step, for every window up to three tiles and a bit,
    sequences of one to eight tiles, spans of the whole sequence and of one tile: every kept
    score lies in exactly one computed tile or piece, no step names a tile outside the
    sequence, dQ and dK/dV compute the same area, and `tile_counts` reports each."""
    monkeypatch.setattr(fa, "EDGE_PIECE", rows)
    for s, budget in [(m * block, b) for m in (1, 2, 5, 8) for b in (fa.SPAN_VMEM_BYTES, 1 << 10)]:
        monkeypatch.setattr(fa, "SPAN_VMEM_BYTES", budget)
        t = fa._tiling(s, s, block, block, 128, 2, 2)
        n = s // block
        i, j = np.arange(s)[:, None], np.arange(s)[None, :]
        for window in range(1, min(s, 3 * block + 5)):
            assert (fa._edge_rows(block, block, window) is not None) == (rows < block)
            areas = {}
            for kernel in ("fwd", "dq", "dkv"):
                kv_major = kernel == "dkv"
                span = (t.q_span if kv_major else t.kv_span) // block
                steps = (fa._q_spans if kv_major else fa._kv_spans)(s, s, t, window)
                hits, area = np.zeros((s, s), np.int32), 0  # [q position, kv position]
                for own in range(n):
                    band = (fa._q_band(own, n, block, block, window) if kv_major
                            else fa._kv_band(own, block, block, window, kernel == "dq"))
                    for sp in range(band.first // span, band.first // span + steps):
                        lo, hi, (t1, in1), (t2, in2) = fa._band_steps(band, sp * span, span)
                        todo = [(sp * span + x, fa.Piece((0, block), (0, block))) for x in range(int(lo), int(hi))]
                        todo += [(sp * span + int(t1), p) for p in band.pieces_first if bool(in1)]
                        todo += [(sp * span + int(t2), p) for p in band.pieces_last if bool(in2)]
                        for other, p in todo:
                            assert 0 <= other < n, (window, kv_major, own, sp, other)
                            q0, kv0 = ((other, own) if kv_major else (own, other))
                            q0, kv0 = q0 * block + p.q[0], kv0 * block + p.kv[0]
                            hits[q0:q0 + p.q[1], kv0:kv0 + p.kv[1]] += 1
                            area += p.q[1] * p.kv[1]
                np.testing.assert_array_equal(hits[(j <= i) & (i - j < window)], 1, err_msg=f"{s} {window} {kernel}")
                assert hits.max() == 1
                got = tile_counts(s, s, True, block, block, window=window, n_rep=2, kernel=kernel)
                assert got.tiles_computed == pytest.approx((2 if kv_major else 1) * area / block**2, abs=1e-9)
                assert got.grid_steps == n * steps
                areas[kernel] = area
            assert areas["dq"] == areas["dkv"] <= areas["fwd"] and areas["fwd"] % block**2 == 0
