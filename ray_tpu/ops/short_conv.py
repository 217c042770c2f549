"""A short causal depthwise convolution, silu and a per-head L2 norm as ONE pass over HBM
each way: two Pallas TPU kernels behind one `jax.custom_vjp` (PERF.md section 6, PR 44).

From x [B, T, C] in the activation's type, w [taps, C] (the last tap is the current
position's) and optionally a bias [C], channel by channel, zeros before the sequence:

    p_t = sum_j w_j x_{t-(taps-1)+j} (+ b)        a = silu(p) = p sigma(p)
    n   = a * rsqrt(sum_head a^2 + 1e-6) * scale    for the parts of the channels that are normed

The channels are `len(scales)` equal parts (the delta-rule mixer's q | k | v); a part whose
scale is None is written as `a`, one with a scale is normed over every `width` lanes (a head)
and multiplied by it. ALL the parts are one call a pass: the part is the kernels' first grid
axis and which part is normed, and by what, follows from its index, so a step's program
holds one forward and one backward kernel a layer and pass whatever the parts (a call a part
was three times the kernels to trace and lower in every process's first step, cached or
not: PERF.md section 6, PR 44). The parts' results, and backward their cotangents, are an
array a part all the same, [B, T, C / parts] each, so nothing is cut or joined around a
call (a cut was a pass of XLA's in front of the scan, the join one behind it: 0.19 and
0.26 ms a call at the Solar-Open2 cell's shape): each is an operand whose block index
STANDS STILL while the grid is in another part (`of_part`), at the first block it will
hold or the last it held, so nothing of it is fetched or written back then. For the results
that needs a grid walked in order by one core (every axis `arbitrary`): two cores walking
halves of it would each write back a block they never filled. A grid step is `_TILE`
positions of one head (or of 128 lanes where nothing is normed); its rows are walked in
chunks of up to 256 (`_ROWS`) by a `lax.fori_loop`, so a kernel's body is one chunk's
arithmetic with only the taps unrolled (the loop's iterations are not overlapped, so a chunk
has to be long enough to fill the units: 32 rows took 2.3 times as long as 256 on a v5e).
All arithmetic is float32 in fast memory, in the order the plain form has it
(models/kda.py:`_conv_silu_norm`: float32 convolution summed tap by tap, silu, norm, scale,
one rounding to the activation's type at the end): nothing is rounded that the plain form
does not round.

The cotangents' type. The results are handed on in float32: the values rounded to x's type,
written by the kernel in float32 (their reader, the scan, computes in float32 anyway; written
in x's type and widened outside, the widening was a pass of XLA's of its own, 0.18 ms a call
by its bytes), so that their cotangents ARRIVE in float32 and nothing is rounded between the
scan's float32 backward and this one. A `custom_vjp` whose results are bfloat16 arrays
receives bfloat16 cotangents: the plain form's by the letter of its casts, and a rounding
this pass can do without. What it is worth was measured on the chip (PERF.md section 6,
PR 44): rounding the cotangents on entry moves the Solar-Open2 cell's `gradient_ratio_all`
by 0.002 (0.876 -> 0.878), not the 3.5 % PR 41 reported for it. The cell's comparison did
move with this pass, 0.848 -> 0.876, and for another reason: the results ARE rounded here,
as models/kda.py says, where XLA's program of the plain form kept more than the letter
inside its fusions (not rounding them reads 0.861; a float32 projection result besides,
0.842 and the parent's per-position ratios to four digits); a kernel's operands and results are
arrays in the types the model states, and these kernels leave nothing exact on purpose.

The backward keeps x alone (w and the bias besides), makes p and a again in fast memory and,
with r the rsqrt, n^ = a r and <.,.> a head's sum:

    d a = scale * r * (d n - n^ <n^, d n>)          (d a = d n where not normed)
    d p = d a * sigma(p) (1 + p (1 - sigma(p)))
    d x_t = sum_j w_j d p_{t+(taps-1)-j}            d w_j = sum_t d p_t x_{t-(taps-1)+j}
    d b = sum_t d p_t

d x is written in x's type; d w and d b are summed in float32 over batch rows and tiles in
an output block that stays in fast memory while a block of channels is walked.

Tiles. Forward: the tile and the 16 rows before it (the halo, a second view of x; only its
last taps-1 rows are used, zeros at the sequence's start) are converted once into a float32
scratch with 8 rows in front, and a chunk's taps are loads of that scratch at the chunk's
row less taps-1-j. Backward: the tiles of a sequence are walked from the last to the first,
and a tile's chunks likewise, because d x needs d p of the taps-1 LATER positions: the
later tile's first 8 rows of d p are carried in a second scratch (zeros after the last
tile). T is padded with zeros to whole tiles where it is not (a copy; no listed cell needs
it): zeros after the sequence change nothing before it, and zero cotangents give d p = 0.

`supports` says what the kernels tile: parts of whole 128-lane registers, a head `width` of
whole registers that divides a part, at most 9 taps (the 8 rows the scratch keeps). Anything
else, and any call under a mesh that leaves an axis to GSPMD (which cannot partition a
Mosaic call: `parallel/sharding.py:partitioned_by_gspmd`), is the caller's plain form. Off a TPU the kernels
run in Pallas' interpreter (`flash_attention._interpret`'s rule).
"""
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ray_tpu.parallel.sharding import partitioned_by_gspmd

from . import flash_attention as _fa

_TILE = 1024  # positions of a grid step
_ROWS = (256, 128, 64, 32, 16)  # positions of a chunk of the loop inside one: the most that divide the tile
_HALO = 16  # rows of the view before a tile: a whole bfloat16 register
_KEEP = 8  # float32 rows kept in front of (behind, backward) a tile: a whole register, >= taps - 1
_LANES = 128
_EPS = 1e-6
_F32 = jnp.float32


def supports(channels: int, parts: int, width, taps: int) -> bool:
    """Whether the kernels tile `channels` channels in `parts` equal parts, the normed ones
    in heads of `width` lanes (None: no part is normed), with `taps` taps."""
    block = _LANES if width is None else width
    return channels % parts == 0 and block % _LANES == 0 and (channels // parts) % block == 0 and 1 <= taps <= _KEEP + 1


def takes_kernels(channels: int, parts: int, width, taps: int) -> bool:
    """`supports`, and no ambient mesh leaves an axis to GSPMD."""
    return supports(channels, parts, width, taps) and not partitioned_by_gspmd()


def _of_part(part, values):
    """The entry of `values` (numbers, booleans or arrays, one a part) that the grid step's part has."""
    if len(values) == 1 or (isinstance(values[0], (bool, int, float)) and len(set(values)) == 1):
        return values[0]
    out = values[-1]
    for i in range(len(values) - 2, -1, -1):
        out = jnp.where(part == i, values[i], out)
    return out


def _chunk_rows(tile: int) -> int:
    return next(rows for rows in _ROWS if tile % rows == 0)


def _fill(ext_ref, x_ref, halo_ref, first):
    """ext_ref [_KEEP + tile, lanes] float32: the rows before the tile (zeros at a sequence's start), the tile."""
    before = halo_ref[...].astype(_F32)[_HALO - _KEEP:]
    ext_ref[:_KEEP, :] = jnp.where(first, 0.0, before)
    ext_ref[_KEEP:, :] = x_ref[...].astype(_F32)


def _activation(ext_ref, at, rows, w, bias):
    """(the taps' shifted inputs, p, sigma(p)) of the `rows` rows of the tile from `at`."""
    taps = len(w)
    xs = [ext_ref[pl.ds(at + (_KEEP - (taps - 1) + j), rows), :] for j in range(taps)]
    p = xs[0] * w[0]
    for j in range(1, taps):
        p = p + xs[j] * w[j]
    if bias is not None:
        p = p + bias
    return xs, p, jax.nn.sigmoid(p)


def _when(pred, make, other):
    """`make()` where `pred` (a Python boolean, or a traced one: then a branch) holds, else `other`."""
    if isinstance(pred, bool):
        return make() if pred else other
    return jax.lax.cond(pred, make, lambda: other)


def _operands(refs, n: int, has_bias: bool, scales):
    """(the first n refs, w's rows a tap [1, lanes] float32, the bias [1, lanes] or None, the
    refs after them, the grid step's part, whether it is normed, its scale)."""
    w_ref = refs[n]
    w = [w_ref[j:j + 1, :].astype(_F32) for j in range(w_ref.shape[0])]
    bias = refs[n + 1][...].astype(_F32) if has_bias else None
    part = pl.program_id(0)
    normed = _of_part(part, tuple(s is not None for s in scales))
    scale = _of_part(part, tuple(1.0 if s is None else s for s in scales))
    return refs[:n], w, bias, refs[n + 1 + has_bias:], part, normed, scale


def _fwd_kernel(*refs, scales, has_bias):
    (x_ref, halo_ref), w, bias, (*o_refs, ext_ref), part, normed, scale = _operands(refs, 2, has_bias, scales)
    rows = _chunk_rows(x_ref.shape[0])
    _fill(ext_ref, x_ref, halo_ref, pl.program_id(3) == 0)

    def chunk(i, _):
        at = pl.multiple_of(i * rows, rows)
        _, p, sig = _activation(ext_ref, at, rows, w, bias)
        a = p * sig
        a = _when(normed, lambda: a * jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + _EPS) * scale, a)
        a = a.astype(x_ref.dtype).astype(_F32)  # the one rounding; held in float32
        for j, o_ref in enumerate(o_refs):
            @pl.when(part == j)
            def _(o_ref=o_ref):
                o_ref[pl.ds(at, rows), :] = a

    jax.lax.fori_loop(0, x_ref.shape[0] // rows, chunk, None)


def _bwd_kernel(*refs, scales, has_bias):
    parts = len(scales)
    (x_ref, halo_ref, *d_refs), w, bias, outs, part, normed, scale = _operands(refs, 2 + parts, has_bias, scales)
    dx_ref, dw_ref = outs[:2]
    ext_ref, dpe_ref = outs[-2:]
    taps, (tile, lanes) = len(w), x_ref.shape
    rows = _chunk_rows(tile)
    n_chunks = tile // rows
    step = pl.program_id(3)  # 0 is the sequence's LAST tile
    _fill(ext_ref, x_ref, halo_ref, step == pl.num_programs(3) - 1)

    @pl.when((pl.program_id(2) == 0) & (step == 0))
    def _():
        for ref in outs[1:2 + has_bias]:
            ref[...] = jnp.zeros_like(ref)

    # d p of the _KEEP positions after the tile: the later tile's first rows, zeros after the last
    later = dpe_ref[:_KEEP, :]
    dpe_ref[tile:, :] = jnp.where(step == 0, 0.0, later)

    def chunk(i, sums):
        at = pl.multiple_of((n_chunks - 1 - i) * rows, rows)
        xs, p, sig = _activation(ext_ref, at, rows, w, bias)
        d = _of_part(part, tuple(d_ref[pl.ds(at, rows), :].astype(_F32) for d_ref in d_refs))

        def through_norm():
            a = p * sig
            r = jax.lax.rsqrt(jnp.sum(a * a, -1, keepdims=True) + _EPS)
            unit = a * r
            return (d - unit * jnp.sum(unit * d, -1, keepdims=True)) * (r * scale)

        dp = _when(normed, through_norm, d) * (sig * (1.0 + p * (1.0 - sig)))
        dpe_ref[pl.ds(at, rows), :] = dp
        dx = w[taps - 1] * dp
        for j in range(taps - 1):
            dx = dx + w[j] * dpe_ref[pl.ds(at + (taps - 1 - j), rows), :]
        dx_ref[pl.ds(at, rows), :] = dx.astype(dx_ref.dtype)
        over_time = [dp * x for x in xs] + ([dp] if has_bias else [])
        # whole registers of 8 rows summed onto one: the 8 rows are summed once, after the loop
        return tuple(s + jnp.sum(y.reshape(rows // _KEEP, _KEEP, lanes), 0) for s, y in zip(sums, over_time))

    sums = jax.lax.fori_loop(0, n_chunks, chunk, (jnp.zeros((_KEEP, lanes), _F32),) * (taps + has_bias))
    sums = [jnp.sum(s, 0, keepdims=True) for s in sums]
    for j in range(taps):
        dw_ref[j:j + 1, :] += sums[j]
    if has_bias:
        outs[2][...] += sums[taps]


def _tiling(t: int):
    """(tile, padded length) for a sequence of t positions: a tile whole registers of 16 rows, at most `_TILE`."""
    tile = min(_TILE, -(-t // _HALO) * _HALO)
    return tile, -(-t // tile) * tile


def _call(scales, width, x, cts, w, bias):
    """One grid step a part, block of its channels, batch row and tile. Forward (`cts` None):
    -> the parts' results, each [B, T, C / parts], rounded to x's type, in float32. Backward:
    the tiles last to first; `cts` the parts' cotangents, shaped as the results -> (d x, d w, (d b,))."""
    bsz, t, channels = x.shape
    parts, taps = len(scales), w.shape[0]
    own = channels // parts
    lanes = _LANES if width is None else width
    blocks = own // lanes  # of a part
    tile, _ = _tiling(t)
    n_tiles = t // tile
    backward, has_bias = cts is not None, bias is not None
    at = (lambda i: n_tiles - 1 - i) if backward else (lambda i: i)
    rows = pl.BlockSpec((None, tile, lanes), lambda p, c, b, i: (b, at(i), p * blocks + c))
    halo = pl.BlockSpec((None, _HALO, lanes),
                        lambda p, c, b, i: (b, jnp.maximum(at(i) * (tile // _HALO) - 1, 0), p * blocks + c))
    column = lambda n: pl.BlockSpec((n, lanes), lambda p, c, b, i: (0, p * blocks + c))  # noqa: E731

    def of_part(j):
        """The block of part j's own array (a result, a cotangent) that the grid step holds:
        its own where the step is in part j; else the first it will hold (before) or the last
        it held (after), so that nothing moves while the other parts are walked."""
        def index(p, c, b, i):
            pick = lambda mine, first, last: jnp.where(p == j, mine, jnp.where(p < j, first, last))  # noqa: E731
            return pick(b, 0, bsz - 1), pick(at(i), at(0), at(n_tiles - 1)), pick(c, 0, blocks - 1)
        return pl.BlockSpec((None, tile, lanes), index)

    operands, in_specs = [x, x], [rows, halo]
    if backward:
        operands, in_specs = operands + list(cts), in_specs + [of_part(j) for j in range(parts)]
    operands, in_specs = operands + [w], in_specs + [column(taps)]
    if has_bias:
        operands, in_specs = operands + [bias.reshape(1, channels)], in_specs + [column(1)]
    static = dict(scales=scales, has_bias=has_bias)
    scratch = [pltpu.VMEM((_KEEP + tile, lanes), _F32)]
    grid = (parts, blocks, bsz, n_tiles)
    if not backward:
        return pl.pallas_call(
            functools.partial(_fwd_kernel, **static), name="short_conv_fwd", interpret=_fa._interpret(), grid=grid,
            in_specs=in_specs, out_specs=[of_part(j) for j in range(parts)],
            out_shape=[jax.ShapeDtypeStruct((bsz, t, own), _F32)] * parts, scratch_shapes=scratch,
            compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",) * 4),
        )(*operands)
    out_specs = [rows, column(taps)] + ([column(1)] if has_bias else [])
    out_shape = [jax.ShapeDtypeStruct(x.shape, x.dtype), jax.ShapeDtypeStruct((taps, channels), _F32)] + (
        [jax.ShapeDtypeStruct((1, channels), _F32)] if has_bias else [])
    return pl.pallas_call(
        functools.partial(_bwd_kernel, **static), name="short_conv_bwd", interpret=_fa._interpret(), grid=grid,
        in_specs=in_specs, out_specs=out_specs, out_shape=out_shape, scratch_shapes=scratch * 2,
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel", "parallel", "arbitrary", "arbitrary")),
    )(*operands)


def _padded(x, length: int):
    """x [B, T, C] with its positions padded with zeros to `length`."""
    return jnp.pad(x, ((0, 0), (0, length - x.shape[1]), (0, 0))) if length > x.shape[1] else x


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def short_conv(x, w, bias, scales: tuple, width):
    """The module's docstring: x [B, T, C], w [taps, C], bias [C] or None, `scales` a part
    of the channels (None: silu only; a number: normed over heads of `width` lanes, times
    it) -> the parts, each [B, T, C / parts]: the values rounded to x's type, held in
    float32. The caller checks `takes_kernels`."""
    return _short_conv_fwd(x, w, bias, scales, width)[0]


def _short_conv_fwd(x, w, bias, scales, width):
    t = x.shape[1]
    out = _call(scales, width, _padded(x, _tiling(t)[1]), None, w, bias)
    return tuple(y[:, :t] for y in out), (x, w, bias)


def _short_conv_bwd(scales, width, kept, cts):
    x, w, bias = kept
    t = x.shape[1]
    length = _tiling(t)[1]
    dx, dw, *db = _call(scales, width, _padded(x, length), [_padded(ct, length) for ct in cts], w, bias)
    return dx[:, :t], dw.astype(w.dtype), db[0].reshape(bias.shape).astype(bias.dtype) if db else None


short_conv.defvjp(_short_conv_fwd, _short_conv_bwd)
