"""The routed experts' grouped matmul: every touched expert's weights
streamed once.

:func:`grouped_matmul` has ``jax.lax.ragged_dot``'s meaning. ``x`` is
``[rows, k]`` with its rows sorted by group, ``w`` is ``[groups, k, n]``,
``group_sizes`` ``[groups]`` int32: the first ``group_sizes[0]`` rows are
multiplied by ``w[0]``, the next ``group_sizes[1]`` by ``w[1]``, and so
on. **Rows past ``sum(group_sizes)`` are UNSPECIFIED**, in the result and
as operands: the kernel never fetches, zeroes or writes a row tile that
holds none of the groups' rows (such a tile of the result is whatever the
buffer held, NaN bit patterns among it), ``ragged_dot`` leaves there what
its backend leaves, and neither reads ``x`` there. A caller reads the
first ``sum(group_sizes)`` rows and nothing else
(:class:`~fluxmpi_tpu.models.decoder.ExpertMLP` sums them back by token
with :func:`combine`, which reads the live row tiles only).
Operands go to the MXU as given, accumulation and the ``[rows, n]`` result
are float32.

An expert layer at serving sizes is a weight-streaming problem: 512
(token, expert) rows over 128 experts of ``[2048, 1024]`` are three rows
an expert, each there to read 4 MB. So on a TPU the kernel walks VISITS,
the non-empty meetings of a row tile (``tile_rows`` rows) with a group, in
row order; the group ids, row tiles and group offsets are scalar
prefetched and the weight block's index map is ``w[group of the visit]``:
an expert that received no row is never read, and one whose rows lie
inside one row tile is read exactly once (one that straddles a tile
boundary once a tile: :func:`weight_visits` counts them). Inside a visit
only the ``sub_rows``-row sub-tiles that hold rows of the group are
multiplied; a row mask keeps the neighbours' rows. The walk ends with the
last group: a layer that holds a share of its router's experts sorts the
pairs of the experts held elsewhere last, and their tiles cost nothing.

:func:`combine` is the way back: the down projection's rows, still sorted
by group, scaled and added into their tokens' rows. Only the first
``live`` rows are read, so a layer whose held experts received a quarter
of the pairs moves a quarter of the float32 result. Its kernel keeps a
``[tokens, tile_n]`` column block of the sum in VMEM, streams the live
row tiles past it once a column block (the steps past them stay on the
last live tile and add nothing) and adds each row where its token says
(scalar prefetched); anywhere but a TPU it is a masked ``segment_sum``,
whose reverse rule the kernel is given too.

The weights may be held TRANSPOSED (``transposed=True``: ``w`` is
``[groups, n, k]`` and a group's rows meet ``w[g].T``). That is how a
layer holds a projection whose ``n`` is no whole number of 128-lane tiles
(1,856 columns): the chip's compiler lays such an array out with its
OTHER dimension on the lanes and copies all of it before a Mosaic kernel
may read it, so the layer keeps ``k`` (whole tiles) on the lanes itself
and the kernel contracts both operands' last dimension. A weight block is
whole 128-lane tiles of the columns (:func:`_column_block`); the last
block may end past the width (Pallas neither reads nor writes past it).

What runs is chosen from what can be observed, never by an option: on a
TPU backend, with bfloat16 operands and weights whose minor dimension is
whole 128-lane tiles (the other whole 64s) and whose block fits, the
kernel, its tiles by :func:`_tile_rule` from the shapes alone; anywhere
else (a CPU, float32, narrow widths) ``jax.lax.ragged_dot`` exactly as
before (of the transpose, where the weights are held so).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


__all__ = ["combine", "grouped_matmul", "live_row_tile", "row_tile",
           "weight_visits"]

# One weight block ``[k, tile_n]`` (double-buffered by the pipeline): all
# of an expert's ``[2048, 1024]`` or ``[1024, 2048]``, one contiguous read.
_WEIGHT_BLOCK_BYTES = 4 * 2**20
# A visit holds ``_TILE_ROWS`` rows (the decode tick's 512 pairs are one
# tile: each touched expert is read once) and multiplies the ``_SUB_ROWS``
# sub-tiles that hold rows of its group. Off sweeps on a v5e (PERF.md §6,
# PR 33): sub-tiles of 16 to 128 rows cost the same (the MXU pays 128),
# 256 more; row tiles of 256 to 1,024 within 4% of each other at every
# prompt bucket; a weight block of half the columns 10-20% slower.
_TILE_ROWS = 512
_SUB_ROWS = 128
_VMEM_LIMIT_BYTES = 64 * 2**20
_LANES = 128
_HALF_LANES = 64
# The chip's compiler names a Mosaic call's instruction by the last
# component of its path (the jitted function, the ``pallas_call``); the
# benchmark's readers find the routed experts' matmul by the name XLA's
# own has, ``ragged-dot``.
_KERNEL_NAME = "ragged-dot-gmm"
# The combine's kernel, and the float32 ``[tokens, tile_n]`` block of the
# result it keeps resident (double-buffered by the pipeline).
_COMBINE_NAME = "expert-combine"
_COMBINE_BLOCK_BYTES = 4 * 2**20


def _column_block(n: int, widest: int) -> int | None:
    """The columns of one column block of a width ``n`` where a block may
    hold ``widest`` columns: all of ``n`` if they fit, else the fewest
    blocks of whole 128-lane tiles, as equal as whole tiles let them be
    (4,096 under 1,024: four of 1,024; 2,688 = 21 tiles under 1,129:
    three of 896; 1,856 = 14.5 tiles under 780: 640, 640 and a last block
    of 576, whose columns past the width are neither read nor written).
    None where not one tile fits."""
    if n <= widest:
        return n
    most = widest // _LANES
    if most < 1:
        return None
    tiles = -(-n // _LANES)
    return -(-tiles // -(-tiles // most)) * _LANES


def _tile_rule(rows: int, k: int, n: int, itemsize: int):
    """``(tile_rows, sub_rows, tile_n)`` from the static shapes, nothing
    timed or probed; None where no weight block of at least 128 columns
    fits (the caller keeps ``ragged_dot``). ``rows`` is a multiple of
    ``_SUB_ROWS`` (the caller pads)."""
    tile_n = _column_block(n, _WEIGHT_BLOCK_BYTES // (k * itemsize))
    if tile_n is None:
        return None
    return live_row_tile(rows), _SUB_ROWS, tile_n


def _padded(rows: int) -> int:
    return -(-rows // _SUB_ROWS) * _SUB_ROWS


def live_row_tile(rows: int) -> int:
    """The rows of one row tile of a call over ``rows`` rows (padded to
    whole sub-tiles), from ``rows`` alone and on any backend: the
    granularity at which the kernels' walks stop past the groups."""
    padded = _padded(rows)
    return next(t for t in (_TILE_ROWS, 256, _SUB_ROWS) if padded % t == 0)


def row_tile(rows: int, k: int, n: int, x_dtype, w_dtype=None, *,
             transposed: bool = False) -> int | None:
    """The rows of one row tile of the kernel :func:`grouped_matmul` runs
    for these shapes and dtypes on this backend, or None where it runs
    ``ragged_dot`` (what :func:`weight_visits` counts visits by). The
    weights' MINOR dimension (``n``, or ``k`` of ``transposed`` ones) is
    whole 128-lane tiles, the other whole 64s and a tile at least."""
    w_dtype = x_dtype if w_dtype is None else w_dtype
    minor, other = (k, n) if transposed else (n, k)
    if (
        jax.default_backend() != "tpu"
        or x_dtype != jnp.bfloat16 or w_dtype != jnp.bfloat16
        or minor % _LANES or other % _HALF_LANES or other < _LANES
    ):
        return None
    tiles = _tile_rule(_padded(rows), k, n, 2)
    return None if tiles is None else tiles[0]


def weight_visits(group_sizes, tile_rows: int) -> int:
    """How many (row tile, group) visits the kernel makes for these group
    sizes (host side, numpy; any leading dimensions are so many calls):
    a group's rows meet ``last tile - first tile + 1`` row tiles."""
    sizes = np.asarray(group_sizes, np.int64)
    ends = np.cumsum(sizes, axis=-1)
    tiles = (ends - 1) // tile_rows - (ends - sizes) // tile_rows + 1
    return int(np.where(sizes > 0, tiles, 0).sum())


@functools.partial(jax.jit, static_argnames=("rows", "tile_rows"))
def _visits(group_sizes, rows: int, tile_rows: int):
    """The kernel's walk, one entry a grid step (``rows // tile_rows +
    groups`` of them, the most visits any sizes make): the step's row
    tile, the weight block it holds, the rows ``[lo, hi)`` of the tile
    that belong to its group, and whether it is the tile's first visit
    (the output block is zeroed there). The walk ends with the last
    group's last row: the steps past it stay on the last visited tile
    and hold the last real visit's weight block with ``lo == hi == 0``
    and ``fresh == 0``, so they fetch nothing, multiply nothing and zero
    nothing, and a row tile past the groups is never visited. Jitted, and
    in ``lax`` rather than ``jax.numpy``: a program's projections share
    one walk, and a warm start pays for tracing it (PERF.md §6, PR 33)."""
    lax = jax.lax
    groups = group_sizes.shape[0]
    steps = rows // tile_rows + groups
    i32 = jnp.int32

    def const(value, like):
        return lax.full_like(like, value)

    sizes = lax.convert_element_type(group_sizes, i32)
    ends = lax.cumsum(sizes)
    starts = lax.sub(ends, sizes)
    first = lax.div(starts, const(tile_rows, starts))
    last = lax.div(lax.sub(ends, const(1, ends)), const(tile_rows, ends))
    held = lax.gt(sizes, const(0, sizes))
    count = lax.select(held, lax.add(lax.sub(last, first), const(1, first)),
                       const(0, first))
    visit_ends = lax.cumsum(count)
    step = lax.iota(i32, steps)
    # The group whose visits hold the step: how many groups' visits end
    # at or before it; past the walk, every group's.
    before = lax.reduce_sum(
        lax.convert_element_type(
            lax.le(lax.broadcast_in_dim(visit_ends, (steps, groups), (1,)),
                   lax.broadcast_in_dim(step, (steps, groups), (0,))),
            i32,
        ),
        (1,),
    )
    walking = lax.lt(before, const(groups, before))
    group = lax.min(before, const(groups - 1, before))
    ids = lax.iota(i32, groups)
    last_real = lax.reduce_max(lax.select(held, ids, const(0, ids)), (0,))
    # Past the walk: the last real visit's group, on its last tile.
    group = lax.select(
        walking, group, lax.broadcast_in_dim(last_real, group.shape, ())
    )

    def of_group(values):
        return values.at[group].get(mode="promise_in_bounds")

    tile = lax.select(
        walking,
        lax.add(of_group(lax.add(lax.sub(first, visit_ends), count)), step),
        lax.max(of_group(last), const(0, step)),
    )
    base = lax.mul(tile, const(tile_rows, tile))

    def within(rows_of_group):
        return lax.select(
            walking,
            lax.clamp(const(0, base), lax.sub(rows_of_group, base),
                      const(tile_rows, base)),
            const(0, base),
        )

    lo, hi = within(of_group(starts)), within(of_group(ends))
    previous = lax.concatenate(
        [lax.full((1,), -1, i32), lax.slice(tile, (0,), (steps - 1,))], 0
    )
    fresh = lax.convert_element_type(
        lax.bitwise_and(walking, lax.ne(tile, previous)), i32
    )
    return tile, group, lo, hi, fresh


def _gmm_kernel(tile_ref, weight_ref, lo_ref, hi_ref, fresh_ref,
                x_ref, w_ref, o_ref, *, sub_rows: int, transposed: bool):
    del tile_ref, weight_ref  # read by the index maps
    from jax.experimental import pallas as pl

    lax = jax.lax
    step = pl.program_id(1)
    lo, hi = lo_ref[step], hi_ref[step]

    @pl.when(fresh_ref[step] != 0)
    def _first_visit_of_the_tile():
        o_ref[...] = lax.full_like(o_ref[...], 0)

    def sub_tile(s, carry):
        at = pl.ds(pl.multiple_of(s * sub_rows, sub_rows), sub_rows)
        product = lax.dot_general(
            x_ref[at, :], w_ref[...],
            (((1,), (1 if transposed else 0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        row = s * sub_rows + lax.broadcasted_iota(
            jnp.int32, product.shape, 0
        )
        o_ref[at, :] = lax.select(
            (row >= lo) & (row < hi), product, o_ref[at, :]
        )
        return carry

    # Only the sub-tiles that hold rows of the visit's group.
    sub = jnp.int32(sub_rows)
    lax.fori_loop(
        lax.div(lo, sub), lax.div(hi + (sub - 1), sub), sub_tile, None
    )


def _gmm(x, w, group_sizes, *, tiles, transposed: bool = False,
         interpret: bool = False):
    """The kernel over ``x`` whose rows ``tiles[0]`` divides; ``w``
    ``[groups, k, n]``, or ``[groups, n, k]`` where ``transposed``. A
    last column block past the width is partial."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..parallel._compat import pallas_tpu_compiler_params

    tile_rows, sub_rows, tile_n = tiles
    rows, k = x.shape
    groups = w.shape[0]
    n = w.shape[1] if transposed else w.shape[2]

    def x_index(j, step, tile, weight, lo, hi, fresh):
        return tile[step], 0

    def w_index(j, step, tile, weight, lo, hi, fresh):
        return (weight[step], j, 0) if transposed else (weight[step], 0, j)

    def o_index(j, step, tile, weight, lo, hi, fresh):
        return tile[step], j

    return pl.pallas_call(
        functools.partial(_gmm_kernel, sub_rows=sub_rows,
                          transposed=transposed),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(-(-n // tile_n), rows // tile_rows + groups),
            in_specs=[
                pl.BlockSpec((tile_rows, k), x_index),
                pl.BlockSpec((None, tile_n, k) if transposed
                             else (None, k, tile_n), w_index),
            ],
            out_specs=pl.BlockSpec((tile_rows, tile_n), o_index),
        ),
        out_shape=jax.ShapeDtypeStruct((rows, n), jnp.float32),
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=_KERNEL_NAME,
    )(*_visits(group_sizes, rows, tile_rows), x, w)


def _combine_kernel(live_ref, token_ref, y_ref, scale_ref, o_ref, scaled_ref,
                    *, tile_rows: int):
    """One (column block, row tile) step of :func:`combine`: the tile's
    live rows, scaled, each added into its token's row of ``o_ref``."""
    from jax.experimental import pallas as pl

    lax = jax.lax
    step = pl.program_id(1)

    @pl.when(step == 0)
    def _first_tile_of_the_columns():
        o_ref[...] = lax.full_like(o_ref[...], 0)

    base = step * tile_rows
    count = lax.clamp(jnp.int32(0), live_ref[0] - base, jnp.int32(tile_rows))

    @pl.when(count > 0)
    def _a_tile_with_live_rows():
        scaled_ref[...] = y_ref[...] * scale_ref[...]

        def row(r, carry):
            at = pl.ds(token_ref[base + r], 1)
            o_ref[at, :] = o_ref[at, :] + scaled_ref[pl.ds(r, 1), :]
            return carry

        lax.fori_loop(0, count, row, None)


def _combine(y, token, scale, live, *, tokens: int, tiles,
             interpret: bool = False):
    """The kernel: a grid of (column blocks, row tiles), the column
    block of the result resident while the LIVE row tiles stream past it
    (a step past them stays on the last live tile and adds nothing)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..parallel._compat import pallas_tpu_compiler_params

    tile_rows, tile_n = tiles
    rows, n = y.shape

    def tile_of(step, live):
        last = jax.lax.max((live[0] - 1) // tile_rows, 0)
        return jax.lax.min(step, last)

    return pl.pallas_call(
        functools.partial(_combine_kernel, tile_rows=tile_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(-(-n // tile_n), rows // tile_rows),
            in_specs=[
                pl.BlockSpec((tile_rows, tile_n),
                             lambda j, s, live, token: (tile_of(s, live), j)),
                pl.BlockSpec((tile_rows, 1),
                             lambda j, s, live, token: (tile_of(s, live), 0)),
            ],
            out_specs=pl.BlockSpec((tokens, tile_n),
                                   lambda j, s, live, token: (0, j)),
            scratch_shapes=[pltpu.VMEM((tile_rows, tile_n), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((tokens, n), jnp.float32),
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=_VMEM_LIMIT_BYTES,
        ),
        interpret=interpret,
        name=_COMBINE_NAME,
    )(jnp.reshape(live, (1,)).astype(jnp.int32), token.astype(jnp.int32),
      y, scale.astype(jnp.float32)[:, None])


def _combine_tiles(rows: int, n: int, tokens: int):
    """``(tile_rows, tile_n)`` of the combine's kernel from the static
    shapes (``rows`` and ``tokens`` as the caller pads them: whole
    sub-tiles, whole sublanes), or None where it does not apply: the
    widest column block whose ``[tokens, tile_n]`` float32 part of the
    result fits ``_COMBINE_BLOCK_BYTES``."""
    if n % _LANES:
        return None
    tile_n = _column_block(n, _COMBINE_BLOCK_BYTES // (tokens * 4))
    if tile_n is None:
        return None
    return live_row_tile(rows), tile_n


@functools.lru_cache(maxsize=None)
def _jitted_combine(interpret: bool, tokens: int):
    """One jitted function a token count, as :func:`_jitted`: a program
    lowers the kernel once a distinct shape. Its reverse rule is the
    masked ``segment_sum``'s, in plain XLA (a live row's cotangent is
    its token's, scaled), so the layer differentiates where the kernel
    runs as where it does not."""

    @jax.custom_vjp
    def combine_live_rows(y, token, scale, live):
        # Whole sub-tiles of rows, whole sublanes of tokens.
        pad = _padded(y.shape[0]) - y.shape[0]
        if pad:
            y = jnp.pad(y, ((0, pad), (0, 0)))
            token, scale = jnp.pad(token, (0, pad)), jnp.pad(scale, (0, pad))
        whole = -(-tokens // 8) * 8
        tiles = _combine_tiles(y.shape[0], y.shape[1], whole)
        return _combine(y, token, scale, live, tokens=whole, tiles=tiles,
                        interpret=interpret)[:tokens]

    def forward(y, token, scale, live):
        return combine_live_rows(y, token, scale, live), (y, token, scale,
                                                          live)

    def backward(kept, cotangent):
        y, token, scale, live = kept
        rows = (jnp.arange(y.shape[0]) < live)[:, None]
        by_row = jnp.where(rows, cotangent[token], 0.0)
        return (by_row * scale[:, None], None,
                jnp.sum(jnp.where(rows, y, 0.0) * by_row, axis=1), None)

    combine_live_rows.defvjp(forward, backward)

    def expert_combine(y, token, scale, live):
        return combine_live_rows(y, token, scale, live)

    expert_combine.__name__ = expert_combine.__qualname__ = _COMBINE_NAME
    return jax.jit(expert_combine)


def combine(y, token, scale, live, tokens: int):
    """The routed experts' results summed back by token: ``out[t]`` is
    the sum of ``scale[r] * y[r]`` over the sorted rows ``r < live`` with
    ``token[r] == t``, float32 ``[tokens, n]``. ``y`` is a
    :func:`grouped_matmul` result ``[rows, n]`` (float32; its rows past
    ``live`` unspecified and never read), ``token`` ``[rows]`` int32,
    ``scale`` ``[rows]`` float32, ``live`` a scalar. On a TPU backend
    with ``n`` a multiple of 128 it is a Pallas kernel that reads the
    live row tiles only, each once a column block, and adds a row into
    its token's row of the resident block; anywhere else a masked
    ``segment_sum``."""
    tiles = _combine_tiles(_padded(y.shape[0]), y.shape[1],
                           -(-tokens // 8) * 8)
    if jax.default_backend() != "tpu" or y.dtype != jnp.float32 or (
            tiles is None):
        # Masked before it is scaled: a row past ``live`` may hold NaN,
        # which must reach neither the sum nor ``scale``'s gradient.
        rows = jnp.arange(y.shape[0]) < live
        return jax.ops.segment_sum(
            jnp.where(rows[:, None], y, 0.0) * scale[:, None], token,
            num_segments=tokens,
        )
    return _jitted_combine(False, tokens)(y, token, scale, live)


@functools.lru_cache(maxsize=None)
def _jitted(interpret: bool, transposed: bool = False):
    """One jitted function a layout of the weights: a program lowers the
    kernel once a distinct ``(rows, k, n)`` and calls it from every
    layer."""

    def ragged_dot_gmm(x, w, group_sizes):
        rows = x.shape[0]
        padded = _padded(rows)
        k, n = w.shape[1:][::-1] if transposed else w.shape[1:]
        tiles = _tile_rule(padded, k, n, w.dtype.itemsize)

        def gmm(x):
            return _gmm(x, w, group_sizes, tiles=tiles,
                        transposed=transposed, interpret=interpret)

        if padded == rows:
            return gmm(x)
        return gmm(jnp.pad(x, ((0, padded - rows), (0, 0))))[:rows]

    ragged_dot_gmm.__name__ = ragged_dot_gmm.__qualname__ = _KERNEL_NAME
    return jax.jit(ragged_dot_gmm)


def grouped_matmul(x, w, group_sizes, *, transposed: bool = False):
    """``jax.lax.ragged_dot(x, w, group_sizes)`` with a float32 result
    whose rows past ``sum(group_sizes)`` are unspecified; see the module
    docstring. ``transposed``: ``w`` is held ``[groups, n, k]`` and each
    group's rows are multiplied by ``w[g].T``."""
    k, n = w.shape[1:][::-1] if transposed else w.shape[1:]
    if row_tile(x.shape[0], k, n, x.dtype, w.dtype,
                transposed=transposed) is None:
        return jax.lax.ragged_dot(
            x, jnp.swapaxes(w, 1, 2) if transposed else w, group_sizes,
            preferred_element_type=jnp.float32,
        )
    return _jitted(False, transposed)(x, w, group_sizes)
