"""The routed experts' grouped matmul: every touched expert's weights
streamed once.

:func:`grouped_matmul` has ``jax.lax.ragged_dot``'s meaning. ``x`` is
``[rows, k]`` with its rows sorted by group, ``w`` is ``[groups, k, n]``,
``group_sizes`` ``[groups]`` int32: the first ``group_sizes[0]`` rows are
multiplied by ``w[0]``, the next ``group_sizes[1]`` by ``w[1]``, and so
on; rows past ``sum(group_sizes)`` come out zero. Operands go to the MXU
as given, accumulation and the ``[rows, n]`` result are float32.

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
multiplied; a row mask keeps the neighbours' rows. The rows past the last
group are one more group that multiplies nothing, so their tiles are
visited and come out zero without a pass over the output.

What runs is chosen from what can be observed, never by an option: on a
TPU backend, with bfloat16 operands and ``k`` and ``n`` multiples of 128
whose weight block fits, the kernel, its tiles by :func:`_tile_rule` from
the shapes alone; anywhere else (a CPU, float32, narrow widths)
``jax.lax.ragged_dot`` exactly as before.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


__all__ = ["grouped_matmul", "row_tile", "weight_visits"]

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
# The chip's compiler names a Mosaic call's instruction by the last
# component of its path (the jitted function, the ``pallas_call``); the
# benchmark's readers find the routed experts' matmul by the name XLA's
# own has, ``ragged-dot``.
_KERNEL_NAME = "ragged-dot-gmm"


def _tile_rule(rows: int, k: int, n: int, itemsize: int):
    """``(tile_rows, sub_rows, tile_n)`` from the static shapes, nothing
    timed or probed; None where no weight block of at least 128 columns
    fits (the caller keeps ``ragged_dot``). ``rows`` is a multiple of
    ``_SUB_ROWS`` (the caller pads)."""
    tile_n = n
    while k * tile_n * itemsize > _WEIGHT_BLOCK_BYTES and tile_n % 256 == 0:
        tile_n //= 2
    if k * tile_n * itemsize > _WEIGHT_BLOCK_BYTES:
        return None
    tile_rows = next(t for t in (_TILE_ROWS, 256, _SUB_ROWS) if rows % t == 0)
    return tile_rows, _SUB_ROWS, tile_n


def _padded(rows: int) -> int:
    return -(-rows // _SUB_ROWS) * _SUB_ROWS


def row_tile(rows: int, k: int, n: int, x_dtype, w_dtype=None) -> int | None:
    """The rows of one row tile of the kernel :func:`grouped_matmul` runs
    for these shapes and dtypes on this backend, or None where it runs
    ``ragged_dot`` (what :func:`weight_visits` counts visits by)."""
    w_dtype = x_dtype if w_dtype is None else w_dtype
    if (
        jax.default_backend() != "tpu"
        or x_dtype != jnp.bfloat16 or w_dtype != jnp.bfloat16
        or k % _LANES or n % _LANES
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
    groups`` of them): the step's row tile, the weight block it holds,
    the rows ``[lo, hi)`` of the tile that belong to its group, and
    whether it is the tile's first visit (the output block is zeroed
    there). The rows past the last group are one more group, the tail,
    which multiplies nothing (``lo == hi == 0``); steps past the last
    visit stay on the last tile as the tail, and both hold the last real
    visit's weight block, so neither fetches one. Jitted, and in ``lax``
    rather than ``jax.numpy``: a program's projections share one walk,
    and a warm start pays for tracing it (PERF.md §6, PR 33)."""
    lax = jax.lax
    groups = group_sizes.shape[0]
    tiles = rows // tile_rows
    i32 = jnp.int32

    def const(value, like):
        return lax.full_like(like, value)

    sizes = lax.convert_element_type(group_sizes, i32)
    ends = lax.cumsum(sizes)
    starts = lax.sub(ends, sizes)
    total = lax.slice(ends, (groups - 1,), (groups,))
    # With the tail: [groups + 1].
    all_starts = lax.concatenate([starts, total], 0)
    all_sizes = lax.concatenate([sizes, lax.sub(const(rows, total), total)], 0)
    all_ends = lax.add(all_starts, all_sizes)
    first = lax.div(all_starts, const(tile_rows, all_starts))
    last = lax.div(lax.sub(all_ends, const(1, all_ends)),
                   const(tile_rows, all_ends))
    held = lax.gt(all_sizes, const(0, all_sizes))
    count = lax.select(held, lax.add(lax.sub(last, first), const(1, first)),
                       const(0, first))
    visit_ends = lax.cumsum(count)
    step = lax.iota(i32, tiles + groups)
    # The group whose visits hold the step: how many groups' visits end
    # at or before it (the tail's id, ``groups``, past the walk).
    before = lax.le(
        lax.broadcast_in_dim(visit_ends, (tiles + groups, groups + 1), (1,)),
        lax.broadcast_in_dim(step, (tiles + groups, groups + 1), (0,)),
    )
    group = lax.min(
        lax.reduce_sum(lax.convert_element_type(before, i32), (1,)),
        const(groups, step),
    )

    def of_group(values):
        return values.at[group].get(mode="promise_in_bounds")

    tile = lax.min(
        lax.add(of_group(lax.add(lax.sub(first, visit_ends), count)), step),
        const(tiles - 1, step),
    )
    base = lax.mul(tile, const(tile_rows, tile))
    zero = const(0, total)
    real_starts = lax.concatenate([starts, zero], 0)
    real_ends = lax.concatenate([ends, zero], 0)

    def within(rows_of_group):
        return lax.clamp(const(0, base), lax.sub(rows_of_group, base),
                         const(tile_rows, base))

    lo, hi = within(of_group(real_starts)), within(of_group(real_ends))
    previous = lax.concatenate(
        [const(-1, zero), lax.slice(tile, (0,), (tiles + groups - 1,))], 0
    )
    fresh = lax.convert_element_type(lax.ne(tile, previous), i32)
    ids = lax.iota(i32, groups)
    last_real = lax.reduce_max(
        lax.select(lax.gt(sizes, const(0, sizes)), ids, const(0, ids)), (0,)
    )
    weight = lax.select(
        lax.lt(group, const(groups, group)), group,
        lax.broadcast_in_dim(last_real, group.shape, ()),
    )
    return tile, weight, lo, hi, fresh


def _gmm_kernel(tile_ref, weight_ref, lo_ref, hi_ref, fresh_ref,
                x_ref, w_ref, o_ref, *, sub_rows: int):
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
            x_ref[at, :], w_ref[...], (((1,), (0,)), ((), ())),
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


def _gmm(x, w, group_sizes, *, tiles, interpret: bool = False):
    """The kernel over ``x`` whose rows ``tiles[0]`` divides."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..parallel._compat import pallas_tpu_compiler_params

    tile_rows, sub_rows, tile_n = tiles
    rows, k = x.shape
    groups, _, n = w.shape

    def x_index(j, step, tile, weight, lo, hi, fresh):
        return tile[step], 0

    def w_index(j, step, tile, weight, lo, hi, fresh):
        return weight[step], 0, j

    def o_index(j, step, tile, weight, lo, hi, fresh):
        return tile[step], j

    return pl.pallas_call(
        functools.partial(_gmm_kernel, sub_rows=sub_rows),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(n // tile_n, rows // tile_rows + groups),
            in_specs=[
                pl.BlockSpec((tile_rows, k), x_index),
                pl.BlockSpec((None, k, tile_n), w_index),
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


@functools.lru_cache(maxsize=None)
def _jitted(interpret: bool):
    """One jitted function: a program lowers the kernel once a distinct
    ``(rows, k, n)`` and calls it from every layer."""

    def ragged_dot_gmm(x, w, group_sizes):
        rows = x.shape[0]
        padded = _padded(rows)
        tiles = _tile_rule(padded, w.shape[1], w.shape[2], w.dtype.itemsize)
        if padded == rows:
            return _gmm(x, w, group_sizes, tiles=tiles, interpret=interpret)
        x = jnp.pad(x, ((0, padded - rows), (0, 0)))
        return _gmm(x, w, group_sizes, tiles=tiles, interpret=interpret)[:rows]

    ragged_dot_gmm.__name__ = ragged_dot_gmm.__qualname__ = _KERNEL_NAME
    return jax.jit(ragged_dot_gmm)


def grouped_matmul(x, w, group_sizes):
    """``jax.lax.ragged_dot(x, w, group_sizes)`` with a float32 result;
    see the module docstring."""
    if row_tile(x.shape[0], w.shape[1], w.shape[2], x.dtype, w.dtype) is None:
        return jax.lax.ragged_dot(
            x, w, group_sizes, preferred_element_type=jnp.float32
        )
    return _jitted(False)(x, w, group_sizes)
