"""State-space (Mamba-2) layers: the decode tick's state update and the
prefill's chunked scan.

A Mamba-2 head keeps, a sequence, a state ``H`` ``[head_dim, d_state]``
that one token moves by ``H_t = a_t H_{t-1} + (D_t x_t) B_t^T`` and reads
as ``y_t = H_t C_t`` (``x_t`` ``[head_dim]``; ``B_t``, ``C_t`` ``[d_state]``,
one pair a GROUP of consecutive heads: ``groups`` of them, head ``h``
reading group ``h // (heads / groups)``, one group for all heads where the
arrays carry no group axis; ``D_t > 0`` the step, ``a_t = exp(D_t A)``
the decay, both a head). Whatever the context's length the state is
``heads x head_dim x d_state`` numbers a layer, float32 in the serving
engine's pool: the recurrence compounds its rounding over a whole answer.

**The decode tick** (:func:`ssm_state_update`, plain reference
:func:`ssm_state_update_reference`). The states of all sequences lie in ONE
pool ``[layers, entries, d_state, heads * head_dim]``
(:mod:`fluxmpi_tpu.serving.cache`: the state kind; entry 0 is the trash
entry idle slots point at; :func:`to_pool_layout` is the one statement of
a state's layout there), and beside it, entry for entry, the tail pool
``[layers, entries, tiles, 128]``: what the layer's convolution still
needs of the sequence, its last ``d_conv - 1`` pre-convolution columns
(:func:`tail_to_pool_layout`). ``entries[slot]`` names each batch slot's
entry. On a TPU a Pallas kernel (``name="ssm_state_update"``) walks the
LIVE slots only (:func:`live_entries`: their pool entries and rows,
compacted, scalar prefetched; the grid's steps past the last live slot
hold its blocks and do nothing): each live state is read once, moved, read
out against ``C`` and written back where it lay, and the slot's new tail
is copied over its old one, both pools aliased in and out, so an idle
slot's state and tail are neither read nor written. Left to XLA the
update streams every entry and a scatter of tails pays 1.3 us a row,
idle or live. Anywhere else than a TPU the reference's arithmetic runs (a
gather, the update, two scatters that drop idle slots).

A state lies in the pool TRANSPOSED, ``H^T`` of all heads side by side:
``[d_state, heads * head_dim]``, ``d_state`` on the sublanes and a head's
``head_dim`` on consecutive lanes. So a token's ``D_t x_t`` and ``a_t``
(repeated over a head's lanes) are ROWS that broadcast over the sublanes
for free, ``y`` is a row again (a sum over the sublanes), and only ``B``
and ``C`` have to become columns: once a state and group, not once a
head; a group's heads are consecutive lanes, whole 128-lane tiles of the
walk (eight groups over 4,096 lanes: four tiles each), so a tile picks
its group's columns and nothing is broadcast a head. (The
first kernel kept ``[heads, head_dim, d_state]`` and paid a lane
broadcast and a lane reduction a head: 23 us a state where its two
transfers take 10.) Tiles come from the shapes alone: one sequence's
state of one layer a grid step, walked 128 lanes at a time. A tail lies
in its pool as whole 128-lane tiles, its columns end to end: the block
the kernel writes is the layout the pool is held in, and nothing between
the pool and the kernel changes a tiling (a pool of ``[d_conv - 1,
conv_dim]`` rows was copied whole a layer a tick).

**The prefill** (:func:`ssd_chunk_scan`): the same recurrence over a
whole prompt in chunks of ``chunk`` tokens, as matmuls. With ``l_t`` the
running sum of ``D_r A`` inside a chunk: ``y_t = sum_{s<=t} exp(l_t - l_s)
(C_t . B_s) D_s x_s + exp(l_t) C_t H_start`` and ``H_end = exp(l_last)
H_start + sum_s exp(l_last - l_s) D_s x_s B_s^T``, the chunks in order
under a ``lax.scan`` that carries ``H`` in float32. A position whose
``D_t`` is 0 leaves the state as it was and adds nothing to later ones:
that is how a prompt padded to its bucket ends in the state of its last
real token. Matmul operands are in ``x``'s dtype, decays, sums and the
state float32. Plain ``jax.numpy``: the scan is a few percent of a
prefill's operations.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

__all__ = ["from_pool_layout", "live_entries", "ssd_chunk_scan",
           "ssm_state_update", "ssm_state_update_reference",
           "tail_from_pool_layout", "tail_to_pool_layout", "to_pool_layout"]

TRASH_ENTRY = 0
# The chip's compiler names a Mosaic call's instruction by the last
# component of its path: the jitted wrapper and the ``pallas_call`` both
# carry this name, and the benchmark's readers find the kernel by it.
_KERNEL_NAME = "ssm_state_update"
# A state block of 128 x 8,192 float32 is 4 MiB, in and out and each
# double-buffered by the pipeline.
_VMEM_LIMIT_BYTES = 48 * 2**20
_LANES = 128


def to_pool_layout(state):
    """``[..., heads, head_dim, d_state]`` (how the recurrence is written)
    as a pool holds it: ``[..., d_state, heads * head_dim]``."""
    *lead, heads, head_dim, d_state = state.shape
    return jnp.moveaxis(
        state.reshape(*lead, heads * head_dim, d_state), -1, -2)


def from_pool_layout(state, heads: int):
    """The inverse of :func:`to_pool_layout`."""
    *lead, d_state, inner = state.shape
    return jnp.moveaxis(state, -1, -2).reshape(
        *lead, heads, inner // heads, d_state)


def tail_to_pool_layout(tail):
    """``[..., d_conv - 1, conv_dim]`` (a sequence's last pre-convolution
    columns, oldest first) as the tail pool holds it: ``[..., tiles,
    128]``, the columns end to end, padded with zeros to whole 128-lane
    tiles."""
    *lead, taps, width = tail.shape
    flat = jnp.pad(tail.reshape(*lead, taps * width),
                   [(0, 0)] * len(lead) + [(0, -(taps * width) % _LANES)])
    return flat.reshape(*lead, -1, _LANES)


def tail_from_pool_layout(tail, shape):
    """The inverse of :func:`tail_to_pool_layout`; ``shape`` is
    ``(d_conv - 1, conv_dim)``."""
    *lead, tiles, lanes = tail.shape
    taps, width = shape
    return tail.reshape(*lead, tiles * lanes)[..., :taps * width].reshape(
        *lead, taps, width)


def live_entries(entries):
    """``(ids, rows, count)`` of the slots whose pool entry is not the
    trash entry, compacted in slot order: ``ids[i]`` the i-th live slot's
    entry and ``rows[i]`` its slot, both ``[slots]`` int32 and, past
    ``count[0]``, held at the last live one's (all 0 where none is
    live). One call a tick serves every layer's update."""
    entries = entries.astype(jnp.int32)
    live = entries != TRASH_ENTRY
    count = jnp.sum(live.astype(jnp.int32))
    order = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    at = jnp.minimum(jnp.arange(entries.shape[0], dtype=jnp.int32),
                     jnp.maximum(count - 1, 0))
    rows = jnp.where(count > 0, order[at], 0)
    return entries[rows], rows, count[None]


def _check_update_shapes(pool, tail_pool, entries, tail, x, dt, a, b, c,
                         layer):
    if pool.ndim != 4 or tail_pool.ndim != 4 or x.ndim != 3:
        raise ValueError(
            f"a state pool is [layers, entries, d_state, heads * head_dim], "
            f"its tail pool [layers, entries, tiles, {_LANES}] and x "
            f"[slots, heads, head_dim]; got {pool.shape}, {tail_pool.shape} "
            f"and {x.shape}"
        )
    _, _, d_state, inner = pool.shape
    slots, heads = entries.shape[0], x.shape[1]
    groups = b.shape[1] if b.ndim == 3 else 1
    grouped = (slots, groups, d_state) if b.ndim == 3 else (slots, d_state)
    want = {"x": (slots, heads, inner // heads), "dt": (slots, heads),
            "a": (slots, heads), "b": grouped, "c": grouped}
    got = {"x": x.shape, "dt": dt.shape, "a": a.shape, "b": b.shape,
           "c": c.shape}
    if (entries.ndim != 1 or got != want or inner % heads
            or heads % groups):
        raise ValueError(
            f"for {slots} slots over a pool {pool.shape}: expected {want}, "
            f"got {got}"
        )
    held = (*pool.shape[:2], -(-math.prod(tail.shape[1:]) // _LANES), _LANES)
    if tail.ndim != 3 or tail.shape[0] != slots or tail_pool.shape != held:
        raise ValueError(
            f"for {slots} slots over a pool {pool.shape}: expected tails "
            f"[{slots}, d_conv - 1, conv_dim] and their pool {held}, got "
            f"{tail.shape} and {tail_pool.shape}"
        )
    if not 0 <= layer < pool.shape[0]:
        raise ValueError(
            f"layer {layer} outside the pool's {pool.shape[0]} layers"
        )


def _rows(x, dt, a):
    """``(a_t, D_t x_t)`` as float32 rows ``[slots, heads * head_dim]``,
    a head's decay repeated over its lanes."""
    f32 = jnp.float32
    slots, heads, head_dim = x.shape
    stepped = (dt.astype(f32)[..., None] * x.astype(f32)).reshape(slots, -1)
    return jnp.repeat(a.astype(f32), head_dim, axis=1), stepped


def ssm_state_update_reference(pool, tail_pool, entries, tail, x, dt, a, b,
                               c, *, layer: int = 0):
    """The contract in plain ``jax.numpy``: gather the slots' states of
    one layer, ``H <- a H + (dt x) B^T``, ``y = H C`` (float32, from the
    state before it is rounded to the pool's dtype), scatter the live
    slots' states and their new tails back. Returns ``(y [slots, heads,
    head_dim] float32, pool, tail_pool)``; an idle slot's ``y`` is zero
    and no entry but the live slots' is written, in either pool."""
    _check_update_shapes(pool, tail_pool, entries, tail, x, dt, a, b, c,
                         layer)
    f32 = jnp.float32
    live = entries != TRASH_ENTRY
    slots, heads, head_dim = x.shape
    decay, stepped = _rows(x, dt, a)
    state = pool[layer, entries].astype(f32)  # [slots, d_state, inner]

    def columns(v):
        """``[slots, (groups,) d_state]`` as ``[slots, d_state, inner]``:
        each lane its group's column."""
        v = v.astype(f32).reshape(slots, -1, v.shape[-1])
        return jnp.repeat(jnp.swapaxes(v, 1, 2), state.shape[2] // v.shape[1],
                          axis=2)

    moved = state * decay[:, None, :] + columns(b) * stepped[:, None, :]
    y = jnp.sum(moved * columns(c), axis=1)
    # Idle slots are sent past the pools and dropped: the trash entry, and
    # every entry no live slot names, stays bit for bit what it was.
    where = jnp.where(live, entries, pool.shape[1])
    pool = pool.at[layer, where].set(moved.astype(pool.dtype), mode="drop")
    tail_pool = tail_pool.at[layer, where].set(
        tail_to_pool_layout(tail).astype(tail_pool.dtype), mode="drop")
    return jnp.where(live[:, None, None],
                     y.reshape(slots, heads, head_dim), 0.0), pool, tail_pool


def _update_kernel(ids_ref, rows_ref, count_ref, s_ref, t_ref, a_ref, x_ref,
                   b_ref, c_ref, n_ref, o_ref, w_ref, y_ref):
    del ids_ref, rows_ref  # read by the index maps
    from jax.experimental import pallas as pl

    step = pl.program_id(0)
    count = count_ref[0]
    d_state = s_ref.shape[0]

    @pl.when(step < count)
    def _move():
        # B and C as columns (their entry n on sublane n of every lane):
        # a row broadcast over the sublanes, transposed, once a state
        # and group.
        def column(row_ref, group):
            return jnp.broadcast_to(row_ref[group:group + 1, :],
                                    (_LANES, d_state)).T

        groups = b_ref.shape[0]
        tiles = a_ref.shape[0] // groups  # 128 lanes of the state each
        for group in range(groups):
            b, c = column(b_ref, group), column(c_ref, group)
            for tile in range(group * tiles, (group + 1) * tiles):
                lanes = slice(tile * _LANES, (tile + 1) * _LANES)
                moved = (s_ref[:, lanes].astype(jnp.float32)
                         * a_ref[tile:tile + 1, :]
                         + b * x_ref[tile:tile + 1, :])  # [d_state, 128]
                o_ref[:, lanes] = moved.astype(o_ref.dtype)
                y_ref[tile:tile + 1, :] = jnp.sum(moved * c, axis=0,
                                                  keepdims=True)
        w_ref[...] = n_ref[...]  # the slot's new tail over its old one

    @pl.when((step == 0) & (count == 0))
    def _nothing_live():
        # The blocks held are the trash entry's: written back as read.
        o_ref[...] = s_ref[...]
        w_ref[...] = t_ref[...]


@functools.lru_cache(maxsize=None)
def _jitted(layer: int, interpret: bool):
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    from ..parallel._compat import pallas_tpu_compiler_params

    def ssm_state_update(pool, tail_pool, ids, rows, count, tail, x, dt, a,
                         b, c):
        slots, heads, head_dim = x.shape
        _, _, d_state, inner = pool.shape
        f32 = jnp.float32
        tiles = inner // _LANES

        def entry_index(i, ids_ref, rows_ref, count_ref):
            return layer, ids_ref[i], 0, 0

        def row_index(i, ids_ref, rows_ref, count_ref):
            return rows_ref[i], 0, 0

        def trash_index(i, ids_ref, rows_ref, count_ref):
            return layer, TRASH_ENTRY, 0, 0

        state_block = pl.BlockSpec((None, None, d_state, inner), entry_index)
        # One sequence's tail of one layer, whole, as the pool holds it.
        # No old tail is read but the trash entry's, once a call (the
        # same block at every step): what is written back as read where
        # nothing is live.
        held = (None, None, *tail_pool.shape[2:])
        old_tail = pl.BlockSpec(held, trash_index)
        tail_block = pl.BlockSpec(held, entry_index)
        new_tail = pl.BlockSpec(held[1:], row_index)
        # A slot's row of ``inner`` numbers as ``[inner / 128, 128]``:
        # whole tiles (a ``[1, inner]`` block is padded to eight rows on
        # its way: 9% of the state's own bytes), lane tile ``j`` of the
        # state meeting sublane ``j`` of the row.
        tiled = pl.BlockSpec((None, tiles, _LANES), row_index)
        b, c = (v.astype(f32).reshape(slots, -1, d_state) for v in (b, c))
        narrow = pl.BlockSpec((None, b.shape[1], d_state), row_index)
        decay, stepped = _rows(x, dt, a)
        pool, tail_pool, y = pl.pallas_call(
            _update_kernel,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=3,
                grid=(slots,),
                in_specs=[state_block, old_tail, tiled, tiled, narrow,
                          narrow, new_tail],
                out_specs=[state_block, tail_block, tiled],
            ),
            out_shape=[
                jax.ShapeDtypeStruct(pool.shape, pool.dtype),
                jax.ShapeDtypeStruct(tail_pool.shape, tail_pool.dtype),
                jax.ShapeDtypeStruct((slots, tiles, _LANES), f32),
            ],
            # Operands 3 and 4 (after the three prefetched scalars) are
            # the two pools.
            input_output_aliases={3: 0, 4: 1},
            compiler_params=pallas_tpu_compiler_params(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=_VMEM_LIMIT_BYTES,
            ),
            interpret=interpret,
            name=_KERNEL_NAME,
        )(
            ids, rows, count, pool, tail_pool,
            decay.reshape(slots, tiles, _LANES),
            stepped.reshape(slots, tiles, _LANES),
            b, c, tail_to_pool_layout(tail).astype(tail_pool.dtype),
        )
        # A slot the walk never reached left its row of ``y`` unwritten.
        reached = jnp.zeros((slots,), bool).at[rows].set(count[0] > 0)
        return jnp.where(reached[:, None, None],
                         y.reshape(slots, heads, head_dim), 0.0
                         ), pool, tail_pool

    return jax.jit(ssm_state_update)


def ssm_state_update(pool, tail_pool, entries, tail, x, dt, a, b, c, *,
                     layer: int = 0, live=None,
                     interpret: bool | None = None):
    """One token a slot, for the LIVE slots of one layer, in place: the
    state ``H <- a H + (dt x) B^T`` in ``pool`` ``[layers, entries,
    d_state, heads * head_dim]`` with ``y = H C`` read out, and the new
    convolution tail over the old one in ``tail_pool`` ``[layers, entries,
    tiles, 128]`` (:func:`tail_to_pool_layout`); see the module docstring.
    ``entries`` ``[slots]`` int32 names each slot's pool entry (0, the
    trash entry: an idle slot); ``tail`` ``[slots, d_conv - 1,
    conv_dim]`` (rounded to the tail pool's dtype), ``x`` ``[slots,
    heads, head_dim]``, ``dt`` and ``a`` ``[slots, heads]``, ``b`` and
    ``c`` ``[slots, groups, d_state]`` (or ``[slots, d_state]``: one
    group). ``live``: :func:`live_entries` of
    ``entries`` where the caller has it (one call a tick for all layers).
    Returns ``(y [slots, heads, head_dim] float32, pool, tail_pool)``,
    ``y`` zero for idle slots, whose entry (the trash entry) is written in
    neither pool. In place where the caller's program donates the pools
    (the engine's decode step). ``interpret=None``: the compiled kernel on
    a TPU backend (states of whole 128-lane tiles), the reference's
    arithmetic elsewhere; ``True``: the kernel in Pallas interpret mode
    (the tests)."""
    _check_update_shapes(pool, tail_pool, entries, tail, x, dt, a, b, c,
                         layer)
    if interpret is None:
        # The kernel walks whole 128-lane tiles of whole sublane tiles,
        # a group of heads whole tiles too.
        groups = b.shape[1] if b.ndim == 3 else 1
        if (jax.default_backend() != "tpu"
                or pool.shape[3] % (groups * _LANES) or pool.shape[2] % 8):
            return ssm_state_update_reference(
                pool, tail_pool, entries, tail, x, dt, a, b, c, layer=layer)
        interpret = False
    ids, rows, count = live_entries(entries) if live is None else live
    return _jitted(int(layer), bool(interpret))(
        pool, tail_pool, ids, rows, count, tail, x, dt, a, b, c)


def ssd_chunk_scan(x, dt, a_rate, b, c, *, chunk: int, initial_state=None):
    """The recurrence over whole sequences, chunk by chunk; see the
    module docstring. ``x`` ``[batch, seq, heads, head_dim]`` (its dtype
    is the matmuls' operand dtype), ``dt`` ``[batch, seq, heads]`` float32
    (the step, 0 at positions that are padding), ``a_rate`` ``[heads]``
    (``A``, negative), ``b`` and ``c`` ``[batch, seq, d_state]``: one
    group for all heads, or ``[batch, seq, groups, d_state]``: the groups
    are independent recurrences over their own heads, so they are folded
    into the batch and one group is the case that folds nothing.
    ``initial_state`` ``[batch, heads, head_dim, d_state]`` (default
    zeros). Returns ``(y [batch, seq, heads, head_dim] float32, the state
    after the last position, float32)``."""
    f32 = jnp.float32
    if b.ndim == 4:
        return _scan_by_group(x, dt, a_rate, b, c, chunk, initial_state)
    batch, seq, heads, head_dim = x.shape
    d_state = b.shape[-1]
    dtype = x.dtype
    pad = (-seq) % chunk
    if pad:
        # Steps of 0: nothing enters, nothing decays.
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
    chunks = (seq + pad) // chunk

    def by_chunk(v):
        return jnp.moveaxis(
            v.reshape(batch, chunks, chunk, *v.shape[2:]), 1, 0)

    a_rate = a_rate.astype(f32)
    causal = jnp.tril(jnp.ones((chunk, chunk), bool))[None, :, :, None]

    def one_chunk(state, inputs):
        xc, dtc, bc, cc = inputs  # [batch, chunk, ...]
        dtc = dtc.astype(f32)
        bc, cc = bc.astype(dtype), cc.astype(dtype)
        run = jnp.cumsum(dtc * a_rate, axis=1)  # l_t [batch, chunk, heads]
        last = run[:, -1]  # [batch, heads]
        stepped = dtc[..., None] * xc.astype(f32)  # D_s x_s
        # Inside the chunk: (C_t . B_s) exp(l_t - l_s), s <= t, a head.
        meet = jnp.einsum("btn,bsn->bts", cc, bc,
                          preferred_element_type=f32)
        decay = jnp.exp(jnp.where(
            causal, run[:, :, None, :] - run[:, None, :, :], -jnp.inf))
        y = jnp.einsum(
            "btsh,bshp->bthp", (meet[..., None] * decay).astype(dtype),
            stepped.astype(dtype), preferred_element_type=f32,
        )
        # What the chunk's first state still adds at position t.
        y = y + jnp.exp(run)[..., None] * jnp.einsum(
            "btn,bhpn->bthp", cc, state.astype(dtype),
            preferred_element_type=f32,
        )
        entered = jnp.einsum(
            "bshp,bsn->bhpn",
            (stepped * jnp.exp(last[:, None] - run)[..., None]).astype(dtype),
            bc, preferred_element_type=f32,
        )
        return state * jnp.exp(last)[:, :, None, None] + entered, y

    if initial_state is None:
        initial_state = jnp.zeros((batch, heads, head_dim, d_state), f32)
    state, y = jax.lax.scan(
        one_chunk, initial_state.astype(f32),
        (by_chunk(x), by_chunk(dt), by_chunk(b), by_chunk(c)),
    )
    y = jnp.moveaxis(y, 0, 1).reshape(batch, seq + pad, heads, head_dim)
    return y[:, :seq], state


def _scan_by_group(x, dt, a_rate, b, c, chunk, initial_state):
    """:func:`ssd_chunk_scan` with a group axis on ``b`` and ``c``: each
    group's heads as a batch row of their own (``a_rate`` a row too)."""
    batch, seq, heads, head_dim = x.shape
    groups, d_state = b.shape[2:]
    if heads % groups:
        raise ValueError(f"{heads} heads are not whole groups of {groups}")
    per_group = heads // groups

    def fold(v):
        """``[batch, seq, groups, ...]`` as ``[batch * groups, seq, ...]``."""
        return jnp.moveaxis(v, 2, 1).reshape(batch * groups, seq, *v.shape[3:])

    if initial_state is not None:
        initial_state = initial_state.reshape(
            batch * groups, per_group, head_dim, d_state)
    y, state = ssd_chunk_scan(
        fold(x.reshape(batch, seq, groups, per_group, head_dim)),
        fold(dt.reshape(batch, seq, groups, per_group)),
        jnp.tile(a_rate.reshape(1, groups, 1, per_group),
                 (batch, 1, 1, 1)).reshape(batch * groups, 1, per_group),
        fold(b), fold(c), chunk=chunk, initial_state=initial_state,
    )
    y = jnp.moveaxis(y.reshape(batch, groups, seq, per_group, head_dim), 1, 2)
    return (y.reshape(batch, seq, heads, head_dim),
            state.reshape(batch, heads, head_dim, d_state))
