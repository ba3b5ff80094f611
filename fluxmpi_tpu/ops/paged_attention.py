"""Decode attention over a paged K/V pool, read in place through the
block tables.

The serving engine's decode tick attends ONE query row per batch slot
against that slot's cached keys and values, which live scattered over a
shared pool of fixed-size blocks (:mod:`fluxmpi_tpu.serving.cache`).
:func:`paged_decode_attention` reads the pool where it lies: the kernel's
K/V block index is ``tables[slot, j]``, taken from scalar-prefetched
block tables, so no per-slot contiguous copy of a cache is ever built.

Contract (shared by the kernel and its plain reference,
:func:`paged_decode_reference`):

- ``q`` is ``[slots, heads, head_dim]``; the pools are ``[layers,
  num_blocks, block_size, kv_heads * head_dim]`` (a block is one
  contiguous ``[block_size, kv_heads * head_dim]`` tile: no relayout, and
  no 64-wide minor dimension padded to 128 lanes); ``layer`` picks the
  pool's leading index statically, inside the block index map.
  ``kv_heads`` divides ``heads``: each K/V head serves ``heads //
  kv_heads`` consecutive query heads (grouped-query attention).
- ``lengths[slot]`` counts the slot's positions ``0 .. length - 1``
  (the new token's row included: the caller writes it first). Position
  ``p`` lives at ``(tables[slot, p // block_size], p % block_size)``.
- With ``window``, a slot attends positions ``length - window ..
  length - 1`` only, and its table is a RING of ``tables.shape[1]``
  blocks: position ``p`` lives at ``(tables[slot, (p // block_size) %
  ring], p % block_size)``, so a sequence longer than the ring
  overwrites what the window has left behind (the ring must hold
  ``window + block_size`` positions). Only the blocks that meet the
  window are visited; the first one's head is masked by position.
- The kernel WALKS THE LIVE BLOCKS ONLY (:func:`live_block_walk`): one
  flat list of visits ``(slot, pool block, block index)``, every live
  slot's live blocks in order (a window layer's: those that meet the
  window), scalar prefetched, under a grid bound that is the list's
  COUNT, so a call's steps follow what is live and not ``slots x table
  width`` (a step that neither computed nor fetched still cost ~0.19 us:
  4,000 of them a tick were a seventh of it). The K/V index map reads
  the visit's block, so the pipeline fetches the next slot's first block
  behind a slot's last; a slot's first visit resets the statistics, its
  last writes its rows. The tail of the last live block is masked by
  position. So whatever the table's unused entries point at (the trash
  block) and whatever finite garbage lies past a length never reaches
  the output, and an all-trash table is never visited (where NOTHING is
  live the call's one step fetches the first table's first entry, the
  trash block, and computes nothing). The list is made
  on the device from ``lengths`` (and the window): once a call, or once
  a tick for every layer that shares the table where the caller hands
  it in (``walk=``). At full tables the walk is the old grid. Pallas
  interpret mode takes no dynamic grid bound: there the grid is the
  list's whole length and the steps past the count do nothing.
- A slot of length 0 (an idle slot: all-trash table) outputs zeros: the
  output is ONE block, zeroed by the call's first step and written back
  once, and no visit names a dead slot (where nothing is live the call
  is that one step).
- Softmax statistics and accumulators are float32 over operands in the
  pool's dtype, as in :mod:`fluxmpi_tpu.ops.flash_attention`; the output
  has ``q``'s dtype.

All heads of a slot share one visit. The per-head products come out
of two plain matmuls over the folded ``kv_heads * head_dim`` lanes: the
query rows are laid out block-diagonally (``[heads, kv_heads *
head_dim]``, head ``h``'s query in its K/V head's lanes, zeros
elsewhere), so ``q_bd @ k.T`` is ``[heads, block_size]`` scores with no
per-head slicing, and of ``p @ v`` (``[heads, kv_heads * head_dim]``)
each head keeps its K/V head's lanes.

**A cache of latent rows** (:func:`paged_latent_decode_attention`, plain
reference :func:`paged_latent_decode_reference`): a latent-attention
layer keeps ONE row a token, ``[c; k_r]`` (a compressed latent of
``rank`` lanes and one rotary key shared by all heads), in ONE pool
``[layers, num_blocks, block_size, width]``, ``width >= rank + rope``
(the cache pads a row to whole 128-lane tiles with zeros: the chip's
compiler COPIES a pool whose rows are not, 7.1 GB of temporaries at 1,089
blocks of 1,024 rows of 576; the queries are padded with zeros to match,
so the lanes past ``rank + rope`` never count). Its decode step brings
the ABSORBED queries: ``q_abs`` ``[slots, heads, rank]`` (each head's
query carried through its key projection, so it meets ``c`` directly) and
``q_rope`` ``[slots, heads, rope]``, both already carrying the softmax
scale. The score of head ``h`` against position ``p`` is ``q_abs[h] .
c[p] + q_rope[h] . k_r[p]`` and the result ``sum_p softmax(score)[p]
c[p]`` (``[slots, heads, rank]``: the caller carries it through the value
projection): every live block is fetched ONCE for all heads and used as
key (all its lanes) and as value (the first ``rank``). Tables, lengths,
the trash block, idle slots and the tail mask are as above; no window.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..parallel._compat import pallas_tpu_compiler_params
from .flash_attention import _LANES, _NEG_INF, _SUBLANES

__all__ = ["live_block_walk", "paged_decode_attention",
           "paged_decode_reference", "paged_latent_decode_attention",
           "paged_latent_decode_reference"]

# The chip's compiler names a Mosaic call's instruction by the last
# component of its path: the latent kernel's jitted wrapper and its
# ``pallas_call`` both carry this name, and the benchmark's readers find
# the kernel by it.
_LATENT_KERNEL_NAME = "paged_latent_decode"


def live_block_walk(tables, lengths, *, window: int | None = None,
                    block_size: int):
    """The visits one decode kernel call makes, in order: ``(slot, block,
    index, count)``, the first three ``[slots * tables.shape[1]]`` int32
    (what a call over full tables walks), ``count`` ``[1]``. Visit ``i <
    count[0]`` reads pool block ``block[i]``, which holds positions
    ``index[i] * block_size ...`` of slot ``slot[i]``: every slot's live
    blocks (with ``window``: those that meet it, found on the ring), slot
    by slot, first block first. Past ``count`` the arrays hold the last
    visit (all 0, and the table's first entry, where nothing is live).
    One call a tick serves every layer that shares the table."""
    slots, num_j = tables.shape
    tables, lengths = tables.astype(jnp.int32), lengths.astype(jnp.int32)
    with jax.named_scope("kv_walk"):
        first = _first_block(lengths, block_size, window)
        visits = -(-lengths // block_size) - first  # a slot's; 0: idle
        ends = jnp.cumsum(visits)
        starts, count = ends - visits, ends[-1]
        at = jnp.minimum(jnp.arange(slots * num_j, dtype=jnp.int32),
                         jnp.maximum(count - 1, 0))[:, None]
        # The one slot whose visits hold ``at`` (none where nothing is
        # live: slot 0, index 0), and what the visit reads of it.
        mine = (starts[None, :] <= at) & (at < ends[None, :])

        def of_slot(values):
            return jnp.sum(jnp.where(mine, values[None, :], 0), axis=1)

        slot = of_slot(jnp.arange(slots, dtype=jnp.int32))
        index = of_slot(first - starts) + at[:, 0]
        # A window's table is a ring.
        block = tables.reshape(-1)[slot * num_j + index % num_j]
        return slot, block, index, count[None]


def _first_block(length, block_size, window):
    """The first block a slot of ``length`` positions attends."""
    if window is None:
        return jnp.zeros_like(length)
    return jnp.maximum(length - window, 0) // block_size


def _online_softmax(s, live, m_scratch, l_scratch):
    """One block's scores ``s`` ``[rows, block_size]`` (``live``: the
    positions that count) into the running maximum and sum; returns the
    block's weights ``p`` and the factor ``[rows, 1]`` that carries the
    accumulator over to the new maximum."""
    s = jnp.where(live, s, _NEG_INF)
    m_prev = m_scratch[...]  # [rows, 128], value replicated over lanes
    m_new = jnp.maximum(
        m_prev, jnp.broadcast_to(jnp.max(s, axis=1, keepdims=True),
                                 m_prev.shape))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.where(live, jnp.exp(s - m_new[:, :1]), 0.0)
    l_scratch[...] = l_scratch[...] * alpha + jnp.broadcast_to(
        jnp.sum(p, axis=1, keepdims=True), alpha.shape)
    m_scratch[...] = m_new
    return p, alpha[:, :1]


def _visit(slot_ref, index_ref, count_ref, lengths_ref, o_ref, scratch,
           block_size, window, attend, result):
    """One grid step of a walk (:func:`live_block_walk`): ``attend(base,
    length)`` folds the visited block, whose first position is ``base``,
    into the ``scratch`` statistics and accumulator, which a slot's
    first visit resets and after whose last ``result()`` is written to
    the slot's rows of ``o_ref``. ``o_ref`` is the WHOLE
    output, zeroed by the first step: a slot no visit names keeps
    zeros."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _zero():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Interpret mode takes no dynamic grid bound: there the grid is the
    # walk's whole length, and the steps past the count do nothing.
    @pl.when(i < count_ref[0])
    def _live():
        slot = slot_ref[i]
        length = lengths_ref[slot]
        base = index_ref[i] * block_size
        m_scratch, l_scratch, acc_scratch = scratch

        @pl.when(index_ref[i] == _first_block(length, block_size, window))
        def _init():
            m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
            l_scratch[...] = jnp.zeros_like(l_scratch)
            acc_scratch[...] = jnp.zeros_like(acc_scratch)

        attend(base, length)

        @pl.when(base + block_size >= length)
        def _finish():
            o_ref[slot] = result().astype(o_ref.dtype)


def _normalised(l_scratch, acc_scratch):
    l_final = l_scratch[...][:, :1]
    return acc_scratch[...] / jnp.where(l_final == 0.0, 1.0, l_final)


def _paged_decode_kernel(
    slot_ref, block_ref, index_ref, count_ref, lengths_ref,
    q_ref, k_ref, v_ref, o_ref, m_scratch, l_scratch, acc_scratch,
    *, sm_scale: float, head_dim: int, block_size: int, group: int,
    window: int | None,
):
    del block_ref  # read by the index maps
    # heads (padded to whole sublane tiles), kv_heads * head_dim
    rows, width = acc_scratch.shape

    def own_lanes():
        # own[h, c]: lane c of the folded minor dimension belongs to the
        # K/V head of query head h. Padding rows (h >= heads) own nothing.
        head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
        if group > 1:
            head = head // group
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        return (lane >= head * head_dim) & (lane < (head + 1) * head_dim)

    def attend(base, length):
        k = k_ref[...]  # [block_size, width]; q_ref: the visit's slot's
        q = q_ref[0].astype(jnp.float32)
        if group > 1:
            # [rows, head_dim] -> every K/V head's lanes hold the row.
            q = jnp.concatenate([q] * (width // head_dim), axis=1)
        # The select runs on 32-bit tiles (the mask comes from int32
        # iotas); the rounding back to the pool's dtype is exact.
        q_bd = jnp.where(own_lanes(), q, 0.0).astype(k.dtype)
        s = jax.lax.dot_general(
            q_bd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [rows, block_size]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        live = pos < length
        if window is not None:
            live &= pos >= length - window
        p, alpha = _online_softmax(s, live, m_scratch, l_scratch)
        # [rows, width]: every head's values; each keeps its own lanes.
        pv = jax.lax.dot_general(
            p, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scratch[...] = acc_scratch[...] * alpha + pv

    def result():
        out = jnp.where(own_lanes(), _normalised(l_scratch, acc_scratch), 0.0)
        if group > 1:
            # Row h keeps its K/V head's head_dim lanes: [rows, head_dim].
            return sum(
                out[:, c:c + head_dim] for c in range(0, width, head_dim)
            )
        return jnp.sum(out, axis=0, keepdims=True)

    _visit(slot_ref, index_ref, count_ref, lengths_ref, o_ref,
           (m_scratch, l_scratch, acc_scratch), block_size, window,
           attend, result)


def _check_shapes(q, k_pool, v_pool, tables, lengths, layer, window):
    """Returns ``heads // kv_heads``."""
    slots, heads, head_dim = q.shape
    if k_pool.shape != v_pool.shape or k_pool.ndim != 4:
        raise ValueError(
            f"k_pool and v_pool must share one [layers, num_blocks, "
            f"block_size, kv_heads * head_dim] shape; got {k_pool.shape} "
            f"and {v_pool.shape}"
        )
    kv_heads, rest = divmod(k_pool.shape[3], head_dim)
    if rest or not kv_heads or heads % kv_heads:
        raise ValueError(
            f"pool minor dimension {k_pool.shape[3]} is not kv_heads * "
            f"head_dim for a kv_heads that divides {heads} heads of "
            f"{head_dim}"
        )
    tables_ok = tables.ndim == 2 and tables.shape[0] == slots
    if not tables_ok or lengths.shape != (slots,):
        raise ValueError(
            f"tables must be [slots, max_blocks] and lengths [slots] for "
            f"{slots} slots; got {tables.shape} and {lengths.shape}"
        )
    if not 0 <= layer < k_pool.shape[0]:
        raise ValueError(
            f"layer {layer} outside the pool's {k_pool.shape[0]} layers"
        )
    if window is not None and not (
        1 <= window <= (tables.shape[1] - 1) * k_pool.shape[2]
    ):
        raise ValueError(
            f"a ring of {tables.shape[1]} blocks of {k_pool.shape[2]} "
            f"cannot hold a window of {window} and one block more"
        )
    return heads // kv_heads


def _walk_call(kernel, walk, lengths, operands, in_specs, out_shape,
               scratch, interpret, **kwargs):
    """``kernel`` once a visit of ``walk`` (:func:`live_block_walk`; one
    step where nothing is live: the output is zeroed there). The walk's
    four arrays and ``lengths`` are scalar prefetched ahead of
    ``operands``, so an index map of ``in_specs`` sees ``(i, slot, block,
    index, count, lengths)``. The output is ONE block, written back once.
    ``scratch``: the rows and lanes of the float32 accumulator, under the
    running maximum and sum."""
    from jax.experimental.pallas import tpu as pltpu

    slot, block, index, count = walk
    rows, lanes = scratch
    # The chip walks ``count`` steps; interpret mode takes no dynamic
    # bound and walks the whole list.
    steps = slot.shape[0] if interpret else jnp.maximum(count[0], 1)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=(steps,),
            in_specs=in_specs,
            out_specs=pl.BlockSpec(
                out_shape.shape, lambda i, *_: (0,) * len(out_shape.shape)),
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, lanes), jnp.float32),
            ],
        ),
        out_shape=out_shape,
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("arbitrary",),
        ),
        interpret=interpret,
        **kwargs,
    )(slot, block, index, count, lengths.astype(jnp.int32), *operands)


def _visited_rows(i, slot_ref, *_):
    return slot_ref[i], 0, 0


def _visited_block(layer, block_shape):
    """The pool block a visit reads, of ``layer``."""
    return pl.BlockSpec(
        (None, None, *block_shape),
        lambda i, slot_ref, block_ref, *_: (layer, block_ref[i], 0, 0))


@functools.partial(jax.jit, static_argnames=("layer", "window", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    layer: int = 0,
    window: int | None = None,
    walk=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One query row per slot against its paged cache; see the module
    docstring for the contract. Returns ``[slots, heads, head_dim]`` in
    ``q``'s dtype. ``walk``: :func:`live_block_walk` of ``tables``,
    ``lengths`` and ``window`` where the caller has it (one call a tick
    for all layers that share the table). ``interpret=None`` runs the
    compiled kernel on a TPU backend and Pallas interpret mode
    elsewhere."""
    group = _check_shapes(q, k_pool, v_pool, tables, lengths, layer, window)
    slots, heads, head_dim = q.shape
    _, _, block_size, width = k_pool.shape
    rows = -(-heads // _SUBLANES) * _SUBLANES
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    if walk is None:
        walk = live_block_walk(tables, lengths, window=window,
                               block_size=block_size)

    kv_spec = _visited_block(layer, (block_size, width))
    if group > 1:
        # One query row a head, each head_dim wide, padded to whole
        # sublane tiles.
        q_rows = jnp.pad(q, ((0, 0), (0, rows - heads), (0, 0)))
    else:
        q_rows = q.reshape(slots, 1, width)
    out = _walk_call(
        functools.partial(
            _paged_decode_kernel, sm_scale=1.0 / (head_dim**0.5),
            head_dim=head_dim, block_size=block_size, group=group,
            window=window,
        ),
        walk, lengths, (q_rows, k_pool, v_pool),
        [pl.BlockSpec((1, *q_rows.shape[1:]), _visited_rows), kv_spec,
         kv_spec],
        jax.ShapeDtypeStruct(q_rows.shape, q.dtype), (rows, width),
        interpret,
    )
    if group > 1:
        return out[:, :heads]
    return out.reshape(slots, heads, head_dim)


def paged_decode_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    layer: int = 0,
    window: int | None = None,
) -> jnp.ndarray:
    """The same contract in plain ``jax.numpy``: gather one layer's
    tabled blocks, dense scores, a position mask. The kernel's reference
    in the tests, and the engine's ``attention="naive"`` route."""
    group = _check_shapes(q, k_pool, v_pool, tables, lengths, layer, window)
    slots, heads, head_dim = q.shape
    block_size, ring = k_pool.shape[2], tables.shape[1]
    k = k_pool[layer][tables].reshape(slots, -1, heads // group, head_dim)
    v = v_pool[layer][tables].reshape(slots, -1, heads // group, head_dim)
    qg = q.reshape(slots, heads // group, group, head_dim)
    s = jnp.einsum(
        "skgd,stkd->skgt", qg.astype(k.dtype), k,
        preferred_element_type=jnp.float32,
    ) / (head_dim**0.5)
    # The position each gathered row holds: entry * block_size + offset,
    # or, in a ring, the newest position below length that maps there.
    pos = jnp.arange(k.shape[1])[None, :]
    if window is not None:
        last = (jnp.maximum(lengths, 1)[:, None] - 1) // block_size
        entry = pos // block_size
        pos = (last - (last - entry) % ring) * block_size + pos % block_size
    live = (pos >= 0) & (pos < lengths[:, None])
    if window is not None:
        live &= pos >= lengths[:, None] - window
    live = live[:, None, None, :]
    s = jnp.where(live, s, _NEG_INF)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "skgt,stkd->skgd", p / jnp.where(l == 0.0, 1.0, l),
        v.astype(jnp.float32),
    )
    return out.reshape(slots, heads, head_dim).astype(q.dtype)


# ---------------------------------------------------------------------------
# A cache of latent rows
# ---------------------------------------------------------------------------


def _paged_latent_kernel(
    slot_ref, block_ref, index_ref, count_ref, lengths_ref,
    qa_ref, qr_ref, kv_ref, o_ref, m_scratch, l_scratch, acc_scratch,
    *, rank: int, block_size: int,
):
    del block_ref  # read by the index map

    def attend(base, length):
        c = kv_ref[:, :rank]  # [block_size, rank]: key and value
        k_rope = kv_ref[:, rank:]
        contract_last = (((1,), (1,)), ((), ()))
        s = jax.lax.dot_general(
            qa_ref[0], c, contract_last, preferred_element_type=jnp.float32,
        ) + jax.lax.dot_general(
            qr_ref[0], k_rope, contract_last,
            preferred_element_type=jnp.float32,
        )  # [rows, block_size]
        pos = base + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        p, alpha = _online_softmax(s, pos < length, m_scratch, l_scratch)
        # p is narrowed to the pool's dtype only as an operand of its own
        # product; statistics and the accumulator stay float32.
        pv = jax.lax.dot_general(
            p.astype(c.dtype), c, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [rows, rank]
        acc_scratch[...] = acc_scratch[...] * alpha + pv

    _visit(slot_ref, index_ref, count_ref, lengths_ref, o_ref,
           (m_scratch, l_scratch, acc_scratch), block_size, None, attend,
           lambda: _normalised(l_scratch, acc_scratch))


def _check_latent_shapes(q_abs, q_rope, pool, tables, lengths, layer):
    slots, heads, rank = q_abs.shape
    if q_rope.shape[:2] != (slots, heads) or pool.ndim != 4 or (
        pool.shape[3] < rank + q_rope.shape[2]
    ):
        raise ValueError(
            f"q_abs [slots, heads, rank], q_rope [slots, heads, rope] and a "
            f"pool [layers, num_blocks, block_size, >= rank + rope]; got "
            f"{q_abs.shape}, {q_rope.shape} and {pool.shape}"
        )
    tables_ok = tables.ndim == 2 and tables.shape[0] == slots
    if not tables_ok or lengths.shape != (slots,):
        raise ValueError(
            f"tables must be [slots, max_blocks] and lengths [slots] for "
            f"{slots} slots; got {tables.shape} and {lengths.shape}"
        )
    if not 0 <= layer < pool.shape[0]:
        raise ValueError(
            f"layer {layer} outside the pool's {pool.shape[0]} layers"
        )


@functools.lru_cache(maxsize=None)
def _latent_jitted(layer: int, interpret: bool):
    def paged_latent_decode(q_abs, q_rope, pool, tables, lengths, walk):
        slots, heads, rank = q_abs.shape
        block_size, width = pool.shape[2:]
        rope = width - rank  # the pool's lanes past the latent
        rows = -(-heads // _SUBLANES) * _SUBLANES
        pad = (0, 0), (0, rows - heads)
        if walk is None:
            walk = live_block_walk(tables, lengths, block_size=block_size)

        out = _walk_call(
            functools.partial(
                _paged_latent_kernel, rank=rank, block_size=block_size),
            walk, lengths,
            (
                jnp.pad(q_abs, (*pad, (0, 0))).astype(pool.dtype),
                jnp.pad(
                    q_rope, (*pad, (0, rope - q_rope.shape[2]))
                ).astype(pool.dtype),
                pool,
            ),
            [
                pl.BlockSpec((1, rows, rank), _visited_rows),
                pl.BlockSpec((1, rows, rope), _visited_rows),
                _visited_block(layer, (block_size, width)),
            ],
            jax.ShapeDtypeStruct((slots, rows, rank), q_abs.dtype),
            (rows, rank), interpret, name=_LATENT_KERNEL_NAME,
        )
        return out[:, :heads]

    return jax.jit(paged_latent_decode)


def paged_latent_decode_attention(
    q_abs: jnp.ndarray,
    q_rope: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    layer: int = 0,
    walk=None,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """The absorbed queries of one token per slot against its paged cache
    of latent rows; see the module docstring for the contract. Returns
    ``[slots, heads, rank]`` in ``q_abs``'s dtype. ``walk`` and
    ``interpret`` as :func:`paged_decode_attention` takes them. Tiles come
    from the shapes alone: one block of the pool a grid step."""
    _check_latent_shapes(q_abs, q_rope, pool, tables, lengths, layer)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    return _latent_jitted(int(layer), bool(interpret))(
        q_abs, q_rope, pool, tables, lengths, walk
    )


def paged_latent_decode_reference(
    q_abs: jnp.ndarray,
    q_rope: jnp.ndarray,
    pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    layer: int = 0,
) -> jnp.ndarray:
    """The same contract in plain ``jax.numpy``: gather one layer's
    tabled blocks, dense scores, a position mask. The kernel's reference
    in the tests, and the engine's ``attention="naive"`` route."""
    _check_latent_shapes(q_abs, q_rope, pool, tables, lengths, layer)
    slots, _, rank = q_abs.shape
    width = rank + q_rope.shape[2]
    rows = pool[layer][tables].reshape(slots, -1, pool.shape[3])[..., :width]
    q = jnp.concatenate([q_abs, q_rope], axis=-1).astype(rows.dtype)
    s = jnp.einsum("shc,stc->sht", q, rows,
                   preferred_element_type=jnp.float32)
    live = (jnp.arange(rows.shape[1])[None, :] < lengths[:, None])[:, None, :]
    s = jnp.where(live, s, _NEG_INF)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "sht,stc->shc", p / jnp.where(l == 0.0, 1.0, l),
        rows[..., :rank].astype(jnp.float32),
    )
    return out.astype(q_abs.dtype)
