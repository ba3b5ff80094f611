"""Block/paged KV cache: heterogeneous sequence lengths share device HBM.

The research-API decode path (:mod:`fluxmpi_tpu.models.generate`)
allocates one contiguous ``[batch, max_len]`` KV cache per call — every
sequence pays for the longest possible one. A serving engine cannot: a
mixed workload of 8-token and 500-token requests sharing per-request
max-len rows wastes most of the pool. :class:`BlockKVCache` is the
vLLM-style answer scaled to this repo: a sequence's ``max_len`` cache
positions are cut into fixed-size **blocks**,

- the physical pool is ``[num_layers, num_blocks, block_size, heads *
  head_dim]`` per K and V (device-resident, donated through the decode
  and prefill steps so it updates in place). Heads are folded into the
  minor dimension: one block of one layer is a contiguous
  ``[block_size, heads * head_dim]`` tile that the decode kernel reads
  without a relayout, and a 64-wide head does not pad a 128-lane minor
  dimension to twice its bytes. :meth:`_Kind.pool_shape` is the one
  place that says so;
- a **free-list allocator** hands blocks to sequences at admission and
  takes them back at eviction — a freed block is immediately reusable
  by the next request (the free-list round-trip the serving tests
  assert);
- each sequence carries a **block table** (``[max_blocks_per_seq]``
  int32 row): logical position ``p`` of the sequence lives at pool slot
  ``(table[p // block_size], p % block_size)``. The decode step writes
  the new position's row there and attends **through the table**: the
  kernel's K/V block index is ``table[j]``, so the pool is read in
  place and no contiguous per-sequence copy exists
  (:func:`fluxmpi_tpu.ops.paged_attention.paged_decode_attention`).

**Four kinds of layer** (:attr:`BlockKVCache.kinds`), each a class below
that owns its pool(s), its free list, its tables AND the device-side code
that knows its rows (what a decode tick writes and reads, what a prefill
writes): **full** and **window** (:class:`_Kind`: K and V rows a token,
over the whole context or as a ring), **latent** (:class:`_Latent`: one
row a token and no V) and **state** (:class:`_State`: one entry a
SEQUENCE). The cache is built from the model's own records
(:class:`fluxmpi_tpu.models.decoder.Keeps`, one a KEEPING SUBLAYER:
:meth:`~fluxmpi_tpu.models.DecoderLM.cache_layers`), and a "layer" here
is one such sublayer, not one of the model's layers: a layer that keeps
nothing is none, and a layer that runs a state-space mixer and attention
side by side is two, one of the state kind and one of the full kind, so
a sequence then holds an entry of the state pool AND blocks of the K/V
pool, and :meth:`BlockKVCache.can_alloc` says no as soon as either runs
out.

**How a model reaches its cache: the protocol.** A served forward is
handed ONE view of the cache as the call argument ``cache``
(:class:`PrefillView` for a prompt, :class:`DecodeView` for a tick), and
the model hands each keeping sublayer the handle of its own number,
``cache.sublayer(n)``, ``n`` counting the model's records that are not
None. Nothing counts calls: the order sublayers run in is the model's own business. Every
handle says its ``kind`` (the record's) and whether it ``reads_pool``
(a tick: one token a row against what the pool holds) or serves the
call's own tokens (a prefill), and beyond that offers what its kind of
mixer needs and nothing else, arrays as the mixer has them (``[batch,
seq, heads, width]``; a tick's ``seq`` is 1):

- full, window: ``attend(q, k, v) -> out``. A tick writes the token's key
  and value rows at ``(table[pos // block_size], pos % block_size)`` and
  attends through the table; a prefill attends the prompt causally
  (within the window) and keeps ``k`` and ``v`` for the pool.
- latent: a prefill's ``attend(q, k, v, row) -> out`` attends the keys
  and values the layer rebuilt and keeps ``row`` alone; a tick's
  ``attend_absorbed(q_abs, q_rope, row) -> out`` writes ``row`` and
  attends the pool's rows with the layer's absorbed queries.
- state: a prefill's ``keep(tail, state)`` takes what is left of the
  prompt; a tick's ``tail() -> [slots, d_conv - 1, conv_dim]`` gives the
  slots' convolution tails and ``update(tail, x, step, decay, b, c) ->
  y`` moves the LIVE slots' states where they lie and writes their new
  tails over the old, in one walk
  (:func:`fluxmpi_tpu.ops.ssm.ssm_state_update`: idle slots' entries are
  neither read nor written).

A tick's view writes as it goes; a prefill's view only COLLECTS during
the forward and writes once after it, one scatter a kind. Either way the
step takes the pools back from :meth:`_View.pools`. A mixer checks the
``kind`` of what it is handed and raises while it is traced
(``models/decoder.py``), so a model that numbers its sublayers wrongly
fails loudly and never reads another layer's rows.

**Block 0 is the trash block**: it is never allocated. Unused table
entries point at it, masked prefill positions and idle batch slots
write into it, and decode attention never reads it into a result: a
sequence's length bounds the blocks its kernel visits and masks the
last one's tail by position (an idle slot has length 0) — so padding
and inactive slots need no special-case shapes.

Admission is **token-budget based**: a request reserves its worst-case
``ceil((prompt + max_new_tokens) / block_size)`` blocks up front, so an
admitted request can never strand mid-decode out of pool (the simple,
preemption-free contract; lazy growth + sequence preemption is the
follow-up documented in docs/serving.md). :meth:`fits_device` checks
the pool's byte footprint against the PR 9 memory plane's
``bytes_limit`` before any device allocation happens — an engine that
would OOM the chip refuses at construction, not at the first admission.
"""

from __future__ import annotations

import math
from typing import Any, Sequence

import numpy as np

import jax
import jax.numpy as jnp

__all__ = ["BlockKVCache", "DecodeView", "PrefillView",
           "blocks_for_tokens"]

TRASH_BLOCK = 0
_LANES = 128


def blocks_for_tokens(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` cache positions."""
    return -(-int(tokens) // int(block_size))


class _Handle:
    """One keeping sublayer's way into one view: layer ``layer`` of the
    view's kind ``at`` (module docstring: what each kind's offers)."""

    def __init__(self, view: "_View", at: int, layer: int):
        self.view, self.at, self.layer = view, at, layer
        self.of = view.cache.kinds[at]
        self.number = self.of.layer_ids[layer]  # among the model's
        self.kind = self.of.name
        self.reads_pool = view.reads_pool


class _Kind:
    """The sublayers that keep the same span of a sequence in the same
    form, their free list, their pools and the code that knows their
    rows. This class is the FULL kind (K and V rows a token, a table
    spanning ``max_len``) and, with ``window``, the WINDOW kind, whose
    table is a RING: a layer that attends a sliding window keeps at most
    ``window + block_size`` positions of a sequence, rounded up to blocks,
    position ``p`` at table entry ``(p // block_size) % ring``, so a long
    sequence costs it no more than its ring (one window size a model; the
    pool holds the rings of as many sequences as ``num_blocks`` holds at
    ``max_blocks_per_seq``). ``entries`` is the width of a sequence's
    table row for these layers, ``layer_ids`` the model's keeping
    sublayers that are of this kind, in order, ``width`` a row's (``heads
    * head_dim``). ``latent`` and ``state`` tell the other two kinds."""

    latent = False
    state = None
    pools = 2  # K and V

    def __init__(self, layer_ids: tuple[int, ...], entries: int,
                 num_blocks: int, width: int, window: int | None = None):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved trash "
                f"block), got {num_blocks}"
            )
        self.layer_ids = layer_ids
        self.window = window
        self.entries = entries
        self.num_blocks = num_blocks
        self.width = width
        # LIFO free list: the most recently freed block is handed out
        # next — the round-trip the reuse test pins down.
        self.free: list[int] = list(range(num_blocks - 1, 0, -1))
        self.k_pool = None
        self.v_pool = None

    @property
    def name(self) -> str:
        """The ``kind`` of the records this kind serves."""
        return "full" if self.window is None else "window"

    @property
    def layers(self) -> int:
        return len(self.layer_ids)

    # -- the pools -----------------------------------------------------

    def pool_shape(self, block_size: int) -> tuple[int, ...]:
        return (self.layers, self.num_blocks, block_size, self.width)

    def pool_bytes(self, block_size: int, itemsize: int) -> int:
        return self.pools * itemsize * math.prod(self.pool_shape(block_size))

    def make_pools(self, block_size: int, dtype) -> None:
        shape = self.pool_shape(block_size)
        self.k_pool = jnp.zeros(shape, dtype)
        self.v_pool = jnp.zeros(shape, dtype) if self.pools == 2 else None

    # -- a decode tick: write the token's rows, then read --------------

    def written_block(self, table, entry):
        """The block each slot's new position lies in: ``table[entry]``,
        ``entry`` ``[slots]`` counted in blocks from the sequence's start
        (a window kind's table is a ring)."""
        if self.window is not None:
            entry = entry % table.shape[1]
        return jnp.take_along_axis(table, entry[:, None], axis=1)[:, 0]

    def walk(self, table, lengths, block_size: int, kernel: bool):
        """What the kind's decode kernel visits, listed once a tick for
        every layer that shares the table: the slots' live blocks."""
        from ..ops.paged_attention import live_block_walk

        if not kernel:
            return None
        return live_block_walk(table, lengths, window=self.window,
                               block_size=block_size)

    class Decode(_Handle):
        def attend(self, query, key, value):
            from ..ops.paged_attention import (
                paged_decode_attention,
                paged_decode_reference,
            )

            view, at, layer = self.view, self.at, self.layer
            slots = query.shape[0]
            k_pool, v_pool = view.k_pools[at], view.v_pools[at]
            with jax.named_scope("kv_write"):
                rows = (layer, view.blocks[at], view.offset)
                k_pool = view.k_pools[at] = k_pool.at[rows].set(
                    key.reshape(slots, -1).astype(k_pool.dtype)
                )
                v_pool = view.v_pools[at] = v_pool.at[rows].set(
                    value.reshape(slots, -1).astype(v_pool.dtype)
                )
            args = (query[:, 0], k_pool, v_pool, view.tables[at],
                    view.lengths)
            with jax.named_scope("decode_attention"):
                if view.kernel:
                    out = paged_decode_attention(
                        *args, layer=layer, window=self.of.window,
                        walk=view.walks[at],
                    )
                else:
                    out = paged_decode_reference(
                        *args, layer=layer, window=self.of.window)
            return out[:, None]

    # -- a prefill: attend the prompt, keep rows, write them once ------

    class Prefill(_Handle):
        def attend(self, query, key, value):
            self.view.rows[self.number] = (key, value)
            return self.view.attend(query, key, value, self.of.window)

    def write(self, view: "PrefillView", at: int, numbers, keys,
              values) -> None:
        """This kind's part of the prompt's rows (``keys`` / ``values``
        ``[sublayers, bucket, heads * width]`` of the row-keeping
        sublayers ``numbers``; ``values`` None: a latent kind) into its
        pools."""
        # Every row-keeping sublayer (the only such kind), or this
        # kind's among them.
        mine = (slice(None) if self.layers == len(numbers)
                else np.asarray([numbers.index(layer)
                                 for layer in self.layer_ids]))
        for pools, rows in ((view.k_pools, keys), (view.v_pools, values)):
            if rows is not None:
                pools[at] = self.scatter(
                    pools[at], rows[mine], view.tables[at], view.length,
                    view.cache.block_size)

    def scatter(self, pool, rows, table, length, block_size: int):
        """``rows`` ``[layers, bucket, width]`` into the layers' pool
        through the sequence's ``table``; positions past ``length`` (and,
        in a window kind's ring, before what the window keeps) land in
        the trash block."""
        pos = jnp.arange(rows.shape[1])
        keep = pos < length
        entry = pos // block_size
        if self.window is not None:
            ring = table.shape[0]
            keep &= entry > (length - 1) // block_size - ring
            entry = entry % ring
        blk = jnp.where(keep, table[entry], jnp.int32(TRASH_BLOCK))
        if rows.shape[2] != pool.shape[3]:
            # A latent row, padded to the pool's whole lane tiles.
            rows = jnp.pad(
                rows, ((0, 0), (0, 0), (0, pool.shape[3] - rows.shape[2]))
            )
        # One row per (layer, position), indexed on every leading
        # dimension: a window over the layers makes XLA move the whole
        # pool into a layers-minor layout and back.
        layers = jnp.arange(rows.shape[0])[:, None]
        return pool.at[(layers, blk[None], (pos % block_size)[None])].set(
            rows.astype(pool.dtype)
        )


class _Latent(_Kind):
    """The LATENT kind: ONE row of ``width`` a token (a compressed latent
    and a shared rotary key) that the decode kernel reads once as key and
    as value
    (:func:`fluxmpi_tpu.ops.paged_attention.paged_latent_decode_attention`),
    in ONE pool (its entry of :attr:`BlockKVCache.v_pools` is None) under
    a table spanning ``max_len``. The row is padded with zeros to whole
    128-lane tiles: the kernel reads the pool in place only so, and that
    is what the row costs on the chip whoever pads it."""

    name = "latent"
    latent = True
    pools = 1

    def pool_shape(self, block_size: int) -> tuple[int, ...]:
        return (self.layers, self.num_blocks, block_size,
                -(-self.width // _LANES) * _LANES)

    class Decode(_Handle):
        def attend_absorbed(self, q_abs, q_rope, row):
            """``row`` ``[slots, 1, width]`` into the pool, then the
            absorbed queries (``[slots, 1, heads, rank | rope]``) against
            the slot's rows; ``[slots, 1, heads, rank]`` back."""
            from ..ops.paged_attention import (
                paged_latent_decode_attention,
                paged_latent_decode_reference,
            )

            view, at, layer = self.view, self.at, self.layer
            pool = view.k_pools[at]
            with jax.named_scope("kv_write"):
                row = jnp.pad(
                    row[:, 0], ((0, 0), (0, pool.shape[3] - row.shape[2]))
                )
                pool = view.k_pools[at] = pool.at[
                    (layer, view.blocks[at], view.offset)
                ].set(row.astype(pool.dtype))
            args = (q_abs[:, 0], q_rope[:, 0], pool, view.tables[at],
                    view.lengths)
            with jax.named_scope("decode_attention"):
                if view.kernel:
                    out = paged_latent_decode_attention(
                        *args, layer=layer, walk=view.walks[at])
                else:
                    out = paged_latent_decode_reference(*args, layer=layer)
            return out[:, None]

    class Prefill(_Handle):
        def attend(self, query, key, value, row):
            # One "head" of the row, and no value.
            self.view.rows[self.number] = (row[:, :, None], None)
            return self.view.attend(query, key, value, None)


class _State(_Kind):
    """The STATE kind: nothing a token. A state-space (Mamba-2) sublayer's
    whole past is ONE recurrent state (``heads x head_dim x d_state``,
    float32: the recurrence compounds its rounding over a whole answer)
    and ONE tail of the last ``d_conv - 1`` pre-convolution columns (the
    pool's dtype), whatever the sequence's length; ``state`` is the two
    shapes. Its "blocks" are ENTRIES: :meth:`BlockKVCache.blocks_for` is 1
    for any number of tokens, a table row is one entry wide, and
    ``num_blocks`` counts one entry a sequence the token-keeping pools
    hold at full length (the engine's slots) plus the trash entry.
    ``k_pool`` is the state pool ``[layers, entries, d_state, heads *
    head_dim]`` (a state transposed, its heads side by side: what the
    update kernel reads in place, :func:`fluxmpi_tpu.ops.ssm.to_pool_layout`)
    and ``v_pool`` the tail pool ``[layers, entries, tiles, 128]``
    (:attr:`tail_tiles`). Admission is then bounded by STATES where a
    token-keeping kind bounds it by tokens: a Mamba-2 layer of 128 heads
    of 64 over a state of 128 holds 4.19 MB a sequence at any length,
    where a layer of 8 K/V heads of 128 holds 4 KB a token."""

    name = "state"

    def __init__(self, layer_ids, num_blocks, state):
        super().__init__(layer_ids, 1, num_blocks, 0)
        self.state = state

    @property
    def tail_tiles(self) -> int:
        """The 128-lane tiles a sequence's convolution tail of one layer
        fills in the tail pool: its ``d_conv - 1`` columns end to end,
        padded with zeros to whole tiles
        (:func:`fluxmpi_tpu.ops.ssm.tail_to_pool_layout`; the update
        kernel's block is one entry's ``[tiles, 128]``, so the pool is
        held as the kernel writes it; rows of three columns were gathered
        and scattered through a copy of the whole pool a layer)."""
        return -(-math.prod(self.state[1]) // _LANES)

    def pool_shape(self, block_size: int) -> tuple[int, ...]:
        heads, head_dim, d_state = self.state[0]
        return (self.layers, self.num_blocks, d_state, heads * head_dim)

    def entry_bytes(self, itemsize: int) -> int:
        """What ONE sequence's entry holds over all the layers: the
        float32 states and the tails as the pools hold them."""
        return self.layers * (4 * math.prod(self.state[0])
                              + itemsize * self.tail_tiles * _LANES)

    def pool_bytes(self, block_size: int, itemsize: int) -> int:
        return self.num_blocks * self.entry_bytes(itemsize)

    def make_pools(self, block_size: int, dtype) -> None:
        shape = self.pool_shape(block_size)
        self.k_pool = jnp.zeros(shape, jnp.float32)
        self.v_pool = jnp.zeros((*shape[:2], self.tail_tiles, _LANES), dtype)

    def walk(self, table, lengths, block_size: int, kernel: bool):
        """Each slot's pool entry (the trash entry: an idle slot), and the
        live ones compacted once for every layer's update."""
        from ..ops.ssm import live_entries

        entries = table[:, 0]
        return entries, live_entries(entries)

    class Decode(_Handle):
        def tail(self):
            """The slots' convolution tails ``[slots, d_conv - 1,
            conv_dim]`` out of the pool (an idle slot's: the trash
            entry's, whatever it holds)."""
            from ..ops.ssm import tail_from_pool_layout

            view = self.view
            entries, _ = view.walks[self.at]
            return tail_from_pool_layout(
                view.v_pools[self.at][self.layer, entries], self.of.state[1])

        def update(self, tail, x, step, decay, b, c):
            """The LIVE slots' states moved one token where they lie and
            their new ``tail`` ``[slots, d_conv - 1, conv_dim]`` written
            over the old, in one walk over them (``x`` ``[slots, heads,
            head_dim]``, ``step`` and ``decay`` ``[slots, heads]``, ``b``
            and ``c`` ``[slots, d_state]``); ``H_t C_t`` ``[slots, heads,
            head_dim]`` back, zero for idle slots."""
            from ..ops.ssm import ssm_state_update

            view, at = self.view, self.at
            entries, live = view.walks[at]
            # The update chooses its own form from the backend and the
            # pool's shape: the kernel on a TPU, its plain twin elsewhere.
            out, view.k_pools[at], view.v_pools[at] = ssm_state_update(
                view.k_pools[at], view.v_pools[at], entries, tail, x, step,
                decay, b, c, layer=self.layer, live=live,
            )
            return out

    class Prefill(_Handle):
        def keep(self, tail, state):
            self.view.states[self.number] = (tail, state)

    def write(self, view: "PrefillView", at: int, *_) -> None:
        """The sequence's one entry, every layer's, whole: nothing of the
        entry's last holder is left."""
        from ..ops.ssm import tail_to_pool_layout, to_pool_layout

        with jax.named_scope("state_write"):
            entry = view.tables[at][0]
            kept = [view.states[layer] for layer in self.layer_ids]
            states = [to_pool_layout(state) for _, state in kept]
            tails = [tail_to_pool_layout(tail) for tail, _ in kept]
            for pools, rows in ((view.k_pools, states), (view.v_pools, tails)):
                rows = jnp.stack(rows)  # [layers, 1, ...]
                pools[at] = jax.lax.dynamic_update_slice(
                    pools[at], rows.astype(pools[at].dtype),
                    (0, entry) + (0,) * (rows.ndim - 2),
                )


class _View:
    """One traced step's hold on the pools: the model's ``cache``
    argument. ``k_pools`` / ``v_pools`` / ``tables``: one entry a kind."""

    def __init__(self, cache: "BlockKVCache", k_pools, v_pools, tables,
                 kernel: bool):
        self.cache = cache
        self.k_pools, self.v_pools = list(k_pools), list(v_pools)
        self.tables = tables
        self.kernel = kernel

    def sublayer(self, number: int) -> _Handle:
        """The handle of the model's keeping sublayer ``number``."""
        at, layer = self.cache.layer_kind[number]
        kind = self.cache.kinds[at]
        make = kind.Decode if self.reads_pool else kind.Prefill
        return make(self, at, layer)

    def pools(self) -> tuple[tuple, tuple]:
        """``(k_pools, v_pools)`` as the step hands them back."""
        return tuple(self.k_pools), tuple(self.v_pools)


class DecodeView(_View):
    """A decode tick's view: ``tables`` ``[slots, entries]`` a kind and
    ``positions`` ``[slots]``, the position each slot's new token takes.
    Idle slots carry all-trash tables: their rows land in the trash block
    and their length is 0. Every handle writes, then reads, as its
    sublayer calls it."""

    reads_pool = True

    def __init__(self, cache, k_pools, v_pools, tables, positions, kernel):
        super().__init__(cache, k_pools, v_pools, tables, kernel)
        # One written block and one walk a kind of layer.
        entry = positions // cache.block_size
        self.blocks = [kind.written_block(table, entry)
                       for table, kind in zip(tables, cache.kinds)]
        self.offset = positions % cache.block_size
        self.lengths = jnp.where(
            tables[0][:, 0] != TRASH_BLOCK, positions + 1, 0
        )
        self.walks = [
            kind.walk(table, self.lengths, cache.block_size, kernel)
            for table, kind in zip(tables, cache.kinds)
        ]

    @property
    def token_mask(self):
        """``[slots, 1]``: the slots that carry a request."""
        return self.tables[0][:, :1] != TRASH_BLOCK


class PrefillView(_View):
    """A prefill's view: ``tables`` ``[entries]`` a kind, the sequence's
    own, and ``length``, the prompt's true length in its padded bucket.
    A handle attends the call's own tokens (the flash kernels with
    ``kernel``) and hands over what its sublayer keeps; :meth:`pools`
    writes it all, once, after the forward."""

    reads_pool = False

    def __init__(self, cache, k_pools, v_pools, tables, length, kernel):
        super().__init__(cache, k_pools, v_pools, tables, kernel)
        self.length = length
        # By keeping sublayer: ``(keys, values)`` ``[1, bucket, heads,
        # width]`` (a latent's row and None), a state's ``(tail, state)``.
        self.rows: dict[int, tuple] = {}
        self.states: dict[int, tuple] = {}
        self._stacked = None

    def attend(self, query, key, value, window):
        from ..models.decoder import causal_attention

        with jax.named_scope("prefill_attention"):
            return causal_attention(
                query, key, value, window=window,
                mode="flash" if self.kernel else "naive",
            )

    def keep_rows(self, keys, values) -> None:
        """Every sublayer's keys and values at once, ``[sublayers, 1,
        bucket, heads, width]``: a model whose layers all keep K/V rows
        and that computes them outside its layers."""
        self._stacked = (list(range(keys.shape[0])), keys, values)

    def pools(self) -> tuple[tuple, tuple]:
        cache = self.cache
        if self._stacked is None:
            # The sublayers that keep rows a token, in order. A latent
            # sublayer keeps rows and no values (such sublayers are of
            # one shape: :class:`BlockKVCache`).
            numbers = sorted(self.rows)
            keys = values = None
            if numbers:
                keys = jnp.stack([self.rows[n][0] for n in numbers])
                if self.rows[numbers[0]][1] is not None:
                    values = jnp.stack([self.rows[n][1] for n in numbers])
            self._stacked = (numbers, keys, values)
        numbers, keys, values = self._stacked
        with jax.named_scope("kv_write"):
            # [sublayers, bucket, heads * width]: the pool's row.
            if keys is not None:
                keys = keys[:, 0].reshape(keys.shape[0], keys.shape[2], -1)
            if values is not None:
                values = values[:, 0].reshape(
                    values.shape[0], values.shape[2], -1)
            for at, kind in enumerate(cache.kinds):
                if kind.state is None:
                    kind.write(self, at, numbers, keys, values)
        for at, kind in enumerate(cache.kinds):
            if kind.state is not None:
                kind.write(self, at)
        return super().pools()


class BlockKVCache:
    """Paged K/V pool + free-list allocator + per-sequence block tables.

    Args:
      layers: what each sublayer of the model keeps of a sequence
        (:class:`fluxmpi_tpu.models.decoder.Keeps` records, as
        ``cache_layers()`` returns them; None: a sublayer that keeps
        nothing and is left out). The others are the cache's layers,
        numbered in order, and sort into :attr:`kinds` (full, window,
        latent, state, in that order, those the model has). Rows a token
        are of ONE shape a model (a prefill stacks them for one scatter a
        kind), window layers of one window, state layers of one shape.
      num_blocks: total pool blocks INCLUDING the reserved trash block
        (capacity = ``(num_blocks - 1) * block_size`` tokens).
      block_size: cache positions per block.
      max_blocks_per_seq: width of a block-table row — the longest
        sequence the engine serves, in blocks.
      dtype: pool dtype (the model's cache dtype).

    :meth:`alloc`, :meth:`free`, :meth:`table_row` and :meth:`blocks_for`
    take the ``kind`` they speak of (default 0: the only kind of a model
    whose layers are all alike); :meth:`can_alloc` answers for all kinds,
    and the block counts are sums over them.

    The pools are created lazily on first :attr:`k_pools` access (so the
    allocator half is importable/testable without a device) and live as
    plain device arrays the engine threads through its jitted steps,
    which reach them through a :class:`DecodeView` / :class:`PrefillView`.
    """

    def __init__(
        self,
        layers: Sequence[Any],
        *,
        num_blocks: int,
        block_size: int,
        max_blocks_per_seq: int,
        dtype: Any = None,
    ):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if max_blocks_per_seq < 1:
            raise ValueError(
                f"max_blocks_per_seq must be >= 1, got {max_blocks_per_seq}"
            )
        layers = [keeps for keeps in layers if keeps is not None]
        self.num_layers = len(layers)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self._dtype = jnp.float32 if dtype is None else dtype
        by_kind: dict[str, list[int]] = {
            "full": [], "window": [], "latent": [], "state": []}
        for number, keeps in enumerate(layers):
            if (keeps.window is not None) != (keeps.kind == "window"):
                raise ValueError(
                    f"a window sublayer, and no other, names its window; "
                    f"got {keeps}"
                )
            by_kind[keeps.kind].append(number)
        shapes = sorted({(layers[n].state, layers[n].tail)
                         for n in by_kind["state"]})
        if len(shapes) > 1:
            raise ValueError(f"one state shape a model, got {shapes}")
        sizes = sorted({layers[n].window for n in by_kind["window"]})
        if len(sizes) > 1:
            raise ValueError(f"one window size a model, got {sizes}")
        rows = sorted({(keeps.heads, keeps.width) for keeps in layers
                       if keeps.kind != "state"})
        if len(rows) > 1:
            raise ValueError(
                f"every layer must cache K/V heads (or a latent row) of "
                f"one shape; got {rows}"
            )
        width = rows[0][0] * rows[0][1] if rows else 0
        # The sequences the token-keeping pools hold at full length (the
        # engine's slots): a ring, or a state entry, each.
        sequences = (self.num_blocks - 1) // self.max_blocks_per_seq
        self.kinds: list[_Kind] = []
        if by_kind["full"]:
            self.kinds.append(_Kind(
                tuple(by_kind["full"]), self.max_blocks_per_seq,
                self.num_blocks, width,
            ))
        if sizes:
            entries = min(
                self.max_blocks_per_seq,
                blocks_for_tokens(sizes[0] + self.block_size, self.block_size),
            )
            self.kinds.append(_Kind(
                tuple(by_kind["window"]), entries, 1 + sequences * entries,
                width, int(sizes[0]),
            ))
        if by_kind["latent"]:
            self.kinds.append(_Latent(
                tuple(by_kind["latent"]), self.max_blocks_per_seq,
                self.num_blocks, width,
            ))
        if shapes:
            # One entry a sequence, and the trash entry.
            self.kinds.append(_State(
                tuple(by_kind["state"]), 1 + sequences, shapes[0]))
        # Per layer: (its kind, its index among that kind's layers).
        where = {
            layer: (at, index)
            for at, kind in enumerate(self.kinds)
            for index, layer in enumerate(kind.layer_ids)
        }
        self.layer_kind = [where[layer] for layer in range(self.num_layers)]
        # Forensics (PR 16): the pool-lifetime peak of used_blocks —
        # "how close did this run actually get to the wall".
        self._high_watermark = 0

    # -- allocator -----------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return sum(len(k.free) for k in self.kinds)

    @property
    def used_blocks(self) -> int:
        return sum(k.num_blocks - 1 - len(k.free) for k in self.kinds)

    @property
    def capacity_tokens(self) -> int:
        """Total cache positions the first kind's allocatable pool holds."""
        return (self.kinds[0].num_blocks - 1) * self.block_size

    @property
    def high_watermark_blocks(self) -> int:
        """Pool-lifetime peak of :attr:`used_blocks` (updated at every
        allocation) — the occupancy forensics gauge."""
        return self._high_watermark

    @property
    def fragmentation(self) -> float:
        """Free-list scatter in [0, 1]: ``1 - (longest contiguous free
        run / free blocks)``, runs and blocks summed over the kinds; 0.0
        when the free space is one run (or empty). Block allocation is
        id-agnostic, so this never blocks an admission — it measures how
        shuffled churn has left the pool, the precursor signal for
        block-coalescing / prefix-cache work that DOES care about
        contiguity."""
        free = longest_runs = 0
        for kind in self.kinds:
            if not kind.free:
                continue
            ids = sorted(kind.free)
            longest = run = 1
            for a, b in zip(ids, ids[1:]):
                run = run + 1 if b == a + 1 else 1
                if run > longest:
                    longest = run
            free += len(ids)
            longest_runs += longest
        return 1.0 - longest_runs / free if free else 0.0

    def blocks_for(self, tokens: int, kind: int = 0) -> int:
        """Blocks a sequence of ``tokens`` positions holds in ``kind``:
        all of them, a window kind's ring at most, or a state kind's one
        entry whatever the length."""
        need = blocks_for_tokens(tokens, self.block_size)
        k = self.kinds[kind]
        if k.state is not None:
            return 1
        return need if k.window is None else min(need, k.entries)

    def can_alloc(self, tokens: int) -> bool:
        """Whether every kind has the blocks ``tokens`` positions need."""
        return all(
            self.blocks_for(tokens, i) <= len(kind.free)
            for i, kind in enumerate(self.kinds)
        )

    def fits_pool(self, tokens: int) -> bool:
        """Whether ``tokens`` positions could EVER be held: in every
        kind, no more blocks than the whole pool has."""
        return all(
            self.blocks_for(tokens, i) <= kind.num_blocks - 1
            for i, kind in enumerate(self.kinds)
        )

    def alloc(self, tokens: int, kind: int = 0) -> list[int]:
        """Reserve ``kind``'s blocks for ``tokens`` cache positions;
        raises ``RuntimeError`` when the pool cannot cover them (callers
        gate on :meth:`can_alloc` — admission control, not this, is
        where "no" is decided)."""
        need = self.blocks_for(tokens, kind)
        free = self.kinds[kind].free
        if need > len(free):
            raise RuntimeError(
                f"KV pool exhausted: need {need} blocks for {tokens} "
                f"tokens, {len(free)} free"
            )
        if need > self.kinds[kind].entries:
            raise ValueError(
                f"{tokens} tokens need {need} blocks but block tables are "
                f"{self.kinds[kind].entries} wide"
            )
        blocks = [free.pop() for _ in range(need)]
        if self.used_blocks > self._high_watermark:
            self._high_watermark = self.used_blocks
        return blocks

    def free(self, blocks: list[int], kind: int = 0) -> None:
        """Return a sequence's blocks to ``kind``'s pool (eviction)."""
        k = self.kinds[kind]
        for b in blocks:
            if not 0 < b < k.num_blocks:
                raise ValueError(f"block id {b} outside the pool")
            if b in k.free:
                raise ValueError(f"double free of block {b}")
        k.free.extend(blocks)

    def table_row(self, blocks: list[int], kind: int = 0):
        """``kind``'s int32 block-table row (``max_blocks_per_seq`` wide,
        or a window kind's ring) for a sequence's blocks; unused entries
        point at the trash block."""
        row = np.full((self.kinds[kind].entries,), TRASH_BLOCK, np.int32)
        row[: len(blocks)] = blocks
        return row

    # -- device pools --------------------------------------------------

    @property
    def pool_shapes(self) -> list[tuple[int, ...]]:
        """Per kind, its K pool's shape: each kind's own statement of its
        layout (its writes, its decode kernel and :attr:`pool_bytes`
        follow)."""
        return [k.pool_shape(self.block_size) for k in self.kinds]

    @property
    def state_kind(self) -> int | None:
        """Which of :attr:`kinds` keeps states (None: no such layer)."""
        return next((at for at, kind in enumerate(self.kinds)
                     if kind.state is not None), None)

    @property
    def tail_tiles(self) -> int:
        """The state kind's :attr:`_State.tail_tiles` (0 without state
        layers)."""
        at = self.state_kind
        return 0 if at is None else self.kinds[at].tail_tiles

    @property
    def state_entry_bytes(self) -> int:
        """What ONE sequence's entry holds over all the state layers
        (:meth:`_State.entry_bytes`; 0 without state layers)."""
        at = self.state_kind
        return 0 if at is None else self.kinds[at].entry_bytes(
            self._itemsize())

    @property
    def pool_bytes(self) -> int:
        """Byte footprint of ALL pools (K and V of every kind; a latent
        kind's one pool; a state kind's float32 states and its tails)."""
        itemsize = self._itemsize()
        return sum(k.pool_bytes(self.block_size, itemsize)
                   for k in self.kinds)

    def _itemsize(self) -> int:
        return np.dtype(self._dtype).itemsize

    def _ensure_pools(self) -> None:
        if self.kinds[0].k_pool is None:
            for kind in self.kinds:
                kind.make_pools(self.block_size, self._dtype)

    @property
    def k_pools(self) -> tuple:
        """The K pools (a latent kind's rows, a state kind's states), one
        a kind: what the engine's steps take (and donate) and hand back."""
        self._ensure_pools()
        return tuple(k.k_pool for k in self.kinds)

    @k_pools.setter
    def k_pools(self, value) -> None:
        for kind, pool in zip(self.kinds, value):
            kind.k_pool = pool

    @property
    def v_pools(self) -> tuple:
        """The V pools, one a kind (a state kind's convolution tails);
        None for a latent kind, which has none (an empty leaf wherever the
        tuple travels)."""
        self._ensure_pools()
        return tuple(k.v_pool for k in self.kinds)

    @v_pools.setter
    def v_pools(self, value) -> None:
        for kind, pool in zip(self.kinds, value):
            kind.v_pool = pool

    def drop_pools(self) -> None:
        """Release the device arrays (engine shutdown — the pool must
        not outlive the engine into the next init cycle)."""
        for kind in self.kinds:
            kind.k_pool = kind.v_pool = None

    # -- memory-plane admission check ----------------------------------

    def fits_device(self, device: Any = None) -> tuple[bool, str]:
        """OOM-safe construction check against the PR 9 memory plane:
        would the pool's byte footprint fit the device's remaining HBM?
        Returns ``(fits, detail)``; backends without memory stats (CPU)
        report ``(True, "no device memory stats")`` — there is nothing
        to check against, and host memory is the OS's problem."""
        from ..telemetry.memory import device_memory_stats

        if device is None:
            device = jax.local_devices()[0]
        stats = device_memory_stats(device)
        limit = stats.get("bytes_limit")
        if not limit:
            return True, "no device memory stats"
        in_use = stats.get("bytes_in_use", 0.0)
        need = float(self.pool_bytes)
        fits = in_use + need <= limit
        return fits, (
            f"pool {need / 2**20:.1f} MiB + in-use {in_use / 2**20:.1f} "
            f"MiB vs limit {limit / 2**20:.1f} MiB"
        )
