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
  dimension to twice its bytes. :attr:`BlockKVCache.pool_shape` is the
  one place that says so;
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
  (:func:`fluxmpi_tpu.ops.paged_attention.paged_decode_attention`, see
  :mod:`fluxmpi_tpu.serving.engine`).

**Four kinds of layer** (:attr:`BlockKVCache.kinds`: full, window,
latent, state), each with its pool(s), its free list and its tables. A
"layer" here is one KEEPING SUBLAYER of the model, in the order their
calls come, not one of its layers: a layer that keeps nothing is none, and
a layer that runs a state-space mixer and attention side by side is two,
one of the state kind and one of the full kind, so a sequence then holds
an entry of the state pool AND blocks of the K/V pool, and
:meth:`BlockKVCache.can_alloc` says no as soon as either runs out.

**Layers that attend a window keep a ring.** A model whose layers are
not all alike (``layer_windows``: some attend their whole context, some
a sliding window) gets one pool, one free list and one table a **kind**
of layer: a full layer's table spans
``max_len``, a window layer's is a ring of ``ceil((window + block_size) /
block_size)`` blocks in which position ``p`` lives at entry ``(p //
block_size) % ring``, so a long sequence costs a window layer no more
than its ring. A model without window layers has the one kind, and
everything below reads as it did.

**Layers that keep a latent keep one row and no V.** A latent-attention
layer (``layer_latent``) caches ONE row a token (a compressed latent and
a shared rotary key, ``num_heads * head_dim`` wide as the caller counts
it) that its decode kernel reads once as key and as value
(:func:`fluxmpi_tpu.ops.paged_attention.paged_latent_decode_attention`).
Such layers are a third kind, with a free list, a table spanning
``max_len`` and ONE pool (:attr:`BlockKVCache.k_pools`; its entry of
:attr:`BlockKVCache.v_pools` is None), counted at one row a token,
padded to whole 128-lane tiles, in :attr:`BlockKVCache.pool_bytes`.

**Layers that keep a state keep one entry a sequence.** A state-space
(Mamba-2) layer (``layer_state``) caches nothing a token: a sequence's
whole past is ONE recurrent state (``heads x head_dim x d_state``,
float32: the recurrence compounds its rounding over a whole answer) and
ONE tail of the last ``d_conv - 1`` pre-convolution columns (the pool's
dtype), whatever its length. Such layers are a fourth kind whose "blocks"
are ENTRIES: :meth:`BlockKVCache.blocks_for` is 1 for any number of
tokens, a table row is one entry wide, the pool holds one entry a sequence
``num_blocks`` holds at ``max_blocks_per_seq`` (the engine's slots) plus
the trash entry, its :attr:`BlockKVCache.k_pools` entry is the state pool
``[layers, entries, d_state, heads * head_dim]`` (a state transposed, its
heads side by side: what the update kernel reads in place) and its
:attr:`BlockKVCache.v_pools` entry the tail pool ``[layers, entries,
tiles, 128]`` (a tail's ``d_conv - 1`` columns end to end, padded to whole
128-lane tiles: the block the same kernel writes a live slot's new tail
into, :func:`fluxmpi_tpu.ops.ssm.tail_to_pool_layout`).
Admission is then bounded by STATES where a token-keeping kind bounds it
by tokens: a Mamba-2 layer of 128 heads of 64 over a state of 128 holds
4.19 MB a sequence at any length, where a layer of 8 K/V heads of 128
holds 4 KB a token. The decode tick moves a live
slot's state where it lies and writes its new tail over the old, in one
walk over the live slots
(:func:`fluxmpi_tpu.ops.ssm.ssm_state_update`); a prefill overwrites an
admitted sequence's entry whole.

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

from typing import Any, Sequence

__all__ = ["BlockKVCache", "blocks_for_tokens"]

TRASH_BLOCK = 0
_LANES = 128


def blocks_for_tokens(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` cache positions."""
    return -(-int(tokens) // int(block_size))


class _Kind:
    """The layers that keep the same span of a sequence, their free list
    and their pools: ``window`` None for layers that keep the whole
    context, else the positions a window layer attends. ``entries`` is
    the width of a sequence's table row for these layers, ``layer_ids``
    the model's layers that are of this kind, in order. ``latent``: the
    layers keep one row a token in ``k_pool`` and no ``v_pool``.
    ``state``: the layers keep ONE entry a sequence, ``((heads, head_dim,
    d_state), tail shape)``: the recurrent state in ``k_pool`` (float32),
    the convolution's tail in ``v_pool``; ``num_blocks`` counts entries."""

    __slots__ = ("layer_ids", "window", "entries", "num_blocks", "free",
                 "k_pool", "v_pool", "latent", "state")

    def __init__(self, layer_ids: tuple[int, ...], window: int | None,
                 entries: int, num_blocks: int, latent: bool = False,
                 state: tuple | None = None):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved trash "
                f"block), got {num_blocks}"
            )
        self.layer_ids = layer_ids
        self.window = window
        self.entries = entries
        self.num_blocks = num_blocks
        self.latent = latent
        self.state = state
        # LIFO free list: the most recently freed block is handed out
        # next — the round-trip the reuse test pins down.
        self.free: list[int] = list(range(num_blocks - 1, 0, -1))
        self.k_pool = None
        self.v_pool = None

    @property
    def layers(self) -> int:
        return len(self.layer_ids)


class BlockKVCache:
    """Paged K/V pool + free-list allocator + per-sequence block tables.

    Args:
      num_layers, num_heads, head_dim: the model's cache geometry
        (``num_layers`` keeping sublayers, see above; ``num_heads`` K/V
        heads of ``head_dim`` each of those that keep rows).
      num_blocks: total pool blocks INCLUDING the reserved trash block
        (capacity = ``(num_blocks - 1) * block_size`` tokens).
      block_size: cache positions per block.
      max_blocks_per_seq: width of a block-table row — the longest
        sequence the engine serves, in blocks.
      dtype: pool dtype (the model's cache dtype).
      layer_windows: per layer, the positions it attends (a window
        layer) or None (a layer that attends its whole context; the
        default for every layer). Layers of the two **kinds** live in
        pools of their own, each with its free list and its tables
        (:attr:`kinds`, full layers first): a window layer keeps at most
        ``window + block_size`` positions of a sequence, rounded up to
        blocks, as a RING (position ``p`` at table entry ``(p //
        block_size) % entries``), so its table row is that many entries
        wide and a long sequence costs it no more than that. One window
        size a model. The window kind's pool holds the rings of as many
        sequences as ``num_blocks`` holds at ``max_blocks_per_seq``.
      layer_latent: per layer, whether it keeps ONE row of ``num_heads *
        head_dim`` a token (a latent read as key and as value) in place
        of K and V (default: no layer). Such layers attend their whole
        context and are a kind of their own, after the other two, with
        one pool.
      layer_state: per layer, None or ``((heads, head_dim, d_state), tail
        shape)`` of a layer that keeps ONE recurrent state (float32) and
        one convolution tail (``dtype``) a SEQUENCE and nothing a token
        (default: no layer). One shape a model. Such layers are the last
        kind: a "block" of theirs is a pool entry, one a sequence.
        ``num_heads`` / ``head_dim`` then speak of the other layers.

    :meth:`alloc`, :meth:`free`, :meth:`table_row` and :meth:`blocks_for`
    take the ``kind`` they speak of (default 0: the only kind of a model
    without window layers); :meth:`can_alloc` answers for all kinds, and
    the block counts are sums over them.

    The pools are created lazily on first :attr:`k_pools` access (so the
    allocator half is importable/testable without a device) and live as
    plain device arrays the engine threads through its jitted steps.
    """

    def __init__(
        self,
        *,
        num_layers: int,
        num_heads: int,
        head_dim: int,
        num_blocks: int,
        block_size: int,
        max_blocks_per_seq: int,
        dtype: Any = None,
        layer_windows: Sequence[int | None] | None = None,
        layer_latent: Sequence[bool] | None = None,
        layer_state: Sequence[tuple | None] | None = None,
    ):
        if block_size < 1:
            raise ValueError(f"block_size must be >= 1, got {block_size}")
        if max_blocks_per_seq < 1:
            raise ValueError(
                f"max_blocks_per_seq must be >= 1, got {max_blocks_per_seq}"
            )
        self.num_layers = int(num_layers)
        self.num_heads = int(num_heads)
        self.head_dim = int(head_dim)
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        self.max_blocks_per_seq = int(max_blocks_per_seq)
        self._dtype = dtype
        windows = tuple(layer_windows or (None,) * self.num_layers)
        if len(windows) != self.num_layers:
            raise ValueError(
                f"layer_windows names {len(windows)} layers, not "
                f"{self.num_layers}"
            )
        latent = tuple(bool(x) for x in
                       layer_latent or (False,) * self.num_layers)
        if len(latent) != self.num_layers:
            raise ValueError(
                f"layer_latent names {len(latent)} layers, not "
                f"{self.num_layers}"
            )
        if any(w is not None and x for w, x in zip(windows, latent)):
            raise ValueError("a latent layer attends its whole context")
        states = tuple(layer_state or (None,) * self.num_layers)
        if len(states) != self.num_layers:
            raise ValueError(
                f"layer_state names {len(states)} layers, not "
                f"{self.num_layers}"
            )
        if any(st is not None and (w is not None or x)
               for st, w, x in zip(states, windows, latent)):
            raise ValueError("a state layer keeps no token's rows")
        shapes = sorted({st for st in states if st is not None})
        if len(shapes) > 1:
            raise ValueError(f"one state shape a model, got {shapes}")
        sizes = sorted({w for w in windows if w is not None})
        if len(sizes) > 1:
            raise ValueError(f"one window size a model, got {sizes}")
        self.kinds: list[_Kind] = []
        full = tuple(i for i, w in enumerate(windows)
                     if w is None and not latent[i] and states[i] is None)
        if full:
            self.kinds.append(_Kind(
                full, None, self.max_blocks_per_seq, self.num_blocks
            ))
        if sizes:
            entries = min(
                self.max_blocks_per_seq,
                blocks_for_tokens(sizes[0] + self.block_size, self.block_size),
            )
            sequences = (self.num_blocks - 1) // self.max_blocks_per_seq
            self.kinds.append(_Kind(
                tuple(i for i, w in enumerate(windows) if w is not None),
                int(sizes[0]), entries, 1 + sequences * entries,
            ))
        if any(latent):
            self.kinds.append(_Kind(
                tuple(i for i, x in enumerate(latent) if x), None,
                self.max_blocks_per_seq, self.num_blocks, latent=True,
            ))
        if shapes:
            # One entry a sequence the token-keeping pools hold at full
            # length (the engine's slots), and the trash entry.
            sequences = (self.num_blocks - 1) // self.max_blocks_per_seq
            self.kinds.append(_Kind(
                tuple(i for i, st in enumerate(states) if st is not None),
                None, 1, 1 + sequences, state=shapes[0],
            ))
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
    def free_tokens(self) -> int:
        return len(self.kinds[0].free) * self.block_size

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
        import numpy as np

        row = np.full((self.kinds[kind].entries,), TRASH_BLOCK, np.int32)
        row[: len(blocks)] = blocks
        return row

    # -- device pools --------------------------------------------------

    @property
    def pool_shapes(self) -> list[tuple[int, ...]]:
        """Per kind, ``[layers, blocks, block_size, heads * head_dim]``
        (a latent kind: its row, padded with zeros to whole 128-lane tiles,
        which is what the row costs on the chip whoever pads it: the
        decode kernel reads the pool in place only so; a state kind: its
        STATE pool ``[layers, entries, d_state, heads * head_dim]``, the
        tail pool beside it ``[layers, entries,`` :attr:`tail_tiles` ``,
        128]``) — the one
        statement of the pools' layout (the prefill's and the decode's
        ``kv_write``, the decode kernels and :attr:`pool_bytes` follow)."""
        width = self.num_heads * self.head_dim
        return [
            (k.layers, k.num_blocks, k.state[0][2],
             k.state[0][0] * k.state[0][1]) if k.state is not None else
            (k.layers, k.num_blocks, self.block_size,
             -(-width // _LANES) * _LANES if k.latent else width)
            for k in self.kinds
        ]

    @property
    def state_kind(self) -> int | None:
        """Which of :attr:`kinds` keeps states (None: no such layer)."""
        return next((at for at, kind in enumerate(self.kinds)
                     if kind.state is not None), None)

    @property
    def tail_tiles(self) -> int:
        """The 128-lane tiles a sequence's convolution tail of one state
        layer fills in the tail pool: its ``d_conv - 1`` columns end to
        end, padded with zeros to whole tiles (the update kernel's block
        is one entry's ``[tiles, 128]``, so the pool is held as the kernel
        writes it; rows of three columns were gathered and scattered
        through a copy of the whole pool a layer; 0 without state
        layers)."""
        import math

        at = self.state_kind
        return 0 if at is None else -(
            -math.prod(self.kinds[at].state[1]) // _LANES)

    @property
    def state_entry_bytes(self) -> int:
        """What ONE sequence's entry holds over all the state layers:
        the float32 states and the tails as the pools hold them (0
        without state layers)."""
        import math

        at = self.state_kind
        if at is None:
            return 0
        kind = self.kinds[at]
        return kind.layers * (
            4 * math.prod(kind.state[0])
            + self._itemsize() * self.tail_tiles * _LANES)

    @property
    def pool_shape(self) -> tuple[int, ...]:
        """The first kind's pool shape."""
        return self.pool_shapes[0]

    @property
    def pool_bytes(self) -> int:
        """Byte footprint of ALL pools (K and V of every kind; a latent
        kind's one pool; a state kind's float32 states and its tails)."""
        import numpy as np

        itemsize = self._itemsize()
        return sum(
            kind.num_blocks * self.state_entry_bytes if kind.state is not None
            else itemsize * (1 if kind.latent else 2) * int(np.prod(shape))
            for kind, shape in zip(self.kinds, self.pool_shapes)
        )

    def _itemsize(self) -> int:
        import numpy as np

        import jax.numpy as jnp

        return np.dtype(
            self._dtype if self._dtype is not None else jnp.float32).itemsize

    def _ensure_pools(self) -> None:
        if self.kinds[0].k_pool is None:
            import jax.numpy as jnp

            dtype = self._dtype if self._dtype is not None else jnp.float32
            for kind, shape in zip(self.kinds, self.pool_shapes):
                if kind.state is not None:
                    kind.k_pool = jnp.zeros(shape, jnp.float32)
                    kind.v_pool = jnp.zeros(
                        (*shape[:2], self.tail_tiles, _LANES), dtype)
                    continue
                kind.k_pool = jnp.zeros(shape, dtype)
                kind.v_pool = None if kind.latent else jnp.zeros(shape, dtype)

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

    @property
    def k_pool(self):
        """The first kind's K pool."""
        return self.k_pools[0]

    @k_pool.setter
    def k_pool(self, value) -> None:
        self.kinds[0].k_pool = value

    @property
    def v_pool(self):
        return self.v_pools[0]

    @v_pool.setter
    def v_pool(self, value) -> None:
        self.kinds[0].v_pool = value

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
            import jax

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
