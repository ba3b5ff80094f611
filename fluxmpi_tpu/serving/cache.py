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

from typing import Any

__all__ = ["BlockKVCache", "blocks_for_tokens"]

TRASH_BLOCK = 0


def blocks_for_tokens(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` cache positions."""
    return -(-int(tokens) // int(block_size))


class BlockKVCache:
    """Paged K/V pool + free-list allocator + per-sequence block tables.

    Args:
      num_layers, num_heads, head_dim: the model's cache geometry
        (``head_dim = qkv_features // num_heads``).
      num_blocks: total pool blocks INCLUDING the reserved trash block
        (capacity = ``(num_blocks - 1) * block_size`` tokens).
      block_size: cache positions per block.
      max_blocks_per_seq: width of a block-table row — the longest
        sequence the engine serves, in blocks.
      dtype: pool dtype (the model's cache dtype).

    The pools are created lazily on first :attr:`k_pool` access (so the
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
    ):
        if num_blocks < 2:
            raise ValueError(
                f"num_blocks must be >= 2 (block 0 is the reserved trash "
                f"block), got {num_blocks}"
            )
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
        # LIFO free list: the most recently freed block is handed out
        # next — the round-trip the reuse test pins down.
        self._free: list[int] = list(range(self.num_blocks - 1, 0, -1))
        # Forensics (PR 16): the pool-lifetime peak of used_blocks —
        # "how close did this run actually get to the wall".
        self._high_watermark = 0
        self._k_pool = None
        self._v_pool = None

    # -- allocator -----------------------------------------------------

    @property
    def free_blocks(self) -> int:
        return len(self._free)

    @property
    def used_blocks(self) -> int:
        return (self.num_blocks - 1) - len(self._free)

    @property
    def capacity_tokens(self) -> int:
        """Total cache positions the allocatable pool holds."""
        return (self.num_blocks - 1) * self.block_size

    @property
    def free_tokens(self) -> int:
        return len(self._free) * self.block_size

    @property
    def high_watermark_blocks(self) -> int:
        """Pool-lifetime peak of :attr:`used_blocks` (updated at every
        allocation) — the occupancy forensics gauge."""
        return self._high_watermark

    @property
    def fragmentation(self) -> float:
        """Free-list scatter in [0, 1]: ``1 - (longest contiguous free
        run / free blocks)``; 0.0 when the free space is one run (or
        empty). Block allocation is id-agnostic, so this never blocks
        an admission — it measures how shuffled churn has left the
        pool, the precursor signal for block-coalescing / prefix-cache
        work that DOES care about contiguity."""
        if not self._free:
            return 0.0
        ids = sorted(self._free)
        longest = run = 1
        for a, b in zip(ids, ids[1:]):
            run = run + 1 if b == a + 1 else 1
            if run > longest:
                longest = run
        return 1.0 - longest / len(ids)

    def blocks_for(self, tokens: int) -> int:
        return blocks_for_tokens(tokens, self.block_size)

    def can_alloc(self, tokens: int) -> bool:
        return self.blocks_for(tokens) <= len(self._free)

    def alloc(self, tokens: int) -> list[int]:
        """Reserve the blocks for ``tokens`` cache positions; raises
        ``RuntimeError`` when the pool cannot cover them (callers gate
        on :meth:`can_alloc` — admission control, not this, is where
        "no" is decided)."""
        need = self.blocks_for(tokens)
        if need > len(self._free):
            raise RuntimeError(
                f"KV pool exhausted: need {need} blocks for {tokens} "
                f"tokens, {len(self._free)} free"
            )
        if need > self.max_blocks_per_seq:
            raise ValueError(
                f"{tokens} tokens need {need} blocks but block tables are "
                f"{self.max_blocks_per_seq} wide"
            )
        blocks = [self._free.pop() for _ in range(need)]
        if self.used_blocks > self._high_watermark:
            self._high_watermark = self.used_blocks
        return blocks

    def free(self, blocks: list[int]) -> None:
        """Return a sequence's blocks to the pool (eviction)."""
        for b in blocks:
            if not 0 < b < self.num_blocks:
                raise ValueError(f"block id {b} outside the pool")
            if b in self._free:
                raise ValueError(f"double free of block {b}")
        self._free.extend(blocks)

    def table_row(self, blocks: list[int]):
        """``[max_blocks_per_seq]`` int32 block-table row for a
        sequence's blocks; unused entries point at the trash block."""
        import numpy as np

        row = np.full((self.max_blocks_per_seq,), TRASH_BLOCK, np.int32)
        row[: len(blocks)] = blocks
        return row

    # -- device pools --------------------------------------------------

    @property
    def pool_shape(self) -> tuple[int, ...]:
        """``[layers, blocks, block_size, heads * head_dim]`` — the one
        statement of the pool's layout (the prefill's and the decode's
        ``kv_write``, the decode kernel and :attr:`pool_bytes` follow)."""
        return (
            self.num_layers,
            self.num_blocks,
            self.block_size,
            self.num_heads * self.head_dim,
        )

    @property
    def pool_bytes(self) -> int:
        """Byte footprint of BOTH pools (K and V)."""
        import numpy as np

        import jax.numpy as jnp

        dtype = self._dtype if self._dtype is not None else jnp.float32
        itemsize = np.dtype(dtype).itemsize
        n = 1
        for d in self.pool_shape:
            n *= d
        return 2 * n * itemsize

    def _ensure_pools(self) -> None:
        if self._k_pool is None:
            import jax.numpy as jnp

            dtype = self._dtype if self._dtype is not None else jnp.float32
            self._k_pool = jnp.zeros(self.pool_shape, dtype)
            self._v_pool = jnp.zeros(self.pool_shape, dtype)

    @property
    def k_pool(self):
        self._ensure_pools()
        return self._k_pool

    @k_pool.setter
    def k_pool(self, value) -> None:
        self._k_pool = value

    @property
    def v_pool(self):
        self._ensure_pools()
        return self._v_pool

    @v_pool.setter
    def v_pool(self, value) -> None:
        self._v_pool = value

    def drop_pools(self) -> None:
        """Release the device arrays (engine shutdown — the pool must
        not outlive the engine into the next init cycle)."""
        self._k_pool = None
        self._v_pool = None

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
