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
  num_blocks, block_size, heads * head_dim]`` (a block is one contiguous
  ``[block_size, heads * head_dim]`` tile: no relayout, and no 64-wide
  minor dimension padded to 128 lanes); ``layer`` picks the pool's
  leading index statically, inside the block index map.
- ``lengths[slot]`` counts the slot's live positions ``0 .. length - 1``
  (the new token's row included: the caller writes it first). Position
  ``p`` lives at ``(tables[slot, p // block_size], p % block_size)``.
- Blocks at or past ``ceil(length / block_size)`` do no work: their grid
  steps neither compute nor fetch (the index map holds the last live
  block, which the pipeline does not fetch twice). The tail of the last
  live block is masked by position. So whatever the table's unused
  entries point at (the trash block) and whatever finite garbage lies
  past a length never reaches the output.
- A slot of length 0 (an idle slot: all-trash table) outputs zeros.
- Softmax statistics and accumulators are float32 over operands in the
  pool's dtype, as in :mod:`fluxmpi_tpu.ops.flash_attention`; the output
  has ``q``'s dtype.

All heads of a slot share one grid step. The per-head products come out
of two plain matmuls over the folded ``heads * head_dim`` lanes: the
query row is laid out block-diagonally (``[heads, heads * head_dim]``,
head ``h``'s query in its own lanes, zeros elsewhere), so ``q_bd @ k.T``
is ``[heads, block_size]`` scores with no per-head slicing, and of ``p @
v`` (``[heads, heads * head_dim]``) each head keeps its own lanes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from ..parallel._compat import pallas_tpu_compiler_params
from .flash_attention import _LANES, _NEG_INF, _SUBLANES

__all__ = ["paged_decode_attention", "paged_decode_reference"]


def _paged_decode_kernel(
    tables_ref, lengths_ref, q_ref, k_ref, v_ref, o_ref,
    m_scratch, l_scratch, acc_scratch,
    *, sm_scale: float, head_dim: int, block_size: int, num_j: int,
):
    del tables_ref  # read by the index maps
    slot = pl.program_id(0)
    j = pl.program_id(1)
    length = lengths_ref[slot]
    # heads (padded to whole sublane tiles), heads * head_dim
    rows, width = acc_scratch.shape

    def own_lanes():
        # own[h, c]: lane c of the folded minor dimension belongs to head
        # h. Padding rows (h >= heads) own nothing.
        head = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 0)
        lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)
        return (lane >= head * head_dim) & (lane < (head + 1) * head_dim)

    @pl.when(j == 0)
    def _init():
        m_scratch[...] = jnp.full_like(m_scratch, _NEG_INF)
        l_scratch[...] = jnp.zeros_like(l_scratch)
        acc_scratch[...] = jnp.zeros_like(acc_scratch)

    @pl.when(j * block_size < length)
    def _compute():
        k = k_ref[...]  # [block_size, width]
        # The select runs on 32-bit tiles (the mask comes from int32
        # iotas); the rounding back to the pool's dtype is exact.
        q_bd = jnp.where(
            own_lanes(), q_ref[0].astype(jnp.float32), 0.0
        ).astype(k.dtype)
        s = jax.lax.dot_general(
            q_bd, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        ) * sm_scale  # [rows, block_size]
        live = j * block_size + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        ) < length
        s = jnp.where(live, s, _NEG_INF)

        m_prev = m_scratch[...]  # [rows, 128], value replicated over lanes
        l_prev = l_scratch[...]
        m_cur = jnp.broadcast_to(
            jnp.max(s, axis=1, keepdims=True), m_prev.shape
        )
        m_new = jnp.maximum(m_prev, m_cur)
        alpha = jnp.exp(m_prev - m_new)
        p = jnp.where(live, jnp.exp(s - m_new[:, :1]), 0.0)
        l_scratch[...] = l_prev * alpha + jnp.broadcast_to(
            jnp.sum(p, axis=1, keepdims=True), l_prev.shape
        )
        # [rows, width]: every head's values; each keeps its own lanes.
        pv = jax.lax.dot_general(
            p, v_ref[...].astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        acc_scratch[...] = acc_scratch[...] * alpha[:, :1] + pv
        m_scratch[...] = m_new

    @pl.when(j == num_j - 1)
    def _finish():
        l_final = l_scratch[...][:, :1]
        l_safe = jnp.where(l_final == 0.0, 1.0, l_final)
        out = jnp.where(own_lanes(), acc_scratch[...] / l_safe, 0.0)
        o_ref[0] = jnp.sum(out, axis=0, keepdims=True).astype(o_ref.dtype)


def _check_shapes(q, k_pool, v_pool, tables, lengths, layer):
    slots, heads, head_dim = q.shape
    if k_pool.shape != v_pool.shape or k_pool.ndim != 4:
        raise ValueError(
            f"k_pool and v_pool must share one [layers, num_blocks, "
            f"block_size, heads * head_dim] shape; got {k_pool.shape} and "
            f"{v_pool.shape}"
        )
    if k_pool.shape[3] != heads * head_dim:
        raise ValueError(
            f"pool minor dimension {k_pool.shape[3]} is not heads * "
            f"head_dim = {heads} * {head_dim}"
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


@functools.partial(jax.jit, static_argnames=("layer", "interpret"))
def paged_decode_attention(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    layer: int = 0,
    interpret: bool | None = None,
) -> jnp.ndarray:
    """One query row per slot against its paged cache; see the module
    docstring for the contract. Returns ``[slots, heads, head_dim]`` in
    ``q``'s dtype. ``interpret=None`` runs the compiled kernel on a TPU
    backend and Pallas interpret mode elsewhere."""
    from jax.experimental.pallas import tpu as pltpu

    _check_shapes(q, k_pool, v_pool, tables, lengths, layer)
    slots, heads, head_dim = q.shape
    _, _, block_size, width = k_pool.shape
    num_j = tables.shape[1]
    rows = -(-heads // _SUBLANES) * _SUBLANES
    if interpret is None:
        interpret = jax.default_backend() != "tpu"

    def kv_index(slot, j, tables_ref, lengths_ref):
        # Past the live blocks the index stays on the last live one: an
        # unchanged block index is not fetched again.
        last = jnp.maximum(
            (lengths_ref[slot] + block_size - 1) // block_size - 1, 0
        )
        return layer, tables_ref[slot, jnp.minimum(j, last)], 0, 0

    def row_index(slot, j, tables_ref, lengths_ref):
        return slot, 0, 0

    kv_spec = pl.BlockSpec((None, None, block_size, width), kv_index)
    row_spec = pl.BlockSpec((1, 1, width), row_index)
    out = pl.pallas_call(
        functools.partial(
            _paged_decode_kernel, sm_scale=1.0 / (head_dim**0.5),
            head_dim=head_dim, block_size=block_size, num_j=num_j,
        ),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(slots, num_j),
            in_specs=[row_spec, kv_spec, kv_spec],
            out_specs=row_spec,
            scratch_shapes=[
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, _LANES), jnp.float32),
                pltpu.VMEM((rows, width), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((slots, 1, width), q.dtype),
        compiler_params=pallas_tpu_compiler_params(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(
        tables.astype(jnp.int32), lengths.astype(jnp.int32),
        q.reshape(slots, 1, width), k_pool, v_pool,
    )
    return out.reshape(slots, heads, head_dim)


def paged_decode_reference(
    q: jnp.ndarray,
    k_pool: jnp.ndarray,
    v_pool: jnp.ndarray,
    tables: jnp.ndarray,
    lengths: jnp.ndarray,
    *,
    layer: int = 0,
) -> jnp.ndarray:
    """The same contract in plain ``jax.numpy``: gather one layer's
    tabled blocks, dense scores, a position mask. The kernel's reference
    in the tests, and the engine's ``attention="naive"`` route."""
    _check_shapes(q, k_pool, v_pool, tables, lengths, layer)
    slots, heads, head_dim = q.shape
    k = k_pool[layer][tables].reshape(slots, -1, heads, head_dim)
    v = v_pool[layer][tables].reshape(slots, -1, heads, head_dim)
    s = jnp.einsum(
        "shd,sthd->sht", q.astype(k.dtype), k,
        preferred_element_type=jnp.float32,
    ) / (head_dim**0.5)
    live = (jnp.arange(k.shape[1])[None, :] < lengths[:, None])[:, None, :]
    s = jnp.where(live, s, _NEG_INF)
    p = jnp.where(live, jnp.exp(s - jnp.max(s, axis=-1, keepdims=True)), 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum(
        "sht,sthd->shd", p / jnp.where(l == 0.0, 1.0, l),
        v.astype(jnp.float32),
    )
    return out.astype(q.dtype)
