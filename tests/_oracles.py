"""Shared oracles for the test suite (single source — the segment-mask
semantics, and what a grouped matmul may leave past its groups, must not
drift between test files)."""

import numpy as np

import jax
import jax.numpy as jnp


def dense_seg_attention(q, k, v, qseg, kseg, causal=False, window=None):
    """Dense oracle with the kernel's segment semantics: attend iff ids
    equal and key id nonzero. Fully-masked rows are garbage here (uniform
    softmax) — compare valid rows only."""
    scale = 1.0 / np.sqrt(q.shape[-1])
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) * scale
    mask = (qseg[:, :, None] == kseg[:, None, :]) & (kseg[:, None, :] != 0)
    if causal:
        sq, sk = q.shape[1], k.shape[1]
        pos = jnp.arange(sq)[:, None] >= jnp.arange(sk)[None, :]
        if window is not None:
            pos = pos & (
                jnp.arange(sq)[:, None] - jnp.arange(sk)[None, :] < window
            )
        mask = mask & pos[None]
    s = jnp.where(mask[:, None], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def poisoned_past_the_groups(grouped):
    """``grouped`` (a grouped matmul ``(x, w, sizes)``) with every row of
    its result past ``sum(sizes)`` NaN: what its contract lets a backend
    leave there (``fluxmpi_tpu.ops.grouped_matmul``), so a caller that
    reads such a row shows."""

    def poisoned(x, w, sizes):
        out = grouped(x, w, sizes)
        live = jnp.arange(out.shape[0])[:, None] < jnp.sum(sizes)
        return jnp.where(live, out, jnp.nan)

    return poisoned
