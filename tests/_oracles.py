"""Shared oracles for the test suite (single source — the segment-mask
semantics, and what a grouped matmul may leave past its groups, must not
drift between test files), and the hand-driven caches of the state-space
layers' tests."""

import numpy as np

import jax
import jax.numpy as jnp

from fluxmpi_tpu.ops import ssm
from fluxmpi_tpu.serving.cache import DecodeView


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


# ---------------------------------------------------------------------------
# State layers (Mamba-2) driven by hand: test_granite.py, test_nemotron.py
# ---------------------------------------------------------------------------


class DenseState:
    """A state sublayer's handle that reads a pool: ONE sequence's state
    and tail, moved a token a call (what the engine's pools hold an entry
    of)."""

    kind, reads_pool = "state", True

    def __init__(self, config):
        heads, hd, n = (config.mamba_n_heads, config.mamba_d_head,
                        config.mamba_d_state)
        self.pool = jnp.zeros((1, 2, n, heads * hd), jnp.float32)
        self.tail_shape = (config.mamba_d_conv - 1, config.mamba_conv_dim)
        self.tails = jnp.zeros(
            (1, 2, *ssm.tail_to_pool_layout(jnp.zeros(self.tail_shape)).shape))

    def tail(self):
        return ssm.tail_from_pool_layout(self.tails[0, 1:], self.tail_shape)

    def update(self, tail, x, step, decay, b, c):
        y, self.pool, self.tails = ssm.ssm_state_update_reference(
            self.pool, self.tails, jnp.ones((1,), jnp.int32), tail, x, step,
            decay, b, c)
        return y


class Kept:
    """A state sublayer's handle of a prefill: what the layer hands a
    cache."""

    kind, reads_pool = "state", False

    def keep(self, tail, state):
        self.tail, self.state = tail, state


def served_logits(eng, variables, prompt, ticks):
    """The logits the engine's own programs give: the prefill program
    over ``prompt`` (into slot 1's blocks and state entry), then
    ``ticks`` decode ticks over the engine's pools through the cache's
    decode view as the decode step builds it, each fed the token the last
    put first. ``(tokens, logits [ticks, vocab])``."""
    cache, model = eng.cache, eng.model
    total = len(prompt) + ticks + 1
    kinds = range(len(cache.kinds))
    tables = [cache.table_row(cache.alloc(total, kind), kind)
              for kind in kinds]
    bucket = eng._bucket(len(prompt))
    padded = np.zeros((bucket,), np.int32)
    padded[:len(prompt)] = prompt
    first, k_pools, v_pools = eng._prefill_step(bucket)(
        variables, cache.k_pools, cache.v_pools, jnp.asarray(padded),
        jnp.int32(len(prompt)), tuple(jnp.asarray(t) for t in tables))
    # Slot 0 idles beside it.
    slot_tables = tuple(
        jnp.stack([jnp.zeros_like(jnp.asarray(t)), jnp.asarray(t)])
        for t in tables)

    @jax.jit
    def tick(k_pools, v_pools, position, token):
        positions = jnp.stack([jnp.int32(0), position])
        view = DecodeView(
            cache, k_pools, v_pools, slot_tables, positions, kernel=False)
        logits = model.apply(
            variables, jnp.stack([jnp.int32(0), token])[:, None],
            pos_offset=positions, token_mask=view.token_mask, cache=view,
            mutable=["intermediates"],
        )[0]
        return logits[1, 0], *view.pools()

    tokens, rows = [int(first)], []
    for t in range(ticks):
        row, k_pools, v_pools = tick(
            k_pools, v_pools, jnp.int32(len(prompt) + t),
            jnp.int32(tokens[-1]))
        rows.append(row)
        tokens.append(int(jnp.argmax(row)))
    return tokens, jnp.stack(rows)
