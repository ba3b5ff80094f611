"""Chunked fused unembed + softmax cross-entropy.

The LM-head analogue of flash attention: the ``[tokens, vocab]`` logits
matrix of a language model head is the largest single tensor in the
training step (batch 8 x seq 1024 x vocab 32768 in f32 is 1 GB — bigger
than the model), yet the loss needs only one scalar per token. This op
streams the unembedding matmul over vocab tiles inside one ``lax.scan``,
keeping a running logsumexp and the target logit — the full logits tensor
is NEVER materialized, forward or backward. Peak memory drops from
O(tokens·vocab) to O(tokens·chunk). The matmuls run in the HIDDEN
STATES' dtype (bf16 on TPU) with f32 accumulation; the embedding table
may stay f32 — it is cast per-tile for the MXU, and its gradient comes
back in its own dtype (f32 moments for the model's largest parameter).

No analogue in the reference (its models are user-land Flux code;
README.md:31-70 quick-start): this is TPU-native performance surface, the
same memory-vs-recompute trade `jax.checkpoint` makes but specialized to
the head, where recomputation is one chunked matmul per direction.

Backward math, per tile c with logits ``z_c = h @ W_cᵀ``:
``dz_c = (softmax(z)_c - onehot_c) * g`` → ``dh += dz_c @ W_c`` and
``dW_c = dz_cᵀ @ h`` — softmax rebuilt from the saved per-token
logsumexp, so the residuals are just ``(h, W, targets, lse)``.

Vocab sizes that don't divide ``chunk`` are handled by zero-padding the
last tile and masking its dead columns to -inf (their softmax weight is
exactly 0, so forward and backward are untouched) — the tile size never
silently shrinks (GPT-2's 50257 runs 7 tiles of 8192, not 29 of 1733).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

__all__ = ["unembed_cross_entropy", "tp_unembed_cross_entropy"]


def _tiles(W, chunk: int):
    """Pad ``W`` [V, d] to a whole number of ``chunk``-row tiles and
    return ``(W3 [K, chunk, d], offsets [K])``. Shared by the primal,
    fwd, and bwd so the tiling cannot diverge between them."""
    vocab, d = W.shape
    pad = (-vocab) % chunk
    if pad:
        W = jnp.concatenate([W, jnp.zeros((pad, d), W.dtype)], axis=0)
    k = W.shape[0] // chunk
    offsets = jnp.arange(k, dtype=jnp.int32) * chunk
    return W.reshape(k, chunk, d), offsets


def _col_mask(off, chunk: int, vocab: int):
    """[1, chunk] validity mask for a tile starting at ``off`` (False on
    the zero-padded columns past the real vocab)."""
    return (off + jnp.arange(chunk))[None, :] < vocab


def _scan_lse(h2, W3, offsets, targets1, vocab: int,
              want_zsum: bool = False):
    """Shared forward scan: running (m, l, target-logit, Σ valid z) over
    vocab tiles. h2 [N, d]; W3 [K, C, d]; targets1 [N]. Returns
    (lse [N], t [N], zsum [N]) in f32. The zsum accumulator (which feeds
    label smoothing) is a STATIC opt-in so the eps=0 program carries no
    extra per-tile reduction."""
    n = h2.shape[0]
    chunk = W3.shape[1]

    def body(carry, xs):
        m, l, t, zsum = carry
        w_c, off = xs
        mask = _col_mask(off, chunk, vocab)
        z = jax.lax.dot_general(
            h2, w_c.astype(h2.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [N, C]
        if want_zsum:
            zsum = zsum + jnp.sum(jnp.where(mask, z, 0.0), axis=-1)
        z = jnp.where(mask, z, -jnp.inf)
        m_new = jnp.maximum(m, jnp.max(z, axis=-1))
        l = l * jnp.exp(m - m_new) + jnp.sum(
            jnp.exp(z - m_new[:, None]), axis=-1
        )
        local = targets1 - off
        in_chunk = (local >= 0) & (local < chunk)
        picked = jnp.take_along_axis(
            z, jnp.clip(local, 0, chunk - 1)[:, None], axis=1
        )[:, 0]
        t = jnp.where(in_chunk, picked, t)
        return (m_new, l, t, zsum), None

    init = (
        jnp.full((n,), -jnp.inf, jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
        jnp.zeros((n,), jnp.float32),
    )
    (m, l, t, zsum), _ = jax.lax.scan(body, init, (W3, offsets))
    return m + jnp.log(l), t, zsum


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def _fused_ce(h2, W, targets1, chunk, label_smoothing):
    return _fused_ce_fwd(h2, W, targets1, chunk, label_smoothing)[0]


def _fused_ce_fwd(h2, W, targets1, chunk, label_smoothing):
    W3, offsets = _tiles(W, chunk)
    eps = label_smoothing
    lse, t, zsum = _scan_lse(h2, W3, offsets, targets1, W.shape[0],
                             want_zsum=bool(eps))
    # (1-eps)*(lse - t) + eps*(lse - mean_v z) = lse - (1-eps)t - eps*zsum/V
    loss = lse - (1.0 - eps) * t
    if eps:
        loss = loss - eps * zsum / W.shape[0]
    return loss, (h2, W, targets1, lse)


def _fused_ce_bwd(chunk, label_smoothing, res, g, smooth_vocab=None):
    h2, W, targets1, lse = res
    vocab, d = W.shape
    # Smoothing spreads eps/V over the GLOBAL vocab — under the TP
    # spelling the local shard is only vocab/tp of it.
    v_smooth = vocab if smooth_vocab is None else smooth_vocab
    eps = label_smoothing
    n = h2.shape[0]
    W3, offsets = _tiles(W, chunk)
    gf = g.astype(jnp.float32)

    def body(dh, xs):
        w_c, off = xs
        mask = _col_mask(off, chunk, vocab)
        z = jax.lax.dot_general(
            h2, w_c.astype(h2.dtype), (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [N, C]
        z = jnp.where(mask, z, -jnp.inf)
        p = jnp.exp(z - lse[:, None])  # 0 exactly on padded columns
        local = targets1 - off
        in_chunk = (local >= 0) & (local < chunk)
        onehot = (
            jax.nn.one_hot(
                jnp.clip(local, 0, chunk - 1), chunk, dtype=jnp.float32
            )
            * in_chunk[:, None]
        )
        # d loss / dz = p - [(1-eps)·onehot + eps/V on valid columns]
        if eps:
            target = (1.0 - eps) * onehot + (eps / v_smooth) * mask
        else:
            target = onehot
        dz = (p - target) * gf[:, None]  # [N, C]
        dh = dh + jax.lax.dot_general(
            dz, w_c.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )
        dw_c = jax.lax.dot_general(
            dz, h2.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32,
        )  # [C, d]
        return dh, dw_c

    dh, dW3 = jax.lax.scan(
        body, jnp.zeros((n, d), jnp.float32), (W3, offsets)
    )
    dW = dW3.reshape(-1, d)[:vocab]  # drop the zero-pad rows
    return dh.astype(h2.dtype), dW.astype(W.dtype), None


_fused_ce.defvjp(_fused_ce_fwd, _fused_ce_bwd)


def unembed_cross_entropy(
    h: jnp.ndarray,
    embedding: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    chunk: int = 8192,
    label_smoothing: float = 0.0,
) -> jnp.ndarray:
    """Per-token ``softmax_cross_entropy(h @ embeddingᵀ, targets)`` without
    materializing the logits.

    Args:
      h: hidden states ``[..., d_model]`` — the matmuls run in THIS
        dtype (pass bf16 for MXU speed) with f32 accumulation.
      embedding: ``[vocab, d_model]`` — the ``nn.Embed`` table of a
        weight-tied head (what ``embed.attend`` contracts against). May
        be f32 while ``h`` is bf16: tiles are cast for the matmul, and
        the gradient returns in the table's own dtype.
      targets: int labels, shape ``h.shape[:-1]``.
      chunk: vocab tile size; a trailing partial tile is zero-padded and
        masked (never silently shrunk). Peak memory is O(tokens·chunk).
      label_smoothing: ``eps`` in [0, 1): the target distribution becomes
        ``(1-eps)·onehot + eps/vocab`` (a running Σz accumulator in the
        same scan — still no logits tensor).

    Returns:
      Per-token losses with shape ``h.shape[:-1]``, f32 — same values as
      ``optax.softmax_cross_entropy_with_integer_labels(h @ embeddingᵀ,
      targets)`` (smoothed: ``optax.softmax_cross_entropy`` against the
      smoothed one-hots) up to accumulation order.
    """
    if h.shape[:-1] != targets.shape:
        raise ValueError(
            f"targets shape {targets.shape} must equal the hidden states' "
            f"leading shape {h.shape[:-1]}"
        )
    vocab, d = embedding.shape
    if h.shape[-1] != d:
        raise ValueError(
            f"hidden dim {h.shape[-1]} != embedding dim {d}"
        )
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    if not 0.0 <= label_smoothing < 1.0:
        raise ValueError(
            f"label_smoothing must be in [0, 1), got {label_smoothing}"
        )
    lead = h.shape[:-1]
    h2 = h.reshape(-1, d)
    targets1 = targets.reshape(-1).astype(jnp.int32)
    # The loss head's name in a device trace; its backward pass carries
    # it as transpose(jvp(ce_head)).
    with jax.named_scope("ce_head"):
        out = _fused_ce(h2, embedding, targets1, min(chunk, vocab),
                        float(label_smoothing))
    return out.reshape(lead)


# ---------------------------------------------------------------------------
# Tensor-parallel (vocab-sharded) spelling — the Megatron parallel CE.
# ---------------------------------------------------------------------------
#
# The custom VJP sits OUTSIDE the shard_map: forward and backward are each
# one explicit shard_map call over primal values, so no cotangent ever
# crosses a shard_map boundary — every collective and scale factor below
# is explicit rather than inherited from transpose rules.


def _tp_ce_fwd_body(h2, Wl, targets1, *, chunk, axis_name,
                    label_smoothing):
    """Per-rank forward: local chunked scan over this rank's vocab shard,
    then pmax+psum combine into the exact global (loss, lse)."""
    v_local = Wl.shape[0]
    off0 = jax.lax.axis_index(axis_name) * v_local
    W3, offsets = _tiles(Wl, chunk)
    lse_l, t_l, zsum_l = _scan_lse(
        h2, W3, offsets, targets1 - off0, v_local,
        want_zsum=bool(label_smoothing),
    )
    m_g = jax.lax.pmax(lse_l, axis_name)
    lse = m_g + jnp.log(jax.lax.psum(jnp.exp(lse_l - m_g), axis_name))
    local = targets1 - off0
    owned = (local >= 0) & (local < v_local)
    t = jax.lax.psum(jnp.where(owned, t_l, 0.0), axis_name)
    eps = label_smoothing
    loss = lse - (1.0 - eps) * t
    if eps:
        v_global = v_local * jax.lax.psum(1, axis_name)
        zsum = jax.lax.psum(zsum_l, axis_name)
        loss = loss - eps * zsum / v_global
    return loss, lse


def _tp_ce_bwd_body(h2, Wl, targets1, lse, g, *, chunk, axis_name,
                    batch_axes, label_smoothing):
    """Per-rank backward: the shared bwd scan computes exactly this
    shard's contributions when fed the GLOBAL lse and shard-local target
    ids (p = exp(z_local - lse_global) are true global-softmax columns).
    dh sums over vocab shards — one psum; with the token dim sharded
    over ``batch_axes``, dWl additionally sums each shard's per-token
    contributions over those axes."""
    v_local = Wl.shape[0]
    off0 = jax.lax.axis_index(axis_name) * v_local
    v_global = v_local * jax.lax.psum(1, axis_name)
    dh_part, dWl, _ = _fused_ce_bwd(
        chunk, label_smoothing, (h2, Wl, targets1 - off0, lse), g,
        smooth_vocab=v_global,
    )
    if batch_axes:
        dWl = jax.lax.psum(dWl, batch_axes)
    return jax.lax.psum(dh_part, axis_name), dWl


def _tp_maps(mesh, axis_name, chunk, batch_axes, label_smoothing):
    from ..parallel._compat import shard_map_unchecked

    from jax.sharding import PartitionSpec as _P

    tok = _P(batch_axes) if batch_axes else _P()
    tok_h = _P(batch_axes, None) if batch_axes else _P(None, None)
    fwd = shard_map_unchecked(
        functools.partial(_tp_ce_fwd_body, chunk=chunk, axis_name=axis_name,
                          label_smoothing=label_smoothing),
        mesh,
        in_specs=(tok_h, _P(axis_name, None), tok),
        out_specs=(tok, tok),
    )
    bwd = shard_map_unchecked(
        functools.partial(_tp_ce_bwd_body, chunk=chunk, axis_name=axis_name,
                          batch_axes=batch_axes,
                          label_smoothing=label_smoothing),
        mesh,
        in_specs=(tok_h, _P(axis_name, None), tok, tok, tok),
        out_specs=(tok_h, _P(axis_name, None)),
    )
    return fwd, bwd


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6, 7))
def _fused_ce_tp(h2, W, targets1, chunk, axis_name, mesh, batch_axes,
                 label_smoothing):
    return _tp_maps(mesh, axis_name, chunk, batch_axes,
                    label_smoothing)[0](h2, W, targets1)[0]


def _fused_ce_tp_fwd(h2, W, targets1, chunk, axis_name, mesh, batch_axes,
                     label_smoothing):
    loss, lse = _tp_maps(mesh, axis_name, chunk, batch_axes,
                         label_smoothing)[0](h2, W, targets1)
    return loss, (h2, W, targets1, lse)


def _fused_ce_tp_bwd(chunk, axis_name, mesh, batch_axes, label_smoothing,
                     res, g):
    h2, W, targets1, lse = res
    dh, dW = _tp_maps(mesh, axis_name, chunk, batch_axes,
                      label_smoothing)[1](h2, W, targets1, lse, g)
    return dh, dW, None


_fused_ce_tp.defvjp(_fused_ce_tp_fwd, _fused_ce_tp_bwd)


def tp_unembed_cross_entropy(
    h: jnp.ndarray,
    embedding: jnp.ndarray,
    targets: jnp.ndarray,
    *,
    mesh=None,
    axis_name: str | None = None,
    batch_axis_name: str | tuple | None = None,
    chunk: int = 8192,
    label_smoothing: float = 0.0,
) -> jnp.ndarray:
    """:func:`unembed_cross_entropy` for a VOCAB-SHARDED embedding table —
    the Megatron-style parallel cross-entropy (``label_smoothing``
    supported: the Σz term psums across vocab shards).

    Each tensor-parallel rank holds ``[vocab/tp, d]`` of the weight-tied
    table (the ``transformer_tp_rules`` layout, ``P(tp, None)``) and
    computes a chunked partial logsumexp plus the target logit for the
    ids it owns; one ``pmax`` + two ``psum``s combine them into the exact
    global loss — the full table, the logits, and the gathered softmax
    never exist anywhere. The backward is local for the table gradient
    (each rank's shard gradient depends only on its own columns) and one
    ``psum`` for the hidden-states gradient. Both directions are explicit
    ``shard_map`` calls under a module-level ``custom_vjp``, so no
    cotangent depends on shard_map transpose rules.

    Composes inside an auto-sharded jit (``shard_map`` nests under
    ``jit``): pass the global (sharded) arrays. ``vocab`` must divide
    evenly by the tp axis size.

    ``batch_axis_name``: mesh axis (or axes) the TOKEN dim is sharded
    over — pass your dp axis on a dp×tp mesh so every device works on
    its own token slice instead of replicating the whole batch through
    the head (the per-shard table gradient then psums over these axes;
    token count must divide their total extent). Default ``None``
    replicates the token work across non-tp axes — correct everywhere,
    wasteful on multi-axis meshes.
    """
    from .. import config as _config
    from ..runtime import global_mesh

    mesh = mesh or global_mesh()
    tp = axis_name or _config.TP_AXIS_NAME
    n = mesh.shape.get(tp)
    if n is None:
        raise ValueError(f"mesh has no axis {tp!r}")
    vocab, d = embedding.shape
    if vocab % n:
        raise ValueError(
            f"vocab {vocab} must divide evenly over the {tp!r} axis "
            f"(size {n}) for the vocab-sharded head"
        )
    if h.shape[:-1] != targets.shape:
        raise ValueError(
            f"targets shape {targets.shape} must equal the hidden states\' "
            f"leading shape {h.shape[:-1]}"
        )
    if h.shape[-1] != d:
        raise ValueError(f"hidden dim {h.shape[-1]} != embedding dim {d}")
    if chunk < 1:
        raise ValueError(f"chunk must be >= 1, got {chunk}")
    batch_axes = batch_axis_name
    if isinstance(batch_axes, str):
        batch_axes = (batch_axes,)
    if batch_axes:
        for ax in batch_axes:
            if ax not in mesh.shape:
                raise ValueError(f"mesh has no axis {ax!r}")
            if ax == tp:
                raise ValueError(
                    "batch_axis_name cannot include the tp axis"
                )
    lead = h.shape[:-1]
    h2 = h.reshape(-1, d)
    targets1 = targets.reshape(-1).astype(jnp.int32)
    if not 0.0 <= label_smoothing < 1.0:
        raise ValueError(
            f"label_smoothing must be in [0, 1), got {label_smoothing}"
        )
    local_chunk = min(chunk, vocab // n)
    with jax.named_scope("ce_head"):
        out = _fused_ce_tp(
            h2, embedding, targets1, local_chunk, tp, mesh,
            tuple(batch_axes) if batch_axes else None,
            float(label_smoothing),
        )
    return out.reshape(lead)
