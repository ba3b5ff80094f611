"""A decoder LM built from a configuration, and its expert layer.

:class:`TransformerLM` is one block (pre-LayerNorm, GELU, learned
positions, ``nn.MultiHeadDotProductAttention``); today's open models are
not that block. :class:`DecoderLM` reads its block from a
:class:`DecoderConfig` (the keys of a Hugging Face ``config.json``, see
:meth:`DecoderConfig.from_hf`):

- RMSNorm, four a layer in the sandwich placement:
  ``h = x + N_post_attn(Attn(N_in(x)))``, ``y = h + N_post_ff(FF(N_pre_ff(h)))``;
- attention with ``num_heads`` query heads over ``num_kv_heads`` K/V
  heads of ``head_dim`` (independent of ``hidden_size``), RMSNorm over
  ``head_dim`` on ``q`` and ``k``, a sigmoid output gate, and a kind per
  layer (``layer_types``): ``"sliding_attention"`` (rotary positions, the
  mask ``0 <= i - j < sliding_window``) or ``"full_attention"`` (the
  causal mask, no rotary);
- a gated (SwiGLU) MLP in the first ``num_dense_layers`` layers and an
  :class:`ExpertMLP` in the rest;
- an untied head, and the embedding scaled by ``sqrt(hidden_size)``
  where ``mup_enabled``.

Compute runs in ``dtype`` (bfloat16 in the served configuration) with
float32 accumulation; the norms, the router, the rotary angles and the
softmax statistics are float32. Parameters are held in the dtype they
are given in.

The serving engine's seam is ``attention_fn`` (as in
:class:`TransformerLM`): where set, every layer hands it ``(query, key,
value)`` (``[batch, seq, heads | kv_heads, head_dim]``, after the head
norms and the rotary) in layer order and takes ``[batch, seq, heads,
head_dim]`` back; :meth:`DecoderLM.cache_layers` says what each layer
keeps in a cache. ``pos_offset`` (``[batch]``) places each row at its own
position and ``head_at`` (``[batch]``) takes the head at one position a
row: a prefill never builds ``[prompt, vocab]`` logits. ``token_mask``
(``[batch, seq]``) names the real tokens: padding and idle slots are
routed to no expert.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

from .transformer import _resolve_attention_mode

__all__ = ["DecoderConfig", "DecoderLM", "ExpertMLP", "causal_attention"]

SLIDING, FULL = "sliding_attention", "full_attention"


@dataclasses.dataclass(frozen=True)
class DecoderConfig:
    """The sizes and kinds of one decoder LM (hashable: a module field)."""

    vocab_size: int
    hidden_size: int
    layer_types: tuple[str, ...]
    num_attention_heads: int
    num_key_value_heads: int
    head_dim: int
    intermediate_size: int
    num_dense_layers: int = 0
    num_experts: int = 0
    num_experts_per_tok: int = 0
    num_shared_experts: int = 0
    moe_intermediate_size: int = 0
    sliding_window: int | None = None
    max_position_embeddings: int = 2048
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 1.0
    mup_enabled: bool = False

    def __post_init__(self):
        unknown = set(self.layer_types) - {SLIDING, FULL}
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if SLIDING in self.layer_types and not self.sliding_window:
            raise ValueError("sliding_attention layers need sliding_window")
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads are not a multiple "
                f"of {self.num_key_value_heads} K/V heads"
            )
        if self.score_func != "sigmoid":
            raise ValueError(f"unknown score_func {self.score_func!r}")

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @classmethod
    def from_hf(cls, cfg: dict) -> "DecoderConfig":
        """From the keys of a ``config.json`` (``model_type: "afmoe"``);
        keys this class does not know are left alone."""
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in cfg.items() if k in names}
        known["layer_types"] = tuple(cfg["layer_types"])
        return cls(**known)


def _dot(x, w, dtype):
    """``x @ w`` on operands in ``dtype``, accumulated in float32."""
    return jnp.dot(x.astype(dtype), w.astype(dtype),
                   preferred_element_type=jnp.float32)


def _rms_norm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x32), axis=-1, keepdims=True)
    return x32 * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


class RMSNorm(nn.Module):
    eps: float
    dtype: Any

    @nn.compact
    def __call__(self, x):
        scale = self.param("scale", nn.initializers.ones, (x.shape[-1],))
        return _rms_norm(x, scale, self.eps).astype(self.dtype)


def _rotary(x, positions, theta: float):
    """Rotary positions on ``x`` ``[batch, seq, heads, head_dim]`` at
    ``positions`` ``[batch, seq]``: the two halves of a head rotated by
    ``position * theta ** (-2i / head_dim)``, float32."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v, *, window: int | None, mode: str):
    """Causal attention of ``q`` ``[batch, seq, heads, head_dim]`` over
    ``k`` / ``v`` with fewer (grouped) heads, within ``window`` keys where
    given. ``mode`` ``"flash"``: the Pallas kernels (K/V never repeated to
    the query heads); ``"naive"``: dense scores, float32 softmax."""
    if mode == "flash":
        from ..ops.flash_attention import flash_attention

        return flash_attention(q, k, v, causal=True, window=window)
    b, s, h, d = q.shape
    kvh = k.shape[2]
    qg = q.reshape(b, s, kvh, h // kvh, d)
    scores = jnp.einsum("bqkgd,btkd->bkgqt", qg, k,
                        preferred_element_type=jnp.float32) / (d ** 0.5)
    i = jnp.arange(s)[:, None]
    j = jnp.arange(s)[None, :]
    mask = j <= i
    if window is not None:
        mask &= i - j < window
    scores = jnp.where(mask, scores, -jnp.inf)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgqt,btkd->bqkgd", p.astype(v.dtype), v,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, s, h, d).astype(q.dtype)


class Attention(nn.Module):
    config: DecoderConfig
    layer_type: str
    dtype: Any
    attention: str = "naive"
    attention_fn: Callable | None = None

    @nn.compact
    def __call__(self, u, positions):
        c = self.config
        heads, kvh, hd = (c.num_attention_heads, c.num_key_value_heads,
                          c.head_dim)
        init = nn.initializers.normal(0.02)
        b, s, d = u.shape
        wq = self.param("wq", init, (d, heads * hd))
        wk = self.param("wk", init, (d, kvh * hd))
        wv = self.param("wv", init, (d, kvh * hd))
        wg = self.param("wg", init, (d, heads * hd))
        wo = self.param("wo", init, (heads * hd, d))
        q_scale = self.param("q_norm", nn.initializers.ones, (hd,))
        k_scale = self.param("k_norm", nn.initializers.ones, (hd,))
        q = _dot(u, wq, self.dtype).reshape(b, s, heads, hd)
        k = _dot(u, wk, self.dtype).reshape(b, s, kvh, hd)
        v = _dot(u, wv, self.dtype).reshape(b, s, kvh, hd).astype(self.dtype)
        q = _rms_norm(q, q_scale, c.rms_norm_eps)
        k = _rms_norm(k, k_scale, c.rms_norm_eps)
        window = None
        if self.layer_type == SLIDING:
            window = c.sliding_window
            with jax.named_scope("rope"):
                q = _rotary(q, positions, c.rope_theta)
                k = _rotary(k, positions, c.rope_theta)
        q, k = q.astype(self.dtype), k.astype(self.dtype)
        if self.attention_fn is not None:
            out = self.attention_fn(q, k, v)
        else:
            out = causal_attention(
                q, k, v, window=window,
                mode=_resolve_attention_mode(self.attention),
            )
        gate = jax.nn.sigmoid(_dot(u, wg, self.dtype))
        out = out.reshape(b, s, heads * hd).astype(jnp.float32) * gate
        return _dot(out, wo, self.dtype).astype(self.dtype)


class GatedMLP(nn.Module):
    """``(silu(u W1) * (u W3)) W2``."""

    width: int
    dtype: Any

    @nn.compact
    def __call__(self, u):
        init = nn.initializers.normal(0.02)
        d = u.shape[-1]
        w1 = self.param("w1", init, (d, self.width))
        w3 = self.param("w3", init, (d, self.width))
        w2 = self.param("w2", init, (self.width, d))
        h = jax.nn.silu(_dot(u, w1, self.dtype)) * _dot(u, w3, self.dtype)
        return _dot(h, w2, self.dtype).astype(self.dtype)


class ExpertMLP(nn.Module):
    """Routed experts without a capacity: no token is dropped.

    Every token scores all ``num_experts`` (``sigmoid(u Wr)``, float32),
    takes the ``top_k`` largest of score + bias, and weighs the chosen by
    their scores, normalised to sum 1 (``route_norm``), times
    ``route_scale``. The layer HOLDS the contiguous range
    ``expert_range`` of the experts (default: all): the (token, expert)
    pairs are sorted by expert, the held experts' pairs first, and one
    grouped matmul (:func:`~fluxmpi_tpu.ops.grouped_matmul.grouped_matmul`:
    ``jax.lax.ragged_dot``'s meaning) a projection computes them;
    pairs routed to experts held elsewhere add nothing here (on one chip
    the layer runs without its exchange). The shared expert, which every
    token passes, is added where ``include_shared``.

    Returns the layer's output and sows ``expert_tokens`` (``[num_experts]``
    int32: the pairs each expert received) into ``intermediates``.
    Tokens that ``token_mask`` leaves out (padding, idle slots) are
    routed nowhere: they count for no expert and reach no grouped matmul.
    """

    num_experts: int
    top_k: int
    width: int
    shared_width: int = 0
    route_norm: bool = True
    route_scale: float = 1.0
    expert_range: tuple[int, int] | None = None
    include_shared: bool = True
    dtype: Any = jnp.float32

    def route(self, u, router, bias):
        """``(experts [tokens, top_k], weights [tokens, top_k])``."""
        scores = jax.nn.sigmoid(jnp.dot(
            u.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        ))
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                   self.top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if self.route_norm:
            weights = weights / (
                jnp.sum(weights, axis=-1, keepdims=True) + 1e-20
            )
        return experts, weights * self.route_scale

    @nn.compact
    def __call__(self, u, token_mask=None):
        init = nn.initializers.normal(0.02)
        shape, d = u.shape, u.shape[-1]
        u = u.reshape(-1, d)
        tokens, n, k = u.shape[0], self.num_experts, self.top_k
        lo, hi = self.expert_range or (0, n)
        held = hi - lo
        router = self.param("router", init, (d, n))
        bias = self.param("bias", nn.initializers.zeros, (n,))
        w1 = self.param("w1", init, (held, d, self.width))
        w3 = self.param("w3", init, (held, d, self.width))
        w2 = self.param("w2", init, (held, self.width, d))
        with jax.named_scope("moe_route"):
            experts, weights = self.route(u, router, bias)
            flat = experts.reshape(-1)
            rank = (flat - lo) % n
            if token_mask is not None:
                keep = jnp.repeat(token_mask.reshape(-1), k)
                flat = jnp.where(keep, flat, n)  # counted nowhere
                rank = jnp.where(keep, rank, n)  # and sorted last
            counts = jnp.zeros((n,), jnp.int32).at[flat].add(1, mode="drop")
            self.sow("intermediates", "expert_tokens", counts)
            # Held experts first, in order; the pairs of the others after.
            order = jnp.argsort(rank, stable=True)
            back = jnp.zeros_like(order).at[order].set(
                jnp.arange(order.shape[0], dtype=order.dtype)
            )
            sizes = counts[lo:hi]
        with jax.named_scope("moe_experts"):
            rows = u.astype(self.dtype)[order // k]

            # Imported here: ``fluxmpi_tpu.ops`` brings Pallas with it, which
            # a model without expert layers may never need.
            from ..ops.grouped_matmul import grouped_matmul

            def grouped(x, w):
                # Rows past the held experts' pairs come out zero.
                return grouped_matmul(
                    x.astype(self.dtype), w.astype(self.dtype), sizes
                )

            h = jax.nn.silu(grouped(rows, w1)) * grouped(rows, w3)
            y = grouped(h, w2)[back].reshape(tokens, k, d)
            out = jnp.sum(y * weights[..., None], axis=1)
        if self.shared_width and self.include_shared:
            with jax.named_scope("moe_shared"):
                out = out + GatedMLP(
                    self.shared_width, self.dtype, name="shared"
                )(u).astype(jnp.float32)
        return out.astype(self.dtype).reshape(shape)


class DecoderLayer(nn.Module):
    config: DecoderConfig
    index: int
    dtype: Any
    attention: str = "naive"
    attention_fn: Callable | None = None
    expert_range: tuple[int, int] | None = None

    @nn.compact
    def __call__(self, x, positions, token_mask=None):
        c = self.config

        def norm(name):
            return RMSNorm(c.rms_norm_eps, self.dtype, name=name)

        attn = Attention(
            c, c.layer_types[self.index], self.dtype, self.attention,
            self.attention_fn, name="attn",
        )
        h = x + norm("norm_post_attn")(attn(norm("norm_in")(x), positions))
        u = norm("norm_pre_ff")(h)
        if self.index < c.num_dense_layers:
            y = GatedMLP(c.intermediate_size, self.dtype, name="mlp")(u)
        else:
            ff = ExpertMLP(
                num_experts=c.num_experts, top_k=c.num_experts_per_tok,
                width=c.moe_intermediate_size,
                shared_width=c.num_shared_experts * c.moe_intermediate_size,
                route_norm=c.route_norm, route_scale=c.route_scale,
                expert_range=self.expert_range, dtype=self.dtype, name="moe",
            )
            y = ff(u, token_mask)
        return h + norm("norm_post_ff")(y)


class DecoderLM(nn.Module):
    """Embedding, ``config.num_layers`` :class:`DecoderLayer`, RMSNorm,
    untied head. ``__call__`` returns float32 logits ``[batch, seq,
    vocab]``, or ``[batch, vocab]`` with ``head_at``."""

    # No capacity, no dropped token: a batched prefill computes for each
    # position what a one-token tick computes.
    batched_prefill_safe = True

    config: DecoderConfig
    dtype: Any = jnp.float32
    attention: str = "naive"
    attention_fn: Callable | None = None
    expert_range: tuple[int, int] | None = None

    @property
    def vocab_size(self) -> int:
        return self.config.vocab_size

    @property
    def max_len(self) -> int:
        return self.config.max_position_embeddings

    @property
    def num_layers(self) -> int:
        return self.config.num_layers

    def cache_layers(self) -> tuple[tuple[int, int, int | None], ...]:
        """What each layer keeps of a sequence: ``(kv_heads, head_dim,
        window)``, ``window`` None where a layer attends its whole
        context."""
        c = self.config
        return tuple(
            (c.num_key_value_heads, c.head_dim,
             c.sliding_window if kind == SLIDING else None)
            for kind in c.layer_types
        )

    def expert_row_tile(self, tokens: int) -> int | None:
        """The rows of one row tile of the expert layers' grouped matmul
        in a call over ``tokens`` tokens, None where it is XLA's
        ``ragged_dot`` (or the model has no expert layer): what
        :func:`~fluxmpi_tpu.ops.grouped_matmul.weight_visits` counts
        a tick's weight visits by."""
        from ..ops.grouped_matmul import row_tile

        c = self.config
        if c.num_dense_layers >= c.num_layers:
            return None
        return row_tile(
            tokens * c.num_experts_per_tok, c.hidden_size,
            c.moe_intermediate_size, self.dtype,
        )

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, pos_offset=None,
                 head_at=None, token_mask=None):
        del train  # no dropout, no state: one forward for both
        c = self.config
        init = nn.initializers.normal(0.02)
        embed = self.param("embed", init, (c.vocab_size, c.hidden_size))
        b, s = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(s)[None], (b, s))
        if pos_offset is not None:
            positions = positions + jnp.asarray(pos_offset)[:, None]
        x = embed[tokens].astype(self.dtype)
        if c.mup_enabled:
            x = x * jnp.asarray(c.hidden_size ** 0.5, self.dtype)
        for i in range(c.num_layers):
            x = DecoderLayer(
                c, i, self.dtype, self.attention, self.attention_fn,
                self.expert_range, name=f"layer_{i}",
            )(x, positions, token_mask)
        if head_at is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(head_at)[:, None, None], axis=1
            )[:, 0]
        x = RMSNorm(c.rms_norm_eps, self.dtype, name="norm_out")(x)
        head = self.param("head", init, (c.hidden_size, c.vocab_size))
        return _dot(x, head, self.dtype)
