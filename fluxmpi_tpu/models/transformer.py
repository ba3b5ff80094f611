"""Transformer encoder (BASELINE config 5 — the wrapped-model adapter path).

The reference's fifth benchmark config drives a Flux.Chain Transformer
encoder through the ``FluxMPIFluxModel`` adapter (BASELINE.md config 5;
reference ext/FluxMPIFluxExt.jl). Here the encoder is a flax module (its
state is natively a pytree, so ``synchronize`` needs no adapter — the
adapter path is exercised separately by wrapping it in
:class:`fluxmpi_tpu.FluxModelWrapper`-style containers in tests).

TPU-first choices: bf16-friendly dtype threading, pre-LayerNorm blocks
(stable without warmup at large batch), attention via
``nn.MultiHeadDotProductAttention`` (lowers to MXU-tiled batched matmuls),
static shapes throughout. For sequence lengths beyond one chip's HBM, swap
the attention callable for :func:`fluxmpi_tpu.parallel.ring.ring_attention`.
"""

from __future__ import annotations

from typing import Callable

import flax.linen as nn
import jax
import jax.numpy as jnp

__all__ = ["TransformerEncoder", "TransformerLM"]


def _resolve_attention_mode(mode: str) -> str:
    """Resolve the ``attention=`` switch: ``"auto"`` engages the Pallas
    flash kernel on TPU backends and keeps the dense attend elsewhere
    (the kernel only *runs* in pallas interpret mode off-TPU — correct,
    but an emulation path, not a fast one)."""
    if mode == "auto":
        return "flash" if jax.default_backend() == "tpu" else "naive"
    if mode not in ("naive", "flash"):
        raise ValueError(
            f"attention must be 'naive', 'flash', or 'auto'; got {mode!r}"
        )
    return mode


class EncoderBlock(nn.Module):
    d_model: int
    num_heads: int
    d_ff: int
    dropout: float
    dtype: jnp.dtype
    attention_fn: Callable | None = None
    decode: bool = False
    attention: str = "naive"
    attention_causal: bool = False
    ln_eps: float = 1e-6

    def make_ff(self) -> nn.Module | None:
        """Hook: return a module for the feed-forward sublayer (called as
        ``ff(h, train=train)``), or ``None`` for the default dense MLP.
        Subclasses swap in alternatives (e.g. a mixture-of-experts layer,
        :class:`fluxmpi_tpu.models.moe.MoEEncoderBlock`)."""
        return None

    @nn.compact
    def __call__(self, x, *, train: bool = True, mask=None):
        attn_kwargs = {}
        mode = _resolve_attention_mode(self.attention)
        if mode == "flash":
            if self.attention_fn is not None:
                raise ValueError(
                    "attention='flash' conflicts with an explicit "
                    "attention_fn — pass one or the other"
                )
            from ..ops.flash_attention import flash_attention_fn

            # The flash kernel rides BOTH hot paths. Training: the mask
            # (causal and/or padding/packing) is recovered into segment
            # ids; ``attention_causal`` folds the causal structure into
            # the kernel so upper-triangle tiles skip compute. Decode:
            # flax's cache-index mask is a trailing valid prefix —
            # exactly representable by segment ids (positions past the
            # cache index land in segment 0 and their fully-masked
            # k-tiles are skipped). That is generate()'s contiguous
            # cache; the serving engine's paged pool has its own decode
            # kernel (ops/paged_attention.py). The decode mask
            # is representable by construction, so the O(s·k) runtime
            # fidelity check is skipped there; training masks arrive
            # from callers and stay checked.
            attn_kwargs["attention_fn"] = flash_attention_fn(
                causal=self.attention_causal and not self.decode,
                mask_check=not self.decode,
            )
        elif self.attention_fn is not None and not self.decode:
            # Autoregressive decoding uses flax's KV cache with the plain
            # dense single-query attend — a custom attention_fn
            # (ring/ulysses) is a training-time kernel and is bypassed at
            # decode. The attention='flash' switch above is the decode-
            # capable path.
            attn_kwargs["attention_fn"] = self.attention_fn
        h = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype, name="ln1")(x)
        h = nn.MultiHeadDotProductAttention(
            num_heads=self.num_heads,
            dtype=self.dtype,
            dropout_rate=self.dropout,
            deterministic=not train,
            decode=self.decode,
            name="attn",
            **attn_kwargs,
        )(h, h, mask=mask)
        x = x + h
        h = nn.LayerNorm(epsilon=self.ln_eps, dtype=self.dtype, name="ln2")(x)
        ff = self.make_ff()
        if ff is None:
            h = nn.Dense(self.d_ff, dtype=self.dtype, name="ff1")(h)
            h = nn.gelu(h)
            h = nn.Dense(self.d_model, dtype=self.dtype, name="ff2")(h)
        else:
            h = ff(h, train=train)
        return x + h


class TransformerEncoder(nn.Module):
    """Pre-LN encoder stack over already-embedded inputs
    ``(batch, seq, d_model)``."""

    num_layers: int = 4
    d_model: int = 128
    num_heads: int = 4
    d_ff: int = 512
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attention_fn: Callable | None = None
    decode: bool = False
    attention: str = "naive"
    attention_causal: bool = False
    ln_eps: float = 1e-6

    def make_block(self, i: int) -> nn.Module:
        """Hook: build encoder block ``i`` (subclasses swap the block type)."""
        return EncoderBlock(
            d_model=self.d_model,
            num_heads=self.num_heads,
            d_ff=self.d_ff,
            dropout=self.dropout,
            dtype=self.dtype,
            attention_fn=self.attention_fn,
            decode=self.decode,
            attention=self.attention,
            attention_causal=self.attention_causal,
            ln_eps=self.ln_eps,
            name=f"block_{i}",
        )

    @nn.compact
    def __call__(self, x, *, train: bool = True, mask=None):
        x = x.astype(self.dtype)
        for i in range(self.num_layers):
            x = self.make_block(i)(x, train=train, mask=mask)
        return nn.LayerNorm(epsilon=self.ln_eps, dtype=jnp.float32, name="ln_out")(x)


class TransformerLM(nn.Module):
    """Token-level wrapper: embedding + learned positions + encoder + LM
    head (weight-tied). Subclasses override :meth:`make_encoder` to swap the
    block type (e.g. :class:`fluxmpi_tpu.models.moe.MoETransformerLM`)."""

    # Whether a batched causal forward over the prompt is token-exact
    # with single-position decoding — the gate for generate()'s default
    # batched prefill. Plain dense blocks: yes. Subclasses whose
    # batched forward computes DIFFERENT per-token functions (MoE
    # capacity routing drops over-capacity tokens a one-token tick
    # never drops) override this to False and keep the scan prefill.
    # Deliberately a plain class attribute, not a dataclass field.
    batched_prefill_safe = True

    vocab_size: int = 1024
    max_len: int = 512
    num_layers: int = 4
    d_model: int = 128
    num_heads: int = 4
    d_ff: int = 512
    dropout: float = 0.0
    dtype: jnp.dtype = jnp.float32
    attention_fn: Callable | None = None
    decode: bool = False
    # attention="flash"|"naive"|"auto": the kernel-plane switch. "flash"
    # routes every attend — the training forward (and its custom_vjp
    # backward) AND cached single-position decode — through the Pallas
    # flash kernels of fluxmpi_tpu.ops.flash_attention; "auto" picks
    # flash on TPU and naive elsewhere. Orthogonal to attention_fn
    # (ring/ulysses sequence parallelism), which stays a training-time
    # kernel; combining both raises.
    attention: str = "naive"
    ln_eps: float = 1e-6

    def make_encoder(self) -> nn.Module:
        """Hook: build the encoder stack (subclasses swap the block type)."""
        return TransformerEncoder(
            num_layers=self.num_layers,
            d_model=self.d_model,
            num_heads=self.num_heads,
            d_ff=self.d_ff,
            dropout=self.dropout,
            dtype=self.dtype,
            attention_fn=self.attention_fn,
            decode=self.decode,
            attention=self.attention,
            # The LM always applies its own causal mask at train time, so
            # the flash kernel can fold causality in and skip the upper
            # triangle (decode composes causality from the cache index
            # instead — EncoderBlock drops the flag there).
            attention_causal=True,
            ln_eps=self.ln_eps,
            name="encoder",
        )

    @nn.compact
    def __call__(self, tokens, *, train: bool = True, targets=None,
                 loss_chunk: int = 8192, pos_offset=None,
                 hidden: bool = False):
        """Returns logits ``[..., vocab]``; or, with ``targets`` (int
        labels, same shape as ``tokens``), the per-token cross-entropy
        losses computed by the chunked fused head
        (:func:`fluxmpi_tpu.ops.unembed_cross_entropy`) — the
        ``[tokens, vocab]`` logits tensor is never materialized, and the
        head matmuls run in the model dtype with f32 accumulation.
        ``loss_chunk`` tiles the vocab on that path.

        ``hidden=True`` instead returns ``(hidden_states, embedding)`` —
        the pre-head ``[..., d_model]`` activations and the tied
        ``[vocab, d_model]`` table — for composing custom heads, e.g.
        the vocab-sharded
        :func:`fluxmpi_tpu.ops.tp_unembed_cross_entropy` under tensor
        parallelism.

        With ``decode=True`` (autoregressive inference,
        :func:`fluxmpi_tpu.models.generate`): tokens arrive one position
        per call, ``pos_offset`` (traced int scalar) selects the position
        embedding, the attention layers read/extend their flax KV caches
        (``mutable=["cache"]``), and no causal mask is needed — the cache
        index provides causality.

        Without ``decode``, a ``pos_offset`` of shape ``[batch]`` places
        row ``r``'s tokens at positions ``pos_offset[r] + [0, seq)``: the
        position embedding is gathered per row. That is the serving
        engine's paged decode entry (one token a slot, every slot at its
        own position, the K/V state held by the engine's ``attention_fn``
        instead of a flax cache). ``pos_offset=None`` is the plain
        forward, unchanged."""
        embed = nn.Embed(self.vocab_size, self.d_model, dtype=self.dtype, name="embed")
        pos = self.param(
            "pos_embed",
            nn.initializers.normal(0.02),
            (self.max_len, self.d_model),
        )
        seq = tokens.shape[-1]
        if self.decode:
            if targets is not None:
                raise ValueError("targets (fused loss) is a training path; "
                                 "decode=True is inference")
            offset = 0 if pos_offset is None else pos_offset
            pos_slice = jax.lax.dynamic_slice_in_dim(pos, offset, seq)
            x = embed(tokens) + pos_slice[None].astype(self.dtype)
            mask = None
        else:
            if pos_offset is None:
                pos_rows = pos[:seq][None, :, :]
            else:
                at = jnp.asarray(pos_offset)[:, None] + jnp.arange(seq)
                pos_rows = pos[at]
            x = embed(tokens) + pos_rows.astype(self.dtype)
            # The causal mask, which the flash path folds into its kernels
            # (``attention_causal``): handed a mask that says nothing
            # else, ``flash_attention_fn`` would recover one segment id a
            # token from it and the kernels would compare ids on every
            # tile (PERF.md §6, PR 42: 2.22 ms a layer where causality
            # alone takes 1.97).
            flash = _resolve_attention_mode(self.attention) == "flash"
            mask = None if flash else nn.make_causal_mask(tokens)
        x = self.make_encoder()(x, train=train, mask=mask)
        if hidden:
            if targets is not None:
                raise ValueError("pass either targets or hidden, not both")
            return x, embed.embedding
        if targets is not None:
            from ..ops import unembed_cross_entropy

            # The table passes through in its own (f32 param) dtype: the
            # op casts tiles to x's dtype for the MXU but returns the
            # embedding gradient un-quantized — same optimizer numerics
            # as the dense head for the model's largest parameter.
            return unembed_cross_entropy(
                x.astype(self.dtype), embed.embedding, targets,
                chunk=loss_chunk,
            )
        return embed.attend(x.astype(jnp.float32))
