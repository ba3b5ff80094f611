"""A decoder LM built from a configuration, and its expert layer.

:class:`TransformerLM` is one block (pre-LayerNorm, GELU, learned
positions, ``nn.MultiHeadDotProductAttention``); today's open models are
not that block. :class:`DecoderLM` reads its block from a
:class:`DecoderConfig` (the keys of a Hugging Face ``config.json``, see
:meth:`DecoderConfig.from_hf`):

- RMSNorm, four a layer in the sandwich placement
  (``norm_placement="sandwich"``: ``h = x + N_post_attn(Attn(N_in(x)))``,
  ``y = h + N_post_ff(FF(N_pre_ff(h)))``) or two in the pre-norm one
  (``"pre"``: ``h = x + Attn(N_in(x))``, ``y = h + FF(N_pre_ff(h))``);
- one of FOUR kinds of mixer a layer (``layer_types``). Two keep K
  and V: ``num_heads`` query heads over ``num_kv_heads`` K/V heads of
  ``head_dim`` (independent of ``hidden_size``), RMSNorm over ``head_dim``
  on ``q`` and ``k``, a sigmoid output gate (``output_gate``), as
  ``"sliding_attention"`` (rotary positions, the mask ``0 <= i - j <
  sliding_window``) or ``"full_attention"`` (the causal mask; rotary
  positions only where ``full_attention_rope``).
  The third, ``"latent_attention"`` (multi-head latent attention, MLA),
  keeps ONE row a token: a latent ``c`` of ``kv_lora_rank`` (RMSNormed)
  and one rotary key ``k_r`` of ``qk_rope_head_dim`` shared by all heads;
  each head's key is ``[Wkvb_K c; k_r]`` and its value ``Wkvb_V c``
  (:class:`LatentAttention`: the un-absorbed form over a call's own
  tokens, the absorbed form against a cache of rows), rotary angles
  scaled as ``rope_scaling`` says (``deepseek_yarn``). The fourth,
  ``"mamba"`` (:class:`MambaMixer`: Mamba-2), keeps nothing a token: a
  recurrent STATE a sequence (``mamba_n_heads x mamba_d_head x
  mamba_d_state``) and the last ``mamba_d_conv - 1`` inputs of its
  causal convolution; over a call's own tokens the recurrence runs as a
  chunked scan, against a cache one step a token. A full layer may run
  without the head norms (``qk_norm``) and at a configured softmax scale
  (``attention_multiplier``). A fifth kind of layer, ``"mamba_attention"``,
  runs TWO of them side by side on the one normed input, a Mamba-2 mixer
  and full attention: ``h = x + ssm_out_multiplier * Mamba(ssm_in_multiplier
  * N(x)) + attention_out_multiplier * Attn(attention_in_multiplier *
  N(x))``, one norm and one residual join, so the layer keeps a state AND
  keys and values;
- a gated (SwiGLU) MLP in the first ``num_dense_layers`` layers and an
  :class:`ExpertMLP` in the rest (scores ``sigmoid(u Wr)`` normalised
  over the chosen, or ``score_func="softmax"``: the largest logits,
  weighed by a softmax over the chosen), with a shared MLP of
  ``num_shared_experts x moe_intermediate_size`` or of a width of its own
  (``shared_intermediate_size``); with ``mlp_activation="relu2"`` every
  MLP, routed or shared, is UN-GATED: ``relu(u W_up)^2 W_down``, two
  matrices;
- a layer is that pair, mixer then feed-forward (``block="pair"``), or
  ONE sublayer (``block="single"``: ``y = x + Sub(N_in(x))``), which
  ``layer_types`` names: a mixer alone, or ``"experts"``, the routed
  feed-forward alone, which keeps nothing of a sequence;
- a head of its own or the embedding's transpose
  (``tie_word_embeddings``); the embedding scaled by
  ``sqrt(hidden_size)`` where ``mup_enabled`` and by
  ``embedding_multiplier``, each sublayer's result by
  ``residual_multiplier`` as it joins the stream, the logits divided by
  ``logits_scaling``; and the other muP multipliers a configuration may
  carry, each applied in the forward where its model applies it and not
  at all at 1.0: ``key_multiplier``, the five ``ssm_multipliers`` over the
  segments ``[z; x; B; C; dt]`` of a Mamba mixer's input projection, the
  two ``mlp_multipliers`` (a gated MLP's gate, inside the activation, and
  its result), ``lm_head_multiplier``.

``num_experts`` counts the experts a layer HOLDS; where the router is
wider (``num_routed_experts``: this chip's share of an expert-parallel
deployment) the layer routes over all of them and computes its own
experts' part of the result (:class:`ExpertMLP`, ``expert_range``).

Compute runs in ``dtype`` (bfloat16 in the served configuration) with
float32 accumulation; the norms, the router, the rotary angles and the
softmax statistics are float32. Parameters are held in the dtype they
are given in.

The serving engine's seam is the call argument ``cache``: a view of a
serving cache that hands each KEEPING sublayer the handle of its own
number, ``cache.sublayer(n)``, ``n`` its place in
:meth:`DecoderLM.cache_layers` with the Nones left out (static: known
from ``layer_types``). A handle offers what its kind of mixer needs and
nothing else, and a mixer handed one of another kind raises while it is
traced; :mod:`fluxmpi_tpu.serving.cache` states that protocol, once, and
holds its two implementations (a prefill's, a decode tick's).
``pos_offset`` (``[batch]``) places each row at its own
position and ``head_at`` (``[batch]``) takes the head at one position a
row: a prefill never builds ``[prompt, vocab]`` logits. ``token_mask``
(``[batch, seq]``) names the real tokens: padding and idle slots are
routed to no expert and move no Mamba layer's state (their step is 0).
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import numpy as np

from .transformer import _resolve_attention_mode

__all__ = ["DecoderConfig", "DecoderLM", "ExpertMLP", "Keeps",
           "LatentAttention", "MambaMixer", "causal_attention"]

SLIDING, FULL = "sliding_attention", "full_attention"
LATENT = "latent_attention"
MAMBA = "mamba"
# Two mixers side by side on one normed input: a Mamba-2 mixer and full
# attention, both results joined to the stream at once.
PARALLEL = "mamba_attention"
# Not a mixer: a layer of a ``block="single"`` model that is its routed
# experts alone.
EXPERTS = "experts"


class Keeps(NamedTuple):
    """What ONE keeping sublayer keeps of a sequence in a serving cache
    (:meth:`DecoderLM.cache_layers`; :mod:`fluxmpi_tpu.serving.cache`
    builds its pools from these and names its handles by ``kind``).
    ``"full"``: ``heads`` K/V heads of ``width`` a token, the whole
    context; ``"window"``: the same within the newest ``window`` positions;
    ``"latent"``: ONE row of ``width`` a token, read as key and as value,
    and no V; ``"state"``: nothing a token, one recurrent ``state``
    ``(heads, head_dim, d_state)`` and one ``tail`` ``(d_conv - 1,
    conv_dim)`` of pre-convolution columns a SEQUENCE, whatever its
    length."""

    kind: str
    heads: int = 1
    width: int = 1
    window: int | None = None
    state: tuple[int, ...] | None = None
    tail: tuple[int, ...] | None = None


def _own(handle, *kinds: str):
    """``handle`` (a sublayer's of a serving cache, or None), which must
    be of one of ``kinds``: no mixer reads what another kind keeps."""
    if handle is not None and handle.kind not in kinds:
        raise TypeError(
            f"a sublayer that keeps {' or '.join(kinds)} was handed the "
            f"cache handle of a {handle.kind!r} sublayer"
        )
    return handle


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
    # The block around the attention: four norms or two, the gate or none.
    norm_placement: str = "sandwich"
    output_gate: bool = True
    # The router's width where it is not the experts held (0: the same).
    num_routed_experts: int = 0
    # Latent attention: what a token leaves in the cache (kv_lora_rank +
    # qk_rope_head_dim) and the heads rebuilt from it.
    kv_lora_rank: int = 0
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # ``rope_scaling`` of the config.json as sorted (key, value) pairs
    # (hashable); None: plain rotary angles.
    rope_scaling: tuple | None = None
    # RMSNorm over ``head_dim`` on q and k (full and window layers), and
    # the softmax scale where it is not ``head_dim ** -0.5``.
    qk_norm: bool = True
    attention_multiplier: float | None = None
    # muP-style multipliers: on the embedding, on each sublayer's result
    # as it joins the residual stream, and the logits' divisor.
    embedding_multiplier: float = 1.0
    residual_multiplier: float = 1.0
    logits_scaling: float = 1.0
    # The head is the embedding's transpose.
    tie_word_embeddings: bool = False
    # The shared MLP's width (0: num_shared_experts * moe_intermediate_size).
    shared_intermediate_size: int = 0
    # What a layer is: a mixer then a feed-forward ("pair"), or the one
    # sublayer its entry of ``layer_types`` names ("single").
    block: str = "pair"
    # The MLPs, routed and shared: "swiglu" (gate, up, down) or "relu2"
    # (un-gated: up, down).
    mlp_activation: str = "swiglu"
    # Mamba-2 layers: heads of ``mamba_d_head``, a state of ``mamba_d_state``
    # a head dimension, B and C shared by the consecutive heads of each of
    # ``mamba_n_groups`` groups, a causal depthwise convolution over
    # ``mamba_d_conv`` positions, the prefill's scan in chunks of
    # ``mamba_chunk_size``.
    mamba_n_heads: int = 0
    mamba_d_head: int = 0
    mamba_d_state: int = 0
    mamba_n_groups: int = 1
    mamba_d_conv: int = 4
    mamba_chunk_size: int = 256
    # Rotary positions on the full layers too (window layers always).
    full_attention_rope: bool = False
    # muP multipliers of a ``"mamba_attention"`` layer's two branches (on
    # the normed input each reads and on its result as both join the
    # stream), on the keys, on the five segments ``[z; x; B; C; dt]`` of a
    # Mamba mixer's input projection, on a gated MLP's gate (inside the
    # activation) and result, and on the logits. 1.0: not applied.
    attention_in_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    key_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_out_multiplier: float = 1.0
    ssm_multipliers: tuple[float, ...] = (1.0,) * 5
    mlp_multipliers: tuple[float, float] = (1.0, 1.0)
    lm_head_multiplier: float = 1.0

    def __post_init__(self):
        if self.block not in ("pair", "single"):
            raise ValueError(f"unknown block {self.block!r}")
        if len(self.ssm_multipliers) != 5 or len(self.mlp_multipliers) != 2:
            raise ValueError(
                "ssm_multipliers are five ([z; x; B; C; dt]) and "
                "mlp_multipliers two (gate, down); got "
                f"{self.ssm_multipliers} and {self.mlp_multipliers}"
            )
        if self.mlp_activation not in ("swiglu", "relu2"):
            raise ValueError(
                f"unknown mlp_activation {self.mlp_activation!r}")
        unknown = set(self.layer_types) - {SLIDING, FULL, LATENT, MAMBA} - (
            {EXPERTS} if self.block == "single" else {PARALLEL})
        if unknown:
            raise ValueError(f"unknown layer types {sorted(unknown)}")
        if SLIDING in self.layer_types and not self.sliding_window:
            raise ValueError("sliding_attention layers need sliding_window")
        if LATENT in self.layer_types and not (
            self.kv_lora_rank and self.qk_nope_head_dim
            and self.qk_rope_head_dim and self.v_head_dim
        ):
            raise ValueError(
                "latent_attention layers need kv_lora_rank, "
                "qk_nope_head_dim, qk_rope_head_dim and v_head_dim"
            )
        if self.norm_placement not in ("sandwich", "pre"):
            raise ValueError(
                f"unknown norm_placement {self.norm_placement!r}"
            )
        scaling = dict(self.rope_scaling or ())
        if scaling and scaling.get("type") != "deepseek_yarn":
            raise ValueError(
                f"unknown rope_scaling type {scaling.get('type')!r}"
            )
        if self.num_routed_experts and (
            self.num_routed_experts < self.num_experts
        ):
            raise ValueError(
                f"a router over {self.num_routed_experts} experts cannot "
                f"hold {self.num_experts}"
            )
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"{self.num_attention_heads} query heads are not a multiple "
                f"of {self.num_key_value_heads} K/V heads"
            )
        if self.score_func not in ("sigmoid", "softmax"):
            raise ValueError(f"unknown score_func {self.score_func!r}")
        if {MAMBA, PARALLEL} & set(self.layer_types):
            if not (self.mamba_n_heads and self.mamba_d_head
                    and self.mamba_d_state):
                raise ValueError(
                    "mamba layers need mamba_n_heads, mamba_d_head and "
                    "mamba_d_state"
                )
            if self.mamba_n_groups < 1 or (
                    self.mamba_n_heads % self.mamba_n_groups):
                raise ValueError(
                    f"{self.mamba_n_heads} heads are not whole groups of B "
                    f"and C for {self.mamba_n_groups}"
                )

    @property
    def num_layers(self) -> int:
        return len(self.layer_types)

    @property
    def latent_row(self) -> int:
        """What a token leaves in a latent layer's cache."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def mamba_inner(self) -> int:
        """A Mamba layer's inner width: its heads side by side."""
        return self.mamba_n_heads * self.mamba_d_head

    @property
    def mamba_conv_dim(self) -> int:
        """What the convolution runs over: ``[x; B; C]``, B and C a
        group."""
        return self.mamba_inner + 2 * self.mamba_n_groups * self.mamba_d_state

    @property
    def expert_layer_ids(self) -> tuple[int, ...]:
        """The layers that have routed experts: past the leading dense
        ones, or the ``"experts"`` layers of a ``block="single"`` model."""
        if self.block == "single":
            return tuple(i for i, kind in enumerate(self.layer_types)
                         if kind == EXPERTS)
        return tuple(range(self.num_dense_layers, self.num_layers))

    @property
    def shared_width(self) -> int:
        """The width of the MLP every token passes beside the experts."""
        return (self.shared_intermediate_size
                or self.num_shared_experts * self.moe_intermediate_size)

    def kept_by(self, layer_type: str) -> tuple[Keeps | None, ...]:
        """What a layer of ``layer_type`` keeps of a sequence, a sublayer:
        see :meth:`DecoderLM.cache_layers`."""
        state = Keeps(
            "state",
            state=(self.mamba_n_heads, self.mamba_d_head, self.mamba_d_state),
            tail=(self.mamba_d_conv - 1, self.mamba_conv_dim))
        full = Keeps("full", self.num_key_value_heads, self.head_dim)
        return {
            EXPERTS: (None,), MAMBA: (state,), PARALLEL: (state, full),
            LATENT: (Keeps("latent", width=self.latent_row),), FULL: (full,),
            SLIDING: (full._replace(kind="window",
                                    window=self.sliding_window),),
        }[layer_type]

    @classmethod
    def from_hf(cls, cfg: dict) -> "DecoderConfig":
        """From the keys of a ``config.json``, ``model_type`` ``"afmoe"``,
        ``"nemotron_h"`` (``hybrid_override_pattern``: every layer ONE
        sublayer, ``M`` Mamba-2, ``E`` routed experts, ``*`` attention,
        read as full attention without positions, head norms or gate;
        ``mlp_hidden_act`` ``relu2``: un-gated experts of
        ``moe_intermediate_size`` and a shared one of
        ``moe_shared_expert_intermediate_size``; the router
        ``n_routed_experts`` wide, sigmoid scores normalised over the
        chosen times ``routed_scaling_factor``; ``mamba_num_heads``,
        ``mamba_head_dim``, ``ssm_state_size``, ``n_groups``,
        ``conv_kernel``, ``chunk_size``; pre-norm), ``"sarvam_mla"`` (whose
        names for the same things are mapped:
        ``first_k_dense_replace``, ``routed_scaling_factor``; every layer
        latent attention in a pre-norm block without the output gate;
        ``head_dim`` there is the cache's row, not a head's width) or
        ``"granitemoehybrid"`` (``layer_types`` of ``"mamba"`` and
        ``"attention"``, read as full attention without positions, head
        norms or gate at the scale ``attention_multiplier``;
        ``num_local_experts`` experts of ``intermediate_size`` chosen by
        the largest router logits and weighed by a softmax over the
        chosen; a shared MLP of ``shared_intermediate_size``; pre-norm;
        the three multipliers; a tied head) or ``"falcon_h1"`` (every
        layer a ``"mamba_attention"`` pair of mixers on one normed input,
        rotary full attention without head norms or gate, a dense SwiGLU
        MLP; ``mamba_d_ssm`` the heads side by side; the muP multipliers
        under their published names; pre-norm; it raises for what this
        class cannot build: attention in some layers only, a Mamba mixer
        without its MLP, a norm before the gate, a projection bias, a
        rotary scaling); keys this class does not know are left alone."""
        names = {f.name for f in dataclasses.fields(cls)}
        known = {k: v for k, v in cfg.items() if k in names and v is not None}
        if cfg.get("model_type") == "sarvam_mla":
            heads = cfg["num_attention_heads"]
            known.update(
                layer_types=(LATENT,) * cfg["num_hidden_layers"],
                num_dense_layers=cfg["first_k_dense_replace"],
                route_scale=cfg["routed_scaling_factor"],
                num_key_value_heads=heads,
                head_dim=cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
                norm_placement="pre", output_gate=False,
            )
        elif cfg.get("model_type") == "granitemoehybrid":
            known.update(
                layer_types=tuple(MAMBA if kind == MAMBA else FULL
                                  for kind in cfg["layer_types"]),
                head_dim=cfg["hidden_size"] // cfg["num_attention_heads"],
                num_experts=cfg["num_local_experts"],
                moe_intermediate_size=cfg["intermediate_size"],
                score_func="softmax", norm_placement="pre",
                output_gate=False, qk_norm=False,
            )
        elif cfg.get("model_type") == "nemotron_h":
            kinds = {"M": MAMBA, "E": EXPERTS, "*": FULL}
            pattern = cfg["hybrid_override_pattern"]
            if set(pattern) - set(kinds) or (
                    len(pattern) != cfg["num_hidden_layers"]):
                raise ValueError(
                    f"hybrid_override_pattern {pattern!r}: "
                    f"{cfg['num_hidden_layers']} layers of M, E or *"
                )
            if cfg["mlp_hidden_act"] != "relu2" or cfg["n_group"] != 1:
                raise ValueError(
                    "nemotron_h as served: relu2 MLPs, one group of experts"
                )
            routed = cfg["n_routed_experts"]
            known.update(
                layer_types=tuple(kinds[kind] for kind in pattern),
                block="single", mlp_activation="relu2",
                num_routed_experts=routed,
                num_experts=cfg.get("num_experts", routed),
                shared_intermediate_size=cfg["n_shared_experts"]
                * cfg["moe_shared_expert_intermediate_size"],
                route_scale=cfg["routed_scaling_factor"],
                route_norm=cfg["norm_topk_prob"],
                rms_norm_eps=cfg["layer_norm_epsilon"],
                mamba_n_heads=cfg["mamba_num_heads"],
                mamba_d_head=cfg["mamba_head_dim"],
                mamba_d_state=cfg["ssm_state_size"],
                mamba_n_groups=cfg["n_groups"],
                mamba_d_conv=cfg["conv_kernel"],
                mamba_chunk_size=cfg["chunk_size"],
                norm_placement="pre", output_gate=False, qk_norm=False,
            )
        elif cfg.get("model_type") == "falcon_h1":
            known.update(_falcon_h1_fields(cfg))
        else:
            known["layer_types"] = tuple(cfg["layer_types"])
        if known.get("rope_scaling") is not None:
            known["rope_scaling"] = tuple(sorted(cfg["rope_scaling"].items()))
        return cls(**known)


def _falcon_h1_fields(cfg: dict) -> dict:
    """What ``model_type: "falcon_h1"`` fixes beyond the keys it shares
    with :class:`DecoderConfig` by name (the Mamba sizes, the
    multipliers); raises for a value this class cannot build."""
    as_built = {
        "attn_layer_indices": None, "mamba_use_mlp": True,
        "mamba_norm_before_gate": False, "mamba_rms_norm": True,
        "mamba_conv_bias": True, "mamba_proj_bias": False,
        "attention_bias": False, "mlp_bias": False, "projectors_bias": False,
        "hidden_act": "silu", "rope_scaling": None,
    }
    odd = {k: cfg[k] for k, v in as_built.items() if k in cfg and cfg[k] != v}
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    if cfg.get("mamba_d_ssm", inner) != inner:
        odd["mamba_d_ssm"] = cfg["mamba_d_ssm"]
    if odd:
        raise ValueError(
            f"falcon_h1 as served: {as_built}, mamba_d_ssm = mamba_n_heads "
            f"* mamba_d_head; got {odd}"
        )
    return dict(
        layer_types=(PARALLEL,) * cfg["num_hidden_layers"],
        num_dense_layers=cfg["num_hidden_layers"],  # no expert anywhere
        norm_placement="pre", output_gate=False, qk_norm=False,
        full_attention_rope=True,
        ssm_multipliers=tuple(cfg["ssm_multipliers"]),
        mlp_multipliers=tuple(cfg["mlp_multipliers"]),
    )


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
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = positions.astype(jnp.float32)[..., None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    x32 = x.astype(jnp.float32)
    a, b = x32[..., :half], x32[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def causal_attention(q, k, v, *, window: int | None, mode: str):
    """Causal attention of ``q`` ``[batch, seq, heads, head_dim]`` over
    ``k`` / ``v`` with fewer (grouped) heads, within ``window`` keys where
    given; ``v`` may have a width of its own (the result's). ``mode``
    ``"flash"``: the Pallas kernels (K/V never repeated to
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
    return out.reshape(b, s, h, v.shape[-1]).astype(q.dtype)


def _yarn_mscale(factor: float, mscale: float) -> float:
    return 0.1 * mscale * math.log(factor) + 1.0 if factor > 1 else 1.0


def yarn_frequencies(dim: int, theta: float, scaling: dict) -> np.ndarray:
    """The ``dim // 2`` rotary frequencies: ``theta ** (-2i / dim)``, and
    under ``deepseek_yarn`` (a non-empty ``scaling``) between that and
    that over ``factor``, a linear ramp over the pairs from the one whose
    wavelength makes ``beta_fast`` turns in
    ``original_max_position_embeddings`` positions to ``beta_slow``
    turns. float64, computed once while tracing."""
    plain = theta ** -(np.arange(0, dim, 2, dtype=np.float64) / dim)
    if not scaling:
        return plain

    def pair_of(turns):
        return dim * math.log(
            scaling["original_max_position_embeddings"]
            / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_of(scaling["beta_fast"])), 0)
    high = min(math.ceil(pair_of(scaling["beta_slow"])), dim - 1)
    ramp = np.clip(
        (np.arange(dim // 2) - low) / max(high - low, 0.001), 0.0, 1.0
    )
    return plain / scaling["factor"] * ramp + plain * (1.0 - ramp)


def latent_scales(c: "DecoderConfig"):
    """``(frequencies, cos / sin multiplier, softmax scale)`` of the
    latent layers: plain rotary angles and ``q_head_dim ** -0.5`` without
    ``rope_scaling``; with it the ``deepseek_yarn`` frequencies, ``mscale /
    mscale_all_dim`` on cos and sin, and the scale times the square of
    ``0.1 * mscale_all_dim * ln(factor) + 1``."""
    dim = c.qk_rope_head_dim
    scale = (c.qk_nope_head_dim + dim) ** -0.5
    scaling = dict(c.rope_scaling or ())
    trig = 1.0
    if scaling:
        factor = scaling["factor"]
        all_dim = scaling.get("mscale_all_dim", 0)
        if all_dim:
            scale *= _yarn_mscale(factor, all_dim) ** 2
        trig = _yarn_mscale(factor, scaling.get("mscale", 1)) / _yarn_mscale(
            factor, all_dim)
    return yarn_frequencies(dim, c.rope_theta, scaling), trig, scale


def _rotary_pairs(x, positions, freq, trig_scale: float):
    """Rotary positions on ``x`` ``[batch, seq, heads, dim]`` whose
    CONSECUTIVE lanes pair up (``(x0, x1), (x2, x3), ...``: the
    interleaved layout of the latent models' weights), pair ``i`` rotated
    by ``position * freq[i]``; the lanes stay where they were. float32.
    A pair's partner comes by a signed permutation matrix on the lanes
    (exact in any dtype: one product of a value with 1 or -1 a lane):
    halves of ``dim // 2`` lanes, or a lane rotation's slices, would each
    be padded to a whole 128-lane tile (at 16,384 tokens and 64 heads of
    64 rotary dims, 0.5 GB apiece)."""
    dim = x.shape[-1]
    angle = positions.astype(jnp.float32)[..., None, None] * jnp.repeat(
        jnp.asarray(freq, jnp.float32), 2)
    cos, sin = jnp.cos(angle) * trig_scale, jnp.sin(angle) * trig_scale
    swap = np.zeros((dim, dim), np.float32)
    first = np.arange(0, dim, 2)
    swap[first + 1, first] = -1.0  # partner[2i] = -x[2i + 1]
    swap[first, first + 1] = 1.0  # partner[2i + 1] = x[2i]
    partner = jnp.einsum(
        "...d,de->...e", x, jnp.asarray(swap, x.dtype),
        precision=jax.lax.Precision.HIGHEST, preferred_element_type=x.dtype,
    )
    return x.astype(jnp.float32) * cos + partner.astype(jnp.float32) * sin


class Attention(nn.Module):
    config: DecoderConfig
    layer_type: str
    dtype: Any
    attention: str = "naive"

    @nn.compact
    def __call__(self, u, positions, cache=None):
        c = self.config
        cache = _own(
            cache, "window" if self.layer_type == SLIDING else "full")
        heads, kvh, hd = (c.num_attention_heads, c.num_key_value_heads,
                          c.head_dim)
        init = nn.initializers.normal(0.02)
        b, s, d = u.shape
        wq = self.param("wq", init, (d, heads * hd))
        wk = self.param("wk", init, (d, kvh * hd))
        wv = self.param("wv", init, (d, kvh * hd))
        wg = (self.param("wg", init, (d, heads * hd))
              if c.output_gate else None)
        wo = self.param("wo", init, (heads * hd, d))
        q = _dot(u, wq, self.dtype).reshape(b, s, heads, hd)
        k = _dot(u, wk, self.dtype).reshape(b, s, kvh, hd)
        if c.key_multiplier != 1.0:
            k = k * c.key_multiplier
        v = _dot(u, wv, self.dtype).reshape(b, s, kvh, hd).astype(self.dtype)
        if c.qk_norm:
            q_scale = self.param("q_norm", nn.initializers.ones, (hd,))
            k_scale = self.param("k_norm", nn.initializers.ones, (hd,))
            q = _rms_norm(q, q_scale, c.rms_norm_eps)
            k = _rms_norm(k, k_scale, c.rms_norm_eps)
        if c.attention_multiplier is not None:
            # The attention divides by sqrt(head_dim) itself; the rest of
            # a configured scale rides on the queries, applied before
            # their one rounding to ``dtype``.
            q = q * (c.attention_multiplier * hd ** 0.5)
        window = c.sliding_window if self.layer_type == SLIDING else None
        if window is not None or c.full_attention_rope:
            with jax.named_scope("rope"):
                q = _rotary(q, positions, c.rope_theta)
                k = _rotary(k, positions, c.rope_theta)
        q, k = q.astype(self.dtype), k.astype(self.dtype)
        if cache is not None:
            out = cache.attend(q, k, v)
        else:
            out = causal_attention(
                q, k, v, window=window,
                mode=_resolve_attention_mode(self.attention),
            )
        out = out.reshape(b, s, heads * hd)
        if wg is not None:
            out = out.astype(jnp.float32) * jax.nn.sigmoid(
                _dot(u, wg, self.dtype))
        return _dot(out, wo, self.dtype).astype(self.dtype)


class LatentAttention(nn.Module):
    """Multi-head latent attention. Of ``u`` at position ``t``: ``q = u
    Wq`` as heads of ``[q_nope; q_rope]``; ``[c; k_r] = u Wkva``, ``c``
    RMSNormed, ``q_rope`` and ``k_r`` (ONE rotary key for all heads)
    rotated to ``t``. ``row = [c; k_r]`` is all a cache keeps of a token.

    Un-absorbed (a call over its own tokens: the plain forward, a
    prefill): ``[k_nope_h; v_h] = c Wkvb``, ``k_h = [k_nope_h; k_r]``,
    causal softmax attention of scale ``s`` (:func:`latent_scales`),
    ``out = [o_1 .. o_H] Wo``. Absorbed (against a cache of rows: a
    handle that ``reads_pool``): ``q~_h = Wkvb[K, h]^T q_nope_h``, scores
    ``s * (q~_h . c + q_rope_h . k_r)``, ``o~_h = sum p c``, ``o_h = Wkvb[V,
    h] o~_h``: the same numbers, each cached row read once as key and as
    value. Both forms rebuild from the row AS STORED (``dtype``)."""

    config: DecoderConfig
    dtype: Any
    attention: str = "naive"

    @nn.compact
    def __call__(self, u, positions, cache=None):
        c = self.config
        cache = _own(cache, "latent")
        heads, rank = c.num_attention_heads, c.kv_lora_rank
        nope, rope, vd = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim
        init = nn.initializers.normal(0.02)
        b, s, d = u.shape
        wq = self.param("wq", init, (d, heads * (nope + rope)))
        wkva = self.param("wkva", init, (d, rank + rope))
        kv_scale = self.param("kv_norm", nn.initializers.ones, (rank,))
        wkvb = self.param("wkvb", init, (rank, heads * (nope + vd)))
        wo = self.param("wo", init, (heads * vd, d))
        freq, trig, scale = latent_scales(c)
        # The attention divides by sqrt(q_head_dim) itself; what the
        # rotary scaling adds to the scale rides on the queries, applied
        # before their one rounding to ``dtype`` (a float32 q of a
        # 16,384-token prompt is 1 GB).
        extra = scale * (nope + rope) ** 0.5
        q = (_dot(u, wq, self.dtype) * extra).astype(self.dtype).reshape(
            b, s, heads, nope + rope)
        kva = _dot(u, wkva, self.dtype)
        latent = _rms_norm(kva[..., :rank], kv_scale, c.rms_norm_eps)
        with jax.named_scope("rope"):
            q_rope = _rotary_pairs(q[..., nope:], positions, freq, trig)
            k_rope = _rotary_pairs(
                kva[..., None, rank:], positions, freq, trig)[..., 0, :]
        q_nope = q[..., :nope]
        row = jnp.concatenate([latent, k_rope], axis=-1).astype(self.dtype)
        w = wkvb.astype(self.dtype).reshape(rank, heads, nope + vd)
        if cache is not None and cache.reads_pool:
            rest = (nope + rope) ** -0.5  # the scale's other part
            with jax.named_scope("latent_absorb"):
                q_abs = jnp.einsum(
                    "bshn,chn->bshc", q_nope, w[..., :nope],
                    preferred_element_type=jnp.float32,
                )
            ctx = cache.attend_absorbed(
                (q_abs * rest).astype(self.dtype),
                (q_rope * rest).astype(self.dtype), row)
            with jax.named_scope("latent_expand"):
                out = jnp.einsum(
                    "bshc,chv->bshv", ctx.astype(self.dtype), w[..., nope:],
                    preferred_element_type=jnp.float32,
                )
        else:
            def expand(part):
                return jnp.einsum(
                    "bsc,chn->bshn", row[..., :rank], part,
                    preferred_element_type=jnp.float32,
                ).astype(self.dtype)

            k = jnp.concatenate([
                expand(w[..., :nope]),
                jnp.broadcast_to(row[:, :, None, rank:], (b, s, heads, rope)),
            ], axis=-1)
            v = expand(w[..., nope:])
            qf = jnp.concatenate(
                [q_nope, q_rope.astype(self.dtype)], axis=-1)
            if cache is not None:
                out = cache.attend(qf, k, v, row)
            else:
                out = causal_attention(
                    qf, k, v, window=None,
                    mode=_resolve_attention_mode(self.attention),
                )
        out = out.reshape(b, s, heads * vd)
        return _dot(out, wo, self.dtype).astype(self.dtype)


def _a_log_init(key, shape, dtype=jnp.float32):
    """``log A`` with ``A`` uniform in [1, 16], as Mamba-2 publishes it."""
    return jnp.log(jax.random.uniform(key, shape, dtype, 1.0, 16.0))


def _conv_init(key, shape, dtype=jnp.float32):
    """``[channels, taps]`` uniform in +-1 / sqrt(taps): what Mamba-2's
    depthwise convolution is left at."""
    bound = shape[-1] ** -0.5
    return jax.random.uniform(key, shape, dtype, -bound, bound)


def _dt_bias_init(key, shape, dtype=jnp.float32):
    """The inverse softplus of a step drawn log-uniform in [0.001, 0.1]
    (Mamba-2's): with a zero projection the step IS that draw."""
    step = jnp.exp(jax.random.uniform(
        key, shape, dtype, math.log(0.001), math.log(0.1)))
    return step + jnp.log(-jnp.expm1(-step))


class MambaMixer(nn.Module):
    """A Mamba-2 mixer. Of ``u`` at position ``t``: ``[z; xBC; dt] = u
    W_in`` (``inner``; ``inner + 2 G d_state``; ``heads``); ``xBC_t <-
    silu(b_c + sum_j w_c[:, j] xBC_{t - d_conv + 1 + j})`` (depthwise,
    causal, zeros before the start), split ``[x (heads x head_dim); B; C
    (G x d_state each: one pair a group of heads / G consecutive heads, G
    = mamba_n_groups)]``; ``D_t = softplus(dt_t + dt_bias)``, ``a_t =
    exp(D_t A)``, ``A = -exp(a_log)`` a head; the state a head ``H_t =
    a_t H_{t-1} + D_t x_t B_t^T``, ``y_t = H_t C_t + d_skip x_t`` with its
    group's B and C; ``g = y silu(z)``, RMSNormed over each group's
    ``inner / G`` channels (all of ``inner`` for one group); ``g W_out``.

    Over a call's own tokens (the plain forward, a prefill) the state
    starts at zero and the chunked scan
    (:func:`~fluxmpi_tpu.ops.ssm.ssd_chunk_scan`) computes the
    recurrence; positions that ``token_mask`` leaves out get ``D_t = 0``,
    so the state after a padded prompt is the state after its last real
    token, and a prefill's handle is handed what a cache keeps of the
    sequence: ``cache.keep(tail, state)``, the last ``d_conv - 1`` real
    PRE-convolution ``xBC`` columns (zeros before the start) and the final
    state. Against a cache (a handle that ``reads_pool``, one token a
    row): ``tail = cache.tail()``, one convolution step over ``[tail;
    xBC]``, and ``cache.update(new tail, x, D, a, B, C)`` moves the
    row's state in its pool and returns ``H_t C_t``. ``xBC`` is rounded
    to ``dtype`` where it leaves the projection: a cached tail holds what
    the prefill's convolution read."""

    config: DecoderConfig
    dtype: Any

    @nn.compact
    def __call__(self, u, token_mask=None, cache=None):
        c = self.config
        cache = _own(cache, "state")
        heads, hd, n, taps = (c.mamba_n_heads, c.mamba_d_head,
                              c.mamba_d_state, c.mamba_d_conv)
        groups = c.mamba_n_groups
        inner, conv_dim = c.mamba_inner, c.mamba_conv_dim
        init = nn.initializers.normal(0.02)
        f32 = jnp.float32
        b, s, d = u.shape
        w_in = self.param("w_in", init, (d, inner + conv_dim + heads))
        conv_w = self.param("conv_w", _conv_init, (conv_dim, taps))
        conv_b = self.param("conv_b", nn.initializers.zeros, (conv_dim,))
        dt_bias = self.param("dt_bias", _dt_bias_init, (heads,))
        a_log = self.param("a_log", _a_log_init, (heads,))
        d_skip = self.param("d_skip", nn.initializers.ones, (heads,))
        norm = self.param("norm", nn.initializers.ones, (inner,))
        w_out = self.param("w_out", init, (inner, d))
        # One row a token from here on (``[batch * seq, width]``): a
        # decode tick's ``[slots, 1, width]`` arrays would each be tiled
        # one row a tile.
        with jax.named_scope("ssm_in_proj"):
            proj = _dot(u.reshape(b * s, d), w_in, self.dtype)
            if any(m != 1.0 for m in c.ssm_multipliers):
                # One multiplier a segment of ``[z; x; B; C; dt]``.
                proj = proj * np.repeat(
                    np.asarray(c.ssm_multipliers, np.float32),
                    [inner, inner, groups * n, groups * n, heads])
            z = proj[:, :inner]
            xbc = proj[:, inner:inner + conv_dim].astype(self.dtype)
            step = jax.nn.softplus(
                proj[:, inner + conv_dim:] + dt_bias.astype(f32))
            if token_mask is not None:
                step = jnp.where(token_mask.reshape(-1, 1), step, 0.0)
        a_rate = -jnp.exp(a_log.astype(f32))
        cached = cache is not None and cache.reads_pool
        with jax.named_scope("ssm_conv"):
            if cached:  # seq is 1: the tail, then the token
                before = cache.tail().astype(self.dtype)
            else:
                before = jnp.zeros((b, taps - 1, conv_dim), self.dtype)
            padded = jnp.concatenate(
                [before, xbc.reshape(b, s, conv_dim)], axis=1)
            conv = conv_b.astype(f32) + sum(
                padded[:, j:j + s].astype(f32) * conv_w[:, j].astype(f32)
                for j in range(taps)
            )
            conv = jax.nn.silu(conv).reshape(b * s, conv_dim)
            x = conv[:, :inner]
            # B and C: ``[tokens, d_state]``, or ``[tokens, groups,
            # d_state]`` with more groups than one.
            state_in, state_out = (
                v if groups == 1 else v.reshape(b * s, groups, n)
                for v in (conv[:, inner:inner + groups * n],
                          conv[:, inner + groups * n:]))
        if cached:
            with jax.named_scope("ssm_update"):
                y = cache.update(
                    padded[:, 1:], x.reshape(b, heads, hd), step,
                    jnp.exp(step * a_rate), state_in, state_out,
                )
        else:
            from ..ops.ssm import ssd_chunk_scan

            with jax.named_scope("ssm_scan"):
                y, state = ssd_chunk_scan(
                    x.reshape(b, s, heads, hd).astype(self.dtype),
                    step.reshape(b, s, heads), a_rate,
                    state_in.reshape(b, s, *state_in.shape[1:]),
                    state_out.reshape(b, s, *state_out.shape[1:]),
                    chunk=c.mamba_chunk_size,
                )
            if cache is not None:
                # The last ``taps - 1`` real columns: ``padded`` holds
                # position p at p + taps - 1, zeros before the start.
                length = (jnp.full((b,), s) if token_mask is None
                          else jnp.sum(token_mask, axis=1))
                at = length[:, None] + jnp.arange(taps - 1)[None]
                cache.keep(
                    jnp.take_along_axis(padded, at[..., None], axis=1), state)
        with jax.named_scope("ssm_gate_norm"):
            y = y.reshape(b * s, inner) + jnp.repeat(
                d_skip.astype(f32), hd) * x
            gated = y * jax.nn.silu(z)
            if groups == 1:
                out = _rms_norm(gated, norm, c.rms_norm_eps)
            else:  # each group's channels normed by themselves
                out = _rms_norm(
                    gated.reshape(b * s, groups, inner // groups),
                    norm.reshape(groups, inner // groups), c.rms_norm_eps,
                ).reshape(b * s, inner)
        with jax.named_scope("ssm_out_proj"):
            return _dot(out, w_out, self.dtype).astype(self.dtype).reshape(
                b, s, d)


# The float32 result a feed-forward may hold for all its tokens at once:
# over every cell served before the latent model (the widest: 8,704
# tokens x 8 pairs x 2,048 = 570 MB), under a 16,384-token prompt's
# 16,384-wide hidden layer (1.07 GB) and pairs (2.1 GB), which do not fit
# beside that model's weights and cache.
_SLAB_BYTES = 768 * 2**20


def _slabs(tokens: int, width: int) -> int:
    """In how many equal slabs ``tokens`` pass a per-token layer whose
    float32 intermediate is ``width`` a token: the fewest that keep it
    under ``_SLAB_BYTES`` and divide ``tokens`` (1: all at once)."""
    n = -(-tokens * width * 4 // _SLAB_BYTES)
    while tokens % n:
        n += 1
    return n


class GatedMLP(nn.Module):
    """``(silu(g u W1) * (u W3)) W2 * r``: ``g`` (``gate_multiplier``)
    inside the activation and ``r`` (``down_multiplier``) on the result,
    both 1.0 and not applied unless the configuration has them."""

    width: int
    dtype: Any
    gate_multiplier: float = 1.0
    down_multiplier: float = 1.0

    @nn.compact
    def __call__(self, u):
        init = nn.initializers.normal(0.02)
        d = u.shape[-1]
        w1 = self.param("w1", init, (d, self.width))
        w3 = self.param("w3", init, (d, self.width))
        w2 = self.param("w2", init, (self.width, d))

        def mlp(u):
            gate = _dot(u, w1, self.dtype)
            if self.gate_multiplier != 1.0:
                gate = gate * self.gate_multiplier
            out = _dot(jax.nn.silu(gate) * _dot(u, w3, self.dtype), w2,
                       self.dtype)
            if self.down_multiplier != 1.0:
                out = out * self.down_multiplier
            return out.astype(self.dtype)

        tokens = math.prod(u.shape[:-1])
        slabs = _slabs(tokens, self.width)
        if slabs == 1:
            return mlp(u)
        return jax.lax.map(mlp, u.reshape(slabs, -1, d)).reshape(u.shape)


class ReluSquaredMLP(nn.Module):
    """``relu(u W_up)^2 W_down``: un-gated, two matrices."""

    width: int
    dtype: Any

    @nn.compact
    def __call__(self, u):
        init = nn.initializers.normal(0.02)
        d = u.shape[-1]
        w_up = self.param("w_up", init, (d, self.width))
        w_down = self.param("w_down", init, (self.width, d))

        def mlp(u):
            h = jnp.square(jax.nn.relu(_dot(u, w_up, self.dtype)))
            return _dot(h, w_down, self.dtype).astype(self.dtype)

        tokens = math.prod(u.shape[:-1])
        slabs = _slabs(tokens, self.width)
        if slabs == 1:
            return mlp(u)
        return jax.lax.map(mlp, u.reshape(slabs, -1, d)).reshape(u.shape)


class ExpertMLP(nn.Module):
    """Routed experts without a capacity: no token is dropped.

    Every token scores all ``num_experts`` (``sigmoid(u Wr)``, float32),
    takes the ``top_k`` largest of score + bias, and weighs the chosen by
    their scores, normalised to sum 1 (``route_norm``), times
    ``route_scale``; with ``score_func="softmax"`` the scores are the
    logits ``u Wr`` themselves and the weights a softmax over the
    ``top_k`` chosen. The layer HOLDS the contiguous range
    ``expert_range`` of the experts (default: all): the (token, expert)
    pairs are sorted by expert, the held experts' pairs first, and one
    grouped matmul (:func:`~fluxmpi_tpu.ops.grouped_matmul.grouped_matmul`:
    ``jax.lax.ragged_dot``'s meaning, its rows past the groups
    UNSPECIFIED) a projection computes them;
    pairs routed to experts held elsewhere add nothing here (on one chip
    the layer runs without its exchange). The shared expert, which every
    token passes, is added where ``include_shared``. An expert is SwiGLU
    (``w1``, ``w3``, ``w2``: three grouped matmuls) or, with
    ``activation="relu2"``, un-gated: ``relu(u W_up)^2 W_down``, two
    grouped matmuls and no gate product, both matrices held ``[experts,
    width, hidden]`` (the up projection as its checkpoint holds it,
    ``[out, in]``): the hidden size lies on the lanes of both, and a
    width that is no whole number of 128-lane tiles (1,856) costs no
    padding and no copy before the kernel reads them.

    What the layer does with the grouped matmuls' results is bounded by
    the pairs its held experts received (``sum(sizes)``), never by a
    capacity: where it holds fewer experts than its router is wide, the
    kernel's walk ends at the last held pair's row tile and
    :func:`~fluxmpi_tpu.ops.grouped_matmul.combine` adds the live rows
    into their tokens' rows, reading the live row tiles only; if every
    pair went to held experts every tile is worked. The row gather and
    the gate product stay single passes over all the sorted rows (what
    they leave past the live rows is never read): row tile by row tile
    under a ``lax.cond`` they were slower on the chip than the passes
    they bounded (PERF.md §6, PR 40). With every expert held the bound
    is the static row count: the pairs are gathered back by token as
    before, and the pairs of tokens ``token_mask`` leaves out, which lie
    past the groups, are masked. Either way the layer is
    reverse-differentiable where its grouped matmul is.

    Returns the layer's output and sows ``expert_tokens`` (``[held]``
    int32: the pairs each HELD expert received; what went to experts held
    elsewhere is not this layer's work) into ``intermediates``.
    Tokens that ``token_mask`` leaves out (padding, idle slots) are
    routed nowhere: they count for no expert and reach no grouped matmul.
    A call over so many tokens that the float32 result of the (token,
    expert) pairs would pass ``_SLAB_BYTES`` takes them in equal slabs
    (:func:`_slabs`).
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
    score_func: str = "sigmoid"
    activation: str = "swiglu"

    def route(self, u, router, bias):
        """``(experts [tokens, top_k], weights [tokens, top_k])``."""
        scores = jnp.dot(
            u.astype(jnp.float32), router.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        if self.score_func == "sigmoid":
            scores = jax.nn.sigmoid(scores)
        _, experts = jax.lax.top_k(scores + bias.astype(jnp.float32),
                                   self.top_k)
        weights = jnp.take_along_axis(scores, experts, axis=-1)
        if self.score_func == "softmax":
            # The largest logits, weighed by a softmax over the chosen.
            return experts, jax.nn.softmax(weights, axis=-1) * self.route_scale
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
        n, k = self.num_experts, self.top_k
        lo, hi = self.expert_range or (0, n)
        held = hi - lo
        router = self.param("router", init, (d, n))
        bias = self.param("bias", nn.initializers.zeros, (n,))
        gated = self.activation == "swiglu"
        if gated:
            w1 = self.param("w1", init, (held, d, self.width))
            w3 = self.param("w3", init, (held, d, self.width))
            w2 = self.param("w2", init, (held, self.width, d))
        else:
            w_up = self.param("w_up", init, (held, self.width, d))
            w_down = self.param("w_down", init, (held, self.width, d))

        def routed(u, token_mask):
            """The held experts' part for the tokens ``u`` ``[tokens,
            d]``, and the pairs each held expert received."""
            tokens = u.shape[0]
            with jax.named_scope("moe_route"):
                experts, weights = self.route(u, router, bias)
                flat = experts.reshape(-1)
                rank = (flat - lo) % n
                if token_mask is not None:
                    keep = jnp.repeat(token_mask.reshape(-1), k)
                    flat = jnp.where(keep, flat, n)  # counted nowhere
                    rank = jnp.where(keep, rank, n)  # and sorted last
                counts = jnp.zeros((n,), jnp.int32).at[flat].add(
                    1, mode="drop")
                # Held experts first, in order; the others' pairs after.
                order = jnp.argsort(rank, stable=True)
                sizes = counts[lo:hi]
            with jax.named_scope("moe_experts"):
                # Imported here: ``fluxmpi_tpu.ops`` brings Pallas with it,
                # which a model without expert layers may never need.
                from ..ops.grouped_matmul import combine, grouped_matmul

                def grouped(x, w, **layout):
                    # Rows past the held experts' pairs: unspecified.
                    return grouped_matmul(
                        x.astype(self.dtype), w.astype(self.dtype), sizes,
                        **layout,
                    )

                rows = u.astype(self.dtype)[order // k]
                if gated:
                    gate, up = grouped(rows, w1), grouped(rows, w3)
                    y = grouped(jax.nn.silu(gate) * up, w2)
                else:
                    with jax.named_scope("relu2_up"):
                        up = grouped(rows, w_up, transposed=True)
                    with jax.named_scope("relu2_down"):
                        y = grouped(jnp.square(jax.nn.relu(up)), w_down)
                if held < n:
                    # The held pairs' rows added into their tokens' rows:
                    # the rows past them (3 of 4, 7 of 8) are not read.
                    return combine(
                        y, order // k, weights.reshape(-1)[order],
                        jnp.sum(sizes), tokens), sizes
                back = jnp.zeros_like(order).at[order].set(
                    jnp.arange(order.shape[0], dtype=order.dtype)
                )
                y = y[back]
                if token_mask is not None:
                    # A masked token's pairs lie past the groups.
                    y = jnp.where((back < jnp.sum(sizes))[:, None], y, 0.0)
                y = y.reshape(tokens, k, d)
                return jnp.sum(y * weights[..., None], axis=1), sizes

        slabs = _slabs(u.shape[0], k * d)
        if slabs == 1:
            out, sizes = routed(u, token_mask)
        else:
            # A long prompt's tokens in equal slabs: routing is per token.
            mask = (jnp.ones(u.shape[:1], bool) if token_mask is None
                    else token_mask.reshape(-1))
            out, sizes = jax.lax.map(
                lambda slab: routed(*slab),
                (u.reshape(slabs, -1, d), mask.reshape(slabs, -1)),
            )
            out, sizes = out.reshape(-1, d), jnp.sum(sizes, axis=0)
        self.sow("intermediates", "expert_tokens", sizes)
        if self.shared_width and self.include_shared:
            with jax.named_scope("moe_shared"):
                shared = GatedMLP if gated else ReluSquaredMLP
                out = out + shared(
                    self.shared_width, self.dtype, name="shared"
                )(u).astype(jnp.float32)
        return out.astype(self.dtype).reshape(shape)


def _held_experts(config, expert_range):
    """``(router width, the range of it a layer holds or None for all)``:
    a router wider than the experts held is this chip's share."""
    routed = config.num_routed_experts or config.num_experts
    return routed, expert_range or (
        (0, config.num_experts) if routed > config.num_experts else None)


class DecoderLayer(nn.Module):
    config: DecoderConfig
    index: int
    dtype: Any
    attention: str = "naive"
    expert_range: tuple[int, int] | None = None

    @nn.compact
    def __call__(self, x, positions, token_mask=None, cache=None):
        c = self.config
        # This layer's keeping sublayers are numbered from the count of
        # those before it (:meth:`DecoderLM.cache_layers` without its
        # Nones): known from ``layer_types``, whatever order calls come in.
        first = sum(kept is not None for before in c.layer_types[:self.index]
                    for kept in c.kept_by(before))

        def handle(offset):
            return None if cache is None else cache.sublayer(first + offset)

        def norm(name):
            return RMSNorm(c.rms_norm_eps, self.dtype, name=name)

        def join(stream, result):
            """The sublayer's result onto the residual stream."""
            if c.residual_multiplier == 1.0:
                return stream + result
            return (stream.astype(jnp.float32) + c.residual_multiplier
                    * result.astype(jnp.float32)).astype(self.dtype)

        sandwich = c.norm_placement == "sandwich"
        kind = c.layer_types[self.index]

        def scaled(v, by):
            """``by * v`` in float32, rounded once (``v`` itself at 1.0)."""
            return v if by == 1.0 else (
                v.astype(jnp.float32) * by).astype(self.dtype)

        def mixer(u):
            if kind == PARALLEL:
                # Both mixers read the one normed input; the state is the
                # layer's first keeping sublayer, the K/V rows its second.
                with jax.named_scope("ssm_branch"):
                    m = MambaMixer(c, self.dtype, name="mamba")(
                        scaled(u, c.ssm_in_multiplier), token_mask, handle(0))
                with jax.named_scope("attn_branch"):
                    a = Attention(
                        c, FULL, self.dtype, self.attention, name="attn",
                    )(scaled(u, c.attention_in_multiplier), positions,
                      handle(1))
                with jax.named_scope("mixer_join"):
                    return (c.ssm_out_multiplier * m.astype(jnp.float32)
                            + c.attention_out_multiplier
                            * a.astype(jnp.float32)).astype(self.dtype)
            if kind == MAMBA:
                return MambaMixer(c, self.dtype, name="mamba")(
                    u, token_mask, handle(0))
            if kind == LATENT:
                return LatentAttention(
                    c, self.dtype, self.attention, name="attn",
                )(u, positions, handle(0))
            return Attention(
                c, kind, self.dtype, self.attention, name="attn",
            )(u, positions, handle(0))

        def feed_forward(u):
            if self.index not in c.expert_layer_ids:
                with jax.named_scope("mlp"):
                    if c.mlp_activation == "swiglu":
                        return GatedMLP(
                            c.intermediate_size, self.dtype,
                            *c.mlp_multipliers, name="mlp")(u)
                    return ReluSquaredMLP(
                        c.intermediate_size, self.dtype, name="mlp")(u)
            routed, held = _held_experts(c, self.expert_range)
            return ExpertMLP(
                num_experts=routed, top_k=c.num_experts_per_tok,
                width=c.moe_intermediate_size,
                shared_width=c.shared_width,
                route_norm=c.route_norm, route_scale=c.route_scale,
                expert_range=held, dtype=self.dtype,
                score_func=c.score_func, activation=c.mlp_activation,
                name="moe",
            )(u, token_mask)

        if c.block == "single":  # one sublayer: x + Sub(N_in(x))
            sub = feed_forward if kind == EXPERTS else mixer
            return join(x, sub(norm("norm_in")(x)))
        a = mixer(norm("norm_in")(x))
        h = join(x, norm("norm_post_attn")(a) if sandwich else a)
        y = feed_forward(norm("norm_pre_ff")(h))
        return join(h, norm("norm_post_ff")(y) if sandwich else y)


class DecoderLM(nn.Module):
    """Embedding, ``config.num_layers`` :class:`DecoderLayer`, RMSNorm,
    a head of its own or the embedding's transpose
    (``tie_word_embeddings``). ``__call__`` returns float32 logits
    ``[batch, seq, vocab]``, or ``[batch, vocab]`` with ``head_at``."""

    # No capacity, no dropped token: a batched prefill computes for each
    # position what a one-token tick computes.
    batched_prefill_safe = True

    config: DecoderConfig
    dtype: Any = jnp.float32
    attention: str = "naive"
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

    def cache_layers(self) -> tuple[Keeps | None, ...]:
        """What each sublayer keeps of a sequence in a serving cache
        (None: a layer that is its experts alone keeps NOTHING). A layer
        with one mixer is one entry; a ``"mamba_attention"`` layer is TWO,
        its state and then its K/V heads. The n-th entry that is not None
        is the sublayer ``cache.sublayer(n)`` serves."""
        c = self.config
        return tuple(kept for kind in c.layer_types
                     for kept in c.kept_by(kind))

    def expert_row_tile(self, tokens: int) -> int | None:
        """The rows of one row tile of the expert layers' grouped matmul
        in a call over ``tokens`` tokens, None where it is XLA's
        ``ragged_dot`` (or the model has no expert layer): what
        :func:`~fluxmpi_tpu.ops.grouped_matmul.weight_visits` counts
        a tick's weight visits by."""
        from ..ops.grouped_matmul import row_tile

        c = self.config
        if not c.expert_layer_ids:
            return None
        return row_tile(
            tokens * c.num_experts_per_tok, c.hidden_size,
            c.moe_intermediate_size, self.dtype,
            transposed=c.mlp_activation == "relu2",
        )

    def expert_row_tiles(self, tokens: int, held_pairs) -> tuple[int, int]:
        """``(worked, spanned)``: of the row tiles the (token, expert)
        pairs of a call over ``tokens`` tokens span, summed over the
        expert layers, how many the layers work when their held experts
        received ``held_pairs`` (``[expert layers]``) of them: every tile
        where every expert is held, else the tiles up to each layer's
        last held pair (:class:`ExpertMLP`)."""
        from ..ops.grouped_matmul import live_row_tile

        c = self.config
        rows = tokens * c.num_experts_per_tok
        tile = live_row_tile(rows)
        spanned = -(-rows // tile) * len(held_pairs)
        routed, held = _held_experts(c, self.expert_range)
        if held is None or held[1] - held[0] == routed:
            return spanned, spanned
        return int(np.sum(-(-np.asarray(held_pairs) // tile))), spanned

    @nn.compact
    def __call__(self, tokens, *, train: bool = False, pos_offset=None,
                 head_at=None, token_mask=None, cache=None):
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
        if c.embedding_multiplier != 1.0:
            x = x * jnp.asarray(c.embedding_multiplier, self.dtype)
        for i in range(c.num_layers):
            x = DecoderLayer(
                c, i, self.dtype, self.attention, self.expert_range,
                name=f"layer_{i}",
            )(x, positions, token_mask, cache)
        if head_at is not None:
            x = jnp.take_along_axis(
                x, jnp.asarray(head_at)[:, None, None], axis=1
            )[:, 0]
        x = RMSNorm(c.rms_norm_eps, self.dtype, name="norm_out")(x)
        with jax.named_scope("lm_head"):
            if c.tie_word_embeddings:
                logits = jnp.einsum(
                    "...d,vd->...v", x.astype(self.dtype),
                    embed.astype(self.dtype),
                    preferred_element_type=jnp.float32,
                )
            else:
                head = self.param("head", init, (c.hidden_size, c.vocab_size))
                logits = _dot(x, head, self.dtype)
            if c.lm_head_multiplier != 1.0:
                logits = logits * c.lm_head_multiplier
        return logits if c.logits_scaling == 1.0 else logits / c.logits_scaling
