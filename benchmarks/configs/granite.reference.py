"""Plain reference for the Granite 4.0-H (``model_type:
"granitemoehybrid"``) configurations.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the Mamba-2 recurrence STEP BY STEP under ``lax.scan`` (never the chunked
form), dense attention, dense over the experts held, no sorting, no
kernel, no cache, no batching. It imports nothing of the program and takes
nothing the program made: the weights come from :func:`make_weights` (the
benchmark's own, from the seed), as bfloat16 VALUES, so the float32
reference holds exactly the numbers the program holds.

The model, from ``config.json`` of ``ibm-granite/granite-4.0-h-small``;
what is NOT a key of that file is marked [assumed] here and listed under
``assumed`` in the configuration's file (there is no network here).

- ``x0 = embedding_multiplier * E[tok]``.
- Layer ``l``, pre-norm, two RMSNorms (``rms_norm_eps``): ``h = x +
  residual_multiplier * Mixer_l(N1(x))``, ``y = h + residual_multiplier *
  (MoE(N2(h)) + Shared(N2(h)))``. No positions enter anywhere
  (``position_embedding_type: "nope"``).
- ``Mixer_l`` where ``layer_types[l] == "mamba"`` (Mamba-2) on ``u``:
  ``[z; xBC; dt] = u W_in`` (``inner = mamba_n_heads * mamba_d_head``;
  ``inner + 2 * mamba_d_state``; ``mamba_n_heads``) [order assumed].
  ``xBC_t <- silu(b_c + sum_j w_c[:, j] xBC_{t - d_conv + 1 + j})``
  (depthwise, causal, zeros before the start); ``[x; B; C]`` [order
  assumed], ``x_t`` as heads of ``mamba_d_head``, ``B_t`` and ``C_t``
  ``mamba_d_state`` each, one group shared by all heads. ``D_t =
  softplus(dt_t + dt_bias)`` a head (no clamp), ``a_t = exp(D_t A)``, ``A =
  -exp(a_log)``. State a head ``H_t = a_t H_{t-1} + D_t x_t B_t^T``
  (``[mamba_d_head, mamba_d_state]``, from zero), ``y_t = H_t C_t + d_skip
  x_t``. ``g = y * silu(z)`` [gate before norm, assumed], ``o = g /
  rms(g) * w_n`` over all of ``inner`` (one group), ``Mixer = o W_out``.
- ``Mixer_l`` where ``layer_types[l] == "attention"``: ``q, k, v = u Wq, u
  Wk, u Wv`` as ``num_attention_heads`` / ``num_key_value_heads`` heads of
  ``hidden_size / num_attention_heads``, no rotary, no head norm,
  ``softmax_causal(attention_multiplier * q . k)``, ``Wo``.
- ``MoE(u)``: ``l = u Wr`` over the ROUTER's width (``num_routed_experts``,
  the published ``num_local_experts``), the ``num_experts_per_tok``
  largest, weights ``softmax`` over those chosen logits [assumed];
  ``sum_e w_e SwiGLU_e(u)`` at ``intermediate_size`` OVER THE EXPERTS HELD
  HERE (the first ``num_local_experts`` of the router's: this chip's share
  of an expert-parallel stage; what the absent experts would add is left
  out, here as in the program). ``Shared(u)``: one SwiGLU of
  ``shared_intermediate_size`` that every token passes. No token is
  dropped; no bias anywhere but the convolution's.
- Last: RMSNorm and ``logits = N(x) E^T / logits_scaling`` (the head is
  the embedding, tied; this chip's slice of the vocabulary).

Departures: none in the mathematics. :func:`served_gaps` makes and
applies the weights layer by layer, the attention's query rows in blocks
of ``QUERY_BLOCK`` and the feed-forward (which is per token) over the
tokens of all the sequences in slabs of ``SLAB``. The initialisation is
[assumed]: normal (std ``initializer_range``) for every matrix but the
embedding (``initializer_range / embedding_multiplier``: :func:`ends`
says why), norms at 1, and the Mamba layer's per-channel and per-head
scalars as Mamba-2 publishes them (``A`` uniform in [1, 16], ``dt_bias``
the inverse softplus of a log-uniform step in [0.001, 0.1], ``d_skip`` 1,
the depthwise convolution's four taps and its bias uniform in +-1 /
sqrt(4)): with normal(0.02) there the decay is 0.5 a token and ``x``,
``B``, ``C`` are ~0.03, so the state would add nothing that a wrong state
could spoil.

``precision`` selects the arithmetic of every matrix product: ``"f32"``
is the reference itself; ``"bf16"`` and ``"fp8"`` are the lower
precisions the control computes in (operands rounded to 8 or to 4
significant bits, products accumulated in float32; the recurrent state
rounded likewise after every step, as a state held in that precision is).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

QUERY_BLOCK = 128
PARTS = 2
HEAD_ROWS = 256
SLAB = 4096
MAMBA = "mamba"


# ---------------------------------------------------------------------------
# Weights, from the seed, layer by layer
# ---------------------------------------------------------------------------


def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16
    )


def _per_expert(key, experts, shape, std):
    """``[len(experts), *shape]``, expert ``e`` from a key of its own: a
    share of the experts holds the numbers the whole layer holds."""
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(experts)
    return jax.vmap(lambda k: _normal(k, shape, std))(keys)


def _routed(cfg: dict) -> int:
    """The router's width: the experts of the whole layer."""
    return cfg.get("num_routed_experts") or cfg["num_local_experts"]


def _sizes(cfg: dict):
    """``(heads, head_dim, d_state, taps, inner, conv_dim)`` of a Mamba
    layer."""
    heads, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n = cfg["mamba_d_state"]
    return heads, hd, n, cfg["mamba_d_conv"], heads * hd, heads * hd + 2 * n


def layer_key(key, layer):
    return jax.random.fold_in(key, 1000 + layer)


def mixer_weights(cfg: dict, key, kind: str) -> dict:
    """The mixer of one layer whose key (:func:`layer_key`) is ``key``."""
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    if kind != MAMBA:
        heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd = d // heads
        k = jax.random.split(jax.random.fold_in(key, 3), 4)
        return {"wq": _normal(k[0], (d, heads * hd), std),
                "wk": _normal(k[1], (d, kvh * hd), std),
                "wv": _normal(k[2], (d, kvh * hd), std),
                "wo": _normal(k[3], (heads * hd, d), std)}
    heads, _, _, taps, inner, conv_dim = _sizes(cfg)
    k = jax.random.split(jax.random.fold_in(key, 4), 6)
    bound = 1.0 / math.sqrt(taps)
    step = jnp.exp(jax.random.uniform(
        k[4], (heads,), jnp.float32, math.log(0.001), math.log(0.1)))
    return {
        "w_in": _normal(k[0], (d, inner + conv_dim + heads), std),
        "w_out": _normal(k[1], (inner, d), std),
        "conv_w": jax.random.uniform(
            k[2], (conv_dim, taps), jnp.float32, -bound, bound
        ).astype(jnp.bfloat16),
        "conv_b": jax.random.uniform(
            k[3], (conv_dim,), jnp.float32, -bound, bound
        ).astype(jnp.bfloat16),
        "dt_bias": step + jnp.log(-jnp.expm1(-step)),
        "a_log": jnp.log(jax.random.uniform(
            k[5], (heads,), jnp.float32, 1.0, 16.0)),
        "d_skip": jnp.ones((heads,), jnp.float32),
        "norm": jnp.ones((inner,), jnp.float32),
    }


def layer_weights(cfg: dict, key, layer, *, kind: str | None = None,
                  mixer: bool = True, experts: bool = True) -> dict:
    """One layer's weights (bfloat16; norms and the Mamba layer's per-head
    scalars float32). ``layer`` may be traced where ``kind`` says which
    mixer it has; ``mixer=False`` / ``experts=False`` leave those out."""
    if kind is None:
        kind = cfg["layer_types"][layer]
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    shared = cfg["shared_intermediate_size"]
    key = layer_key(key, layer)
    k = dict(zip(("w1", "w3", "w2", "router"), jax.random.split(key, 4)))
    ones = jnp.ones((d,), jnp.float32)
    w = {
        "norm_in": ones, "norm_pre_ff": ones,
        "router": _normal(k["router"], (d, _routed(cfg)), std),
        # The shared MLP every token passes.
        "w1": _normal(k["w1"], (d, shared), std),
        "w3": _normal(k["w3"], (d, shared), std),
        "w2": _normal(k["w2"], (shared, d), std),
    }
    if mixer:
        w.update(mixer_weights(cfg, key, kind))
    if experts:
        w.update(expert_block(cfg, key, jnp.arange(cfg["num_local_experts"])))
    return w


def expert_block(cfg: dict, key, experts) -> dict:
    """``ew1``, ``ew3`` ``[len(experts), hidden, width]`` and ``ew2``
    ``[len(experts), width, hidden]`` of a layer whose key
    (:func:`layer_key`) is ``key``."""
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    std = cfg["initializer_range"]
    return {
        name: _per_expert(jax.random.fold_in(key, 7 + i), experts, shape, std)
        for i, (name, shape) in enumerate(
            (("ew1", (d, f)), ("ew3", (d, f)), ("ew2", (f, d)))
        )
    }


def ends(cfg: dict, key) -> dict:
    """The embedding (the tied head) and the final norm. The embedding's
    std is ``initializer_range / embedding_multiplier`` [assumed]: the
    SCALED embedding enters the stream at ``initializer_range``. At the
    full ``initializer_range`` the tied head reads ``embedding_multiplier
    * |E[tok]|^2`` for the token just fed, 12 standard deviations over
    every other logit at hidden 4,096: the model would echo its input
    whatever its layers compute, and no comparison of served tokens could
    tell a wrong computation from a right one."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    std = cfg["initializer_range"] / cfg["embedding_multiplier"]
    return {"embed": _normal(jax.random.fold_in(key, 1), (v, d), std),
            "norm_out": jnp.ones((d,), jnp.float32)}


def make_weights(cfg: dict, key) -> dict:
    """Every weight of the model in the reference's layout: what the
    program is built from (``granite.program.to_program``)."""
    w = ends(cfg, key)
    w["layers"] = [layer_weights(cfg, key, i)
                   for i in range(cfg["num_hidden_layers"])]
    return w


# ---------------------------------------------------------------------------
# The forward pass
# ---------------------------------------------------------------------------


def _round_mantissa(x, bits: int):
    """``x`` (float32) rounded to ``bits`` explicit mantissa bits, ties
    to even: what storing it in a narrower float does to its value."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & jnp.uint32(1))
    u = u & jnp.uint32(0xFFFFFFFF ^ ((1 << drop) - 1))
    return jax.lax.bitcast_convert_type(u, jnp.float32)


LOW_BITS = {"bf16": 7, "fp8": 3}


def _held(x, precision: str):
    """``x`` as a value held in ``precision``."""
    return x if precision == "f32" else _round_mantissa(
        x, LOW_BITS[precision])


def _mm(spec: str, a, b, precision: str):
    a = _held(a.astype(jnp.float32), precision)
    b = _held(b.astype(jnp.float32), precision)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


def mamba(u, w, cfg: dict, precision: str = "f32", *, state_out: bool = False):
    """The Mamba-2 mixer over ``u`` ``[seq, hidden]``: the recurrence one
    token at a time. With ``state_out`` also the state after the last
    token and the last ``d_conv - 1`` pre-convolution columns (what a
    cache would keep; the reference keeps none)."""
    heads, hd, n, taps, inner, conv_dim = _sizes(cfg)
    f32 = jnp.float32
    t = u.shape[0]
    proj = _mm("td,dn->tn", u, w["w_in"], precision)
    z, xbc = proj[:, :inner], proj[:, inner:inner + conv_dim]
    step = jax.nn.softplus(proj[:, inner + conv_dim:] + w["dt_bias"])
    decay = jnp.exp(step * -jnp.exp(w["a_log"]))
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim), f32), xbc])
    conv = jax.nn.silu(w["conv_b"].astype(f32) + sum(
        padded[j:j + t] * w["conv_w"][:, j].astype(f32) for j in range(taps)
    ))
    x = conv[:, :inner].reshape(t, heads, hd)
    b_in, c_out = conv[:, inner:inner + n], conv[:, inner + n:]

    def token(state, at):
        x_t, step_t, decay_t, b_t, c_t = at
        state = _held(
            decay_t[:, None, None] * state
            + (step_t[:, None] * x_t)[:, :, None] * b_t[None, None, :],
            precision,
        )
        return state, _mm("hpn,n->hp", state, c_t, precision)

    state, y = jax.lax.scan(
        token, jnp.zeros((heads, hd, n), f32), (x, step, decay, b_in, c_out))
    y = y + w["d_skip"][:, None] * x
    gated = y.reshape(t, inner) * jax.nn.silu(z)
    out = _mm("tn,nd->td", _rms_norm(gated, w["norm"], cfg["rms_norm_eps"]),
              w["w_out"], precision)
    return (out, state, padded[t:]) if state_out else out


def attention(u, w, cfg: dict, precision: str = "f32"):
    """Causal grouped-query attention over ``u`` ``[seq, hidden]``, no
    positions, scores scaled by ``attention_multiplier``."""
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["hidden_size"] // heads
    t = u.shape[0]
    q = _mm("td,dn->tn", u, w["wq"], precision).reshape(
        t, kvh, heads // kvh, hd)
    k = _mm("td,dn->tn", u, w["wk"], precision).reshape(t, kvh, hd)
    v = _mm("td,dn->tn", u, w["wv"], precision).reshape(t, kvh, hd)
    block = min(QUERY_BLOCK, t)
    j = jnp.arange(t)[None, :]

    def rows(i0):
        i = i0 + jnp.arange(block)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block)
        s = cfg["attention_multiplier"] * _mm(
            "qkgd,tkd->kgqt", qb, k, precision)
        s = jnp.where(j <= i, s, -jnp.inf)
        return _mm("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v, precision)

    ctx = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * hd)
    return _mm("tn,nd->td", ctx, w["wo"], precision)


def mixer(u, w, cfg: dict, kind: str, precision: str = "f32"):
    return (mamba if kind == MAMBA else attention)(u, w, cfg, precision)


def swiglu(u, w1, w3, w2, precision: str = "f32"):
    h = jax.nn.silu(_mm("td,df->tf", u, w1, precision)) * _mm(
        "td,df->tf", u, w3, precision)
    return _mm("tf,fd->td", h, w2, precision)


def route(u, w, cfg: dict, precision: str = "f32"):
    """``[seq, router width]`` float32: each token's weight on every
    expert of the whole layer, zero on those it did not choose."""
    logits = _mm("td,de->te", u, w["router"], precision)
    picked, chosen = jax.lax.top_k(logits, cfg["num_experts_per_tok"])
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(logits).at[rows, chosen].set(
        jax.nn.softmax(picked, axis=-1))


def experts_dense(u, gates, block: dict, precision: str = "f32"):
    """``sum_e gates[:, e] * SwiGLU_e(u)`` over the experts of ``block``
    (``gates`` ``[seq, len(block)]``): every expert on every token, the
    experts' matrices side by side in one plain product."""
    e, d, f = block["ew1"].shape
    w1 = jnp.transpose(block["ew1"], (1, 0, 2)).reshape(d, e * f)
    w3 = jnp.transpose(block["ew3"], (1, 0, 2)).reshape(d, e * f)
    h = jax.nn.silu(_mm("td,dn->tn", u, w1, precision)) * _mm(
        "td,dn->tn", u, w3, precision)
    h = h * jnp.repeat(gates, f, axis=1)
    return _mm("tn,nd->td", h, block["ew2"].reshape(e * f, d), precision)


def feed_forward(u, w, cfg: dict, precision: str = "f32"):
    """``MoE(u) + Shared(u)`` with all the layer's weights in ``w``: the
    experts held (the first ``num_local_experts`` of the router's) and
    the shared MLP."""
    gates = route(u, w, cfg, precision)[:, :cfg["num_local_experts"]]
    return experts_dense(u, gates, w, precision) + swiglu(
        u, w["w1"], w["w3"], w["w2"], precision)


def mix(x, w, cfg: dict, kind: str, precision: str = "f32"):
    """A layer's first half: ``h = x + r * Mixer(N1(x))``, and ``N2(h)``,
    what its feed-forward reads."""
    eps, r = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    h = x + r * mixer(_rms_norm(x, w["norm_in"], eps), w, cfg, kind, precision)
    return h, _rms_norm(h, w["norm_pre_ff"], eps)


def head(x, w, cfg: dict, precision: str = "f32"):
    x = _rms_norm(x, w["norm_out"], cfg["rms_norm_eps"])
    return _mm("td,vd->tv", x, w["embed"], precision) / cfg["logits_scaling"]


def logits(weights: dict, tokens, cfg: dict, precision: str = "f32"):
    """``[seq, vocab]`` float32 logits of one sequence's full forward,
    all weights in memory (small sizes; :func:`served_gaps` is the same
    mathematics a layer at a time)."""
    x = cfg["embedding_multiplier"] * weights["embed"][tokens].astype(
        jnp.float32)
    for kind, w in zip(cfg["layer_types"], weights["layers"]):
        h, u = mix(x, w, cfg, kind, precision)
        x = h + cfg["residual_multiplier"] * feed_forward(u, w, cfg, precision)
    return head(x, weights, cfg, precision)


# ---------------------------------------------------------------------------
# What the serving comparison reads
# ---------------------------------------------------------------------------


def _mix_step(x, key, layer, *, cfg, kind, precision):
    """A layer's first half over one padded sequence, its weights made
    here from the seed. ``layer`` is traced: one compiled program a
    padded length and kind of mixer."""
    w = layer_weights(cfg, key, layer, kind=kind, experts=False)
    return mix(x, w, cfg, kind, precision)


def _ff_step(u, key, layer, *, cfg, precision):
    """``r * (MoE(u) + Shared(u))`` over a slab of tokens (the
    feed-forward is per token, so tokens of several sequences pass it
    together), its weights made here from the seed."""
    w = layer_weights(cfg, key, layer, kind=MAMBA, mixer=False)
    return cfg["residual_multiplier"] * feed_forward(u, w, cfg, precision)


def padded_lengths(cfg: dict) -> list[int]:
    """The few lengths sequences are padded to (a compiled program a
    length and kind of mixer): the ``PARTS`` equal parts of the longest
    context the configuration serves."""
    whole = cfg["max_position_embeddings"]
    return [-(-whole * part // PARTS) for part in range(1, PARTS + 1)]


def pad(cfg: dict, tokens):
    """``tokens`` right-padded to one of :func:`padded_lengths`."""
    tokens = jnp.asarray(tokens, jnp.int32)
    room = next(n for n in padded_lengths(cfg) if n >= tokens.shape[0])
    return jnp.pad(tokens, (0, room - tokens.shape[0]))


def _layer_by_layer(cfg: dict, key):
    """``hidden_states(sequences, precision)``: for each token sequence
    the last layer's output. A layer at a time for all sequences: the
    mixer a (padded) sequence at a time, the feed-forward over the real
    tokens of all of them in slabs of ``SLAB``. Both mixers are causal,
    so a sequence's padding never reaches what is read."""
    first = jax.jit(lambda k, t: cfg["embedding_multiplier"] * ends(
        cfg, k)["embed"][t].astype(jnp.float32))
    half = jax.jit(functools.partial(_mix_step, cfg=cfg),
                   static_argnames=("kind", "precision"))
    ff = jax.jit(functools.partial(_ff_step, cfg=cfg),
                 static_argnames=("precision",))

    def hidden_states(sequences, precision):
        lengths = [len(tokens) for tokens in sequences]
        xs = [first(key, pad(cfg, tokens)) for tokens in sequences]
        for i, kind in enumerate(cfg["layer_types"]):
            us = []
            for j, n in enumerate(lengths):
                xs[j], u = half(xs[j], key, jnp.int32(i), kind=kind,
                                precision=precision)
                us.append(u[:n])
                del u
            u = jnp.concatenate(us)
            del us
            total = u.shape[0]
            u = jnp.pad(u, ((0, (-total) % SLAB), (0, 0)))
            starts = np.cumsum([0] + lengths)  # where each sequence lies
            for s in range(0, total, SLAB):
                y = ff(u[s:s + SLAB], key, jnp.int32(i), precision=precision)
                # The slab's rows back to the sequences they came from.
                for j, n in enumerate(lengths):
                    lo, hi = max(starts[j], s), min(starts[j] + n, s + SLAB)
                    if lo < hi:
                        xs[j] = xs[j].at[lo - starts[j]:hi - starts[j]].add(
                            y[lo - s:hi - s])
        return [x[:n] for x, n in zip(xs, lengths)]

    return hidden_states


def served_gaps(cfg: dict, key, sequences, *, precision: str = "f32",
                control: str | None = None) -> dict:
    """For each ``(prompt, served_tokens)``: one full forward over the
    prompt with its served tokens (the recurrence from the first token
    on), and at every served position the gap by which the served token's
    reference logit lies below the reference's best. Returned over all
    positions: the mean gap (what is compared), the widest, and the share
    of tokens that are not the reference's first. With ``control`` set,
    the same for the token that the lower precision puts first at each
    position."""

    hidden_states = _layer_by_layer(cfg, key)

    @functools.partial(jax.jit, static_argnames=("prec",))
    def head_rows(x, k, prec):
        return head(x, ends(cfg, k), cfg, prec)

    def head_of(x, k, prec):
        # Rows padded to a multiple of HEAD_ROWS: a compiled head or two.
        rows = x.shape[0]
        x = jnp.pad(x, ((0, (-rows) % HEAD_ROWS), (0, 0)))
        return head_rows(x, k, prec)[:rows]

    fulls = [list(prompt) + list(served) for prompt, served in sequences]
    hidden = hidden_states(fulls, precision)
    if control is not None:
        hidden_c = hidden_states(fulls, control)
    gaps, gaps_control = [], []
    for i, (prompt, served) in enumerate(sequences):
        lo, n = len(prompt) - 1, len(served)
        ref = head_of(hidden[i][lo:lo + n], key, precision)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(
            ref, jnp.asarray(served, jnp.int32)[:, None], axis=-1
        )[:, 0]
        gaps.append(jax.device_get(best - got))
        if control is not None:
            pick = jnp.argmax(head_of(hidden_c[i][lo:lo + n], key, control),
                              axis=-1)
            got_c = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            gaps_control.append(jax.device_get(best - got_c))

    def stats(parts):
        if not parts:
            return {"mean": float("inf"), "widest": float("inf"),
                    "not_first_share": 1.0, "tokens": 0}
        allg = jnp.concatenate([jnp.asarray(p) for p in parts])
        return {"mean": float(jnp.mean(allg)), "widest": float(jnp.max(allg)),
                "not_first_share": float(jnp.mean(allg > 0)),
                "tokens": int(allg.shape[0])}

    out = {"served": stats(gaps)}
    if control is not None:
        out["control"] = stats(gaps_control)
    return out
