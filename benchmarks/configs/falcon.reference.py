"""Plain reference for the Falcon-H1 (``model_type: "falcon_h1"``)
configurations.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the Mamba-2 recurrence STEP BY STEP under ``lax.scan`` (never the chunked
form), dense attention with rotary positions, no kernel, no cache, no
batching. It imports nothing of the program and takes nothing the program
made: the weights come from :func:`make_weights` (the benchmark's own,
from the seed), as bfloat16 VALUES, so the float32 reference holds exactly
the numbers the program holds.

The model, from ``config.json`` of ``tiiuae/Falcon-H1-34B-Instruct``; what
is NOT a key of that file is marked [assumed] here and listed under
``assumed`` in the configuration's file (there is no network here, and the
``falcon_h1`` modelling code is not in this sandbox).

- ``x0 = embedding_multiplier * E[tok]``.
- Layer ``l``, pre-norm, two RMSNorms (``rms_norm_eps``): ``u = N1(x)``;
  ``h = x + ssm_out_multiplier * Mamba(ssm_in_multiplier * u) +
  attention_out_multiplier * Attn(attention_in_multiplier * u)``: BOTH
  mixers read the one normed input and both results join the stream at
  once; ``y = h + MLP(N2(h))``.
- ``Attn(u)``: ``q = u Wq`` (``num_attention_heads`` of ``head_dim``), ``k
  = key_multiplier * u Wk``, ``v = u Wv`` (``num_key_value_heads``); q and
  k rotated at their positions, the two halves of a head by ``position *
  rope_theta ** (-2i / head_dim)`` [rotate-half layout assumed; the scalar
  key multiplier commutes with the rotation]; causal softmax at
  ``head_dim ** -0.5`` over the whole context; no bias, no head norm, no
  gate; ``Wo``.
- ``Mamba(u)`` (Mamba-2): ``[z; x; B; C; dt] = (u W_in) * m`` with ``m``
  the five ``ssm_multipliers``, one a segment (``inner = mamba_d_ssm =
  mamba_n_heads * mamba_d_head``; ``inner``; ``G * mamba_d_state`` twice,
  ``G = mamba_n_groups``; ``mamba_n_heads``) [order of the segments
  assumed]. ``xBC_t <- silu(b_c + sum_j w_c[:, j] xBC_{t - d_conv + 1 +
  j})`` over ``[x; B; C]`` (depthwise, causal, zeros before the start).
  ``D_t = softplus(dt_t + dt_bias)`` a head (no clamp [assumed]), ``a_t =
  exp(D_t A)``, ``A = -exp(a_log)``. State a head ``H_t = a_t H_{t-1} + D_t
  x_t B_t^T`` (``[mamba_d_head, mamba_d_state]``, from zero) with the B and
  C of the head's group ``h // (heads / G)``, ``y_t = H_t C_t + d_skip
  x_t``. ``g = y * silu(z)`` (``mamba_norm_before_gate`` false), RMSNormed
  over each group's ``inner / G`` channels (``mamba_rms_norm``), ``g
  W_out``.
- ``MLP(u) = (silu(mlp_multipliers[0] * u Wg) * (u Wu)) Wd *
  mlp_multipliers[1]``.
- Last: RMSNorm and ``logits = lm_head_multiplier * N(x) W_head`` (untied).

Departures: none in the mathematics. :func:`served_gaps` makes and
applies the weights layer by layer, the embedding in blocks of the
vocabulary and the head in blocks of the hidden size (:data:`END_BLOCKS`:
partial products summed in float32), the attention's query rows in blocks of
``QUERY_BLOCK`` and the MLP (which is per token) over the tokens of all
the sequences in slabs of ``SLAB``. The initialisation is [assumed]:
normal, a standard deviation a matrix (``init_std`` of the configuration's
file, which says why each: at one 0.02 for all, the published multipliers
leave the attention's scores flat, the state's part of the Mamba branch
far under ``d_skip x`` and the MLP a few percent of the stream, and no
comparison of served tokens could tell a wrong state, a wrong rotation or
a wrong MLP from a right one), norms at 1, the Mamba mixer's per-channel
and per-head scalars as Mamba-2 publishes them (``A`` uniform in [1, 16],
``dt_bias`` the inverse softplus of a log-uniform step in [0.001, 0.1],
``d_skip`` 1, the depthwise convolution's taps and its bias uniform in +-1
/ sqrt(taps)). :func:`branch_magnitudes` reads what each branch adds.

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
SLAB = 2048
# The embedding and the head are made, and the head applied, in this many
# equal blocks of ROWS as each is held: the embedding's of the vocabulary,
# the head's (``[hidden, vocab]``) of the hidden size. Rows, so that the
# blocks side by side ARE the matrix and making it transposes nothing (a
# head made in blocks of its columns left the weights' program 2.67 GB of
# temporaries, which the chip's allocator kept reserved beside the pools).
END_BLOCKS = 8


# ---------------------------------------------------------------------------
# Weights, from the seed, layer by layer
# ---------------------------------------------------------------------------


def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16
    )


def _sizes(cfg: dict):
    """``(heads, head_dim, d_state, groups, taps, inner, conv_dim)`` of a
    Mamba mixer."""
    heads, hd = cfg["mamba_n_heads"], cfg["mamba_d_head"]
    n, groups = cfg["mamba_d_state"], cfg["mamba_n_groups"]
    inner = heads * hd
    return (heads, hd, n, groups, cfg["mamba_d_conv"], inner,
            inner + 2 * groups * n)


def layer_key(key, layer):
    return jax.random.fold_in(key, 1000 + layer)


def attention_weights(cfg: dict, key) -> dict:
    d, std = cfg["hidden_size"], cfg["init_std"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
    k = jax.random.split(jax.random.fold_in(key, 3), 4)
    return {"wq": _normal(k[0], (d, heads * hd), std["wq"]),
            "wk": _normal(k[1], (d, kvh * hd), std["wk"]),
            "wv": _normal(k[2], (d, kvh * hd), std["wv"]),
            "wo": _normal(k[3], (heads * hd, d), std["wo"])}


def mamba_weights(cfg: dict, key) -> dict:
    d, std = cfg["hidden_size"], cfg["init_std"]
    heads, _, _, _, taps, inner, conv_dim = _sizes(cfg)
    k = jax.random.split(jax.random.fold_in(key, 4), 6)
    bound = 1.0 / math.sqrt(taps)
    step = jnp.exp(jax.random.uniform(
        k[4], (heads,), jnp.float32, math.log(0.001), math.log(0.1)))
    return {
        "w_in": _normal(k[0], (d, inner + conv_dim + heads), std["w_in"]),
        "w_out": _normal(k[1], (inner, d), std["w_out"]),
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


def mlp_weights(cfg: dict, key) -> dict:
    d, f, std = cfg["hidden_size"], cfg["intermediate_size"], cfg["init_std"]
    k = jax.random.split(jax.random.fold_in(key, 5), 3)
    return {"w1": _normal(k[0], (d, f), std["w1"]),
            "w3": _normal(k[1], (d, f), std["w3"]),
            "w2": _normal(k[2], (f, d), std["w2"])}


def layer_weights(cfg: dict, key, layer, *, mixers: bool = True,
                  mlp: bool = True) -> dict:
    """One layer's weights (bfloat16; norms and the Mamba mixer's per-head
    scalars float32). ``layer`` may be traced; ``mixers=False`` /
    ``mlp=False`` leave those out."""
    key = layer_key(key, layer)
    ones = jnp.ones((cfg["hidden_size"],), jnp.float32)
    w = {"norm_in": ones, "norm_pre_ff": ones}
    if mixers:
        w.update(attention_weights(cfg, key))
        w.update(mamba_weights(cfg, key))
    if mlp:
        w.update(mlp_weights(cfg, key))
    return w


def _block_key(key, end: int, block):
    return jax.random.fold_in(jax.random.fold_in(key, end), block)


def embed_block(cfg: dict, key, block):
    """Rows ``[block * v / B, (block + 1) * v / B)`` of the embedding
    ``[vocab, hidden]``."""
    return _normal(_block_key(key, 1, block),
                   (cfg["vocab_size"] // END_BLOCKS, cfg["hidden_size"]),
                   cfg["init_std"]["embed"])


def head_block(cfg: dict, key, block):
    """Rows ``[block * d / B, (block + 1) * d / B)`` of the head
    ``[hidden, vocab]``."""
    return _normal(_block_key(key, 2, block),
                   (cfg["hidden_size"] // END_BLOCKS, cfg["vocab_size"]),
                   cfg["init_std"]["head"])


def ends(cfg: dict, key) -> dict:
    """The embedding, the (untied) head and the final norm, each matrix
    its blocks of rows one under the other."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    blocks = jnp.arange(END_BLOCKS)
    embed = jax.lax.map(lambda b: embed_block(cfg, key, b), blocks)
    head = jax.lax.map(lambda b: head_block(cfg, key, b), blocks)
    return {"embed": embed.reshape(v, d), "head": head.reshape(d, v),
            "norm_out": jnp.ones((d,), jnp.float32)}


def make_weights(cfg: dict, key) -> dict:
    """Every weight of the model in the reference's layout: what the
    program is built from (``falcon.program.to_program``)."""
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


def _rotate(x, theta: float):
    """``x`` ``[seq, heads, head_dim]`` at positions ``0 .. seq - 1``: the
    two halves of a head rotated by ``position * theta ** (-2i /
    head_dim)``."""
    half = x.shape[-1] // 2
    freq = float(theta) ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def mamba(u, w, cfg: dict, precision: str = "f32", *, parts: bool = False,
          state_out: bool = False, state_scale: float = 1.0):
    """The Mamba-2 mixer over ``u`` ``[seq, hidden]`` (already times
    ``ssm_in_multiplier``): the recurrence one token at a time. With
    ``state_out`` also the state after the last token and the last
    ``d_conv - 1`` pre-convolution columns (what a cache would keep; the
    reference keeps none). ``parts``: also the root-mean-squares of ``H_t
    C_t`` and of ``d_skip x_t``. ``state_scale`` 0.0 is the tests' broken
    twin: a model whose state adds nothing."""
    heads, hd, n, groups, taps, inner, conv_dim = _sizes(cfg)
    f32 = jnp.float32
    t = u.shape[0]
    proj = _mm("td,dn->tn", u, w["w_in"], precision) * np.repeat(
        np.asarray(cfg["ssm_multipliers"], np.float32),
        [inner, inner, groups * n, groups * n, heads])
    z, xbc = proj[:, :inner], proj[:, inner:inner + conv_dim]
    step = jax.nn.softplus(proj[:, inner + conv_dim:] + w["dt_bias"])
    decay = jnp.exp(step * -jnp.exp(w["a_log"]))
    padded = jnp.concatenate([jnp.zeros((taps - 1, conv_dim), f32), xbc])
    conv = jax.nn.silu(w["conv_b"].astype(f32) + sum(
        padded[j:j + t] * w["conv_w"][:, j].astype(f32) for j in range(taps)
    ))
    x = conv[:, :inner].reshape(t, heads, hd)
    # B and C a head: its group's.
    b_in, c_out = (
        jnp.repeat(v.reshape(t, groups, n), heads // groups, axis=1)
        for v in (conv[:, inner:inner + groups * n],
                  conv[:, inner + groups * n:]))

    def token(state, at):
        x_t, step_t, decay_t, b_t, c_t = at
        state = _held(
            decay_t[:, None, None] * state
            + (step_t[:, None] * x_t)[:, :, None] * b_t[:, None, :],
            precision,
        )
        return state, _mm("hpn,hn->hp", state, c_t, precision)

    state, read = jax.lax.scan(
        token, jnp.zeros((heads, hd, n), f32), (x, step, decay, b_in, c_out))
    skip = w["d_skip"][:, None] * x
    y = state_scale * read + skip
    gated = (y.reshape(t, inner) * jax.nn.silu(z)).reshape(
        t, groups, inner // groups)
    normed = _rms_norm(gated, w["norm"].reshape(groups, inner // groups),
                       cfg["rms_norm_eps"]).reshape(t, inner)
    out = _mm("tn,nd->td", normed, w["w_out"], precision)
    if parts:
        return out, _rms(read), _rms(skip)
    return (out, state, padded[t:]) if state_out else out


def attention(u, w, cfg: dict, precision: str = "f32"):
    """Causal grouped-query attention with rotary positions over ``u``
    ``[seq, hidden]`` (already times ``attention_in_multiplier``)."""
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd, theta = cfg["head_dim"], cfg["rope_theta"]
    t = u.shape[0]
    q = _rotate(_mm("td,dn->tn", u, w["wq"], precision).reshape(
        t, heads, hd), theta).reshape(t, kvh, heads // kvh, hd)
    k = _rotate(cfg["key_multiplier"] * _mm(
        "td,dn->tn", u, w["wk"], precision).reshape(t, kvh, hd), theta)
    v = _mm("td,dn->tn", u, w["wv"], precision).reshape(t, kvh, hd)
    block = min(QUERY_BLOCK, t)
    j = jnp.arange(t)[None, :]

    def rows(i0):
        i = i0 + jnp.arange(block)[:, None]
        qb = jax.lax.dynamic_slice_in_dim(q, i0, block)
        s = hd ** -0.5 * _mm("qkgd,tkd->kgqt", qb, k, precision)
        s = jnp.where(j <= i, s, -jnp.inf)
        return _mm("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v, precision)

    ctx = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * hd)
    return _mm("tn,nd->td", ctx, w["wo"], precision)


def mlp(u, w, cfg: dict, precision: str = "f32"):
    gate_m, down_m = cfg["mlp_multipliers"]
    h = jax.nn.silu(gate_m * _mm("td,df->tf", u, w["w1"], precision)) * _mm(
        "td,df->tf", u, w["w3"], precision)
    return down_m * _mm("tf,fd->td", h, w["w2"], precision)


def mix(x, w, cfg: dict, precision: str = "f32", state_scale: float = 1.0):
    """A layer's first half: both mixers on the one normed input, both
    results joined to the stream; and ``N2(h)``, what its MLP reads."""
    eps = cfg["rms_norm_eps"]
    u = _rms_norm(x, w["norm_in"], eps)
    h = (x
         + cfg["ssm_out_multiplier"] * mamba(
             cfg["ssm_in_multiplier"] * u, w, cfg, precision,
             state_scale=state_scale)
         + cfg["attention_out_multiplier"] * attention(
             cfg["attention_in_multiplier"] * u, w, cfg, precision))
    return h, _rms_norm(h, w["norm_pre_ff"], eps)


def head(x, w, cfg: dict, precision: str = "f32"):
    x = _rms_norm(x, w["norm_out"], cfg["rms_norm_eps"])
    return cfg["lm_head_multiplier"] * _mm(
        "td,dv->tv", x, w["head"], precision)


def logits(weights: dict, tokens, cfg: dict, precision: str = "f32",
           state_scale: float = 1.0):
    """``[seq, vocab]`` float32 logits of one sequence's full forward,
    all weights in memory (small sizes; :func:`served_gaps` is the same
    mathematics a layer at a time)."""
    x = cfg["embedding_multiplier"] * weights["embed"][tokens].astype(
        jnp.float32)
    for w in weights["layers"]:
        h, u = mix(x, w, cfg, precision, state_scale)
        x = h + mlp(u, w, cfg, precision)
    return head(x, weights, cfg, precision)


def _rms(v):
    return jnp.sqrt(jnp.mean(jnp.square(v)))


def branch_magnitudes(x, w, cfg: dict) -> dict:
    """What one layer with weights ``w`` adds to the stream ``x`` ``[seq,
    hidden]``, as root-mean-squares: the attention branch, the Mamba
    branch, the MLP and their sum (the layer's update), beside the stream
    itself; and inside the Mamba mixer the state's part ``H_t C_t`` of
    ``y_t`` beside ``d_skip x_t``, by which ``state`` (the state's part of
    the Mamba branch as it joins the stream: the branch with and without
    it) is split off."""
    eps = cfg["rms_norm_eps"]
    u = _rms_norm(x, w["norm_in"], eps)
    attn = cfg["attention_out_multiplier"] * attention(
        cfg["attention_in_multiplier"] * u, w, cfg)
    m_in = cfg["ssm_in_multiplier"] * u
    ssm, read, skip = mamba(m_in, w, cfg, parts=True)
    ssm = cfg["ssm_out_multiplier"] * ssm
    stateless = cfg["ssm_out_multiplier"] * mamba(
        m_in, w, cfg, state_scale=0.0)
    h = x + ssm + attn
    ff = mlp(_rms_norm(h, w["norm_pre_ff"], eps), w, cfg)
    out = {"stream": x, "attention": attn, "mamba": ssm,
           "state": ssm - stateless, "mlp": ff, "update": ssm + attn + ff}
    out = {k: float(_rms(v)) for k, v in out.items()}
    out.update(state_read=float(read), skip=float(skip))
    return out


# ---------------------------------------------------------------------------
# What the serving comparison reads
# ---------------------------------------------------------------------------


def _mix_step(x, key, layer, *, cfg, precision):
    """A layer's first half over one padded sequence, its weights made
    here from the seed. ``layer`` is traced: one compiled program a
    padded length."""
    return mix(x, layer_weights(cfg, key, layer, mlp=False), cfg, precision)


def _mlp_step(u, key, layer, *, cfg, precision):
    """``MLP(u)`` over a slab of tokens (the MLP is per token, so tokens
    of several sequences pass it together), its weights made here from
    the seed."""
    return mlp(u, layer_weights(cfg, key, layer, mixers=False), cfg,
               precision)


def _embed_step(key, tokens, *, cfg):
    """``embedding_multiplier * E[tokens]``, a block of the vocabulary
    at a time: a token's row comes from the block that holds it."""
    rows = cfg["vocab_size"] // END_BLOCKS

    def from_block(x, block):
        mine = tokens // rows == block
        got = embed_block(cfg, key, block)[tokens % rows].astype(jnp.float32)
        return jnp.where(mine[:, None], got, x), None

    x, _ = jax.lax.scan(
        from_block, jnp.zeros((tokens.shape[0], cfg["hidden_size"])),
        jnp.arange(END_BLOCKS))
    return cfg["embedding_multiplier"] * x


def _head_step(x, key, *, cfg, precision):
    """``[rows, vocab]`` logits, the head made and applied a block of
    the hidden size at a time, the partial products summed in float32."""
    x = _rms_norm(x, jnp.ones((cfg["hidden_size"],), jnp.float32),
                  cfg["rms_norm_eps"])
    width = cfg["hidden_size"] // END_BLOCKS

    def add_block(total, block):
        part = jax.lax.dynamic_slice_in_dim(x, block * width, width, axis=1)
        return total + _mm("td,dv->tv", part, head_block(cfg, key, block),
                           precision), None

    total, _ = jax.lax.scan(
        add_block, jnp.zeros((x.shape[0], cfg["vocab_size"])),
        jnp.arange(END_BLOCKS))
    return cfg["lm_head_multiplier"] * total


def padded_lengths(cfg: dict) -> list[int]:
    """The few lengths sequences are padded to (a compiled program a
    length): the ``PARTS`` equal parts of the longest context the
    configuration serves."""
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
    mixers a (padded) sequence at a time, the MLP over the real tokens of
    all of them in slabs of ``SLAB``. Both mixers are causal, so a
    sequence's padding never reaches what is read."""
    first = jax.jit(functools.partial(_embed_step, cfg=cfg))
    half = jax.jit(functools.partial(_mix_step, cfg=cfg),
                   static_argnames=("precision",))
    ff = jax.jit(functools.partial(_mlp_step, cfg=cfg),
                 static_argnames=("precision",))

    def hidden_states(sequences, precision):
        lengths = [len(tokens) for tokens in sequences]
        xs = [first(key, pad(cfg, tokens)) for tokens in sequences]
        for i in range(cfg["num_hidden_layers"]):
            us = []
            for j, n in enumerate(lengths):
                xs[j], u = half(xs[j], key, jnp.int32(i),
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
    head_rows = jax.jit(functools.partial(_head_step, cfg=cfg),
                        static_argnames=("precision",))

    def head_of(x, prec):
        # HEAD_ROWS rows at a time: one compiled head.
        rows = x.shape[0]
        x = jnp.pad(x, ((0, (-rows) % HEAD_ROWS), (0, 0)))
        return jnp.concatenate([
            head_rows(x[at:at + HEAD_ROWS], key, precision=prec)
            for at in range(0, x.shape[0], HEAD_ROWS)])[:rows]

    fulls = [list(prompt) + list(served) for prompt, served in sequences]
    hidden = hidden_states(fulls, precision)
    if control is not None:
        hidden_c = hidden_states(fulls, control)
    gaps, gaps_control = [], []
    for i, (prompt, served) in enumerate(sequences):
        lo, n = len(prompt) - 1, len(served)
        ref = head_of(hidden[i][lo:lo + n], precision)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(
            ref, jnp.asarray(served, jnp.int32)[:, None], axis=-1
        )[:, 0]
        gaps.append(jax.device_get(best - got))
        if control is not None:
            pick = jnp.argmax(head_of(hidden_c[i][lo:lo + n], control),
                              axis=-1)
            got_c = jnp.take_along_axis(ref, pick[:, None], axis=-1)[:, 0]
            gaps_control.append(jax.device_get(best - got_c))
        del ref

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
