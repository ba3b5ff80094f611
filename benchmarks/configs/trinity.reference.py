"""Plain reference for the Trinity (``model_type: "afmoe"``) configurations.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision,
dense over the experts: no sorting, no kernel, no cache, no batching. It
imports nothing of the program and takes nothing the program made: the
weights come from :func:`make_weights` (the benchmark's own, from the
seed), as bfloat16 VALUES, so the float32 reference holds exactly the
numbers the program holds.

The model, from ``config.json`` of ``arcee-ai/Trinity-Mini``; what is NOT
a key of that file is marked [assumed] here and listed under ``assumed``
in the configuration's file: it is the ``afmoe`` modelling code of Hugging
Face ``transformers`` as recalled, there is no network here.

- ``x0 = E[tok] * sqrt(hidden_size)`` (``mup_enabled``; the form of the
  scaling [assumed]).
- Layer ``l``, four RMSNorms (``rms_norm_eps``; the sandwich placement
  [assumed]): ``h = x + N_post_attn(Attn_l(N_in(x)))``,
  ``y = h + N_post_ff(FF_l(N_pre_ff(h)))``.
- ``Attn_l(u)``: ``q = u Wq`` as ``num_attention_heads`` heads of
  ``head_dim``, ``k = u Wk``, ``v = u Wv`` as ``num_key_value_heads``
  heads; RMSNorm over ``head_dim`` on ``q`` and on ``k`` [assumed]; where
  ``layer_types[l] == "sliding_attention"``: rotary positions
  (``rope_theta``, no scaling) on ``q``, ``k`` and the mask ``0 <= i - j <
  sliding_window``; where ``"full_attention"``: the causal mask and NO
  rotary [assumed]; scale ``1/sqrt(head_dim)``;
  ``out = (softmax(q k^T) v * sigmoid(u Wg)) Wo`` (the output gate
  [assumed]).
- ``FF_l`` for ``l < num_dense_layers``: ``(silu(u W1) * (u W3)) W2`` at
  ``intermediate_size``. Otherwise ``s = sigmoid(u Wr)`` over
  ``num_experts`` (``score_func``), the ``num_experts_per_tok`` largest of
  ``s + b`` (``b`` the per-expert bias the balancing updates, zero at
  initialisation [assumed]; ``n_group = topk_group = 1``: no group limit),
  weights ``s_e`` of the chosen over their sum (``route_norm``) times
  ``route_scale``; ``FF_l(u) = sum_e w_e SwiGLU_e(u)`` at
  ``moe_intermediate_size`` plus one shared SwiGLU of
  ``num_shared_experts * moe_intermediate_size`` that every token passes.
  No token is dropped.
- Last: RMSNorm and an untied head over ``vocab_size``.

Departures: none in the mathematics. At the published widths the float32
weights are 17 GB, so :func:`served_gaps` makes and applies them layer by
layer and the experts in blocks of ``EXPERT_BLOCK``, the attention's query
rows in blocks of ``QUERY_BLOCK``, and the feed-forward (which is per
token) over the tokens of all the sequences in slabs of ``SLAB``; the initialisation is [assumed]
(normal, std ``initializer_range``; norms at 1; expert bias 0, so routing
is near uniform).

``precision`` selects the arithmetic of every matrix product: ``"f32"``
is the reference itself; ``"bf16"`` and ``"fp8"`` are the lower
precisions the control computes in (operands rounded to 8 or to 4
significant bits, products accumulated in float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

EXPERT_BLOCK = 8
QUERY_BLOCK = 1024
PAD = 1024
HEAD_ROWS = 256
SLAB = 16384
SLIDING = "sliding_attention"


# ---------------------------------------------------------------------------
# Weights, from the seed, layer by layer
# ---------------------------------------------------------------------------


def _normal(key, shape, std):
    return (std * jax.random.normal(key, shape, jnp.float32)).astype(
        jnp.bfloat16
    )


def _per_expert(key, experts, shape, std):
    """``[len(experts), *shape]``, expert ``e`` from a key of its own: a
    block of the experts holds the numbers the whole array holds."""
    keys = jax.vmap(lambda e: jax.random.fold_in(key, e))(experts)
    return jax.vmap(lambda k: _normal(k, shape, std))(keys)


def _is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["num_dense_layers"]


def layer_key(key, layer):
    return jax.random.fold_in(key, 1000 + layer)


def layer_weights(cfg: dict, key, layer, *, dense: bool | None = None,
                  experts: bool = True):
    """One layer's weights (bfloat16; norms and the expert bias float32).
    ``experts=False`` leaves the routed experts' three arrays out
    (:func:`expert_block` makes them a block at a time). ``layer`` may be
    traced where ``dense`` says which kind of layer it is."""
    if dense is None:
        dense = _is_dense(cfg, layer)
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    std = cfg["initializer_range"]
    key = layer_key(key, layer)
    names = ["wq", "wk", "wv", "wg", "wo", "w1", "w3", "w2", "router"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    ones = jnp.ones((d,), jnp.float32)
    w = {
        "norm_in": ones, "norm_post_attn": ones, "norm_pre_ff": ones,
        "norm_post_ff": ones,
        "q_norm": jnp.ones((hd,), jnp.float32),
        "k_norm": jnp.ones((hd,), jnp.float32),
        "wq": _normal(k["wq"], (d, q), std), "wk": _normal(k["wk"], (d, kv), std),
        "wv": _normal(k["wv"], (d, kv), std), "wg": _normal(k["wg"], (d, q), std),
        "wo": _normal(k["wo"], (q, d), std),
    }
    if dense:
        ff = cfg["intermediate_size"]
    else:
        ff = cfg["num_shared_experts"] * cfg["moe_intermediate_size"]
        n = cfg["num_experts"]
        w["router"] = _normal(k["router"], (d, n), std)
        w["bias"] = jnp.zeros((n,), jnp.float32)
        if experts:
            w.update(expert_block(cfg, key, jnp.arange(n)))
    # The dense MLP, or the shared expert every token passes.
    w.update(w1=_normal(k["w1"], (d, ff), std), w3=_normal(k["w3"], (d, ff), std),
             w2=_normal(k["w2"], (ff, d), std))
    return w


def expert_block(cfg: dict, key, experts):
    """``ew1``, ``ew3`` ``[len(experts), hidden, width]`` and ``ew2``
    ``[len(experts), width, hidden]`` of a layer whose key
    (:func:`layer_key`) is ``key``."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    std = cfg["initializer_range"]
    return {
        name: _per_expert(jax.random.fold_in(key, 7 + i), experts, shape, std)
        for i, (name, shape) in enumerate(
            (("ew1", (d, f)), ("ew3", (d, f)), ("ew2", (f, d)))
        )
    }


def ends(cfg: dict, key):
    """The embedding, the final norm and the untied head."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg["initializer_range"]
    ke, kh = jax.random.split(jax.random.fold_in(key, 1), 2)
    return {"embed": _normal(ke, (v, d), std),
            "norm_out": jnp.ones((d,), jnp.float32),
            "head": _normal(kh, (d, v), std)}


def make_weights(cfg: dict, key) -> dict:
    """Every weight of the model in the reference's layout: what the
    program is built from (``trinity.program.to_program``)."""
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


def _mm(spec: str, a, b, precision: str):
    a, b = a.astype(jnp.float32), b.astype(jnp.float32)
    if precision != "f32":
        bits = LOW_BITS[precision]
        a, b = _round_mantissa(a, bits), _round_mantissa(b, bits)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


def _rotary(x, theta):
    """``x`` ``[seq, heads, head_dim]`` at positions ``0 .. seq - 1``."""
    half = x.shape[-1] // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    angle = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None, None] * freq
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    a, b = x[..., :half], x[..., half:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], axis=-1)


def attention(u, w, cfg: dict, sliding, precision: str = "f32"):
    """``Attn_l`` over ``u`` ``[seq, hidden]``. ``sliding`` may be traced
    (one compiled layer serves both kinds)."""
    heads, kvh, hd = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
                      cfg["head_dim"])
    t = u.shape[0]
    eps = cfg["rms_norm_eps"]
    q = _mm("td,dn->tn", u, w["wq"], precision).reshape(t, heads, hd)
    k = _mm("td,dn->tn", u, w["wk"], precision).reshape(t, kvh, hd)
    v = _mm("td,dn->tn", u, w["wv"], precision).reshape(t, kvh, hd)
    q, k = _rms_norm(q, w["q_norm"], eps), _rms_norm(k, w["k_norm"], eps)
    q = jnp.where(sliding, _rotary(q, cfg["rope_theta"]), q)
    k = jnp.where(sliding, _rotary(k, cfg["rope_theta"]), k)
    # Each K/V head serves heads // kvh query heads.
    k = jnp.repeat(k, heads // kvh, axis=1)
    v = jnp.repeat(v, heads // kvh, axis=1)
    window = jnp.where(sliding, cfg.get("sliding_window") or t, t)
    block = min(QUERY_BLOCK, t)
    j = jnp.arange(t)[None, :]

    def rows(q_rows, i0):
        i = i0 + jnp.arange(block)[:, None]
        s = _mm("qhd,khd->hqk", q_rows, k, precision) / jnp.sqrt(
            jnp.float32(hd))
        s = jnp.where((j <= i) & (i - j < window), s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision)

    # Query rows in blocks: [heads, seq, seq] scores do not fit at 8,704.
    starts = jnp.arange(0, t, block)
    ctx = jax.lax.map(
        lambda i0: rows(jax.lax.dynamic_slice_in_dim(q, i0, block), i0),
        starts,
    ).reshape(t, heads * hd)
    gate = jax.nn.sigmoid(_mm("td,dn->tn", u, w["wg"], precision))
    return _mm("tn,nd->td", ctx * gate, w["wo"], precision)


def swiglu(u, w1, w3, w2, precision: str = "f32"):
    h = jax.nn.silu(_mm("td,df->tf", u, w1, precision)) * _mm(
        "td,df->tf", u, w3, precision)
    return _mm("tf,fd->td", h, w2, precision)


def route(u, w, cfg: dict, precision: str = "f32"):
    """``[seq, num_experts]`` float32: each token's weight on every
    expert, zero on those it did not choose."""
    s = jax.nn.sigmoid(_mm("td,de->te", u, w["router"], precision))
    _, chosen = jax.lax.top_k(s + w["bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    if cfg["route_norm"]:
        picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * cfg["route_scale"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


def experts_dense(u, gates, block: dict, precision: str = "f32"):
    """``sum_e gates[:, e] * SwiGLU_e(u)`` over the experts of ``block``
    (``gates`` ``[seq, len(block)]``): every expert on every token. The
    experts' matrices stand side by side, so the sum over experts is the
    contraction of one plain product: ``((silu(u W1) * (u W3)) * g) W2``
    with ``W1``, ``W3`` ``[hidden, experts * width]``, ``W2`` ``[experts *
    width, hidden]`` and each expert's columns weighed by its gate."""
    e, d, f = block["ew1"].shape
    w1 = jnp.transpose(block["ew1"], (1, 0, 2)).reshape(d, e * f)
    w3 = jnp.transpose(block["ew3"], (1, 0, 2)).reshape(d, e * f)
    h = jax.nn.silu(_mm("td,dn->tn", u, w1, precision)) * _mm(
        "td,dn->tn", u, w3, precision)
    h = h * jnp.repeat(gates, f, axis=1)
    return _mm("tn,nd->td", h, block["ew2"].reshape(e * f, d), precision)


def expert_layer(u, w, cfg: dict, precision: str = "f32"):
    """``FF_l`` of an expert layer with all its weights in ``w``."""
    gates = route(u, w, cfg, precision)
    return experts_dense(u, gates, w, precision) + swiglu(
        u, w["w1"], w["w3"], w["w2"], precision)


def attend(x, w, cfg: dict, sliding, precision: str = "f32"):
    """A layer's first half: ``h = x + N_post_attn(Attn(N_in(x)))``, and
    ``N_pre_ff(h)``, what its feed-forward reads."""
    eps = cfg["rms_norm_eps"]
    h = x + _rms_norm(
        attention(_rms_norm(x, w["norm_in"], eps), w, cfg, sliding, precision),
        w["norm_post_attn"], eps,
    )
    return h, _rms_norm(h, w["norm_pre_ff"], eps)


def block(x, w, cfg: dict, sliding, ff, precision: str = "f32"):
    """One layer; ``ff(u)`` is its feed-forward."""
    h, u = attend(x, w, cfg, sliding, precision)
    return h + _rms_norm(ff(u), w["norm_post_ff"], cfg["rms_norm_eps"])


def embed(tokens, table, cfg: dict):
    x = table[tokens].astype(jnp.float32)
    return x * jnp.sqrt(jnp.float32(cfg["hidden_size"])) if cfg[
        "mup_enabled"] else x


def logits(weights: dict, tokens, cfg: dict, precision: str = "f32"):
    """``[seq, vocab]`` float32 logits of one sequence's full forward,
    all weights in memory (small sizes; :func:`served_gaps` is the same
    mathematics a layer at a time)."""
    x = embed(tokens, weights["embed"], cfg)
    for i, w in enumerate(weights["layers"]):
        if _is_dense(cfg, i):
            ff = lambda u, w=w: swiglu(u, w["w1"], w["w3"], w["w2"], precision)
        else:
            ff = lambda u, w=w: expert_layer(u, w, cfg, precision)
        x = block(x, w, cfg, cfg["layer_types"][i] == SLIDING, ff, precision)
    x = _rms_norm(x, weights["norm_out"], cfg["rms_norm_eps"])
    return _mm("td,dv->tv", x, weights["head"], precision)


# ---------------------------------------------------------------------------
# What the serving comparison reads
# ---------------------------------------------------------------------------


def _attend_step(x, key, layer, sliding, *, cfg, precision):
    """A layer's first half over one padded sequence, its weights made
    here from the seed. ``layer`` and ``sliding`` are traced: one
    compiled program a padded length."""
    w = layer_weights(cfg, key, layer, dense=False, experts=False)
    return attend(x, w, cfg, sliding, precision)


def _ff_step(u, key, layer, *, cfg, dense, precision):
    """``N_post_ff(FF_l(u))`` over a slab of tokens (the feed-forward is
    per token, so tokens of several sequences pass it together), the
    experts' weights made a block at a time inside a scan. Returns it and
    ``[tokens, num_experts]`` bool: the experts each token chose (none in
    a dense layer)."""
    w = layer_weights(cfg, key, layer, dense=dense, experts=False)
    chose = jnp.zeros((u.shape[0], cfg["num_experts"]), bool)
    y = swiglu(u, w["w1"], w["w3"], w["w2"], precision)
    if not dense:
        gates = route(u, w, cfg, precision)
        chose = gates > 0
        step = min(EXPERT_BLOCK, cfg["num_experts"])

        def body(acc, e0):
            part = experts_dense(
                u, jax.lax.dynamic_slice_in_dim(gates, e0, step, axis=1),
                expert_block(cfg, layer_key(key, layer), e0 + jnp.arange(step)),
                precision,
            )
            return acc + part, None

        routed, _ = jax.lax.scan(body, jnp.zeros_like(u),
                                 jnp.arange(0, cfg["num_experts"], step))
        y = y + routed  # the shared expert, then the routed ones
    return _rms_norm(y, w["norm_post_ff"], cfg["rms_norm_eps"]), chose


def padded_lengths(cfg: dict) -> list[int]:
    """The few lengths sequences are padded to (a compiled program a
    length): 2 and 4 times ``PAD``, and the longest context the
    configuration serves."""
    whole = -(-cfg["max_position_embeddings"] // PAD) * PAD
    return sorted({min(n, whole) for n in (2 * PAD, 4 * PAD)} | {whole})


def _layer_by_layer(cfg: dict, key):
    """``hidden_states(sequences, precision)``: for each token sequence
    the last layer's output and, per expert layer, the experts each token
    chose. A layer at a time for all sequences: attention a (padded)
    sequence at a time, the feed-forward over the real tokens of all of
    them in slabs of ``SLAB``, so a layer's experts are made from the seed
    once a slab and not once a sequence. The causal masks keep a
    sequence's padding out of what is read."""
    first = jax.jit(lambda k, t: embed(t, ends(cfg, k)["embed"], cfg))
    half = jax.jit(functools.partial(_attend_step, cfg=cfg),
                   static_argnames=("precision",))
    ff = jax.jit(functools.partial(_ff_step, cfg=cfg),
                 static_argnames=("dense", "precision"))

    def hidden_states(sequences, precision):
        lengths = [len(tokens) for tokens in sequences]
        xs = [first(key, pad(cfg, tokens)) for tokens in sequences]
        chosen = [[] for _ in sequences]
        for i, kind in enumerate(cfg["layer_types"]):
            halves = [half(x, key, jnp.int32(i), jnp.asarray(kind == SLIDING),
                           precision=precision) for x in xs]
            u = jnp.concatenate([u[:n] for (_, u), n in zip(halves, lengths)])
            total = u.shape[0]
            u = jnp.pad(u, ((0, (-total) % SLAB), (0, 0)))
            outs = [ff(u[s:s + SLAB], key, jnp.int32(i),
                       dense=_is_dense(cfg, i), precision=precision)
                    for s in range(0, total, SLAB)]
            y = jnp.concatenate([o[0] for o in outs])
            chose = jnp.concatenate([o[1] for o in outs])
            at = 0
            for j, ((h, _), n) in enumerate(zip(halves, lengths)):
                xs[j] = h.at[:n].add(y[at:at + n])
                if not _is_dense(cfg, i):
                    chosen[j].append(chose[at:at + n])
                at += n
        return [x[:n] for x, n in zip(xs, lengths)], chosen

    return hidden_states


def pad(cfg: dict, tokens):
    """``tokens`` right-padded to one of :func:`padded_lengths`."""
    tokens = jnp.asarray(tokens, jnp.int32)
    room = next(n for n in padded_lengths(cfg) if n >= tokens.shape[0])
    return jnp.pad(tokens, (0, room - tokens.shape[0]))


def expert_choices(cfg: dict, key, tokens):
    """``[expert_layers, len(tokens), num_experts]`` bool: the experts the
    reference's router chooses for each token of one sequence."""
    _, chosen = _layer_by_layer(cfg, key)([tokens], "f32")
    return jnp.stack(chosen[0])


def served_gaps(cfg: dict, key, sequences, *, precision: str = "f32",
                control: str | None = None) -> dict:
    """For each ``(prompt, served_tokens)``: one full forward over the
    prompt with its served tokens, and at every served position the gap
    by which the served token's reference logit lies below the
    reference's best. Returned over all positions: the mean gap (what is
    compared), the widest, and the share of tokens that are not the
    reference's first. With ``control`` set, the same for the token that
    the lower precision puts first at each position."""

    hidden_states = _layer_by_layer(cfg, key)

    @functools.partial(jax.jit, static_argnames=("prec",))
    def head_rows(x, k, prec):
        w = ends(cfg, k)
        x = _rms_norm(x, w["norm_out"], cfg["rms_norm_eps"])
        return _mm("td,dv->tv", x, w["head"], prec)

    def head(x, k, prec):
        # Rows padded to a multiple of HEAD_ROWS: a compiled head or two.
        rows = x.shape[0]
        x = jnp.pad(x, ((0, (-rows) % HEAD_ROWS), (0, 0)))
        return head_rows(x, k, prec)[:rows]

    fulls = [list(prompt) + list(served) for prompt, served in sequences]
    hidden, _ = hidden_states(fulls, precision)
    if control is not None:
        hidden_c, _ = hidden_states(fulls, control)
    gaps, gaps_control = [], []
    for i, (prompt, served) in enumerate(sequences):
        lo, n = len(prompt) - 1, len(served)
        ref = head(hidden[i][lo:lo + n], key, precision)
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(
            ref, jnp.asarray(served, jnp.int32)[:, None], axis=-1
        )[:, 0]
        gaps.append(jax.device_get(best - got))
        if control is not None:
            pick = jnp.argmax(head(hidden_c[i][lo:lo + n], key, control),
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
