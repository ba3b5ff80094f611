"""Plain reference for the Sarvam (``model_type: "sarvam_mla"``)
configurations.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
un-absorbed latent attention, dense over the experts held, no sorting, no
kernel, no cache, no batching. It imports nothing of the program and takes
nothing the program made: the weights come from :func:`make_weights` (the
benchmark's own, from the seed), as bfloat16 VALUES, so the float32
reference holds exactly the numbers the program holds.

The model, from ``config.json`` of ``sarvamai/sarvam-105b``; what is NOT a
key of that file is marked [assumed] here and listed under ``assumed`` in
the configuration's file (there is no network here).

- ``x0 = E[tok]`` (no embedding scale).
- Layer ``l``, pre-norm, two RMSNorms (``rms_norm_eps``):
  ``h = x + Attn(N1(x))``, ``y = h + FF_l(N2(h))``.
- ``Attn(u)`` at position ``t`` (multi-head latent attention):
  ``q = u Wq`` as ``num_attention_heads`` heads of ``q_head_dim`` =
  ``[q_nope (qk_nope_head_dim); q_rope (qk_rope_head_dim)]`` (no
  ``q_lora_rank``: one full projection); ``[c; k_r] = u Wkva``
  (``kv_lora_rank + qk_rope_head_dim``); ``c <- RMSNorm(c)``
  (``use_qk_norm`` read as the latent's norm [assumed]); ``q_rope, k_r <-
  rot_t(.)``: ONE rotary key shared by all heads, consecutive lanes
  paired [assumed], frequencies and softmax scale by ``rope_scaling``
  (``deepseek_yarn``: :func:`yarn`). ``[k_nope_h; v_h] = c Wkvb`` (heads of
  ``qk_nope_head_dim + v_head_dim``), ``k_h = [k_nope_h; k_r]``,
  ``p = softmax_causal(s * q_h . k_h)``, ``o_h = sum p v_h``,
  ``out = [o_1 .. o_H] Wo``. What a cache would keep of a token is ``[c;
  k_r]``; the reference keeps none.
- ``FF_l`` for ``l < first_k_dense_replace``: ``(silu(u W1) * (u W3)) W2``
  at ``intermediate_size``. Otherwise ``s = sigmoid(u Wr)`` over the
  ROUTER's width (``num_routed_experts``, the published ``num_experts``)
  [sigmoid assumed], the ``num_experts_per_tok`` largest of ``s + b``
  (``moe_router_enable_expert_bias``; ``b`` zero at initialisation; no
  group limit [assumed]), weights ``s_e`` of the chosen over their sum
  [assumed] times ``routed_scaling_factor``; ``FF_l(u) = sum_e w_e
  SwiGLU_e(u)`` at ``moe_intermediate_size`` OVER THE EXPERTS HELD HERE
  (the first ``num_experts`` of the router's: this chip's share of an
  expert-parallel stage; what the absent experts would add is left out,
  here as in the program) plus one shared SwiGLU of ``num_shared_experts *
  moe_intermediate_size`` that every token passes. No token is dropped.
- Last: RMSNorm and an untied head over ``vocab_size`` (this chip's slice).

Departures: none in the mathematics. At the published widths the float32
weights are 10.6 GB, so :func:`served_gaps` makes and applies them layer
by layer and the experts in blocks of ``EXPERT_BLOCK``, the attention's
query rows in blocks of ``QUERY_BLOCK`` (128), and the feed-forward (which is
per token) over the tokens of all the sequences in slabs of ``SLAB``; the
initialisation is [assumed] (normal, std ``initializer_range``; norms at
1; expert bias 0, so routing is near uniform).

``precision`` selects the arithmetic of every matrix product: ``"f32"``
is the reference itself; ``"bf16"`` and ``"fp8"`` are the lower
precisions the control computes in (operands rounded to 8 or to 4
significant bits, products accumulated in float32).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

EXPERT_BLOCK = 8
QUERY_BLOCK = 128
PAD = 1024
HEAD_ROWS = 256
SLAB = 16384


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


def _is_dense(cfg: dict, layer: int) -> bool:
    return layer < cfg["first_k_dense_replace"]


def _routed(cfg: dict) -> int:
    """The router's width: the experts of the whole layer."""
    return cfg.get("num_routed_experts") or cfg["num_experts"]


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
    d, heads, rank = (cfg["hidden_size"], cfg["num_attention_heads"],
                      cfg["kv_lora_rank"])
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    std = cfg["initializer_range"]
    key = layer_key(key, layer)
    names = ["wq", "wkva", "wkvb", "wo", "w1", "w3", "w2", "router"]
    k = dict(zip(names, jax.random.split(key, len(names))))
    ones = jnp.ones((d,), jnp.float32)
    w = {
        "norm_in": ones, "norm_pre_ff": ones,
        "kv_norm": jnp.ones((rank,), jnp.float32),
        "wq": _normal(k["wq"], (d, heads * (nope + rope)), std),
        "wkva": _normal(k["wkva"], (d, rank + rope), std),
        "wkvb": _normal(k["wkvb"], (rank, heads * (nope + vd)), std),
        "wo": _normal(k["wo"], (heads * vd, d), std),
    }
    if dense:
        ff = cfg["intermediate_size"]
    else:
        ff = cfg["num_shared_experts"] * cfg["moe_intermediate_size"]
        n = _routed(cfg)
        w["router"] = _normal(k["router"], (d, n), std)
        w["bias"] = jnp.zeros((n,), jnp.float32)
        if experts:
            w.update(expert_block(cfg, key, jnp.arange(cfg["num_experts"])))
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
    program is built from (``sarvam.program.to_program``)."""
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


def yarn(cfg: dict):
    """``(frequencies [qk_rope_head_dim / 2], cos / sin multiplier,
    softmax scale s)`` of ``deepseek_yarn``. Frequencies: over the pairs
    ``i`` of the rotary dims, ``theta ** (-2i / dim)`` times a ramp from 1
    down to ``1 / factor``, linear in ``i`` between the pair whose
    wavelength makes ``beta_fast`` turns in
    ``original_max_position_embeddings`` positions (rounded down) and the
    one that makes ``beta_slow`` (rounded up). ``m(x) = 0.1 x ln(factor)
    + 1``: cos and sin times ``m(mscale) / m(mscale_all_dim)``, and ``s =
    q_head_dim ** -0.5 * m(mscale_all_dim) ** 2``. Without
    ``rope_scaling``: plain frequencies, 1 and ``q_head_dim ** -0.5``."""
    dim, theta = cfg["qk_rope_head_dim"], float(cfg["rope_theta"])
    scale = (cfg["qk_nope_head_dim"] + dim) ** -0.5
    pairs = np.arange(dim // 2, dtype=np.float64)
    freq = theta ** (-2.0 * pairs / dim)
    r = cfg.get("rope_scaling")
    if not r:
        return freq, 1.0, scale

    def pair_of(turns):
        return dim * math.log(
            r["original_max_position_embeddings"] / (turns * 2 * math.pi)
        ) / (2 * math.log(theta))

    low = max(math.floor(pair_of(r["beta_fast"])), 0)
    high = min(math.ceil(pair_of(r["beta_slow"])), dim - 1)
    ramp = np.clip((pairs - low) / max(high - low, 0.001), 0.0, 1.0)
    freq = freq * (1.0 - ramp) + freq / r["factor"] * ramp

    def m(x):
        return 0.1 * x * math.log(r["factor"]) + 1.0 if r["factor"] > 1 else 1.0

    return freq, m(r["mscale"]) / m(r["mscale_all_dim"]), scale * m(
        r["mscale_all_dim"]) ** 2


def _rotary(x, freq, trig):
    """``x`` ``[seq, ..., dim]`` at positions ``0 .. seq - 1``: lanes
    ``(2i, 2i + 1)`` are pair ``i``, rotated by ``position * freq[i]``,
    and stay where they were."""
    t = jnp.arange(x.shape[0], dtype=jnp.float32).reshape(
        (-1,) + (1,) * (x.ndim - 1))
    angle = t * jnp.asarray(freq, jnp.float32)
    cos, sin = jnp.cos(angle) * trig, jnp.sin(angle) * trig
    a, b = x[..., 0::2], x[..., 1::2]
    return jnp.stack([a * cos - b * sin, b * cos + a * sin],
                     axis=-1).reshape(x.shape)


def attention(u, w, cfg: dict, precision: str = "f32"):
    """``Attn`` over ``u`` ``[seq, hidden]``, un-absorbed."""
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    nope, rope, vd = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                      cfg["v_head_dim"])
    t = u.shape[0]
    freq, trig, scale = yarn(cfg)
    q = _mm("td,dn->tn", u, w["wq"], precision).reshape(t, heads, nope + rope)
    kva = _mm("td,dn->tn", u, w["wkva"], precision)
    c = _rms_norm(kva[:, :rank], w["kv_norm"], cfg["rms_norm_eps"])
    q_nope, q_rope = q[..., :nope], _rotary(q[..., nope:], freq, trig)
    k_rope = _rotary(kva[:, rank:], freq, trig)  # one key for all heads
    kv = _mm("tc,cn->tn", c, w["wkvb"], precision).reshape(
        t, heads, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    block = min(QUERY_BLOCK, t)
    j = jnp.arange(t)[None, :]

    def rows(i0):
        i = i0 + jnp.arange(block)[:, None]
        qn = jax.lax.dynamic_slice_in_dim(q_nope, i0, block)
        qr = jax.lax.dynamic_slice_in_dim(q_rope, i0, block)
        # q_h . k_h over k_h = [k_nope_h; k_r]: the two parts' sum.
        s = scale * (_mm("qhd,khd->hqk", qn, k_nope, precision)
                     + _mm("qhd,kd->hqk", qr, k_rope, precision))
        s = jnp.where(j <= i, s, -jnp.inf)
        return _mm("hqk,khd->qhd", jax.nn.softmax(s, axis=-1), v, precision)

    # Query rows in blocks: [heads, seq, seq] scores do not fit at 17,408.
    ctx = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * vd)
    return _mm("tn,nd->td", ctx, w["wo"], precision)


def swiglu(u, w1, w3, w2, precision: str = "f32"):
    h = jax.nn.silu(_mm("td,df->tf", u, w1, precision)) * _mm(
        "td,df->tf", u, w3, precision)
    return _mm("tf,fd->td", h, w2, precision)


def route(u, w, cfg: dict, precision: str = "f32"):
    """``[seq, router width]`` float32: each token's weight on every
    expert of the whole layer, zero on those it did not choose."""
    s = jax.nn.sigmoid(_mm("td,de->te", u, w["router"], precision))
    _, chosen = jax.lax.top_k(s + w["bias"], cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, chosen, axis=-1)
    picked = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    picked = picked * cfg["routed_scaling_factor"]
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(s).at[rows, chosen].set(picked)


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


def expert_layer(u, w, cfg: dict, precision: str = "f32"):
    """``FF_l`` of an expert layer with all its weights in ``w``: the
    experts held (the first ``num_experts`` of the router's) and the
    shared one."""
    gates = route(u, w, cfg, precision)[:, :cfg["num_experts"]]
    return experts_dense(u, gates, w, precision) + swiglu(
        u, w["w1"], w["w3"], w["w2"], precision)


def attend(x, w, cfg: dict, precision: str = "f32"):
    """A layer's first half: ``h = x + Attn(N1(x))``, and ``N2(h)``, what
    its feed-forward reads."""
    eps = cfg["rms_norm_eps"]
    h = x + attention(_rms_norm(x, w["norm_in"], eps), w, cfg, precision)
    return h, _rms_norm(h, w["norm_pre_ff"], eps)


def logits(weights: dict, tokens, cfg: dict, precision: str = "f32"):
    """``[seq, vocab]`` float32 logits of one sequence's full forward,
    all weights in memory (small sizes; :func:`served_gaps` is the same
    mathematics a layer at a time)."""
    x = weights["embed"][tokens].astype(jnp.float32)
    for i, w in enumerate(weights["layers"]):
        h, u = attend(x, w, cfg, precision)
        if _is_dense(cfg, i):
            x = h + swiglu(u, w["w1"], w["w3"], w["w2"], precision)
        else:
            x = h + expert_layer(u, w, cfg, precision)
    x = _rms_norm(x, weights["norm_out"], cfg["rms_norm_eps"])
    return _mm("td,dv->tv", x, weights["head"], precision)


# ---------------------------------------------------------------------------
# What the serving comparison reads
# ---------------------------------------------------------------------------


def _attend_step(x, key, layer, *, cfg, precision):
    """A layer's first half over one padded sequence, its weights made
    here from the seed. ``layer`` is traced: one compiled program a
    padded length."""
    w = layer_weights(cfg, key, layer, dense=False, experts=False)
    return attend(x, w, cfg, precision)


def _ff_step(u, key, layer, *, cfg, dense, precision):
    """``FF_l(u)`` over a slab of tokens (the feed-forward is per token,
    so tokens of several sequences pass it together), the held experts'
    weights made a block at a time inside a scan."""
    w = layer_weights(cfg, key, layer, dense=dense, experts=False)
    y = swiglu(u, w["w1"], w["w3"], w["w2"], precision)
    if not dense:
        gates = route(u, w, cfg, precision)
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
        y = y + routed  # the shared expert, then the held routed ones
    return y


def padded_lengths(cfg: dict) -> list[int]:
    """The few lengths sequences are padded to (a compiled program a
    length): 2, 4, 8 and 12 times ``PAD``, and the longest context the
    configuration serves."""
    whole = -(-cfg["max_position_embeddings"] // PAD) * PAD
    return sorted({min(n * PAD, whole) for n in (2, 4, 8, 12)} | {whole})


def pad(cfg: dict, tokens):
    """``tokens`` right-padded to one of :func:`padded_lengths`."""
    tokens = jnp.asarray(tokens, jnp.int32)
    room = next(n for n in padded_lengths(cfg) if n >= tokens.shape[0])
    return jnp.pad(tokens, (0, room - tokens.shape[0]))


def _layer_by_layer(cfg: dict, key):
    """``hidden_states(sequences, precision)``: for each token sequence
    the last layer's output. A layer at a time for all sequences:
    attention a (padded) sequence at a time, the feed-forward over the
    real tokens of all of them in slabs of ``SLAB``, so a layer's experts
    are made from the seed once a slab and not once a sequence. The
    causal mask keeps a sequence's padding out of what is read."""
    first = jax.jit(
        lambda k, t: ends(cfg, k)["embed"][t].astype(jnp.float32))
    half = jax.jit(functools.partial(_attend_step, cfg=cfg),
                   static_argnames=("precision",))
    ff = jax.jit(functools.partial(_ff_step, cfg=cfg),
                 static_argnames=("dense", "precision"))

    def hidden_states(sequences, precision):
        lengths = [len(tokens) for tokens in sequences]
        xs = [first(key, pad(cfg, tokens)) for tokens in sequences]
        for i in range(cfg["num_hidden_layers"]):
            # One sequence at a time, and of each only what is read
            # again (``h`` in place of ``x``, the real tokens of ``u``):
            # eight padded float32 sequences three times over did not fit
            # beside the attention's temporaries (my chip runs, PR 35).
            us = []
            for j, n in enumerate(lengths):
                xs[j], u = half(xs[j], key, jnp.int32(i), precision=precision)
                us.append(u[:n])
                del u
            u = jnp.concatenate(us)
            del us
            total = u.shape[0]
            u = jnp.pad(u, ((0, (-total) % SLAB), (0, 0)))
            starts = np.cumsum([0] + lengths)  # where each sequence lies
            for s in range(0, total, SLAB):
                y = ff(u[s:s + SLAB], key, jnp.int32(i),
                       dense=_is_dense(cfg, i), precision=precision)
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
    hidden = hidden_states(fulls, precision)
    if control is not None:
        hidden_c = hidden_states(fulls, control)
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
