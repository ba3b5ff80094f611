"""Plain reference for the Nemotron-H (``model_type: "nemotron_h"``)
configurations.

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul precision:
the Mamba-2 recurrence STEP BY STEP under ``lax.scan`` (never the chunked
form), dense attention, dense over the experts held, no sorting, no
kernel, no cache, no batching. It imports nothing of the program and takes
nothing the program made: the weights come from :func:`make_weights` (the
benchmark's own, from the seed), as bfloat16 VALUES, so the float32
reference holds exactly the numbers the program holds.

The model, from ``config.json`` of
``nvidia/NVIDIA-Nemotron-3-Nano-30B-A3B-BF16``; what is NOT a key of that
file is marked [assumed] here and listed under ``assumed`` in the
configuration's file (there is no network here).

- ``x_0 = E[tok]``. Layer ``l`` is ONE sublayer, its kind the ``l``-th
  letter of ``hybrid_override_pattern``: ``x_{l+1} = x_l +
  Sub_l(RMSNorm_l(x_l))`` (``layer_norm_epsilon``; no multipliers,
  ``residual_in_fp32`` false). Last: ``logits = RMSNorm_f(x_L) W_head``
  (untied; this chip's slice of the vocabulary).
- ``M`` (Mamba-2) on ``u``: ``[z; xBC; dt] = u W_in`` (``inner =
  mamba_num_heads * mamba_head_dim`` [assumed: not ``expand *
  hidden_size``]; ``inner + 2 * n_groups * ssm_state_size``;
  ``mamba_num_heads``) [order assumed]. ``xBC_t <- silu(b_c + sum_j
  w_c[:, j] xBC_{t - conv_kernel + 1 + j})`` (depthwise, causal, zeros
  before the start); ``[x; B; C]`` [order assumed], ``x_t`` as heads of
  ``mamba_head_dim``, ``B_t`` and ``C_t`` ``[n_groups, ssm_state_size]``:
  head ``h`` reads group ``h // (heads / n_groups)``. ``D_t = softplus(dt_t
  + dt_bias)`` a head (no clamp [assumed]), ``a_t = exp(D_t A)``, ``A =
  -exp(a_log)``. State a head ``H_t = a_t H_{t-1} + D_t x_t B_t^T``
  (``[mamba_head_dim, ssm_state_size]``, from zero), ``y_t = H_t C_t +
  d_skip x_t``. ``g = y * silu(z)`` [gate before norm, assumed], ``o = g /
  rms(g) * w_n`` over each GROUP's ``inner / n_groups`` channels
  [assumed], ``M = o W_out``.
- ``E`` (routed experts) on ``u``: ``s = sigmoid(u W_r)`` over the ROUTER's
  width (``n_routed_experts``), the ``num_experts_per_tok`` largest of ``s
  + b`` (``b`` the selection bias, zero here; ``n_group = topk_group = 1``:
  no group limit), weights ``s[chosen] / sum(s[chosen]) *
  routed_scaling_factor`` (``norm_topk_prob``); ``sum_e w_e relu(u
  W_up^e)^2 W_down^e`` (``mlp_hidden_act: relu2``, un-gated, two matrices
  at ``moe_intermediate_size``) OVER THE EXPERTS HELD HERE (the first
  ``num_experts`` of the router's: this chip's share of an expert-parallel
  stage; what the absent experts would add is left out, here as in the
  program), plus the shared expert ``relu(u S_up)^2 S_down`` at
  ``moe_shared_expert_intermediate_size``, which every token passes. No
  token is dropped; no bias anywhere but the convolution's.
- ``*`` (attention) on ``u``: ``q, k, v = u Wq, u Wk, u Wv`` as
  ``num_attention_heads`` / ``num_key_value_heads`` heads of ``head_dim``,
  NO positional embedding [assumed: the family's published description;
  ``rope_theta`` is then unused], no head norm, no gate,
  ``softmax_causal(q . k / sqrt(head_dim))``, ``Wo``.

Departures: none in the mathematics. An expert's two matrices are both
held ``[width, hidden]`` (``up_proj`` as the checkpoint holds it, ``[out,
in]``; ``down_proj`` ``[in, out]``): a layout, the numbers are the
seed's. :func:`served_gaps` makes and applies the weights layer by layer,
the attention's query rows in blocks of ``QUERY_BLOCK`` and the expert
layers (which are per token) over the tokens of all the sequences in
slabs of ``SLAB``. The initialisation is [assumed]: normal (std
``initializer_range``) for every matrix, norms at 1, and the Mamba layer's
per-channel and per-head scalars as Mamba-2 publishes them (``A`` uniform
in [1, 16], ``dt_bias`` the inverse softplus of a log-uniform step in
[``time_step_min``, ``time_step_max``], ``d_skip`` 1, the depthwise
convolution's taps and its bias uniform in +-1 / sqrt(conv_kernel)).

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
PARTS = 3
HEAD_ROWS = 256
SLAB = 2048
MAMBA, EXPERTS, ATTENTION = "M", "E", "*"


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


def _held(cfg: dict) -> int:
    """The experts this chip holds: the first of the router's."""
    return cfg.get("num_experts") or cfg["n_routed_experts"]


def _sizes(cfg: dict):
    """``(heads, head_dim, d_state, groups, taps, inner, conv_dim)`` of a
    Mamba layer."""
    heads, hd = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    n, groups = cfg["ssm_state_size"], cfg["n_groups"]
    return (heads, hd, n, groups, cfg["conv_kernel"], heads * hd,
            heads * hd + 2 * groups * n)


def layer_key(key, layer):
    return jax.random.fold_in(key, 1000 + layer)


def expert_block(cfg: dict, key, experts) -> dict:
    """``e_up`` and ``e_down``, both ``[len(experts), width, hidden]``, of
    a layer whose key (:func:`layer_key`) is ``key``."""
    shape = (cfg["moe_intermediate_size"], cfg["hidden_size"])
    std = cfg["initializer_range"]
    return {
        name: _per_expert(jax.random.fold_in(key, 7 + i), experts, shape, std)
        for i, name in enumerate(("e_up", "e_down"))
    }


def layer_weights(cfg: dict, key, layer, kind: str, *,
                  experts: bool = True) -> dict:
    """One layer's weights (bfloat16; norms and the Mamba layer's per-head
    scalars float32). ``layer`` may be traced, ``kind`` (a letter of the
    pattern) is not; ``experts=False`` leaves an ``E`` layer's routed
    experts out."""
    d, std = cfg["hidden_size"], cfg["initializer_range"]
    key = layer_key(key, layer)
    w = {"norm_in": jnp.ones((d,), jnp.float32)}
    if kind == ATTENTION:
        heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        hd = cfg["head_dim"]
        k = jax.random.split(jax.random.fold_in(key, 3), 4)
        w.update(wq=_normal(k[0], (d, heads * hd), std),
                 wk=_normal(k[1], (d, kvh * hd), std),
                 wv=_normal(k[2], (d, kvh * hd), std),
                 wo=_normal(k[3], (heads * hd, d), std))
    elif kind == EXPERTS:
        shared = (cfg["n_shared_experts"]
                  * cfg["moe_shared_expert_intermediate_size"])
        k = jax.random.split(jax.random.fold_in(key, 5), 3)
        w.update(router=_normal(k[0], (d, cfg["n_routed_experts"]), std),
                 s_up=_normal(k[1], (d, shared), std),
                 s_down=_normal(k[2], (shared, d), std))
        if experts:
            w.update(expert_block(cfg, key, jnp.arange(_held(cfg))))
    else:
        heads, _, _, _, taps, inner, conv_dim = _sizes(cfg)
        k = jax.random.split(jax.random.fold_in(key, 4), 6)
        bound = 1.0 / math.sqrt(taps)
        step = jnp.exp(jax.random.uniform(
            k[4], (heads,), jnp.float32, math.log(cfg["time_step_min"]),
            math.log(cfg["time_step_max"])))
        w.update(
            w_in=_normal(k[0], (d, inner + conv_dim + heads), std),
            w_out=_normal(k[1], (inner, d), std),
            conv_w=jax.random.uniform(
                k[2], (conv_dim, taps), jnp.float32, -bound, bound
            ).astype(jnp.bfloat16),
            conv_b=jax.random.uniform(
                k[3], (conv_dim,), jnp.float32, -bound, bound
            ).astype(jnp.bfloat16),
            dt_bias=step + jnp.log(-jnp.expm1(-step)),
            a_log=jnp.log(jax.random.uniform(
                k[5], (heads,), jnp.float32, 1.0, 16.0)),
            d_skip=jnp.ones((heads,), jnp.float32),
            norm=jnp.ones((inner,), jnp.float32),
        )
    return w


def ends(cfg: dict, key) -> dict:
    """The embedding, the final norm and the (untied) head."""
    d, v, std = cfg["hidden_size"], cfg["vocab_size"], cfg["initializer_range"]
    return {"embed": _normal(jax.random.fold_in(key, 1), (v, d), std),
            "norm_out": jnp.ones((d,), jnp.float32),
            "head": _normal(jax.random.fold_in(key, 2), (d, v), std)}


def make_weights(cfg: dict, key) -> dict:
    """Every weight of the model in the reference's layout: what the
    program is built from (``nemotron.program.to_program``)."""
    w = ends(cfg, key)
    w["layers"] = [layer_weights(cfg, key, i, kind)
                   for i, kind in enumerate(cfg["hybrid_override_pattern"])]
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


def _in(x, precision: str):
    """``x`` as a value held in ``precision``."""
    return x if precision == "f32" else _round_mantissa(
        x, LOW_BITS[precision])


def _mm(spec: str, a, b, precision: str):
    a = _in(a.astype(jnp.float32), precision)
    b = _in(b.astype(jnp.float32), precision)
    return jnp.einsum(spec, a, b, precision=jax.lax.Precision.HIGHEST)


def _rms_norm(x, scale, eps):
    return x * jax.lax.rsqrt(
        jnp.mean(jnp.square(x), axis=-1, keepdims=True) + eps
    ) * scale


def mamba(u, w, cfg: dict, precision: str = "f32", *, state_out: bool = False):
    """The Mamba-2 mixer over ``u`` ``[seq, hidden]``: the recurrence one
    token at a time. With ``state_out`` also the state after the last
    token and the last ``conv_kernel - 1`` pre-convolution columns (what
    a cache would keep; the reference keeps none)."""
    heads, hd, n, groups, taps, inner, conv_dim = _sizes(cfg)
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
    # Heads as [groups, heads a group]: a group's heads share B and C.
    x = conv[:, :inner].reshape(t, groups, heads // groups, hd)
    b_in = conv[:, inner:inner + groups * n].reshape(t, groups, n)
    c_out = conv[:, inner + groups * n:].reshape(t, groups, n)
    by_group = (groups, heads // groups)

    def token(state, at):
        x_t, step_t, decay_t, b_t, c_t = at
        state = _in(
            decay_t.reshape(by_group)[..., None, None] * state
            + (step_t.reshape(by_group)[..., None] * x_t)[..., None]
            * b_t[:, None, None, :],
            precision,
        )
        return state, _mm("ghpn,gn->ghp", state, c_t, precision)

    state, y = jax.lax.scan(
        token, jnp.zeros((*by_group, hd, n), f32),
        (x, step, decay, b_in, c_out))
    y = y + w["d_skip"].reshape(by_group)[..., None] * x
    gated = y.reshape(t, inner) * jax.nn.silu(z)
    normed = _rms_norm(
        gated.reshape(t, groups, inner // groups),
        w["norm"].reshape(groups, inner // groups), cfg["layer_norm_epsilon"],
    ).reshape(t, inner)
    out = _mm("tn,nd->td", normed, w["w_out"], precision)
    if state_out:
        return out, state.reshape(heads, hd, n), padded[t:]
    return out


def attention(u, w, cfg: dict, precision: str = "f32"):
    """Causal grouped-query attention over ``u`` ``[seq, hidden]``, no
    positions, scores over ``sqrt(head_dim)``."""
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg["head_dim"]
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
        s = _mm("qkgd,tkd->kgqt", qb, k, precision) / math.sqrt(hd)
        s = jnp.where(j <= i, s, -jnp.inf)
        return _mm("kgqt,tkd->qkgd", jax.nn.softmax(s, axis=-1), v, precision)

    ctx = jax.lax.map(rows, jnp.arange(0, t, block)).reshape(t, heads * hd)
    return _mm("tn,nd->td", ctx, w["wo"], precision)


def relu2(u, up, down, precision: str = "f32"):
    """``relu(u up)^2 down``: ``up`` ``[hidden, width]``, ``down``
    ``[width, hidden]``."""
    h = jnp.square(jax.nn.relu(_mm("td,df->tf", u, up, precision)))
    return _mm("tf,fd->td", h, down, precision)


def route(u, w, cfg: dict, precision: str = "f32"):
    """``[seq, router width]`` float32: each token's weight on every
    expert of the whole layer, zero on those it did not choose."""
    scores = jax.nn.sigmoid(_mm("td,de->te", u, w["router"], precision))
    picked, chosen = jax.lax.top_k(scores, cfg["num_experts_per_tok"])
    if cfg["norm_topk_prob"]:
        picked = picked / jnp.sum(picked, axis=-1, keepdims=True)
    rows = jnp.arange(u.shape[0])[:, None]
    return jnp.zeros_like(scores).at[rows, chosen].set(
        picked * cfg["routed_scaling_factor"])


def experts_dense(u, gates, block: dict, precision: str = "f32"):
    """``sum_e gates[:, e] * relu(u W_up^e)^2 W_down^e`` over the experts
    of ``block`` (``gates`` ``[seq, len(block)]``): every expert on every
    token, the experts' matrices side by side in one plain product."""
    e, f, d = block["e_up"].shape
    h = jnp.square(jax.nn.relu(_mm(
        "td,nd->tn", u, block["e_up"].reshape(e * f, d), precision)))
    h = h * jnp.repeat(gates, f, axis=1)
    return _mm("tn,nd->td", h, block["e_down"].reshape(e * f, d), precision)


def experts(u, w, cfg: dict, precision: str = "f32"):
    """``MoE(u) + Shared(u)`` with all the layer's weights in ``w``: the
    experts held (the first ``num_experts`` of the router's) and the
    shared expert."""
    gates = route(u, w, cfg, precision)[:, :_held(cfg)]
    return experts_dense(u, gates, w, precision) + relu2(
        u, w["s_up"], w["s_down"], precision)


SUBLAYERS = {MAMBA: mamba, EXPERTS: experts, ATTENTION: attention}


def sublayer(x, w, cfg: dict, kind: str, precision: str = "f32"):
    """``Sub(N(x))``: what a layer adds to the stream."""
    u = _rms_norm(x, w["norm_in"], cfg["layer_norm_epsilon"])
    return SUBLAYERS[kind](u, w, cfg, precision)


def head(x, w, cfg: dict, precision: str = "f32"):
    x = _rms_norm(x, w["norm_out"], cfg["layer_norm_epsilon"])
    return _mm("td,dv->tv", x, w["head"], precision)


def logits(weights: dict, tokens, cfg: dict, precision: str = "f32"):
    """``[seq, vocab]`` float32 logits of one sequence's full forward,
    all weights in memory (small sizes; :func:`served_gaps` is the same
    mathematics a layer at a time)."""
    x = weights["embed"][tokens].astype(jnp.float32)
    for kind, w in zip(cfg["hybrid_override_pattern"], weights["layers"]):
        x = x + sublayer(x, w, cfg, kind, precision)
    return head(x, weights, cfg, precision)


# ---------------------------------------------------------------------------
# What the serving comparison reads
# ---------------------------------------------------------------------------


def _step(x, key, layer, *, cfg, kind, precision):
    """``Sub(N(x))`` of one layer, its weights made here from the seed:
    over one padded sequence (a mixer), or over a slab of tokens of
    several sequences (the experts, which are per token). ``layer`` is
    traced: one compiled program a shape and kind of layer."""
    return sublayer(x, layer_weights(cfg, key, layer, kind), cfg, kind,
                    precision)


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
    the last layer's output. A layer at a time for all sequences: a mixer
    a (padded) sequence at a time, an expert layer over the real tokens
    of all of them in slabs of ``SLAB``. Both mixers are causal, so a
    sequence's padding never reaches what is read."""
    first = jax.jit(lambda k, t: ends(cfg, k)["embed"][t].astype(jnp.float32))
    step = jax.jit(functools.partial(_step, cfg=cfg),
                   static_argnames=("kind", "precision"))

    def hidden_states(sequences, precision):
        lengths = [len(tokens) for tokens in sequences]
        starts = np.cumsum([0] + lengths)  # where each sequence lies
        xs = [first(key, pad(cfg, tokens)) for tokens in sequences]
        for i, kind in enumerate(cfg["hybrid_override_pattern"]):
            at = jnp.int32(i)
            if kind != EXPERTS:
                xs = [x + step(x, key, at, kind=kind, precision=precision)
                      for x in xs]
                continue
            x = jnp.concatenate([x[:n] for x, n in zip(xs, lengths)])
            total = x.shape[0]
            x = jnp.pad(x, ((0, (-total) % SLAB), (0, 0)))
            for s in range(0, total, SLAB):
                y = step(x[s:s + SLAB], key, at, kind=kind,
                         precision=precision)
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
