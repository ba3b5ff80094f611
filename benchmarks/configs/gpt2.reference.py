"""Plain reference for the GPT-2 configurations (medium, XL).

Straightforward ``jax.numpy`` in float32 at ``highest`` matmul
precision: no kernels, no cache, no batching tricks. It imports nothing
of the program and takes nothing the program made: the weights come from
:func:`make_weights` (the benchmark's own, from the seed) and the token
rows from the benchmark's generator.

GPT-2 as published (Radford et al. 2019; ``config.json`` of
``openai-community/gpt2-*``): learned positions, pre-LayerNorm blocks
(eps 1e-5), causal multi-head attention with biases, a GELU (tanh
approximation) MLP of width 4·d, a final LayerNorm and a head tied to
the token embedding. Departure: dropout is 0 (``reduced`` in the
configuration file).

``precision`` selects the arithmetic of every matrix product:
``"f32"`` is the reference itself; ``"bf16"`` and ``"fp8"`` are the
lower precisions the control computes in (operands rounded to 8 or to 4
significant bits, in the backward pass the gradient to 8 or to 3, as
e4m3 / e5m2 training does; products accumulated in float32).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

def make_weights(cfg: dict, key) -> dict:
    """Random float32 weights in the reference's own layout (per-layer
    arrays stacked on a leading ``num_layers`` axis), GPT-2's published
    init: normal(0.02), residual projections scaled by 1/sqrt(2·layers),
    LayerNorm at 1/0. Biases get a small normal(0.02) instead of the
    published zeros, so that a dropped bias shows in the comparison."""
    n, d, ff = cfg["n_layer"], cfg["n_embd"], cfg["n_inner"]
    v, t = cfg["vocab_size"], cfg["n_positions"]
    std = cfg["initializer_range"]
    res = std / (2.0 * n) ** 0.5
    shapes = {
        "wq": ((n, d, d), std), "wk": ((n, d, d), std),
        "wv": ((n, d, d), std), "wo": ((n, d, d), res),
        "w1": ((n, d, ff), std), "w2": ((n, ff, d), res),
        "bq": ((n, d), std), "bk": ((n, d), std), "bv": ((n, d), std),
        "bo": ((n, d), std), "b1": ((n, ff), std), "b2": ((n, d), std),
    }
    keys = jax.random.split(key, len(shapes) + 2)
    layers = {
        name: scale * jax.random.normal(k, shape, jnp.float32)
        for k, (name, (shape, scale)) in zip(keys[2:], sorted(shapes.items()))
    }
    for name in ("ln1_g", "ln2_g"):
        layers[name] = jnp.ones((n, d), jnp.float32)
    for name in ("ln1_b", "ln2_b"):
        layers[name] = jnp.zeros((n, d), jnp.float32)
    return {
        "wte": std * jax.random.normal(keys[0], (v, d), jnp.float32),
        "wpe": std * jax.random.normal(keys[1], (t, d), jnp.float32),
        "lnf_g": jnp.ones((d,), jnp.float32),
        "lnf_b": jnp.zeros((d,), jnp.float32),
        "layers": layers,
    }


def _round_mantissa(x, bits: int):
    """``x`` (float32) rounded to ``bits`` explicit mantissa bits, ties
    to even — what storing it in a narrower float does to its value
    (exponent range left alone, which only flatters the lower type)."""
    drop = 23 - bits
    u = jax.lax.bitcast_convert_type(x.astype(jnp.float32), jnp.uint32)
    u = u + jnp.uint32((1 << (drop - 1)) - 1) + ((u >> drop) & jnp.uint32(1))
    u = u & jnp.uint32(0xFFFFFFFF ^ ((1 << drop) - 1))
    return jax.lax.bitcast_convert_type(u, jnp.float32)


def _low_product(f, fwd_bits: int, bwd_bits: int):
    """``f`` (a product of two operands) as a lower-precision path
    computes it: operands rounded to ``fwd_bits`` of mantissa on the
    way in, and in the backward pass the incoming gradient rounded to
    ``bwd_bits`` (fp8 training keeps gradients in e5m2) before the two
    transposed products."""

    @jax.custom_vjp
    def product(a, b):
        return f(_round_mantissa(a, fwd_bits), _round_mantissa(b, fwd_bits))

    def fwd(a, b):
        qa, qb = _round_mantissa(a, fwd_bits), _round_mantissa(b, fwd_bits)
        return f(qa, qb), (qa, qb)

    def bwd(res, g):
        _, vjp = jax.vjp(f, *res)
        return vjp(_round_mantissa(g, bwd_bits))

    product.defvjp(fwd, bwd)
    return product


LOW_BITS = {"bf16": (7, 7), "fp8": (3, 2)}


def _mm(a, b, precision: str, spec: str | None = None):
    hi = jax.lax.Precision.HIGHEST
    if spec is None:
        f = functools.partial(jnp.matmul, precision=hi)
    else:
        f = functools.partial(jnp.einsum, spec, precision=hi)
    if precision == "f32":
        return f(a, b)
    if precision not in LOW_BITS:
        raise ValueError(f"unknown precision {precision!r}")
    return _low_product(f, *LOW_BITS[precision])(a, b)


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * g + b


def _block(x, w, cfg, precision):
    heads, eps = cfg["n_head"], cfg["layer_norm_epsilon"]
    b, t, d = x.shape
    hd = d // heads
    h = _layer_norm(x, w["ln1_g"], w["ln1_b"], eps)

    def split(y):
        return y.reshape(b, t, heads, hd)

    q = split(_mm(h, w["wq"], precision) + w["bq"])
    k = split(_mm(h, w["wk"], precision) + w["bk"])
    v = split(_mm(h, w["wv"], precision) + w["bv"])
    s = _mm(q, k, precision, "bqhd,bkhd->bhqk") / jnp.sqrt(jnp.float32(hd))
    causal = jnp.tril(jnp.ones((t, t), bool))
    s = jnp.where(causal[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    ctx = _mm(p, v, precision, "bhqk,bkhd->bqhd").reshape(b, t, d)
    x = x + _mm(ctx, w["wo"], precision) + w["bo"]
    h = _layer_norm(x, w["ln2_g"], w["ln2_b"], eps)
    h = jax.nn.gelu(_mm(h, w["w1"], precision) + w["b1"], approximate=True)
    return x + _mm(h, w["w2"], precision) + w["b2"]


def logits(weights: dict, tokens, cfg: dict, precision: str = "f32"):
    """``[batch, seq, vocab]`` float32 logits of a full causal forward."""
    t = tokens.shape[-1]
    x = weights["wte"][tokens] + weights["wpe"][:t][None]
    body = jax.checkpoint(
        lambda x, w: (_block(x, w, cfg, precision), None)
    )
    x, _ = jax.lax.scan(body, x, weights["layers"])
    x = _layer_norm(x, weights["lnf_g"], weights["lnf_b"],
                    cfg["layer_norm_epsilon"])
    return _mm(x, weights["wte"].T, precision)


def loss(weights: dict, batch, cfg: dict, precision: str = "f32"):
    """Mean next-token cross-entropy over every position of ``batch``
    = ``(inputs, targets)``."""
    x, y = batch
    lg = logits(weights, x, cfg, precision)
    lse = jax.nn.logsumexp(lg, axis=-1)
    picked = jnp.take_along_axis(lg, y[..., None], axis=-1)[..., 0]
    return jnp.mean(lse - picked)


def leaf_norms(tree: dict) -> dict:
    """L2 norm of every leaf, the stacked per-layer arrays layer by
    layer: ``{name: scalar}`` and ``{"layers/name": [num_layers]}``."""
    out = {k: jnp.sqrt(jnp.sum(jnp.square(v)))
           for k, v in tree.items() if k != "layers"}
    for k, v in tree["layers"].items():
        out[f"layers/{k}"] = jnp.sqrt(
            jnp.sum(jnp.square(v), axis=tuple(range(1, v.ndim)))
        )
    return out


def _adamw(w, g, mu, nu, count, opt):
    b1, b2, eps = opt["b1"], opt["b2"], opt["eps"]
    lr, wd = opt["learning_rate"], opt["weight_decay"]
    count = count + 1
    mu = jax.tree_util.tree_map(lambda m, x: b1 * m + (1 - b1) * x, mu, g)
    nu = jax.tree_util.tree_map(
        lambda n, x: b2 * n + (1 - b2) * jnp.square(x), nu, g
    )
    c1 = 1 - b1 ** count
    c2 = 1 - b2 ** count
    w = jax.tree_util.tree_map(
        lambda p, m, n: p - lr * (
            (m / c1) / (jnp.sqrt(n / c2) + eps) + wd * p
        ), w, mu, nu,
    )
    return w, mu, nu, count


def train_readings(cfg: dict, key, batches, *, grad_state_after: int,
                   optimizer: dict, rows_per_block: int,
                   precision: str = "f32", shard=None) -> dict:
    """Follow ``len(batches)`` AdamW updates from the seeded weights and
    return what the comparison reads: each step's loss, the per-leaf
    norms of Adam's first moment after ``grad_state_after`` steps (the
    gradient as the optimizer keeps it) and of the parameters' change
    after all of them. Gradients are accumulated over blocks of
    ``rows_per_block`` rows so that the float32 activations fit.
    ``shard``, where given, maps the weights' shapes to the layout they
    are made and kept in (four chips)."""
    make = functools.partial(make_weights, cfg)
    if shard is None:
        layout = None
        w0 = jax.jit(make)(key)
    else:
        # Made in their layout, never whole on one chip.
        layout = shard(jax.eval_shape(make, key))
        w0 = jax.jit(make, out_shardings=layout)(key)

    @jax.jit
    def grad_block(w, block):
        return jax.value_and_grad(loss)(w, block, cfg, precision)

    @jax.jit
    def accumulate(acc, g, share):
        return jax.tree_util.tree_map(lambda a, x: a + share * x, acc, g)

    update = jax.jit(functools.partial(_adamw, opt=optimizer))
    zeros = jax.jit(lambda t: jax.tree_util.tree_map(jnp.zeros_like, t),
                    out_shardings=layout)
    norms = jax.jit(leaf_norms)
    w, mu, nu, count = w0, zeros(w0), zeros(w0), 0
    losses, grad_state = [], None
    for step, (x, y) in enumerate(batches, 1):
        rows = x.shape[0]
        grads, total = zeros(w0), 0.0
        for lo in range(0, rows, rows_per_block):
            block = (jnp.asarray(x[lo:lo + rows_per_block]),
                     jnp.asarray(y[lo:lo + rows_per_block]))
            share = block[0].shape[0] / rows
            value, g = grad_block(w, block)
            grads = accumulate(grads, g, share)
            total += share * float(value)
        losses.append(total)
        w, mu, nu, count = update(w, grads, mu, nu, count)
        if step == grad_state_after:
            grad_state = jax.device_get(norms(mu))
    delta = jax.jit(
        lambda a, b: leaf_norms(
            jax.tree_util.tree_map(lambda p, q: p - q, a, b)
        )
    )(w, w0)
    return {"losses": losses, "grad_state_norms": grad_state,
            "delta_norms": jax.device_get(delta)}


def served_gaps(cfg: dict, key, sequences, *, precision: str = "f32",
                control: str | None = None) -> dict:
    """For each ``(prompt, served_tokens)``: one full forward over the
    prompt with its served tokens, and at every served position the gap
    by which the served token's reference logit lies below the
    reference's best. Returned over all positions: the mean gap (what is
    compared: it is steady from seed to seed), the widest, and the share
    of tokens that are not the reference's first. With ``control`` set,
    the same for the token that the lower precision puts first at each
    position."""
    weights = jax.jit(functools.partial(make_weights, cfg))(key)

    @functools.partial(jax.jit, static_argnames=("prec",))
    def fwd(w, tokens, prec):
        return logits(w, tokens[None], cfg, prec)[0]

    gaps, gaps_control = [], []
    for prompt, served in sequences:
        full = jnp.asarray(list(prompt) + list(served), jnp.int32)
        # Pad to a multiple of 128 so a handful of programs serve every
        # length; the causal mask keeps the padding out of what is read.
        padded = jnp.pad(full, (0, (-full.shape[0]) % 128))
        lo, n = len(prompt) - 1, len(served)
        ref = fwd(weights, padded, precision)[lo:lo + n]
        best = jnp.max(ref, axis=-1)
        got = jnp.take_along_axis(
            ref, jnp.asarray(served, jnp.int32)[:, None], axis=-1
        )[:, 0]
        gaps.append(jax.device_get(best - got))
        if control is not None:
            pick = jnp.argmax(fwd(weights, padded, control)[lo:lo + n], axis=-1)
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
