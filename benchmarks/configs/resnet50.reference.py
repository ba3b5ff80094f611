"""Plain reference for the ResNet-50 configuration.

ResNet-50 v1.5 (He et al. 2015, with the stride on the 3x3 convolution)
in straightforward ``jax.numpy`` / ``lax.conv`` at float32 and
``highest`` precision, NHWC: a 7x7/2 stem, a 3x3/2 max-pool, bottleneck
stages of (3, 4, 6, 3) blocks at 64·2^i filters, batch normalisation
with batch statistics (momentum 0.9, eps 1e-5, biased variance), global
average pool and a dense head; SGD with momentum. It imports nothing of
the program; weights are the benchmark's own, from the seed.

``precision``: ``"f32"`` is the reference; ``"bf16"`` / ``"fp8"`` round
the operands of every convolution and of the head to 8 / 4 significant
bits, and in the backward pass the gradient to 8 / 3 (the control).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

STAGES = (3, 4, 6, 3)
BN_MOMENTUM, BN_EPS = 0.9, 1e-5
PIXEL_MEAN, PIXEL_STD = 0.45, 0.225


def normalise(images):
    """uint8 pixels -> float32, zero-centred (shared with the program's
    loss so both see the same input arithmetic)."""
    return (images.astype(jnp.float32) * (1.0 / 255.0) - PIXEL_MEAN) / PIXEL_STD


def _block_specs(cfg):
    """``(name, in_channels, filters, stride)`` of every bottleneck."""
    width, cin, out = cfg["num_filters"], cfg["num_filters"], []
    for i, count in enumerate(STAGES):
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out.append((f"stage{i}_block{j}", cin, width * 2 ** i, stride))
            cin = 4 * width * 2 ** i
    return out


def make_weights(cfg: dict, key) -> dict:
    """``{"params": ..., "batch_stats": ...}`` under the names the
    architecture's layers carry. He-normal convolutions, BatchNorm at
    1/0 (0.3 on each block's last, so 16 residual sums stay tame), a
    normal(0.01) head; running statistics at 0/1."""
    counter = iter(range(10_000))

    def conv(kh, cin, cout):
        k = jax.random.fold_in(key, next(counter))
        std = (2.0 / (kh * kh * cin)) ** 0.5
        return {"kernel": std * jax.random.normal(
            k, (kh, kh, cin, cout), jnp.float32)}

    def bn(c, scale=1.0):
        return ({"scale": jnp.full((c,), scale, jnp.float32),
                 "bias": jnp.zeros((c,), jnp.float32)},
                {"mean": jnp.zeros((c,), jnp.float32),
                 "var": jnp.ones((c,), jnp.float32)})

    params, stats = {}, {}
    f0 = cfg["num_filters"]
    params["conv_init"] = conv(7, 3, f0)
    params["bn_init"], stats["bn_init"] = bn(f0)
    for name, cin, f, stride in _block_specs(cfg):
        p, s = {}, {}
        p["conv1"] = conv(1, cin, f)
        p["bn1"], s["bn1"] = bn(f)
        p["conv2"] = conv(3, f, f)
        p["bn2"], s["bn2"] = bn(f)
        p["conv3"] = conv(1, f, 4 * f)
        p["bn3"], s["bn3"] = bn(4 * f, 0.3)
        if cin != 4 * f or stride != 1:
            p["conv_proj"] = conv(1, cin, 4 * f)
            p["bn_proj"], s["bn_proj"] = bn(4 * f)
        params[name], stats[name] = p, s
    k = jax.random.fold_in(key, next(counter))
    feat = 4 * f0 * 2 ** (len(STAGES) - 1)
    params["head"] = {
        "kernel": 0.01 * jax.random.normal(
            k, (feat, cfg["num_classes"]), jnp.float32),
        "bias": jnp.zeros((cfg["num_classes"],), jnp.float32),
    }
    return {"params": params, "batch_stats": stats}


def _round_mantissa(x, bits: int):
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


def _product(f, a, b, precision: str):
    if precision == "f32":
        return f(a, b)
    return _low_product(f, *LOW_BITS[precision])(a, b)


def _conv(x, w, stride, precision):
    f = functools.partial(
        jax.lax.conv_general_dilated, window_strides=(stride, stride),
        padding="SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=jax.lax.Precision.HIGHEST,
    )
    return _product(f, x, w["kernel"], precision)


def _bn(x, p, s):
    mean = jnp.mean(x, axis=(0, 1, 2))
    var = jnp.mean(jnp.square(x), axis=(0, 1, 2)) - jnp.square(mean)
    y = (x - mean) * jax.lax.rsqrt(var + BN_EPS) * p["scale"] + p["bias"]
    new = {"mean": BN_MOMENTUM * s["mean"] + (1 - BN_MOMENTUM) * mean,
           "var": BN_MOMENTUM * s["var"] + (1 - BN_MOMENTUM) * var}
    return y, new


def _bottleneck(x, p, s, stride, precision):
    new = {}
    y = _conv(x, p["conv1"], 1, precision)
    y, new["bn1"] = _bn(y, p["bn1"], s["bn1"])
    y = _conv(jax.nn.relu(y), p["conv2"], stride, precision)
    y, new["bn2"] = _bn(y, p["bn2"], s["bn2"])
    y = _conv(jax.nn.relu(y), p["conv3"], 1, precision)
    y, new["bn3"] = _bn(y, p["bn3"], s["bn3"])
    if "conv_proj" in p:
        x = _conv(x, p["conv_proj"], stride, precision)
        x, new["bn_proj"] = _bn(x, p["bn_proj"], s["bn_proj"])
    return jax.nn.relu(y + x), new


def forward(params, stats, images, cfg, precision="f32"):
    """Training-mode forward: ``(logits, new_batch_stats)``."""
    new = {}
    x = _conv(normalise(images), params["conv_init"], 2, precision)
    x, new["bn_init"] = _bn(x, params["bn_init"], stats["bn_init"])
    x = jax.nn.relu(x)
    x = jax.lax.reduce_window(
        x, -jnp.inf, jax.lax.max, (1, 3, 3, 1), (1, 2, 2, 1), "SAME"
    )
    for name, _, _, stride in _block_specs(cfg):
        block = jax.checkpoint(
            functools.partial(_bottleneck, stride=stride, precision=precision)
        )
        x, new[name] = block(x, params[name], stats[name])
    x = jnp.mean(x, axis=(1, 2))
    head = params["head"]
    dense = functools.partial(jnp.matmul, precision=jax.lax.Precision.HIGHEST)
    logits = _product(dense, x, head["kernel"], precision) + head["bias"]
    return logits, new


def loss(params, stats, batch, cfg, precision="f32"):
    images, labels = batch
    logits, new = forward(params, stats, images, cfg, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[:, None], axis=-1)[:, 0]
    return jnp.mean(lse - picked), new


def leaf_norms(tree) -> dict:
    """L2 norm of every leaf, by its slash-joined path."""
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {"/".join(str(k.key) for k in path): jnp.sqrt(jnp.sum(jnp.square(x)))
            for path, x in flat}


def train_readings(cfg: dict, key, batches, *, grad_state_after: int,
                   optimizer: dict, rows_per_block: int | None = None,
                   precision: str = "f32", shard=None) -> dict:
    """Follow ``len(batches)`` SGD-with-momentum updates from the seeded
    weights: each step's loss, the per-leaf norms of the momentum trace
    after ``grad_state_after`` steps (after one step it is the first
    gradient) and of the change of parameters and running statistics
    after all of them. The batch is never split: its statistics are the
    layer's arithmetic. Blocks are recomputed in the backward pass so
    that the float32 activations fit."""
    del rows_per_block, shard
    lr, momentum = optimizer["learning_rate"], optimizer["momentum"]
    w0 = jax.jit(functools.partial(make_weights, cfg))(key)

    @jax.jit
    def update(params, stats, trace, batch):
        (value, new_stats), g = jax.value_and_grad(loss, has_aux=True)(
            params, stats, batch, cfg, precision
        )
        trace = jax.tree_util.tree_map(lambda t, x: x + momentum * t, trace, g)
        params = jax.tree_util.tree_map(lambda p, t: p - lr * t, params, trace)
        return params, new_stats, trace, value

    params, stats = w0["params"], w0["batch_stats"]
    trace = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad_state = [], None
    for step, (x, y) in enumerate(batches, 1):
        params, stats, trace, value = update(
            params, stats, trace, (jnp.asarray(x), jnp.asarray(y))
        )
        losses.append(float(value))
        if step == grad_state_after:
            grad_state = jax.device_get(jax.jit(leaf_norms)({"params": trace}))
    delta = jax.jit(
        lambda a, b: leaf_norms(
            jax.tree_util.tree_map(lambda p, q: p - q, a, b)
        )
    )({"params": params, "batch_stats": stats}, w0)
    return {"losses": losses, "grad_state_norms": grad_state,
            "delta_norms": jax.device_get(delta)}
