"""Operations and bytes that the algorithm needs, from the shapes of the
configuration alone (never from XLA's cost analysis, which counts what
the compiled program does: recomputation in, custom calls out).
A multiply-add counts as 2 operations."""

from __future__ import annotations


def lm_forward_flops_per_token(cfg: dict, seq_len: int) -> float:
    """GPT-2 forward for one token of a ``seq_len`` sequence: the
    projections, the MLP, causal attention (each query meets on average
    ``(seq_len + 1) / 2`` keys: the causal half, not the square) and the
    tied head."""
    d, ff, layers = cfg["n_embd"], cfg["n_inner"], cfg["n_layer"]
    projections = 2 * 4 * d * d
    mlp = 2 * 2 * d * ff
    attention = 2 * 2 * d * (seq_len + 1) / 2
    head = 2 * d * cfg["vocab_size"]
    return layers * (projections + mlp + attention) + head


def lm_train_flops_per_item(cfg: dict, data: dict) -> float:
    """Forward plus backward (twice the forward) per token trained on;
    recomputation is not counted."""
    return 3.0 * lm_forward_flops_per_token(cfg, data["seq_len"])


def resnet50_forward_flops_per_image(cfg: dict) -> float:
    """ResNet-50 v1.5 forward at ``image_size``: every convolution's
    ``2 * H_out * W_out * k * k * C_in * C_out`` and the dense head."""
    width, side = cfg["num_filters"], cfg["image_size"]

    def conv(side_out, k, cin, cout):
        return 2.0 * side_out * side_out * k * k * cin * cout

    side = -(-side // 2)
    total = conv(side, 7, 3, width)
    side = -(-side // 2)  # the 3x3/2 max-pool
    cin = width
    for i, count in enumerate((3, 4, 6, 3)):
        f = width * 2 ** i
        for j in range(count):
            stride = 2 if i > 0 and j == 0 else 1
            out = -(-side // stride)
            total += conv(side, 1, cin, f)       # conv1 at the input size
            total += conv(out, 3, f, f)          # conv2 carries the stride
            total += conv(out, 1, f, 4 * f)      # conv3
            if cin != 4 * f or stride != 1:
                total += conv(out, 1, cin, 4 * f)  # projection shortcut
            side, cin = out, 4 * f
    return total + 2.0 * cin * cfg["num_classes"]


def resnet50_train_flops_per_item(cfg: dict, data: dict) -> float:
    del data
    return 3.0 * resnet50_forward_flops_per_image(cfg)


def flash_kernel_cost(kind: str, *, rows: int, heads: int, seq_len: int,
                      head_dim: int, itemsize: int = 2) -> dict:
    """One call of a causal flash-attention kernel over ``rows``
    sequences: the matrix products the algorithm needs over the causal
    half (``seq_len * (seq_len + 1) / 2`` query-key pairs) and each
    operand crossing HBM once. ``fwd`` has 2 products (QK^T, PV); ``dq``
    3 (QK^T again, dO V^T, dS K); ``dkv`` 4 (QK^T, dO V^T, P^T dO,
    dS^T Q)."""
    products = {"fwd": 2, "dq": 3, "dkv": 4}[kind]
    pairs = seq_len * (seq_len + 1) / 2
    flops = rows * heads * products * 2.0 * pairs * head_dim
    tensors = {"fwd": 4, "dq": 6, "dkv": 7}[kind]  # q k v o | +do dq | +do dk dv
    bytes_ = rows * heads * tensors * seq_len * head_dim * itemsize
    return {"flops": flops, "bytes": float(bytes_)}


def roofline_seconds(cost: dict, peaks: dict) -> tuple[float, str]:
    """The least time the chip could take for ``cost`` and which of the
    two peaks bounds it."""
    compute = cost["flops"] / peaks["flops_bf16"]
    memory = cost["bytes"] / peaks["hbm_bytes_per_s"]
    return (compute, "compute") if compute >= memory else (memory, "memory")
