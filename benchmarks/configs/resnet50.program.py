"""The ResNet-50 configuration as the program runs it: the model through
``fluxmpi_tpu.models.ResNet50`` (bf16 compute, f32 parameters and batch
statistics), softmax cross-entropy, ``optax.sgd`` with momentum. The
reference's weight layout carries the program's own layer names, so the
two maps are the identity.
"""

from __future__ import annotations

import jax.numpy as jnp

PIXEL_MEAN, PIXEL_STD = 0.45, 0.225


def build_model(cfg: dict, attention: str | None = None):
    del attention
    from fluxmpi_tpu.models import ResNet50

    return ResNet50(num_classes=cfg["num_classes"],
                    num_filters=cfg["num_filters"],
                    dtype=jnp.dtype(cfg["compute_dtype"]))


def make_loss(model):
    import optax

    def loss_fn(params, batch_stats, batch):
        images, labels = batch
        x = (images.astype(jnp.float32) * (1.0 / 255.0) - PIXEL_MEAN) / PIXEL_STD
        logits, new = model.apply(
            {"params": params, "batch_stats": batch_stats}, x, train=True,
            mutable=["batch_stats"],
        )
        loss = optax.softmax_cross_entropy_with_integer_labels(
            logits.astype(jnp.float32), labels
        ).mean()
        return loss, new["batch_stats"]

    return loss_fn


def make_optimizer(opt: dict):
    import optax

    return optax.sgd(**opt)


def grad_state(opt_state):
    """The momentum trace: after one step it is the first gradient."""
    return opt_state[0].trace


def to_program(w: dict, cfg: dict):
    del cfg
    return w["params"], w["batch_stats"]


def from_program(variables, model_state, cfg: dict) -> dict:
    del cfg
    out = {"params": variables}
    if model_state is not None:
        out["batch_stats"] = model_state
    return out


def make_dataset(cfg: dict, data: dict, seed: int):
    """Seeded uint8 images and labels, held in host memory."""
    import numpy as np

    rng = np.random.default_rng(seed)
    side = cfg["image_size"]
    images = rng.integers(0, 256, size=(data["rows"], side, side, 3),
                          dtype=np.uint8)
    labels = rng.integers(0, cfg["num_classes"], size=(data["rows"],),
                          dtype=np.int32)
    return images, labels


def items_per_row(data: dict) -> int:
    del data
    return 1
