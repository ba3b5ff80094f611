"""The Granite 4.0-H (``granitemoehybrid``) configurations as the program
runs them: the model through ``fluxmpi_tpu.models.DecoderLM`` built from
the configuration's own keys, and the map from the reference's weight
layout to the program's parameter tree (renaming only: the numbers are
the benchmark's own, from the seed, bfloat16 on both sides; the expert
bias the program's router adds is zero, the model has none).
"""

from __future__ import annotations

import jax.numpy as jnp

# At import: a program without this model refuses the cell before it
# makes a weight.
from fluxmpi_tpu.models import DecoderConfig, DecoderLM
from fluxmpi_tpu.models.decoder import MambaMixer  # noqa: F401


def build_model(cfg: dict, attention: str = "flash"):
    return DecoderLM(
        config=DecoderConfig.from_hf(cfg),
        dtype=jnp.dtype(cfg["compute_dtype"]), attention=attention,
    )


MIXERS = {
    "mamba": ("mamba", ("w_in", "conv_w", "conv_b", "dt_bias", "a_log",
                        "d_skip", "norm", "w_out")),
    "attention": ("attn", ("wq", "wk", "wv", "wo")),
}
NORMS = ("norm_in", "norm_pre_ff")


def to_program(w: dict, cfg: dict):
    """Reference layout -> ``(variables, model_state)`` of the program."""
    params = {"embed": w["embed"], "norm_out": {"scale": w["norm_out"]}}
    for i, (kind, lay) in enumerate(zip(cfg["layer_types"], w["layers"])):
        name, leaves = MIXERS[kind]
        layer = {norm: {"scale": lay[norm]} for norm in NORMS}
        layer[name] = {leaf: lay[leaf] for leaf in leaves}
        layer["moe"] = {
            "router": lay["router"],
            "bias": jnp.zeros((lay["router"].shape[1],), jnp.float32),
            "w1": lay["ew1"], "w3": lay["ew3"], "w2": lay["ew2"],
            "shared": {"w1": lay["w1"], "w3": lay["w3"], "w2": lay["w2"]},
        }
        params[f"layer_{i}"] = layer
    return {"params": params}, None
