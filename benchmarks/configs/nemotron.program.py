"""The Nemotron-H (``nemotron_h``) configurations as the program runs
them: the model through ``fluxmpi_tpu.models.DecoderLM`` built from the
configuration's own keys, and the map from the reference's weight layout
to the program's parameter tree (renaming only: the numbers are the
benchmark's own, from the seed, bfloat16 on both sides; the expert bias
the program's router adds to its choice is zero, as the seed leaves it).
"""

from __future__ import annotations

import jax.numpy as jnp

# At import: a program without this model refuses the cell before it
# makes a weight.
from fluxmpi_tpu.models import DecoderConfig, DecoderLM
from fluxmpi_tpu.models.decoder import ReluSquaredMLP  # noqa: F401


def build_model(cfg: dict, attention: str = "flash"):
    return DecoderLM(
        config=DecoderConfig.from_hf(cfg),
        dtype=jnp.dtype(cfg["compute_dtype"]), attention=attention,
    )


MIXERS = {
    "M": ("mamba", ("w_in", "conv_w", "conv_b", "dt_bias", "a_log",
                    "d_skip", "norm", "w_out")),
    "*": ("attn", ("wq", "wk", "wv", "wo")),
}


def to_program(w: dict, cfg: dict):
    """Reference layout -> ``(variables, model_state)`` of the program."""
    params = {"embed": w["embed"], "norm_out": {"scale": w["norm_out"]},
              "head": w["head"]}
    for i, (kind, lay) in enumerate(
            zip(cfg["hybrid_override_pattern"], w["layers"])):
        layer = {"norm_in": {"scale": lay["norm_in"]}}
        if kind == "E":
            layer["moe"] = {
                "router": lay["router"],
                "bias": jnp.zeros((lay["router"].shape[1],), jnp.float32),
                "w_up": lay["e_up"], "w_down": lay["e_down"],
                "shared": {"w_up": lay["s_up"], "w_down": lay["s_down"]},
            }
        else:
            name, leaves = MIXERS[kind]
            layer[name] = {leaf: lay[leaf] for leaf in leaves}
        params[f"layer_{i}"] = layer
    return {"params": params}, None
