"""The Sarvam (``sarvam_mla``) configurations as the program runs them: the
model through ``fluxmpi_tpu.models.DecoderLM`` built from the
configuration's own keys, and the map from the reference's weight layout
to the program's parameter tree (renaming only: the numbers are the
benchmark's own, from the seed, bfloat16 on both sides).
"""

from __future__ import annotations

import jax.numpy as jnp

# At import: a program without this model refuses the cell before it
# makes a weight.
from fluxmpi_tpu.models import DecoderConfig, DecoderLM
from fluxmpi_tpu.models.decoder import LatentAttention  # noqa: F401


def build_model(cfg: dict, attention: str = "flash"):
    return DecoderLM(
        config=DecoderConfig.from_hf(cfg),
        dtype=jnp.dtype(cfg["compute_dtype"]), attention=attention,
    )


ATTENTION = ("wq", "wkva", "kv_norm", "wkvb", "wo")
NORMS = ("norm_in", "norm_pre_ff")


def to_program(w: dict, cfg: dict):
    """Reference layout -> ``(variables, model_state)`` of the program."""
    params = {"embed": w["embed"], "head": w["head"],
              "norm_out": {"scale": w["norm_out"]}}
    for i, lay in enumerate(w["layers"]):
        mlp = {"w1": lay["w1"], "w3": lay["w3"], "w2": lay["w2"]}
        layer = {name: {"scale": lay[name]} for name in NORMS}
        layer["attn"] = {name: lay[name] for name in ATTENTION}
        if i < cfg["first_k_dense_replace"]:
            layer["mlp"] = mlp
        else:
            layer["moe"] = {
                "router": lay["router"], "bias": lay["bias"],
                "w1": lay["ew1"], "w3": lay["ew3"], "w2": lay["ew2"],
                "shared": mlp,
            }
        params[f"layer_{i}"] = layer
    return {"params": params}, None
