"""The Falcon-H1 (``falcon_h1``) configurations as the program runs them:
the model through ``fluxmpi_tpu.models.DecoderLM`` built from the
configuration's own keys, and the map from the reference's weight layout
to the program's parameter tree (renaming only: the numbers are the
benchmark's own, from the seed, bfloat16 on both sides; every multiplier
is applied by the program's forward, none is folded into a weight).
"""

from __future__ import annotations

import jax.numpy as jnp

# At import: a program without this block (two mixers side by side in
# one layer) refuses the cell before it makes a weight, and never builds
# some other model from the keys it happens to know.
from fluxmpi_tpu.models import DecoderConfig, DecoderLM
from fluxmpi_tpu.models.decoder import PARALLEL


def build_model(cfg: dict, attention: str = "flash"):
    config = DecoderConfig.from_hf(cfg)
    assert set(config.layer_types) == {PARALLEL}
    return DecoderLM(
        config=config, dtype=jnp.dtype(cfg["compute_dtype"]),
        attention=attention,
    )


MAMBA = ("w_in", "conv_w", "conv_b", "dt_bias", "a_log", "d_skip", "norm",
         "w_out")
ATTENTION = ("wq", "wk", "wv", "wo")
MLP = ("w1", "w3", "w2")
NORMS = ("norm_in", "norm_pre_ff")


def to_program(w: dict, cfg: dict):
    """Reference layout -> ``(variables, model_state)`` of the program."""
    params = {"embed": w["embed"], "head": w["head"],
              "norm_out": {"scale": w["norm_out"]}}
    for i, lay in enumerate(w["layers"]):
        layer = {norm: {"scale": lay[norm]} for norm in NORMS}
        layer["mamba"] = {leaf: lay[leaf] for leaf in MAMBA}
        layer["attn"] = {leaf: lay[leaf] for leaf in ATTENTION}
        layer["mlp"] = {leaf: lay[leaf] for leaf in MLP}
        params[f"layer_{i}"] = layer
    return {"params": params}, None
