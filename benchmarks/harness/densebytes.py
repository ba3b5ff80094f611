"""Bytes of the matrices that a decode tick of a DENSE model with two
mixers a layer (``falcon_h1``: a Mamba-2 mixer beside attention, then a
SwiGLU MLP) HAS to read, from the shapes of the configuration alone
(never from what the compiled program did move): every projection and
MLP matrix of every layer and the untied head, each read once a tick
whatever the batch. The embedding's gathered rows, the norms, the
convolution and the per-head scalars are not counted (under 0.1%), nor
are the sequences' states and K/V, which ``ssmbytes.py`` and
``moebytes.py`` count: the share reads a little low, never high."""

from __future__ import annotations

from harness.moebytes import BYTES


def attention_params(cfg: dict) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kvh = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d * (heads + 2 * kvh) * hd + heads * hd * d


def mamba_params(cfg: dict) -> int:
    """The input and output projections: ``[z; x; B; C; dt]`` columns."""
    d = cfg["hidden_size"]
    inner = cfg["mamba_n_heads"] * cfg["mamba_d_head"]
    state = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return d * (2 * inner + 2 * state + cfg["mamba_n_heads"]) + inner * d


def mlp_params(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def head_params(cfg: dict) -> int:
    return cfg["hidden_size"] * cfg["vocab_size"]


def tick_weight_bytes(cfg: dict) -> int:
    """Every matrix a tick reads once: the layers' and the head."""
    layer = attention_params(cfg) + mamba_params(cfg) + mlp_params(cfg)
    return (cfg["num_hidden_layers"] * layer + head_params(cfg)) * BYTES[
        cfg["param_dtype"]]
