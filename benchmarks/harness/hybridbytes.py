"""Bytes that a decode tick of a model whose layers are ONE sublayer each
(``hybrid_override_pattern``: ``M`` Mamba-2, ``E`` routed experts, ``*``
attention) HAS to move in its un-gated (relu2) routed experts, from the
shapes of the configuration alone (never from what the compiled program
did move). An expert is TWO matrices, ``[hidden_size,
moe_intermediate_size]`` up and back, at the PUBLISHED width: whatever
padding the program holds them in is the program's cost and shows as a
lower share, never as more bytes. Only the layers the pattern marks ``E``
have experts. Kept apart from ``moebytes.py``, which counts gated experts
(three matrices) in every layer past the leading dense ones."""

from __future__ import annotations

from harness.moebytes import BYTES


def expert_layers(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"].count("E")


def expert_bytes(cfg: dict) -> int:
    """One routed expert's two matrices (un-gated: up, down)."""
    return (2 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * BYTES[cfg["param_dtype"]])


def touched_expert_bytes(cfg: dict, touched_pct: float) -> float:
    """A tick's routed-expert weights: the (layer, held expert) cells that
    received a token (``touched_pct`` of all), each read once."""
    cells = expert_layers(cfg) * cfg["num_experts"]
    return touched_pct / 100.0 * cells * expert_bytes(cfg)
