"""Bytes that a decode tick of a model with routed experts and a paged
K/V cache HAS to move, from the shapes of the configuration alone (never
from what the compiled program did move). Kept apart from
``opsbytes.py``, which counts the dense models."""

from __future__ import annotations


BYTES = {"bfloat16": 2, "float32": 4}


def expert_bytes(cfg: dict) -> int:
    """One routed expert's three matrices (SwiGLU: gate, up, down)."""
    return (3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
            * BYTES[cfg["param_dtype"]])


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["num_dense_layers"]


def touched_expert_bytes(cfg: dict, touched_pct: float) -> float:
    """A tick's routed-expert weights: the (layer, expert) cells that
    received a token (``touched_pct`` of all), each read once."""
    cells = expert_layers(cfg) * cfg["num_experts"]
    return touched_pct / 100.0 * cells * expert_bytes(cfg)


def kv_block_bytes(cfg: dict, block_size: int) -> int:
    """One block of one layer, keys and values."""
    width = cfg["num_key_value_heads"] * cfg["head_dim"]
    return 2 * block_size * width * BYTES[cfg["compute_dtype"]]


def kv_tabled_blocks(cfg: dict, engine: dict) -> int:
    """Layer-blocks the slots' tables span (the engine's
    ``kv_blocks_tabled`` a tick): a full layer ``max_len / block_size``
    blocks a slot, a window layer its ring of ``ceil((window +
    block_size) / block_size)``."""
    block = engine["block_size"]
    full = engine["max_len"] // block
    window = cfg.get("sliding_window")
    ring = min(full, -(-(window + block) // block)) if window else full
    per_slot = sum(
        ring if kind == "sliding_attention" else full
        for kind in cfg["layer_types"]
    )
    return engine["slots"] * per_slot
