"""Bytes and operations that a decode tick of a model with latent
attention HAS to spend in its paged latent decode kernel, from the shapes
of the configuration alone (never from what the compiled program did).
A latent layer keeps ONE row a token, ``[c; k_r]`` (``kv_lora_rank +
qk_rope_head_dim``), read once as key (all of it) and as value (``c``).
A multiply-add counts as 2 operations. Kept apart from ``moebytes.py``,
which counts the caches of K and V."""

from __future__ import annotations

from harness.moebytes import BYTES


def latent_row_bytes(cfg: dict) -> int:
    """What one token leaves in one layer's cache (as the algorithm
    counts it: the pool's padding to whole lane tiles is the program's)."""
    return ((cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"])
            * BYTES[cfg["compute_dtype"]])


def latent_block_bytes(cfg: dict, block_size: int) -> int:
    """One block of one layer."""
    return block_size * latent_row_bytes(cfg)


def latent_tabled_blocks(cfg: dict, engine: dict) -> int:
    """Layer-blocks the slots' tables span (the engine's
    ``kv_blocks_tabled`` a tick): every layer ``max_len / block_size``
    blocks a slot."""
    return (engine["slots"] * cfg["num_hidden_layers"]
            * (engine["max_len"] // engine["block_size"]))


def latent_decode_flops(cfg: dict, context_tokens: float) -> float:
    """One tick's absorbed attention over ``context_tokens`` cached
    positions (the live slots' lengths summed), every layer: each head's
    query meets a row as key (``rank + rope`` lanes) and as value
    (``rank``)."""
    rank, rope = cfg["kv_lora_rank"], cfg["qk_rope_head_dim"]
    return (cfg["num_hidden_layers"] * context_tokens
            * cfg["num_attention_heads"] * (rank + rope + rank) * 2.0)
