"""Bytes that a decode tick of a model with state-space (Mamba-2) layers
HAS to move in its state update, from the shapes of the configuration
alone (never from what the compiled program did move). A sequence keeps,
a Mamba layer, ONE recurrent state of ``mamba_n_heads x mamba_d_head x
mamba_d_state`` numbers whatever its length; a tick reads each LIVE
sequence's state once and writes it once. The update's small operands (a
token's ``x``, ``B``, ``C``, step and decay, and ``y`` back: under 1% of
a state) and the convolution's tail, which XLA writes beside the kernel,
are not counted: the share reads a little low, never high. Kept apart
from ``moebytes.py``, which counts the experts and the caches of K and V."""

from __future__ import annotations

from harness.moebytes import BYTES


def mamba_layers(cfg: dict) -> int:
    return sum(1 for kind in cfg["layer_types"] if kind == "mamba")


def state_bytes(cfg: dict) -> int:
    """One sequence's recurrent state of one layer."""
    return (cfg["mamba_n_heads"] * cfg["mamba_d_head"] * cfg["mamba_d_state"]
            * BYTES[cfg["state_dtype"]])


def state_update_bytes(cfg: dict, live_states: float) -> float:
    """One tick's state updates over ``live_states`` live sequences,
    every Mamba layer: each state read and written once."""
    return live_states * mamba_layers(cfg) * 2.0 * state_bytes(cfg)
