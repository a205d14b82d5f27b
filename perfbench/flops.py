"""Operations and bytes the recovery work needs, counted from shapes.

Counts are of what the algorithm requires, whatever implements it: a matrix
product of ``[a, b] @ [b, c]`` is ``2abc`` operations; elementwise work is not
counted. Recomputation does not count: a training step is the forward, plus
the backward at twice the forward's products, for the encoder, the head and
the library reconstruction.
"""

from __future__ import annotations

import json
import math
from pathlib import Path

HERE = Path(__file__).resolve().parent


def peaks(device_kind: str) -> dict:
    table = json.loads((HERE / "peaks.json").read_text())
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in peaks.json ({sorted(table)})")
    return table[device_kind]


def n_terms(cfg: dict) -> int:
    return math.comb(cfg["order"] + cfg["state_dim"] + cfg["input_dim"], cfg["order"])


def n_windows(cfg: dict) -> int:
    return (cfg["buf_len"] - cfg["window"]) // cfg["stride"] + 1


def encoder_flops(cfg: dict) -> float:
    """One window's encoder scan, counted by the encoder's reference file."""
    return _encoder(cfg).flops(cfg)


def _encoder(cfg: dict):
    from reference.mr import encoder_module

    return encoder_module(cfg["encoder"])


def head_flops(cfg: dict) -> float:
    K = n_terms(cfg) * cfg["state_dim"]
    return 2.0 * (cfg["hidden"] * cfg["dense_hidden"] + cfg["dense_hidden"] * K)


def forward_flops(cfg: dict) -> float:
    """One window through encoder and head."""
    return encoder_flops(cfg) + head_flops(cfg)


def recon_flops(cfg: dict) -> float:
    """One window's RK4 reconstruction: 4 library products per step."""
    return (cfg["window"] - 1) * 4 * 2.0 * n_terms(cfg) * cfg["state_dim"]


def weight_count(cfg: dict) -> int:
    H, Dh = cfg["hidden"], cfg["dense_hidden"]
    K = n_terms(cfg) * cfg["state_dim"]
    return _encoder(cfg).weights(cfg) + H * Dh + Dh + Dh * K + K


def forward_bytes(cfg: dict) -> float:
    """One slot's forward over its windows: inputs, weights, coefficients, float32."""
    B, T = n_windows(cfg), cfg["window"]
    D, K = cfg["state_dim"] + cfg["input_dim"], n_terms(cfg) * cfg["state_dim"]
    return 4.0 * (B * T * D + weight_count(cfg) + B * K)


def tick_flops(cfg: dict, steps_per_tick: int, slots: int) -> float:
    """A whole tick: K training steps per slot, then the readout forward."""
    B = n_windows(cfg)
    train = 3.0 * (forward_flops(cfg) + recon_flops(cfg)) * B * steps_per_tick
    return slots * (train + B * forward_flops(cfg))


def kernel_work(cfg: dict, steps_per_tick: int, slots: int) -> tuple[float, float]:
    """(operations, bytes) of a tick's recovery forwards: one per training step
    and one readout, per slot."""
    calls = slots * (steps_per_tick + 1)
    return calls * n_windows(cfg) * forward_flops(cfg), calls * forward_bytes(cfg)
