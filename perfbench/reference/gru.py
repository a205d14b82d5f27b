"""Plain float32 reference of the standard GRU encoder (MERINDA paper Eq. 12-15).

Gate weights are one ``[d_in + hidden, 3 * hidden]`` matrix, columns ordered
reset, update, candidate; the candidate sees ``r * h``. Initialisation is the
documented recipe of the recovery service: a normal draw scaled by
``1 / sqrt(d_in + hidden)``, zero biases, and a zero time-gate vector that
the standard cell never reads.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, d_in: int, hidden: int) -> dict:
    k1, _ = jax.random.split(key)
    scale = 1.0 / jnp.sqrt(d_in + hidden)
    return {
        "w": (jax.random.normal(k1, (d_in + hidden, 3 * hidden)) * scale).astype(jnp.float32),
        "b": jnp.zeros((3 * hidden,), jnp.float32),
        "time_scale": jnp.zeros((hidden,), jnp.float32),
    }


def encode(p: dict, xs, mm, cfg: dict):
    """xs [B, T, d_in] -> final hidden state [B, hidden]."""
    d_in, hidden = xs.shape[-1], cfg["hidden"]
    wx, wh, b = p["w"][:d_in], p["w"][d_in:], p["b"]

    def step(h, x):
        gx = mm(x, wx)
        gh = mm(h, wh[:, : 2 * hidden])
        r = jax.nn.sigmoid(gx[:, :hidden] + gh[:, :hidden] + b[:hidden])
        z = jax.nn.sigmoid(gx[:, hidden : 2 * hidden] + gh[:, hidden:] + b[hidden : 2 * hidden])
        c = jnp.tanh(gx[:, 2 * hidden :] + mm(r * h, wh[:, 2 * hidden :]) + b[2 * hidden :])
        return (1.0 - z) * c + z * h, None

    h0 = jnp.zeros((xs.shape[0], hidden), jnp.float32)
    h, _ = jax.lax.scan(step, h0, jnp.swapaxes(xs, 0, 1))
    return h


def flops(cfg: dict) -> float:
    """Matrix-product operations of one window's scan."""
    D, H = cfg["state_dim"] + cfg["input_dim"], cfg["hidden"]
    return cfg["window"] * 2.0 * (D * 3 * H + H * 3 * H)


def weights(cfg: dict) -> int:
    D, H = cfg["state_dim"] + cfg["input_dim"], cfg["hidden"]
    return (D + H) * 3 * H + 4 * H
