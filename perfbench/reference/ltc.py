"""Plain float32 reference of the Liquid Time-Constant encoder (Hasani et al.).

Each input sample takes ``ltc_substeps`` fused semi-implicit Euler substeps
of ``dh/dt = -[1/tau + f] h + f A`` with ``f = sigmoid(W x + U h + b)``:
``h <- (h + dt' f A) / (1 + dt' (1/tau + f))``, ``dt' = dt / substeps``.
Initialisation is the documented recipe of the recovery service: input and
recurrent weights drawn normal and scaled by ``1 / sqrt`` of their fan-in,
``A`` drawn normal times 0.5, zero bias, ``1/tau`` at 0.5.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def init(key, d_in: int, hidden: int) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_in": (jax.random.normal(k1, (d_in, hidden)) * (1.0 / jnp.sqrt(d_in))).astype(
            jnp.float32
        ),
        "w_rec": (jax.random.normal(k2, (hidden, hidden)) * (1.0 / jnp.sqrt(hidden))).astype(
            jnp.float32
        ),
        "bias": jnp.zeros((hidden,), jnp.float32),
        "a": (jax.random.normal(k3, (hidden,)) * 0.5).astype(jnp.float32),
        "inv_tau": jnp.ones((hidden,), jnp.float32) * 0.5,
    }


def encode(p: dict, xs, mm, cfg: dict):
    """xs [B, T, d_in] -> final hidden state [B, hidden]."""
    n_sub = cfg["ltc_substeps"]
    sub_dt = cfg["dt"] / n_sub

    def step(h, x):
        drive = mm(x, p["w_in"]) + p["bias"]
        for _ in range(n_sub):
            f = jax.nn.sigmoid(drive + mm(h, p["w_rec"]))
            h = (h + sub_dt * f * p["a"]) / (1.0 + sub_dt * (p["inv_tau"] + f))
        return h, None

    h0 = jnp.zeros((xs.shape[0], cfg["hidden"]), jnp.float32)
    h, _ = jax.lax.scan(step, h0, jnp.swapaxes(xs, 0, 1))
    return h


def flops(cfg: dict) -> float:
    """Matrix-product operations of one window's scan."""
    D, H = cfg["state_dim"] + cfg["input_dim"], cfg["hidden"]
    return cfg["window"] * 2.0 * (D * H + cfg["ltc_substeps"] * H * H)


def weights(cfg: dict) -> int:
    D, H = cfg["state_dim"] + cfg["input_dim"], cfg["hidden"]
    return D * H + H * H + 3 * H
