"""Benchmark dynamical systems (paper §6.1 case studies).

Simulation case studies (paper: Matlab + ODE45) are regenerated here with our
RK4 integrator at a fine internal step, then subsampled — numerically
equivalent at the reported tolerances for these smooth systems.

- lorenz:         chaotic Lorenz-63 (sigma, rho, beta)
- f8:             F-8 Crusader aircraft short-period model (cubic, from
                  Kaiser/Kutz/Brunton SINDY-MPC paper, ref [18])
- lotka_volterra: 2-species predator-prey (Hudson Bay lynx/hare regime)
- pathogen:       pathogenic attack / immune response model (ref [18])
- aid:            Bergman minimal model of glucose-insulin dynamics — stands
                  in for the OhioT1D dataset (not redistributable), same
                  dimensionality and 5-min CGM sampling.
- damped_oscillator:  linear 2-state damped harmonic oscillator.
- controlled_pendulum: small-angle pendulum with sinusoidal torque input
                  (SINDYc-style exogenous drive) — pairs with
                  core/engine.recover_many's multi-system batches.

Each system carries its ground-truth sparse coefficient matrix in the
polynomial library basis so recovery error is measured exactly
(MSE(theta_est, theta_true) — paper Table 6 metric).
"""

from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.library import n_library_terms, term_names
from repro.core.ode import odeint


@dataclasses.dataclass(frozen=True)
class SystemSpec:
    name: str
    state_dim: int
    input_dim: int
    order: int  # minimal library order that contains the true dynamics
    dynamics: Callable  # f(y, u, t, args) -> dy/dt
    y0: tuple
    dt: float
    t_end: float
    input_fn: Callable | None = None  # u(t) exogenous drive
    true_coef: Callable | None = None  # () -> [n_terms, n] ground truth


# --- Lorenz-63 --------------------------------------------------------------
def _lorenz(y, u, t, args):
    sigma, rho, beta = 10.0, 28.0, 8.0 / 3.0
    x, yv, z = y[..., 0], y[..., 1], y[..., 2]
    return jnp.stack([sigma * (yv - x), x * (rho - z) - yv, x * yv - beta * z], axis=-1)


def _lorenz_coef():
    # library over (x, y, z), order 2, graded-lex: [1, x, y, z, x2, xy, xz, y2, yz, z2]
    n_terms = n_library_terms(3, 2)
    c = np.zeros((n_terms, 3))
    names = term_names(3, 2, ["x", "y", "z"])
    ix = {n: i for i, n in enumerate(names)}
    c[ix["x"], 0], c[ix["y"], 0] = -10.0, 10.0
    c[ix["x"], 1], c[ix["y"], 1], c[ix["x*z"], 1] = 28.0, -1.0, -1.0
    c[ix["x*y"], 2], c[ix["z"], 2] = 1.0, -8.0 / 3.0
    return c


# --- F-8 Crusader (cubic short-period model, SINDY-MPC ref [18]) ------------
def _f8(y, u, t, args):
    x1, x2, x3 = y[..., 0], y[..., 1], y[..., 2]
    dx1 = (
        -0.877 * x1
        + x3
        - 0.088 * x1 * x3
        + 0.47 * x1**2
        - 0.019 * x2**2
        - x1**2 * x3
        + 3.846 * x1**3
    )
    dx2 = x3
    dx3 = -4.208 * x1 - 0.396 * x3 - 0.47 * x1**2 - 3.564 * x1**3
    return jnp.stack([dx1, dx2, dx3], axis=-1)


def _f8_coef():
    n_terms = n_library_terms(3, 3)
    c = np.zeros((n_terms, 3))
    names = term_names(3, 3, ["x1", "x2", "x3"])
    ix = {n: i for i, n in enumerate(names)}
    c[ix["x1"], 0], c[ix["x3"], 0] = -0.877, 1.0
    c[ix["x1*x3"], 0], c[ix["x1^2"], 0], c[ix["x2^2"], 0] = -0.088, 0.47, -0.019
    c[ix["x1^2*x3"], 0], c[ix["x1^3"], 0] = -1.0, 3.846
    c[ix["x3"], 1] = 1.0
    c[ix["x1"], 2], c[ix["x3"], 2], c[ix["x1^2"], 2], c[ix["x1^3"], 2] = (
        -4.208,
        -0.396,
        -0.47,
        -3.564,
    )
    return c


# --- Lotka-Volterra (Hudson Bay lynx/hare regime) ---------------------------
_LV = (0.55, 0.028, 0.84, 0.026)  # a, b, c, d (per-year, pelt-count scale)


def _lotka(y, u, t, args):
    a, b, c, d = _LV
    h, l = y[..., 0], y[..., 1]
    return jnp.stack([a * h - b * h * l, -c * l + d * h * l], axis=-1)


def _lotka_coef():
    n_terms = n_library_terms(2, 2)
    c = np.zeros((n_terms, 2))
    names = term_names(2, 2, ["h", "l"])
    ix = {n: i for i, n in enumerate(names)}
    a, b, cc, d = _LV
    c[ix["h"], 0], c[ix["h*l"], 0] = a, -b
    c[ix["l"], 1], c[ix["h*l"], 1] = -cc, d
    return c


# --- Pathogenic attack (innate immune response, ref [18]) -------------------
def _pathogen(y, u, t, args):
    # reduced 2-state pathogen (P) / immune-cell (I) interaction
    p, i = y[..., 0], y[..., 1]
    dp = 1.2 * p - 0.9 * p * i
    di = 0.05 + 0.6 * p * i - 0.8 * i
    return jnp.stack([dp, di], axis=-1)


def _pathogen_coef():
    n_terms = n_library_terms(2, 2)
    c = np.zeros((n_terms, 2))
    names = term_names(2, 2, ["p", "i"])
    ix = {n: i for i, n in enumerate(names)}
    c[ix["p"], 0], c[ix["p*i"], 0] = 1.2, -0.9
    c[ix["1"], 1], c[ix["p*i"], 1], c[ix["i"], 1] = 0.05, 0.6, -0.8
    return c


# --- AID: Bergman minimal model (glucose G, remote insulin X, plasma I) -----
_BERGMAN = dict(p1=0.028, p2=0.025, p3=1.3e-5, n=0.23, gb=4.5, ib=15.0)


def _aid_input(t):
    # insulin bolus schedule + meal disturbance (periodic), per 5-min units
    bolus = 25.0 * (jnp.sin(2 * jnp.pi * t / 60.0) > 0.95)
    return jnp.stack([bolus], axis=-1) if jnp.ndim(t) else jnp.array([bolus])


def _aid(y, u, t, args):
    p = _BERGMAN
    g, x, i = y[..., 0], y[..., 1], y[..., 2]
    u_ins = u[..., 0] if u is not None and u.shape[-1] else 0.0
    dg = -p["p1"] * (g - p["gb"]) - x * g
    dx = -p["p2"] * x + p["p3"] * (i - p["ib"])
    di = -p["n"] * (i - p["ib"]) + u_ins / 12.0
    return jnp.stack([dg, dx, di], axis=-1)


def _aid_coef():
    # library over (g, x, i, u), order 2
    n_terms = n_library_terms(4, 2)
    c = np.zeros((n_terms, 3))
    names = term_names(4, 2, ["g", "x", "i", "u"])
    ix = {n: i for i, n in enumerate(names)}
    p = _BERGMAN
    c[ix["1"], 0], c[ix["g"], 0], c[ix["g*x"], 0] = p["p1"] * p["gb"], -p["p1"], -1.0
    c[ix["x"], 1], c[ix["i"], 1], c[ix["1"], 1] = -p["p2"], p["p3"], -p["p3"] * p["ib"]
    c[ix["i"], 2], c[ix["1"], 2], c[ix["u"], 2] = -p["n"], p["n"] * p["ib"], 1.0 / 12.0
    return c


# --- damped harmonic oscillator (linear 2-state testbed) --------------------
_OSC = (2.0, 0.3)  # omega, damping c


def _damped_osc(y, u, t, args):
    omega, c = _OSC
    x, v = y[..., 0], y[..., 1]
    return jnp.stack([v, -(omega**2) * x - c * v], axis=-1)


def _damped_osc_coef():
    n_terms = n_library_terms(2, 2)
    c = np.zeros((n_terms, 2))
    names = term_names(2, 2, ["x", "v"])
    ix = {n: i for i, n in enumerate(names)}
    omega, cc = _OSC
    c[ix["v"], 0] = 1.0
    c[ix["x"], 1], c[ix["v"], 1] = -(omega**2), -cc
    return c


# --- controlled pendulum (small-angle, sinusoidal torque input) -------------
_PEND = (4.9, 0.35)  # g/l, damping


def _pend_input(t):
    tq = 0.6 * jnp.sin(1.1 * t)
    return jnp.stack([tq], axis=-1) if jnp.ndim(t) else jnp.array([tq])


def _pendulum(y, u, t, args):
    gl, c = _PEND
    th, w = y[..., 0], y[..., 1]
    tq = u[..., 0] if u is not None and u.shape[-1] else 0.0
    return jnp.stack([w, -gl * th - c * w + tq], axis=-1)


def _pendulum_coef():
    # library over (th, w, u), order 2
    n_terms = n_library_terms(3, 2)
    c = np.zeros((n_terms, 2))
    names = term_names(3, 2, ["th", "w", "u"])
    ix = {n: i for i, n in enumerate(names)}
    gl, cc = _PEND
    c[ix["w"], 0] = 1.0
    c[ix["th"], 1], c[ix["w"], 1], c[ix["u"], 1] = -gl, -cc, 1.0
    return c


SYSTEMS: dict[str, SystemSpec] = {
    "lorenz": SystemSpec(
        "lorenz", 3, 0, 2, _lorenz, (-8.0, 7.0, 27.0), 0.01, 10.0, None, _lorenz_coef
    ),
    "f8": SystemSpec("f8", 3, 0, 3, _f8, (0.3, 0.0, 0.2), 0.01, 12.0, None, _f8_coef),
    "lotka_volterra": SystemSpec(
        "lotka_volterra", 2, 0, 2, _lotka, (30.0, 4.0), 0.05, 40.0, None, _lotka_coef
    ),
    "pathogen": SystemSpec(
        "pathogen", 2, 0, 2, _pathogen, (0.5, 0.3), 0.02, 30.0, None, _pathogen_coef
    ),
    "aid": SystemSpec("aid", 3, 1, 2, _aid, (7.0, 0.0, 18.0), 5.0, 1000.0, _aid_input, _aid_coef),
    "damped_oscillator": SystemSpec(
        "damped_oscillator", 2, 0, 2, _damped_osc, (1.2, 0.0), 0.01, 20.0, None, _damped_osc_coef
    ),
    "controlled_pendulum": SystemSpec(
        "controlled_pendulum",
        2,
        1,
        2,
        _pendulum,
        (0.6, 0.0),
        0.01,
        20.0,
        _pend_input,
        _pendulum_coef,
    ),
}


def get_system(name: str) -> SystemSpec:
    if name not in SYSTEMS:
        raise KeyError(f"unknown system {name!r}; available: {', '.join(sorted(SYSTEMS))}")
    return SYSTEMS[name]


def embed_true_coef(spec: SystemSpec, n_state: int, n_input: int, order: int) -> np.ndarray:
    """Embed spec's ground-truth Theta into a larger padded library.

    The streaming service (core/stream.py) zero-pads a heterogeneous fleet to
    common (n_state, n_input, order); recovered coefficients then live in the
    padded library basis. This maps the spec's [n_terms_spec, state_dim]
    truth into [n_terms(n_state+n_input, order), n_state] (zeros elsewhere)
    so recovery error is measured in one consistent basis.
    """
    if spec.true_coef is None:
        raise ValueError(f"system {spec.name!r} has no ground-truth coefficients")
    if order < spec.order or n_state < spec.state_dim or n_input < spec.input_dim:
        raise ValueError(f"padded library smaller than {spec.name!r}'s own library")
    small = np.asarray(spec.true_coef(), float)
    # shared naming scheme: states s0.., inputs i0.. — the spec's variables map
    # to the first state/input positions of the padded layout, so every spec
    # term name appears verbatim in the padded library's term list.
    small_names = term_names(
        spec.state_dim + spec.input_dim,
        spec.order,
        [f"s{i}" for i in range(spec.state_dim)] + [f"i{j}" for j in range(spec.input_dim)],
    )
    big_names = term_names(
        n_state + n_input,
        order,
        [f"s{i}" for i in range(n_state)] + [f"i{j}" for j in range(n_input)],
    )
    ix = {name: k for k, name in enumerate(big_names)}
    big = np.zeros((n_library_terms(n_state + n_input, order), n_state))
    for k, name in enumerate(small_names):
        big[ix[name], : spec.state_dim] = small[k]
    return big


def generate_trajectory(
    name: str,
    n_samples: int | None = None,
    noise_std: float = 0.0,
    seed: int = 0,
    oversample: int = 4,
) -> tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Integrate a system and return (ts [T], ys [T, n], us [T, m]).

    Integration runs at dt/oversample internally (RK4) and subsamples to the
    spec's dt — the fixed-step stand-in for the paper's ODE45 generation.
    """
    spec = SYSTEMS[name]
    n_samples = n_samples or int(spec.t_end / spec.dt)
    fine = n_samples * oversample
    ts_fine = jnp.linspace(0.0, n_samples * spec.dt, fine + 1)
    if spec.input_fn is not None:
        us_fine = jax.vmap(spec.input_fn)(ts_fine)
    else:
        us_fine = jnp.zeros((fine + 1, 0))
    y0 = jnp.asarray(spec.y0, jnp.float32)
    ys_fine = odeint(spec.dynamics, y0, ts_fine, us=us_fine, method="rk4")
    sl = slice(None, None, oversample)
    ts, ys, us = np.asarray(ts_fine[sl]), np.asarray(ys_fine[sl]), np.asarray(us_fine[sl])
    return ts, add_sensor_noise(ys, noise_std, seed), us.astype(np.float32)


def add_sensor_noise(ys: np.ndarray, noise_std: float, seed: int) -> np.ndarray:
    """Gaussian sensor noise scaled per channel by the clean signal's spread.

    A fleet of tenants streaming the same system shares one clean
    trajectory and differs only in this draw, so a fleet integrates each
    system once and calls this per stream.
    """
    if noise_std > 0:
        rng = np.random.default_rng(seed)
        ys = ys + noise_std * ys.std(axis=0, keepdims=True) * rng.standard_normal(ys.shape)
    return ys.astype(np.float32)
