"""Stream fleet generator: sensor trajectories for every stream of a run.

A system is a data file under ``systems/``: a polynomial vector field over
its states and inputs (``rhs``: one list of ``[coefficient, exponents]``
monomials per state), an initial state, a sampling interval and sinusoidal
inputs. One generator integrates any of them, so a deployment with another
system adds a data file and no code.

Integration follows ``repro.data.dynamics.generate_trajectory`` (RK4 at
``dt / oversample`` with the input held over each fine step, then
subsampled) and the fleet follows ``repro.launch.serve_mr.build_stream_fleet``:
stream ``i`` streams system ``i mod len(systems)``, tenants of one system
share its clean trajectory and differ in their sensor-noise draw (scaled per
channel by the clean signal's spread), and every stream is zero-padded to the
fleet's common state and input widths. The noise of all streams is one draw
from the population's seed.

A run's seed changes the order in which the streams arrive, never the
population itself (``admission_order``): every seed serves the same streams,
so the work a window holds does not change with the seed.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent


def load_system(name: str) -> dict:
    return json.loads((HERE / "systems" / f"{name}.json").read_text())


def _field(system: dict):
    """dy/dt = sum_k c_k * prod(z ** e_k) per state, z = [y, u]."""
    terms = [
        (np.asarray([c for c, _ in eq]), np.asarray([e for _, e in eq], float))
        for eq in system["rhs"]
    ]

    def f(y, u):
        z = np.concatenate([y, u])
        return np.asarray([c @ np.prod(z[None, :] ** e, axis=1) for c, e in terms])

    return f


def _inputs(system: dict, ts: np.ndarray) -> np.ndarray:
    cols = [s["amplitude"] * np.sin(s["omega"] * ts) for s in system["inputs"]]
    return np.stack(cols, axis=-1) if cols else np.zeros((len(ts), 0))


def trajectory(system: dict, n_samples: int) -> tuple[np.ndarray, np.ndarray]:
    """Clean (ys [n_samples, n], us [n_samples, m]) sampled every ``dt``."""
    over = system["oversample"]
    fine = n_samples * over
    ts = np.linspace(0.0, n_samples * system["dt"], fine + 1)
    us = _inputs(system, ts)
    f = _field(system)
    y = np.asarray(system["y0"], float)
    ys = [y]
    for i in range(fine):
        h, u = ts[i + 1] - ts[i], us[i]
        k1 = f(y, u)
        k2 = f(y + 0.5 * h * k1, u)
        k3 = f(y + 0.5 * h * k2, u)
        k4 = f(y + h * k3, u)
        y = y + (h / 6.0) * (k1 + 2 * k2 + 2 * k3 + k4)
        ys.append(y)
    ys = np.asarray(ys)[::over][1:]
    return ys, us[::over][1:]


class Fleet:
    """Trajectories of ``n_streams`` streams, ``ys [R, T, n]`` and ``us [R, T, m]``.

    ``chunks`` is the one vectorised gather a tick's input needs: the
    ``chunk`` samples of every slot's stream at that slot's cursor (zeros for
    an empty slot), cursors wrapping modulo the trajectory length.
    """

    def __init__(self, systems: list[str], n_streams: int, n_samples: int, noise: float, seed):
        specs = [load_system(s) for s in systems]
        if len({s["dt"] for s in specs}) > 1:
            raise ValueError("a fleet's systems must share one sampling interval")
        self.state_dim = max(s["state_dim"] for s in specs)
        self.input_dim = max(s["input_dim"] for s in specs)
        self.dt = specs[0]["dt"]
        rng = np.random.default_rng(seed)
        clean = [trajectory(s, n_samples) for s in specs]
        kind = np.arange(n_streams) % len(specs)
        ys = np.zeros((n_streams, n_samples, self.state_dim), np.float32)
        us = np.zeros((n_streams, n_samples, self.input_dim), np.float32)
        draw = rng.standard_normal((n_streams, n_samples, self.state_dim))
        for k, (s, (cy, cu)) in enumerate(zip(specs, clean)):
            rows = kind == k
            n, m = s["state_dim"], s["input_dim"]
            spread = cy.std(axis=0, keepdims=True)
            ys[rows, :, :n] = cy[None] + noise * spread[None] * draw[rows, :, :n]
            us[rows, :, :m] = cu[None]
        self.ys, self.us = ys, us
        self.systems = [s["name"] for s in specs]
        self.kind = kind

    @property
    def n_samples(self) -> int:
        return self.ys.shape[1]

    def history(self, stream: int, length: int) -> tuple[np.ndarray, np.ndarray]:
        return self.ys[stream, :length], self.us[stream, :length]

    def chunks(self, streams: np.ndarray, cursors: np.ndarray, chunk: int):
        """(chunks_y [S, C, n], chunks_u [S, C, m]) for slots holding ``streams``
        (-1 = empty) whose next sample index is ``cursors``."""
        live = streams >= 0
        idx = (cursors[:, None] + np.arange(chunk)[None, :]) % self.n_samples
        rows = np.where(live, streams, 0)[:, None]
        mask = live[:, None, None].astype(np.float32)
        return self.ys[rows, idx] * mask, self.us[rows, idx] * mask


def admission_order(n_streams: int, block: int, seed) -> np.ndarray:
    """Stream ids in arrival order: ``0 .. n_streams - 1`` with each run of
    ``block`` consecutive ids shuffled by ``seed``. An id moves fewer than
    ``block`` places, so each admission group holds nearly the same streams
    whatever the seed."""
    rng = np.random.default_rng(seed)
    ids = np.arange(n_streams)
    return np.concatenate([rng.permutation(ids[i:i + block])
                           for i in range(0, n_streams, block)])
