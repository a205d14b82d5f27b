"""The comparison that decides ``correct``: the timed path against the plain reference.

``answers`` collects what the program produced in a run; ``replay`` has the
reference (``reference/mr.py``) answer the same questions from the same seed
and inputs; ``gaps`` reduces the two to the numbers compared, each held to its
limit in ``limits/<workload>.json``:

- ``theta_gap``: the Theta readouts against the reference's replay of each
  slot's life from admission, ``max |dTheta| / max |Theta_ref|`` per slot
  and tick. Serve: every readback of the window for a seed-drawn sample of
  slots, worst slot and tick. Training: the first ticks' readouts, the
  median slot of each slot's worst tick (a few slots' readouts follow
  AdamW's sign on near-zero gradient elements, rounding's noise and not
  the program's: the median is steady from seed to seed, the worst slot is
  not).
- ``loss_gap``, ``moment_gap``, ``change_gap`` (training): the first ticks
  of the fleet's first admitted group, followed by the reference tick by
  tick: each tick's loss (the mean over those slots) relative to the
  reference's; per leaf (a parameter over all those slots) the gap between
  the norms of AdamW's first moment after tick 1, and of the parameters'
  change after the last followed tick, over the reference's norm of that
  leaf or the median leaf's, whichever is larger. Leaves whose first gradient
  in the reference is under a thousandth of the median leaf's move by
  round-off alone and are left out.
- ``recycled_loss_gap``, ``recycled_moment_gap``, ``recycled_change_gap``,
  ``recycled_theta_gap`` (training): the same numbers for a seed-drawn
  sample of the streams that the window admitted into slots freed by
  evictions, with the fleet full, read when the window has closed and each
  has trained 1 to 3 ticks: each stream against the reference's replay of
  its own life from admission, at its own age. The change is taken from the
  documented initial weights, so a slot that keeps its last stream's weights,
  moments or step count fails.
- ``identity_gap`` (training): every stream evicted in the window carries
  the admission statistics of its own history, and a step count the stream
  configuration allows; every refilled stream of the sample carries its own
  history's statistics and exactly ``K`` steps per tick of its age. The
  relative gap of the statistics, infinite on a step count that is not
  allowed.

``replay`` at ``precision="bf16x3"`` is the control, and with ``keep=0.5``
the reference trains on half of each slot's windows: both stand in for the
program to read what a lower precision or a broken batch would give.
"""

from __future__ import annotations

import json
from pathlib import Path

import jax
import numpy as np

import cell
from reference import mr

MOVED = 1e-3  # a leaf whose reference gradient is under this share of the median's is unmoved


def limits(bench_dir: Path, workload: str) -> dict:
    """The workload's limits: ``limits/<workload>.json``."""
    return json.loads((bench_dir / "limits" / f"{workload}.json").read_text())["limits"]


def _chunks(fleet, streams, n_ticks: int, cfg: dict):
    L, C = cfg["buf_len"], cfg["chunk"]
    k = np.arange(n_ticks)[:, None] * C + np.arange(C)[None, :] + L
    idx = k % fleet.n_samples
    s = np.asarray(streams)[:, None, None]
    return fleet.ys[s, idx[None]], fleet.us[s, idx[None]]


def _histories(fleet, streams, cfg: dict):
    L = cfg["buf_len"]
    hy = fleet.ys[np.asarray(streams), :L]
    hu = fleet.us[np.asarray(streams), :L]
    stats = [mr.buffer_stats(h.astype(np.float64)) for h in hy]
    return hy, hu, np.stack([m for m, _ in stats]), np.stack([s for _, s in stats])


def _replay(fleet, streams, n_ticks, cfg, K, service_seed, precision, keep):
    hy, hu, means, scales = _histories(fleet, streams, cfg)
    cy, cu = _chunks(fleet, streams, n_ticks, cfg)
    params = mr.admission_params(service_seed, streams, cfg)
    return mr.replay_many(params, means, scales, hy, hu, cy, cu, cfg, K, precision, keep)


def answers(rec, service_seed: int, cfg: dict) -> dict:
    """What the program answered in the run: serve ticks' Theta readbacks of the
    checked slots, or the training check's first ticks, the refilled slots'
    sample and the evicted streams."""
    if rec.theta_log:
        slots = sorted(rec.theta_log)
        return {
            "streams": [int(rec.slot_stream0[s]) for s in slots],
            "ticks": np.asarray([t for t, _ in rec.theta_log[slots[0]]]),
            "theta": np.stack([np.stack([th for _, th in rec.theta_log[s]]) for s in slots]),
        }
    evicted = [res for _, res, in_window in rec.evicted if in_window]
    out = {
        "first": dict(rec.first),
        "evicted": [(r.stream_id, r.steps, r.mean, r.scale) for r in evicted],
    }
    if rec.recycled:
        rc = {k: v for k, v in rec.recycled.items() if k != "params"}
        p0 = mr.admission_params(service_seed, rc["streams"], cfg)
        rc["change"] = cell.leaf_norms(
            jax.tree.map(lambda a, b: a - np.asarray(b), rec.recycled["params"], p0),
            np.arange(len(rc["streams"])))
        out["recycled"] = rc
    return out


def replay(ans: dict, fleet, cfg: dict, K: int, service_seed: int, precision="highest",
           keep=1.0) -> dict:
    """The reference's answers to the questions of ``ans``."""
    if "theta" in ans:
        n = int(ans["ticks"].max())
        theta = _replay(fleet, ans["streams"], n, cfg, 0, service_seed, precision, keep)[0]
        return dict(ans, theta=theta[:, ans["ticks"] - 1])
    first = ans["first"]
    n = first["loss"].shape[1]
    theta, loss, _, moment, change, grad = _replay(
        fleet, first["streams"], n, cfg, K, service_seed, precision, keep)
    out = dict(ans, first=dict(first, theta=theta, loss=loss, moment=moment[:, 0],
                               change=change[:, -1], grad=grad[:, 0]))
    rc = ans.get("recycled")
    if rc is not None:
        ages = np.asarray(rc["ages"])
        theta, loss, _, moment, change, grad = _replay(
            fleet, rc["streams"], int(ages.max()), cfg, K, service_seed, precision, keep)
        _, _, means, scales = _histories(fleet, rc["streams"], cfg)
        i, t = np.arange(len(ages)), ages - 1
        out["recycled"] = dict(rc, theta=theta[i, t], loss=loss[i, t], moment=moment[i, t],
                               change=change[i, t], grad=grad[:, 0], steps=ages * K,
                               mean=means, scale=scales)
    evicted = list(ans["evicted"])
    for i, (sid, steps, _, _) in enumerate(evicted):
        _, _, m, s = _histories(fleet, [sid], cfg)
        evicted[i] = (sid, steps, m[0], s[0])
    out["evicted"] = evicted
    return out


def slot_gaps(program: np.ndarray, ref: np.ndarray) -> np.ndarray:
    """[slots, ticks, terms, n] -> each slot's worst tick of max|dTheta| / max|Theta_ref|."""
    p = program.reshape(program.shape[0], program.shape[1], -1)
    r = ref.reshape(p.shape)
    if not np.all(np.isfinite(p)):
        return np.full(p.shape[0], np.inf)
    gap = np.abs(p - r).max(axis=2) / np.maximum(np.abs(r).max(axis=2), 1e-30)
    return gap.max(axis=1)


def leaf_norms(per_slot: np.ndarray) -> np.ndarray:
    """[slots, leaves] norms -> each leaf's norm over all the slots."""
    return np.sqrt(np.sum(np.square(per_slot, dtype=np.float64), axis=0))


def norm_gap(program: np.ndarray, ref: np.ndarray, keep: np.ndarray) -> float:
    """Worst leaf's gap of norms over max(its ref norm, the median leaf's)."""
    if not np.all(np.isfinite(program)):
        return float("inf")
    p, r = leaf_norms(program)[keep], leaf_norms(ref)[keep]
    return float(np.max(np.abs(p - r) / np.maximum(r, np.median(r))))


def gaps(ans: dict, ref: dict, cfg: dict, K: int) -> dict:
    """The numbers compared: the program's answers against the reference's."""
    if "theta" in ans:
        return {"theta_gap": float(slot_gaps(ans["theta"], ref["theta"]).max())}
    out = training_gaps(ans["first"], ref["first"])
    if "recycled" in ref:
        recycled = training_gaps(
            dict(ans["recycled"], loss=ans["recycled"]["loss"][:, None],
                 theta=ans["recycled"]["theta"][:, None]),
            dict(ref["recycled"], loss=ref["recycled"]["loss"][:, None],
                 theta=ref["recycled"]["theta"][:, None]))
    else:  # the window refilled no slot: nothing to hold the refill to
        recycled = dict.fromkeys(out, float("inf"))
    out |= {f"recycled_{k}": v for k, v in recycled.items()}
    out["identity_gap"] = float(max(identity_gaps(ans, ref, cfg, K), default=0.0))
    return out


def training_gaps(fa: dict, fr: dict) -> dict:
    """Loss, moment, change and Theta gaps of followed slots: ``loss`` [slots,
    ticks], ``moment``/``change``/``grad`` [slots, leaves] norms, ``theta``
    [slots, ticks, terms, n]."""
    grad = leaf_norms(fr["grad"])
    moved = grad >= MOVED * np.median(grad)
    loss_p, loss_r = fa["loss"].mean(axis=0), fr["loss"].mean(axis=0)
    return {
        "loss_gap": float(np.max(np.abs(loss_p - loss_r) / np.abs(loss_r)))
        if np.all(np.isfinite(loss_p)) else float("inf"),
        "moment_gap": norm_gap(fa["moment"], fr["moment"], moved),
        "change_gap": norm_gap(fa["change"], fr["change"], moved),
        "theta_gap": float(np.median(slot_gaps(fa["theta"], fr["theta"]))),
    }


def identity_gaps(ans: dict, ref: dict, cfg: dict, K: int) -> list[float]:
    """Per evicted stream and per refilled stream of the sample: the relative
    gap of its admission statistics from its own history's, infinite where
    its step count is not one the configuration allows (evicted: within the
    budget, a whole number of ticks; refilled: ``K`` per tick of its age)."""
    pairs = [(cfg["min_steps"] <= steps <= cfg["max_steps"] and steps % K == 0, mean, scale, m, s)
             for (_, steps, mean, scale), (_, _, m, s) in zip(ans["evicted"], ref["evicted"])]
    rc, rr = ans.get("recycled"), ref.get("recycled")
    if rc is not None and rr is not None:
        pairs += [(steps == want, mean, scale, m, s) for steps, want, mean, scale, m, s in zip(
            rc["steps"], rr["steps"], rc["mean"], rc["scale"], rr["mean"], rr["scale"])]
    return [float(max(np.max(np.abs(mean - m) / np.maximum(np.abs(m), s)),
                      np.max(np.abs(scale - s) / s))) if allowed else float("inf")
            for allowed, mean, scale, m, s in pairs]


def judge(numbers: dict, lim: dict) -> bool:
    return all(np.isfinite(v) and v <= lim[k] for k, v in numbers.items())
