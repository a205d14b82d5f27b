"""Readings that the correctness limits are set from, on the chip at a cell's size.

    python3 perfbench/calibrate.py --workload gru_fleet.serve --seconds 5 --seeds 101 102 103

For each seed, in one process: the cell's set-up and a short window at its
own load, then the numbers ``check.gaps`` compares, read four ways against
the reference (``reference/mr.py`` at full float32 precision):

- ``program``: the program's answers, as a benchmark run reads them;
- ``control``: the reference itself in the program's place, every matrix
  product in three bfloat16 passes (the nearest precision below float32);
- ``half_batch`` (training mixes): the reference in the program's place,
  training each slot on half of its windows;
- ``altered``: the program's answers with answers exchanged where they are
  produced: two slots' Theta at one tick, and (training) two refilled
  slots' Theta and two evicted streams' results.

A tick that returns its state unchanged reads 1 by the training numbers'
measure (no parameter moves, no loss changes) and needs no run. One JSON
line per seed is printed. The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys

import run


def swap_answers(ans: dict, kind) -> dict:
    """``ans`` with answers exchanged where they are produced: two slots' Theta
    at the last checked tick, and two evicted streams of different systems
    (``kind``: system index per stream) exchanging their results."""
    out = dict(ans)
    if "theta" in ans:
        out["theta"] = _swap_last(ans["theta"])
        return out
    out["first"] = dict(ans["first"], theta=_swap_last(ans["first"]["theta"]))
    if "recycled" in ans:
        out["recycled"] = dict(ans["recycled"],
                               theta=_swap_last(ans["recycled"]["theta"][:, None])[:, 0])
    ev = list(ans["evicted"])
    others = [k for k in range(1, len(ev)) if kind[ev[k][0]] != kind[ev[0][0]]]
    if others:
        j = others[0]
        (si, ni, mi, ci), (sj, nj, mj, cj) = ev[0], ev[j]
        ev[0], ev[j] = (si, ni, mj, cj), (sj, nj, mi, ci)
    out["evicted"] = ev
    return out


def _swap_last(theta):
    import numpy as np

    theta = np.array(theta)
    theta[[0, 1], -1] = theta[[1, 0], -1]
    return theta


def readings(m) -> dict:
    import check

    K = m.traffic["steps_per_tick"]
    ans = check.answers(m.rec, m.service_seed, m.cfg)
    ref = check.replay(ans, m.fleet, m.cfg, K, m.service_seed)
    out = {
        "program": check.gaps(ans, ref, m.cfg, K),
        "control": check.gaps(
            check.replay(ans, m.fleet, m.cfg, K, m.service_seed, precision="bf16x3"),
            ref, m.cfg, K),
        "altered": check.gaps(swap_answers(ans, m.fleet.kind), ref, m.cfg, K),
    }
    if K:
        out["half_batch"] = check.gaps(
            check.replay(ans, m.fleet, m.cfg, K, m.service_seed, keep=0.5), ref, m.cfg, K)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--first-only", action="store_true",
                    help="training mixes: read only the first ticks' numbers, with no window")
    args = ap.parse_args(argv)
    for seed in args.seeds:
        try:
            m = run.measure(args.workload, seed, args.seconds, False, log=lambda s: None,
                            first_only=args.first_only)
        except run.NoChip as e:
            print(f"calibrate: {e}", file=sys.stderr)
            return 2
        print(json.dumps({"workload": args.workload, "seed": seed} | readings(m)), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
