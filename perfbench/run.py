"""Chip benchmark of the streaming model-recovery service: one cell per run.

    python3 perfbench/run.py --workload gru_fleet.serve --seed 7 --seconds 30 --trace 0

Run from the root of a checkout on a machine with the TPU chips the cell asks
for; without them it exits non-zero and prints no result. A cell
(``BENCHMARK.json`` ``workloads``) names a configuration
(``configs/<config>.json``) and a traffic mix (``traffic/<traffic>.json``);
each metric is read by ``metrics/<name>.py`` and each cell's correctness
limits are ``limits/<workload>.json``, all found by name. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last
``compared``: every number the correctness check compared, with its limit.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import os  # noqa: E402

# the TPU runtime's own log files would go to a fixed path under /tmp
os.environ.setdefault("TPU_LOG_DIR", "disabled")

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".perfbench" / "trace"
for p in (str(ROOT / "src"), str(HERE)):
    if p not in sys.path:
        sys.path.insert(0, p)

import numpy as np  # noqa: E402


class NoChip(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    rec: object
    cfg: dict
    traffic: dict
    peaks: dict
    kernels: list
    trace: object = None


def load_json(path: Path) -> dict:
    return json.loads(path.read_text())


def find_workload(bench: dict, name: str) -> dict:
    for wl in bench["workloads"]:
        if wl["name"] == name:
            return wl
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def cell_metrics(bench: dict, workload: str, kind: str) -> list[dict]:
    """The metrics of ``kind`` (end_to_end | per_layer) this cell reports."""
    return [m for m in bench[kind] if workload in m.get("workloads", [workload])]


def reader_path(bench_dir: Path, name: str) -> Path:
    """``metrics/<name>.py``, else the reader of the quantity before the
    traffic suffix (``idle_share.serve`` -> ``metrics/idle_share.py``)."""
    path = bench_dir / "metrics" / f"{name}.py"
    if not path.exists() and "." in name:
        path = bench_dir / "metrics" / f"{name.rsplit('.', 1)[0]}.py"
    return path


def reader(bench_dir: Path, name: str):
    path = reader_path(bench_dir, name)
    spec = importlib.util.spec_from_file_location(f"perfbench_metric_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def devices_for(chips: int, require_chip: bool):
    import jax

    devices = jax.devices()
    if require_chip and devices[0].platform != "tpu":
        raise NoChip(f"needs a TPU, JAX found {devices[0].platform!r} ({devices[0].device_kind})")
    if len(devices) < chips:
        raise NoChip(f"needs {chips} chips, JAX found {len(devices)}")
    return devices[:chips]


def enable_cache():
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    # no eviction: the cache of one checkout stays small, and LRU bookkeeping
    # files are not needed
    jax.config.update("jax_compilation_cache_max_size", -1)


@dataclasses.dataclass
class Measured:
    """A cell's run up to the close of its window, and what the checks need."""

    bench: dict
    bench_dir: Path
    workload: str
    cfg: dict
    traffic: dict
    devices: list
    peaks: dict
    kernels: list
    limits: dict
    rec: object
    fleet: object
    service_seed: int
    theta_failed: int


def measure(workload: str, seed: int, seconds: float, trace: bool, *, root: Path = ROOT,
            require_chip: bool = True, log=print, first_only: bool = False) -> Measured:
    """Set-up and window of one run of one cell.

    ``root`` holds ``BENCHMARK.json`` and the benchmark's files under
    ``perfbench/``; ``require_chip=False`` skips the look for a TPU (CPU
    rehearsals, which report no device metric anywhere). ``first_only``
    (training mixes, for ``calibrate.py``) stops after the first ticks the
    training check follows: no window, no eviction.
    """
    import jax

    import cell
    import check
    import flops
    from fleet import Fleet, admission_order
    from repro import api  # noqa: F401  the system under test: fail here without it

    bench_dir = root / "perfbench"
    bench = load_json(root / "BENCHMARK.json")
    wl = find_workload(bench, workload)
    devices = devices_for(wl["chips"], require_chip)
    peaks = flops.peaks(devices[0].device_kind) if require_chip else {}
    config = next(c for c in bench["configs"] if c["name"] == wl["config"])
    cfg = load_json(root / config["file"])
    traffic = load_json(bench_dir / "traffic" / f"{wl['traffic']}.json")
    kernels = load_json(bench_dir / "kernels.json")["recovery"]
    limits = check.limits(bench_dir, workload)
    enable_cache()
    # the population (sensor noise, initial weights) is the traffic's own; the
    # run's seed draws the arrival order and the checked samples
    s_order, s_check = np.random.SeedSequence(seed).spawn(2)
    service_seed = traffic["population_seed"]
    check_rng = np.random.default_rng(s_check)
    K = traffic["steps_per_tick"]

    watch, gcw = cell.CompileWatch(), cell.GCWatch()
    try:
        with jax.default_matmul_precision(cfg["matmul_precision"]):
            t0 = time.perf_counter()
            n_streams = cfg["n_slots"] + traffic["backlog_streams"]
            fleet = Fleet(cfg["systems"], n_streams, traffic["samples_per_stream"], cfg["noise"],
                          service_seed)
            order = admission_order(n_streams, traffic["order_block"], s_order)
            t1 = time.perf_counter()
            check_slots = (
                np.sort(check_rng.choice(cfg["n_slots"], traffic["check_slots"], replace=False))
                if traffic["read_theta"] else []
            )
            driver = cell.Driver(cfg, traffic, fleet, order,
                                 cell.build_spec(cfg, traffic, service_seed), check_slots,
                                 n_first=3 if K else 0)
            driver.compile(service_seed)
            t2 = time.perf_counter()
            driver.setup(first_only)
            if first_only:
                return Measured(bench, bench_dir, workload, cfg, traffic, devices, peaks,
                                kernels, limits, driver.rec, fleet, service_seed, 0)
            t3 = time.perf_counter()
            rec = driver.rec
            _, compile_seconds = watch.snapshot()
            rec.phases.update(
                data_s=t1 - t0,
                plan_s=t2 - t1,
                ticks_s=t3 - t2 - rec.phases["admit_s"],
                compile_s=sum(compile_seconds.values()),
            )
            rec.setup_s = time.perf_counter() - T_START
            log("setup " + json.dumps({k: round(v, 3) for k, v in rec.phases.items()}
                                      | {"setup_s": round(rec.setup_s, 3), "ticks": driver.ticks}))
            trace_dir = None
            if trace:
                shutil.rmtree(TRACE_DIR, ignore_errors=True)
                TRACE_DIR.mkdir(parents=True)
                trace_dir = TRACE_DIR
            driver.window(seconds, gcw, watch, trace_dir, traffic["trace_seconds"])
            stats = devices[0].memory_stats() or {}
            rec.memory_peak_bytes = stats.get("peak_bytes_in_use")
            driver.read_recycled(check_rng, traffic["check_recycled"])
    finally:
        watch.close()
        gcw.close()
    evicted = [res for _, res, in_window in rec.evicted if in_window]
    log("window " + json.dumps({
        "seconds": round(rec.window_s, 3), "ticks": len(rec.tick_s),
        "compiles": rec.window_compiles, "traces": rec.window_traces,
        "compiled": {f"{e}:{f}": n for (e, f), n in rec.window_compiled.items()},
        "gc_count": rec.gc_count, "gc_ms": round(rec.gc_ms, 3),
        "longest_ticks_ms": sorted((round(t * 1e3, 2) for t in rec.tick_s), reverse=True)[:5],
        "evictions_per_s": round(len(evicted) / rec.window_s, 4),
        "evicted_steps": dict(sorted(collections.Counter(r.steps for r in evicted).items()))}))
    theta_failed = driver.theta_failed
    # the program's state goes before the reference runs
    driver.service = driver.plan = None
    gc.collect()
    return Measured(bench, bench_dir, workload, cfg, traffic, devices, peaks, kernels, limits,
                    rec, fleet, service_seed, theta_failed)


def conclude(m: Measured, trace: bool) -> dict:
    """The run's result object: the checks, the metrics and the device."""
    import check
    import devtrace

    rec, K = m.rec, m.traffic["steps_per_tick"]
    ans = check.answers(rec, m.service_seed, m.cfg)
    ref = check.replay(ans, m.fleet, m.cfg, K, m.service_seed)
    numbers = check.gaps(ans, ref, m.cfg, K)
    if K:
        identity = check.identity_gaps(ans, ref, m.cfg, K)
        attempted = len(identity)
        failed = sum(1 for g in identity if not g <= m.limits["identity_gap"])
    else:
        attempted, failed = len(rec.tick_s) * rec.live_slots, m.theta_failed
    correct = check.judge(numbers, m.limits) and failed == 0

    run = Run(rec, m.cfg, m.traffic, m.peaks, m.kernels)
    device = {
        "platform": m.devices[0].platform,
        "kind": m.devices[0].device_kind,
        "count": len(m.devices),
        "memory_peak_bytes": rec.memory_peak_bytes,
    }
    breakdown = None
    if trace:
        run.trace = rec.trace = devtrace.reduce(devtrace.find_xplane(TRACE_DIR))
        device.update(busy_s=rec.trace.busy_s, window_s=rec.trace.window_s)
        breakdown = {
            "device_ops": [[n, s] for n, s in rec.trace.top_ops()],
            "idle_gaps": [[n, s] for n, s in rec.trace.idle_gaps],
        }
    metrics = {}
    for metric in cell_metrics(m.bench, m.workload, "per_layer" if trace else "end_to_end"):
        value = reader(m.bench_dir, metric["name"])(run)
        if value is not None:
            metrics[metric["name"]] = {"value": float(value), "unit": metric["unit"]}
    result = {
        "correct": bool(correct),
        "attempted": attempted,
        "failed": failed,
        "metrics": metrics,
        "device": device,
    }
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["compared"] = {k: {"value": v, "limit": m.limits[k]} for k, v in numbers.items()}
    return result


def execute(workload: str, seed: int, seconds: float, trace: bool, **kw) -> dict:
    """One run of one cell; returns the result object (see the module docstring)."""
    return conclude(measure(workload, seed, seconds, trace, **kw), trace)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        result = execute(args.workload, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"perfbench: {e}; nothing was measured", file=sys.stderr)
        return 2
    for name, c in result["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
