"""Streaming-service throughput: batched slots vs serial per-stream recovery.

The service claim (core/stream.py): running K recovery steps for S slots as
ONE vmapped, jit-cached tick program beats ticking S single-slot services
sequentially — at MR sizes every XLA op is tiny, so per-op dispatch overhead
dominates and batching S streams into each op amortizes it (the host-side
analogue of the paper's spatial parallelism across concurrent recoveries).
Both sides are the REAL RecoveryService end to end, including the per-tick
host readback of the convergence scalars: the batched service pays it once
per tick, a per-stream deployment pays it per stream per tick.

Measured:
  stream/ticks_per_sec_batched   S-slot service ticks per second
  stream/ticks_per_sec_serial    equivalent tick rate of S sequential
                                 single-slot services (same per-stream work)
  stream/batched_over_serial     speedup (claim: >= 2x at 4+ slots)
  stream/latency_*               per-stream recovery latency for a fixed
                                 step budget, service vs the sequential
                                 (one-system-at-a-time) recover_many baseline
  stream/banked_tick_over_composite  wall ratio of the banked one-kernel
                                 serve tick (TickSpec tick_kernel="banked":
                                 kernels/mr_step/tick.py ingest + substeps +
                                 EMA readout as ONE program, one packed host
                                 readback) over the composite stage-sequence
                                 tick, both through plan-compiled services
                                 end to end (run_banked_tick). GATED: this
                                 replaced the info-only
                                 fused_tick_over_unfused wall row — the
                                 banked tick is a structural change (fewer
                                 programs, fewer host syncs), so the ratio
                                 is real wall clock even off-TPU.

Sizes are deliberately small (the paper's regime: tiny models, many
iterative updates) and fixed-seed; timing is best-of-``repeats`` (the
run_engine methodology — a background-load spike in one repeat otherwise
dominates on small CI boxes). Wall numbers land in the JSON "info" section;
only dimensionless ratios are gated (benchmarks/gate.py).
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.core import engine
from repro.core.merinda import MRConfig
from repro.core.stream import RecoveryService, StreamConfig
from repro.data.windows import make_windows

REPO_ROOT = Path(__file__).resolve().parent.parent


def _run_cpu_child(prog: str, marker: str, what: str):
    """Run a virtual-device rehearsal in a child interpreter; return the JSON
    it printed after ``marker``.

    The child is a CPU rehearsal by design (``JAX_PLATFORMS=cpu`` in its own
    environment): this process has already touched JAX, and on a machine
    with a chip the parent holds it — a child reaching for it would fail or
    hang.
    """
    env = dict(os.environ)
    env["PYTHONPATH"] = str(REPO_ROOT / "src")
    env["JAX_PLATFORMS"] = "cpu"
    env.pop("XLA_FLAGS", None)
    p = subprocess.run(
        [sys.executable, "-c", prog],
        capture_output=True,
        text=True,
        env=env,
        cwd=REPO_ROOT,
        timeout=900,
    )
    lines = [ln for ln in p.stdout.splitlines() if ln.startswith(marker + " ")]
    if p.returncode != 0 or not lines:
        raise RuntimeError(
            f"{what} subprocess failed (rc={p.returncode})\n"
            f"stdout:\n{p.stdout[-2000:]}\nstderr:\n{p.stderr[-2000:]}"
        )
    return json.loads(lines[0][len(marker) + 1 :])


def run(slots: int = 8, n_ticks: int = 8, repeats: int = 3, smoke: bool = False):
    """Returns (csv_rows, metrics dict). Fixed seeds; see module docstring."""
    if smoke:
        n_ticks, repeats = 6, 2
    from repro.data.dynamics import generate_trajectory

    cfg = MRConfig(state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01, encoder="gru")
    scfg = StreamConfig(
        buf_len=32,
        window=8,
        stride=8,
        chunk=8,
        steps_per_tick=8,
        min_steps=10**9,  # no eviction: fixed recovery work per tick
        max_steps=10**9,
    )
    n_samples = scfg.buf_len + scfg.chunk * (n_ticks + 2)
    _, ys, _ = generate_trajectory("lorenz", n_samples=n_samples)
    L, C = scfg.buf_len, scfg.chunk
    chunks = [
        np.repeat(ys[L + t * C : L + (t + 1) * C][None], slots, axis=0) for t in range(n_ticks)
    ]

    def run_batched(service_cfg: MRConfig = cfg) -> float:
        svc = RecoveryService(service_cfg, scfg, slots)
        for i in range(slots):
            svc.submit(i, ys[:L])
        svc.fill_slots()
        svc.tick_once(chunks[0])  # compile
        t0 = time.perf_counter()
        for t in range(1, n_ticks):
            svc.tick_once(chunks[t])
        return time.perf_counter() - t0

    def run_serial() -> float:
        svcs = []
        for s in range(slots):
            svc = RecoveryService(cfg, scfg, 1, seed=s)
            svc.submit(s, ys[:L])
            svc.fill_slots()
            svcs.append(svc)
        svcs[0].tick_once(chunks[0][:1])  # compile (shared jit cache)
        t0 = time.perf_counter()
        for t in range(1, n_ticks):
            for s in range(slots):
                svcs[s].tick_once(chunks[t][:1])
        return time.perf_counter() - t0

    t_batched = min(run_batched() for _ in range(repeats))
    t_serial = min(run_serial() for _ in range(repeats))
    timed = n_ticks - 1
    tps_batched = timed / t_batched
    tps_serial = timed / t_serial
    speedup = t_serial / t_batched

    # --- per-stream recovery latency vs sequential recover_many -----------
    # fixed budget of `lat_steps` optimizer steps per stream. Service latency
    # = ticks needed at K steps/tick (all S streams finish together); the
    # baseline recovers one system at a time through the scan-jitted engine.
    lat_steps = 64 if smoke else 128
    lat_ticks = lat_steps // scfg.steps_per_tick
    t_service = lat_ticks / tps_batched
    yw, _, _ = make_windows(ys[:L], None, window=scfg.window, stride=scfg.stride)
    yw_b = np.asarray(yw)[None]
    jax.block_until_ready(engine.recover_many(cfg, yw_b, steps=lat_steps, seed=0))  # compile
    t0 = time.perf_counter()
    for s in range(slots):
        jax.block_until_ready(engine.recover_many(cfg, yw_b, steps=lat_steps, seed=s))
    t_recover_serial = time.perf_counter() - t0

    rows = [
        (
            "stream/ticks_per_sec_batched",
            1e6 / tps_batched,
            f"slots={slots};K={scfg.steps_per_tick}",
        ),
        (
            "stream/ticks_per_sec_serial",
            1e6 / tps_serial,
            f"slots={slots};1-slot service x{slots}",
        ),
        ("stream/batched_over_serial", 0.0, f"x{speedup:.2f} (claim: >=2x at 4+ slots)"),
        (
            "stream/latency_service_per_stream",
            t_service / slots * 1e6,
            f"{lat_steps} steps; {slots} streams concurrent",
        ),
        (
            "stream/latency_recover_many_serial",
            t_recover_serial / slots * 1e6,
            f"{lat_steps} steps; one stream at a time",
        ),
    ]
    # gated: the one dimensionless ratio with real margin (~2.5-3x measured
    # vs a 1.5 floor). The latency ratio is informational only — its margin
    # over 1.0 is too thin to gate without flaking on loaded CI runners.
    metrics = {
        "batched_over_serial_speedup": round(speedup, 3),
        "info": {
            "slots": slots,
            "steps_per_tick": scfg.steps_per_tick,
            "n_ticks": timed,
            "latency_speedup_vs_recover_many": round(t_recover_serial / max(t_service, 1e-9), 3),
            "ticks_per_sec_batched": round(tps_batched, 2),
            "ticks_per_sec_serial": round(tps_serial, 2),
            "latency_service_per_stream_s": round(t_service / slots, 4),
            "latency_recover_many_per_stream_s": round(t_recover_serial / slots, 4),
        },
    }
    return rows, metrics


# ---------------------------------------------------------------------------
# banked one-kernel serve tick vs the composite stage sequence
# ---------------------------------------------------------------------------
def run_banked_tick(slots: int = 8, n_ticks: int = 16, repeats: int = 3, smoke: bool = False):
    """Banked one-kernel serve tick vs the composite stage-sequence serving.

    K = 0 serve/monitor ticks — the configuration the banked ``mr_tick``
    kernel collapses into ONE program (ring ingest + window substeps + head
    + EMA readout for ALL slots, one packed [S, 4] status readback).

    The GATED comparator is the composite per-slot stage sequence: ring
    ingest as its own program, then per slot a windows + ``readout_theta``
    program dispatch with its own device->host Theta readback and the EMA /
    delta update on the host — the serving structure a deployment paid
    before the banked kernel existed (the eviction-path readout, run every
    tick), and the "no banking, stages composed separately" baseline of the
    paper's one-kernel claim. At MR sizes each stage's math is microseconds,
    so S per-slot dispatches + S readbacks dominate and the wall ratio is a
    REAL structural speedup even on CPU (measured ~4x at 8 slots).

    For transparency the info section also carries the ratio against the
    one-program composite tick (``TickSpec(tick_kernel="composite")`` with
    K=0 — added alongside the banked kernel): both are single XLA
    executables of the same math, so that ratio sits near 1.0 off-TPU and
    is NOT the gated claim (banked still does it in 1 host sync vs 5).

    Returns (csv_rows, metrics) with gated ``banked_tick_over_composite_wall``.
    """
    if smoke:
        n_ticks, repeats = 10, 2
    from repro import api
    from repro.core.stream import _slot_windows, readout_theta, roll_buffer
    from repro.data.dynamics import generate_trajectory

    scfg = StreamConfig(
        buf_len=32,
        window=8,
        stride=8,
        chunk=8,
        steps_per_tick=0,  # pure serve tick: readout only, no optimizer steps
        min_steps=10**9,
        max_steps=10**9,
    )
    _, ys, _ = generate_trajectory("lorenz", n_samples=32 + 8 * (n_ticks + 2))
    chunks = [
        np.repeat(ys[32 + t * 8 : 32 + (t + 1) * 8][None], slots, axis=0) for t in range(n_ticks)
    ]
    timed = n_ticks - 1

    def make_plan(kind):
        return api.compile_plan(
            api.RecoverySpec(
                state_dim=3,
                order=2,
                hidden=8,
                dense_hidden=16,
                dt=0.01,
                encoder="gru",
                mode="stream",
                n_slots=slots,
                stream=scfg,
                tick=api.TickSpec(steps_per_tick=0, tick_kernel=kind),
            )
        )

    def fresh_service(plan):
        svc = plan.make_service()
        for i in range(slots):
            svc.submit(i, ys[:32])
        svc.fill_slots()
        return svc

    def run_service_ticks(plan):
        """One-program tick loop through the real service (banked or composite)."""
        best, syncs = float("inf"), 0.0
        for _ in range(repeats):
            svc = fresh_service(plan)
            svc.tick_once(chunks[0])  # compile
            t0 = time.perf_counter()
            for t in range(1, n_ticks):
                svc.tick_once(chunks[t])
            best = min(best, time.perf_counter() - t0)
            syncs = float(np.median(svc.sync_log[1:]))
        return best, syncs

    plan_b, plan_c = make_plan("banked"), make_plan("composite")
    t_banked, syncs_banked = run_service_ticks(plan_b)
    t_ctick, syncs_ctick = run_service_ticks(plan_c)

    # composite per-slot stage sequence (the gated baseline): ingest program,
    # then per slot a windows+readout program and its own Theta readback,
    # EMA + convergence delta on the host. Per-slot params are hoisted OUT of
    # the loop (K=0 freezes them) — the baseline is not handicapped with
    # avoidable per-tick work.
    cfg = plan_c.cfg
    ingest = jax.jit(
        lambda by, bu, ny, nu: (roll_buffer(by, ny), roll_buffer(bu, nu)),
        donate_argnums=(0, 1),
    )

    @jax.jit
    def slot_read(p, by, bu, mu, sd):
        yw, uw = _slot_windows(by, bu, mu, sd, scfg)
        return readout_theta(p, cfg, yw, uw)

    no_u = np.zeros((slots, scfg.chunk, cfg.input_dim), np.float32)
    best_seq = float("inf")
    for _ in range(repeats):
        svc = fresh_service(plan_c)
        st = svc.state
        slot_params = [jax.tree.map(lambda a: a[s], st.params) for s in range(slots)]
        mean, scale = st.mean, st.scale
        buf_y, buf_u, theta_h = st.buf_y, st.buf_u, np.asarray(st.theta)

        def tick_stage_seq(buf_y, buf_u, chunk, theta_h):
            buf_y, buf_u = ingest(buf_y, buf_u, jnp.asarray(chunk), jnp.asarray(no_u))
            raw = np.stack(
                [
                    np.asarray(slot_read(slot_params[s], buf_y[s], buf_u[s], mean[s], scale[s]))
                    for s in range(slots)
                ]
            )
            theta_new = scfg.ema * theta_h + (1.0 - scfg.ema) * raw
            delta = np.max(np.abs(theta_new - theta_h), axis=(1, 2))
            delta /= np.max(np.abs(theta_new), axis=(1, 2)) + 1e-3  # noqa: F841
            return buf_y, buf_u, theta_new

        buf_y, buf_u, theta_h = tick_stage_seq(buf_y, buf_u, chunks[0], theta_h)  # compile
        t0 = time.perf_counter()
        for t in range(1, n_ticks):
            buf_y, buf_u, theta_h = tick_stage_seq(buf_y, buf_u, chunks[t], theta_h)
        best_seq = min(best_seq, time.perf_counter() - t0)

    ratio = best_seq / t_banked
    rows = [
        (
            "stream/banked_tick_over_composite",
            1e6 / (timed / t_banked),
            f"x{ratio:.2f} wall, K=0 serve ticks: one banked program + 1 sync "
            f"vs ingest + {slots} per-slot readout dispatches + {slots} syncs "
            f"(one-program composite tick: x{t_ctick / t_banked:.2f}, "
            f"{syncs_ctick:.0f} syncs/tick)",
        ),
    ]
    metrics = {
        "banked_tick_over_composite_wall": round(ratio, 3),
        "info": {
            "slots": slots,
            "n_ticks": timed,
            "banked_ticks_per_sec": round(timed / t_banked, 2),
            "composite_stage_seq_ticks_per_sec": round(timed / best_seq, 2),
            "composite_tick_ticks_per_sec": round(timed / t_ctick, 2),
            "banked_over_composite_tick_wall": round(t_ctick / t_banked, 3),
            "banked_host_syncs_per_tick": syncs_banked,
            "composite_tick_host_syncs_per_tick": syncs_ctick,
        },
    }
    return rows, metrics


# ---------------------------------------------------------------------------
# sharded-slot mesh scaling (repro.api plan surface)
# ---------------------------------------------------------------------------
# Runs in a SUBPROCESS because the virtual-device count must be pinned via
# XLA_FLAGS before any jax import; the parent process already holds a
# single-device jax. One subprocess measures every mesh size so the three
# configurations share identical CPU conditions.
_MESH_SNIPPET = """\
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={device_count}"
import json
import time

import numpy as np

from repro import api
from repro.core.stream import StreamConfig
from repro.data.dynamics import generate_trajectory


def ticks_per_sec(mesh_slots, slots, n_ticks, repeats):
    scfg = StreamConfig(
        buf_len=32, window=8, stride=8, chunk=8, steps_per_tick=8,
        min_steps=10**9, max_steps=10**9,
    )
    spec = api.RecoverySpec(
        state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01, encoder="gru",
        mode="stream", n_slots=slots, stream=scfg, mesh_slots=mesh_slots,
    )
    plan = api.compile_plan(spec)
    _, ys, _ = generate_trajectory("lorenz", n_samples=32 + 8 * (n_ticks + 2))
    chunks = [
        np.repeat(ys[32 + t * 8 : 32 + (t + 1) * 8][None], slots, axis=0)
        for t in range(n_ticks)
    ]
    best = 0.0
    for _ in range(repeats):
        svc = plan.make_service()
        for i in range(slots):
            svc.submit(i, ys[:32])
        svc.fill_slots()
        svc.tick_once(chunks[0])  # compile
        t0 = time.perf_counter()
        for t in range(1, n_ticks):
            svc.tick_once(chunks[t])
        best = max(best, (n_ticks - 1) / (time.perf_counter() - t0))
    # host-boundary accounting (deterministic): every device->host readback
    # is a sync point, every post-admission shard re-pin is a reshard. The
    # per-tick figure is the MEDIAN of the service's sync_log — the first
    # (compile) tick and eviction ticks read extra scalars, and a mean over
    # so few ticks let those outliers move the row between runs.
    return {{
        "tps": best,
        "host_syncs_per_tick": float(np.median(svc.sync_log)),
        "reshards": svc.counters["reshards"],
    }}


def device_plane(mesh_slots, slots, n_ticks, repeats):
    # Device-resident control plane under churn: 2*slots streams over
    # `slots` slots with a hard 16-step budget (2 ticks at K=8), so every
    # slot evicts and refills from the shard-local on-device queue mid-run
    # (>= 2*slots admissions total, half of them via in-program refill).
    # Steady-state host boundary = median of sync_log AFTER the compile
    # tick: only the periodic snapshot (every snapshot_period ticks) reads
    # anything back, and admission never re-pins the slot axis (reshards
    # stays 0 by construction — gated as a ceiling).
    scfg = StreamConfig(
        buf_len=32, window=8, stride=8, chunk=8, steps_per_tick=8,
        min_steps=16, max_steps=16,
    )
    streams = 2 * slots
    spec = api.RecoverySpec(
        state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01, encoder="gru",
        mode="stream", n_slots=slots, stream=scfg, mesh_slots=mesh_slots,
        tick=api.TickSpec(
            steps_per_tick=8, control="device",
            queue_capacity=streams, snapshot_period=4, warm_capacity=slots,
        ),
    )
    plan = api.compile_plan(spec)
    _, ys, _ = generate_trajectory("lorenz", n_samples=32 + 8 * (n_ticks + 2))
    chunks = [
        np.repeat(ys[32 + t * 8 : 32 + (t + 1) * 8][None], slots, axis=0)
        for t in range(n_ticks)
    ]
    best, syncs, reshards, completed, done = 0.0, 0.0, 0, 0, False
    for _ in range(repeats):
        svc = plan.make_service()
        for i in range(streams):
            svc.submit(i, ys[:32])
        svc.fill_slots()
        svc.tick_once(chunks[0])  # compile
        t0 = time.perf_counter()
        for t in range(1, n_ticks):
            svc.tick_once(chunks[t])
        best = max(best, (n_ticks - 1) / (time.perf_counter() - t0))
        syncs = float(np.median(svc.sync_log[1:]))
        reshards = svc.counters["reshards"]
        svc.fill_slots()  # final snapshot: flush the event log
        completed = len(svc.drain())
        done = svc.done
    return {{
        "tps": best,
        "host_syncs_per_tick": syncs,
        "reshards": reshards,
        "admissions": streams,
        "completed": completed,
        "done": bool(done),
    }}


out = {{}}
for m in (1, 2, 4):
    out[str(m)] = ticks_per_sec(m, slots={slots}, n_ticks={n_ticks}, repeats={repeats})
    out[str(m)]["device"] = device_plane(
        m, slots={slots}, n_ticks={n_ticks}, repeats={repeats}
    )
print("MESHBENCH " + json.dumps(out))
"""


def run_mesh_scaling(
    slots: int = 8,
    n_ticks: int = 8,
    repeats: int = 3,
    device_count: int = 4,
    smoke: bool = False,
):
    """Sharded-SlotState service throughput at mesh sizes 1/2/4.

    The plan surface (repro.api) shards the slot axis over a CPU
    virtual-device mesh; measured is ticks/sec (and slots/sec = ticks/sec x
    slots) per mesh size. On CPU the devices share the same cores, so the
    gateable claim is CONSERVATIVE: sharding must not collapse throughput
    (``mesh_slots_per_sec_scaling`` = mesh-2 over mesh-1 ticks/sec stays
    above a floor), while real scaling lives on multi-chip hardware.

    Each mesh size also runs the device-resident control plane
    (``TickSpec(control="device")``) under admission/eviction churn —
    2*slots streams with a 2-tick budget, so every slot refills from the
    shard-local on-device queue mid-run. Gated (ceilings, deterministic):
    ``device_host_syncs_per_tick`` <= 1 steady-state (only the periodic
    snapshot reads back) and ``device_reshards`` == 0 (admission appends to
    device rings; the slot axis is never re-pinned). Returns
    (csv_rows, metrics).
    """
    if smoke:
        n_ticks, repeats = 6, 2
    prog = _MESH_SNIPPET.format(
        device_count=device_count, slots=slots, n_ticks=n_ticks, repeats=repeats
    )
    stats = {int(k): v for k, v in _run_cpu_child(prog, "MESHBENCH", "mesh-scaling").items()}
    tps = {m: s["tps"] for m, s in stats.items()}
    dev = {m: s["device"] for m, s in stats.items()}
    scaling = tps[2] / tps[1]
    rows = [
        (
            f"stream/mesh{m}_ticks_per_sec",
            1e6 / tps[m],
            f"slots={slots};{slots * tps[m]:.1f} slots/s;{device_count} virtual devices;"
            f"host_syncs/tick={stats[m]['host_syncs_per_tick']:.1f};"
            f"reshards={stats[m]['reshards']}",
        )
        for m in sorted(tps)
    ]
    rows += [
        (
            f"stream/mesh{m}_device_ticks_per_sec",
            1e6 / dev[m]["tps"],
            f"control=device;slots={slots};{dev[m]['admissions']} admissions "
            f"({dev[m]['completed']} completed);"
            f"host_syncs/tick={dev[m]['host_syncs_per_tick']:.1f};"
            f"reshards={dev[m]['reshards']}",
        )
        for m in sorted(dev)
    ]
    rows.append(
        (
            "stream/mesh_slots_per_sec_scaling",
            0.0,
            f"x{scaling:.2f} mesh-2 over mesh-1 (CPU virtual devices share cores; "
            "conservative no-collapse floor)",
        )
    )
    # device-resident control plane (core/control.py): gated CEILINGS on the
    # worst mesh size — steady-state median syncs/tick must stay <= 1 (the
    # periodic snapshot is the only readback) and the slot axis must never
    # be re-pinned on admission (reshards == 0). Both are structural, so
    # they are deterministic counters, not wall measurements.
    dev_syncs = max(d["host_syncs_per_tick"] for d in dev.values())
    dev_reshards = max(d["reshards"] for d in dev.values())
    metrics = {
        "mesh_slots_per_sec_scaling": round(scaling, 3),
        "device_host_syncs_per_tick": round(dev_syncs, 3),
        "device_reshards": dev_reshards,
        "info": {
            "device_count": device_count,
            "slots": slots,
            "n_ticks": n_ticks - 1,
            **{
                f"mesh{m}_slots_per_sec": round(slots * tps[m], 2) for m in sorted(tps)
            },
            "mesh4_over_mesh1": round(tps[4] / tps[1], 3),
            # host-plane baseline the device-resident control plane replaces:
            # ALL admissions funnel through one host queue, so every
            # readback/reshard is a cross-mesh sync the sharded service pays.
            **{
                f"mesh{m}_host_syncs_per_tick": round(stats[m]["host_syncs_per_tick"], 2)
                for m in sorted(stats)
            },
            **{f"mesh{m}_reshards": stats[m]["reshards"] for m in sorted(stats)},
            **{
                f"mesh{m}_device_host_syncs_per_tick": round(
                    dev[m]["host_syncs_per_tick"], 2
                )
                for m in sorted(dev)
            },
            **{f"mesh{m}_device_reshards": dev[m]["reshards"] for m in sorted(dev)},
            **{
                f"mesh{m}_device_ticks_per_sec": round(dev[m]["tps"], 2)
                for m in sorted(dev)
            },
            "device_admissions": dev[min(dev)]["admissions"],
            "device_all_completed": all(
                d["completed"] == d["admissions"] and d["done"] for d in dev.values()
            ),
        },
    }
    return rows, metrics


# ---------------------------------------------------------------------------
# chaos drill: kill a shard mid-stream, restore onto the shrunken mesh
# ---------------------------------------------------------------------------
# Subprocess for the same reason as the mesh sweep: the 2-virtual-device
# XLA flag must be set before any jax import. The drill is the resilience
# subsystem end to end (runtime/resilience.py): a 2-shard device-control
# service snapshots SlotState + ControlState every checkpoint_period ticks;
# at tick `kill_at` one shard "fails" (SimulatedFailure), the supervisor
# re-plans the slot mesh on the survivor, recompiles, restores the latest
# snapshot with resharding, re-enqueues in-flight streams, and every
# stream must still converge.
_CHAOS_SNIPPET = """\
import os

os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count={device_count}"
import json
import tempfile
import time

import numpy as np

from repro.api import RecoverySpec, TickSpec
from repro.core.stream import StreamConfig
from repro.data.dynamics import generate_trajectory
from repro.runtime import ServiceSupervisor, kill_shard_once

scfg = StreamConfig(
    buf_len=32, window=8, stride=8, chunk=8, steps_per_tick=8,
    min_steps=16, max_steps=32, delta_tol=0.0,
)
spec = RecoverySpec(
    state_dim=3, input_dim=0, order=2, hidden=8, dense_hidden=16, dt=0.01,
    mode="stream", n_slots={slots}, stream=scfg, seed=0, mesh_slots=2,
    tick=TickSpec(steps_per_tick=8, control="device",
                  queue_capacity={streams}, snapshot_period=1,
                  warm_capacity={slots}),
)
ys = np.stack([
    generate_trajectory("lorenz", n_samples=400, noise_std=0.01, seed=i)[1]
    for i in range({streams})
]).astype(np.float32)
sup = ServiceSupervisor(spec, tempfile.mkdtemp(prefix="bench_chaos_"),
                        checkpoint_period={checkpoint_period},
                        chaos=kill_shard_once({kill_at}, n_lost=1))
t0 = time.perf_counter()
out = sup.serve(ys, max_ticks={max_ticks})
wall = time.perf_counter() - t0
print("CHAOSBENCH " + json.dumps({{
    "recovered_streams_fraction": out["recovered_streams_fraction"],
    "restarts": out["restarts"],
    "final_mesh": list(out["final_mesh"]),
    "ticks": out["ticks"],
    "p50_tick_ms": out["p50_tick_ms"],
    "p99_tick_ms": out["p99_tick_ms"],
    "wall_s": round(wall, 3),
    "n_streams": {streams},
}}))
"""


def run_chaos(
    slots: int = 4,
    streams: int = 6,
    kill_at: int = 3,
    checkpoint_period: int = 2,
    device_count: int = 2,
    smoke: bool = False,
):
    """Shard-loss recovery drill; gated ``recovered_streams_fraction``.

    A 2-shard device-control service loses one virtual device mid-stream;
    the ServiceSupervisor (runtime/resilience.py) restores the latest
    SlotState+ControlState snapshot onto the re-planned 1-device mesh and
    re-enqueues the in-flight streams. The gated metric is the fraction of
    submitted streams that still complete — pinned to EXACTLY 1.0 (floor
    AND ceiling in baselines.json): below means recovery dropped a stream,
    above means the accounting is broken. Deterministic (fixed seeds, no
    wall clock in the gated row); wall numbers land in info. Returns
    (csv_rows, metrics).
    """
    del smoke  # the drill is already smoke-sized; flag kept for symmetry
    prog = _CHAOS_SNIPPET.format(
        device_count=device_count,
        slots=slots,
        streams=streams,
        kill_at=kill_at,
        checkpoint_period=checkpoint_period,
        max_ticks=60,
    )
    stats = _run_cpu_child(prog, "CHAOSBENCH", "chaos-drill")
    frac = stats["recovered_streams_fraction"]
    rows = [
        (
            "stream/chaos_recovered_fraction",
            stats["wall_s"] * 1e6,
            f"{frac:.2f} of {stats['n_streams']} streams after losing 1/"
            f"{device_count} shards at tick {kill_at}; {stats['restarts']} "
            f"restart(s); final mesh {tuple(stats['final_mesh'])}; "
            f"p50={stats['p50_tick_ms']:.1f}ms p99={stats['p99_tick_ms']:.1f}ms",
        ),
    ]
    metrics = {
        "recovered_streams_fraction": frac,
        "info": {
            "n_streams": stats["n_streams"],
            "slots": slots,
            "kill_at_tick": kill_at,
            "checkpoint_period": checkpoint_period,
            "restarts": stats["restarts"],
            "final_mesh": stats["final_mesh"],
            "ticks": stats["ticks"],
            "p50_tick_ms": stats["p50_tick_ms"],
            "p99_tick_ms": stats["p99_tick_ms"],
            "wall_s": stats["wall_s"],
        },
    }
    return rows, metrics


def main(smoke: bool = False):
    rows, metrics = run(smoke=smoke)
    for name, us, derived in rows:
        emit(name, us, derived)
    banked_rows, banked_metrics = run_banked_tick(smoke=smoke)
    for name, us, derived in banked_rows:
        emit(name, us, derived)
    metrics["banked_tick"] = banked_metrics
    mesh_rows, mesh_metrics = run_mesh_scaling(smoke=smoke)
    for name, us, derived in mesh_rows:
        emit(name, us, derived)
    metrics["mesh"] = mesh_metrics
    chaos_rows, chaos_metrics = run_chaos(smoke=smoke)
    for name, us, derived in chaos_rows:
        emit(name, us, derived)
    metrics["chaos"] = chaos_metrics
    return metrics


if __name__ == "__main__":
    main()
