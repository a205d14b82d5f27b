"""Online model-recovery service driver: many streams, few slots, one program.

The MR analogue of launch/serve.py's continuous-batching LM decode loop:
``--streams`` dynamical-system streams are queued into ``--slots`` service
slots (core/stream.py); every tick ingests a fresh observation chunk into
each slot's ring buffer and runs ``--steps-per-tick`` scan-jitted recovery
steps for ALL slots inside one donated, jit-cached program. Slots whose
coefficient estimate stops moving (relative delta below ``--delta-tol``) are
evicted and refilled from the queue; evicted params feed a warm-start
registry.

On exit, every recovered Theta is scored against the system's ground truth
(physical units, data/dynamics.embed_true_coef) and must beat the one-shot
baseline tolerance — streaming ingestion must not cost recovery quality.
The tolerance anchors on the per-system MEDIAN one-shot MSE (a single
baseline draw spreads ~10x on chaotic systems, which would flip the check
on baseline luck rather than streaming quality).

CPU demo (the CI acceptance configuration):

    PYTHONPATH=src python -m repro.launch.serve_mr \
        --streams 12 --slots 4 --steps-per-tick 8

``--plan`` builds the service through the declarative surface instead of
hand-plumbed configs: one ``repro.api.RecoverySpec`` (encoder, precision,
fusion, slots, mesh) is compiled by ``api.compile_plan`` into a
``RecoveryPlan``, and this driver becomes a thin consumer. ``--mesh D``
(requires ``--plan``) shards ``SlotState`` over a D-device mesh along the
slot axis; ``--virtual-devices N`` exports
``XLA_FLAGS=--xla_force_host_platform_device_count=N`` BEFORE jax loads, so
the sharded service runs on CPU virtual devices in CI:

    PYTHONPATH=src python -m repro.launch.serve_mr \
        --plan --mesh 2 --virtual-devices 2 --streams 12 --slots 4

``--fused`` runs every tick's per-window recovery stage through the
stage-fused kernels/mr_step step (encode + RMS-norm + dense head as ONE
dispatch with VMEM-resident hidden state; reference math off-TPU);
``--quant`` additionally serves every evicted stream's coefficients through
the fused fixed-point stage (kernels/mr_step int8: quantized gate + head
weights, PWL activations) — the paper's fixed-point serving configuration
end to end. ``--encoder`` picks the registry row; the multi-substep
families take their fused-solver mr_step variants under ``--fused``, so the
paper's headline LTC baseline runs the acceptance scenario fused:

    PYTHONPATH=src python -m repro.launch.serve_mr \
        --plan --fused --encoder ltc --streams 12 --slots 4

``--tick-kernel banked`` (requires ``--plan``) compiles the one-kernel
banked service tick (kernels/mr_step/tick.py): ring ingest, window
substeps, head and EMA readout as a single slot-banked program with a
packed one-readback status — the CI banked serve scenario:

    PYTHONPATH=src python -m repro.launch.serve_mr \
        --plan --tick-kernel banked --streams 12 --slots 4

``--control device`` (requires ``--plan``) serves through the
device-resident control plane (core/control.py): admission waits in
per-shard on-device rings, eviction and queue refill and the warm-start
gather all run inside the tick program, and the host only reads back a
packed status + event-log snapshot every ``--snapshot-period`` ticks — so
a steady-state tick is ONE donated program with zero readbacks between
snapshots, and admission never re-shards the slot axis. The CI
device-resident sharded serve scenario:

    PYTHONPATH=src python -m repro.launch.serve_mr \
        --plan --control device --mesh 2 --virtual-devices 2 \
        --streams 12 --slots 4

Heavy imports happen inside the entry points (after ``--virtual-devices``
has set XLA_FLAGS), never at module import time.
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

DEFAULT_SYSTEMS = "lorenz,damped_oscillator,controlled_pendulum"


def build_stream_fleet(
    names: list[str],
    n_streams: int,
    n_samples: int,
    noise: float = 0.01,
    seed: int = 0,
):
    """Generate ``n_streams`` trajectories cycling over ``names``, zero-padded
    to the fleet's common (n_state, n_input) dims.

    Returns (spec_per_stream, ys [R, T_total, n], us [R, T_total, m],
    (n_state, n_input, order)). Each stream gets its own noise seed, so two
    streams of the same system are distinct tenants.
    """
    from repro.data.dynamics import add_sensor_noise, generate_trajectory, get_system

    specs = [get_system(n) for n in names]
    dts = {s.dt for s in specs}
    if len(dts) > 1:
        raise ValueError(f"streams must share a sampling dt, got {sorted(dts)}")
    n_max = max(s.state_dim for s in specs)
    m_max = max(s.input_dim for s in specs)
    order = max(s.order for s in specs)
    # one integration per system: tenants of a system share the clean
    # trajectory and differ in their sensor-noise draw
    clean = {s.name: generate_trajectory(s.name, n_samples=n_samples)[1:] for s in specs}
    stream_specs, ys_all, us_all = [], [], []
    for i in range(n_streams):
        spec = specs[i % len(specs)]
        ys, us = clean[spec.name]
        ys = add_sensor_noise(ys, noise, seed + i)
        ys = np.pad(ys, ((0, 0), (0, n_max - spec.state_dim)))
        us = np.pad(us, ((0, 0), (0, m_max - us.shape[-1]))) if m_max else np.zeros((len(ys), 0))
        stream_specs.append(spec)
        ys_all.append(ys)
        us_all.append(us)
    return (
        stream_specs,
        np.stack(ys_all).astype(np.float32),
        np.stack(us_all).astype(np.float32),
        (n_max, m_max, order),
    )


def _theta_mse(theta_phys: np.ndarray, theta_true: np.ndarray) -> float:
    return float(np.mean((theta_phys - theta_true) ** 2))


def run_service(
    service,
    ys: np.ndarray,  # [R, T_total, n]
    us: np.ndarray,  # [R, T_total, m]
    max_ticks: int,
    verbose: bool = True,
) -> dict:
    """Feed all streams through the service until the queue drains.

    Returns {"ticks", "wall_s", "evictions"}. Stream cursors wrap modulo the
    generated trajectory length, so a slow-converging stream never starves.
    """
    n_streams, t_total = ys.shape[:2]
    scfg, cfg = service.scfg, service.cfg
    slots, chunk = service.n_slots, scfg.chunk
    for i in range(n_streams):
        service.submit(i, ys[i, : scfg.buf_len], us[i, : scfg.buf_len])
    service.fill_slots()
    cursors = dict.fromkeys(range(n_streams), scfg.buf_len)
    evictions: list = []
    t0 = time.time()
    while not service.done and service.ticks < max_ticks:
        chunks_y = np.zeros((slots, chunk, cfg.state_dim), np.float32)
        chunks_u = np.zeros((slots, chunk, cfg.input_dim), np.float32)
        for s, sid in enumerate(service.slot_streams()):
            if sid < 0:
                continue
            idx = (cursors[sid] + np.arange(chunk)) % t_total
            chunks_y[s] = ys[sid, idx]
            chunks_u[s] = us[sid, idx]
            cursors[sid] += chunk
        info = service.tick_once(chunks_y, chunks_u)
        for res in info["evicted"]:
            evictions.append(res)
            if verbose:
                print(
                    f"  tick {info['tick']:4d}: evict stream {res.stream_id:3d} "
                    f"({res.reason}, {res.steps} steps) -> admit next; "
                    f"active={info['active']}"
                )
    return {"ticks": service.ticks, "wall_s": time.time() - t0, "evictions": evictions}


def build_parser() -> argparse.ArgumentParser:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument("--systems", default=DEFAULT_SYSTEMS, metavar="SYS[,SYS...]")
    ap.add_argument("--streams", type=int, default=12)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--steps-per-tick", type=int, default=8)
    ap.add_argument("--window", type=int, default=32)
    ap.add_argument("--stride", type=int, default=8)
    ap.add_argument("--buf-len", type=int, default=160)
    ap.add_argument("--chunk", type=int, default=16)
    ap.add_argument("--hidden", type=int, default=32)
    ap.add_argument(
        "--encoder",
        default="gru",
        help="any core/encoders.py registry row (gru, gru_flow, ltc, node, ...); "
        "with --fused the multi-substep families run the fused-solver "
        "kernels/mr_step variants",
    )
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--noise", type=float, default=0.01)
    ap.add_argument("--delta-tol", type=float, default=0.015)
    ap.add_argument("--min-steps", type=int, default=128)
    ap.add_argument("--max-steps", type=int, default=400)
    ap.add_argument("--max-ticks", type=int, default=1200)
    ap.add_argument("--quant", action="store_true", help="int8/PWL kernel readout at eviction")
    ap.add_argument(
        "--fused",
        action="store_true",
        help="stage-fused per-window recovery step (kernels/mr_step) in every tick",
    )
    ap.add_argument(
        "--plan",
        action="store_true",
        help="build the service through repro.api (RecoverySpec -> compile_plan)",
    )
    ap.add_argument(
        "--tick-kernel",
        choices=("auto", "banked", "composite"),
        default="composite",
        help="service-tick structure (requires --plan for non-composite): "
        "'banked' = one-kernel mr_tick serving segment (kernels/mr_step/tick.py), "
        "'auto' resolves from the tick-level VMEM model",
    )
    ap.add_argument(
        "--mesh",
        type=int,
        default=1,
        help="devices sharding the slot axis (requires --plan; 1 = single device)",
    )
    ap.add_argument(
        "--control",
        choices=("host", "device"),
        default="host",
        help="service control plane (requires --plan for 'device'): 'device' "
        "keeps admission queues, eviction and warm-start lookup on-device "
        "(core/control.py), so steady-state ticks run with zero host readbacks "
        "between snapshots and admission never re-shards the slot axis",
    )
    ap.add_argument(
        "--snapshot-period",
        type=int,
        default=1,
        help="device control plane: ticks between status/event-log snapshots. "
        "This driver routes per-stream chunks from the snapshot's slot map, so "
        "the default is 1 (every tick); raise it only when streams share input "
        "feeds and stale routing for N-1 ticks is acceptable",
    )
    ap.add_argument(
        "--queue-capacity",
        type=int,
        default=0,
        help="device control plane: per-shard admission ring capacity "
        "(0 = auto, sized so every stream can wait at once)",
    )
    ap.add_argument(
        "--audit",
        choices=("off", "warn", "error"),
        default="off",
        help="static HLO-contract audit of the compiled plan (requires --plan): "
        "warn prints findings, error refuses to serve a violating plan",
    )
    ap.add_argument(
        "--tune",
        choices=("off", "static", "measured"),
        default="off",
        help="measured-cost autotuning of the plan's lowering (requires --plan; "
        "analysis/tuner.py): 'static' records the candidate table through the "
        "VMEM model, 'measured' lowers + scores every candidate and caches "
        "the decision on disk (warm recompiles pay zero search cost)",
    )
    ap.add_argument(
        "--virtual-devices",
        type=int,
        default=0,
        help="set XLA_FLAGS host-platform device count before jax loads (CPU CI)",
    )
    ap.add_argument(
        "--checkpoint-dir",
        default=None,
        help="service snapshot directory (runtime/resilience.py); with "
        "--checkpoint-period > 0 the service snapshots SlotState + "
        "ControlState + the warm cache there, async and atomic",
    )
    ap.add_argument(
        "--checkpoint-period",
        type=int,
        default=0,
        help="ticks between service snapshots (0 = checkpointing off; "
        "requires --checkpoint-dir)",
    )
    ap.add_argument(
        "--chaos-kill-shard",
        type=int,
        default=-1,
        metavar="TICK",
        help="chaos injection (requires --plan): lose one device at TICK; the "
        "service supervisor re-plans the slot mesh on the survivors, restores "
        "the latest snapshot with resharding and re-submits dropped streams",
    )
    ap.add_argument(
        "--max-restarts",
        type=int,
        default=4,
        help="supervised-restart budget for the chaos/recovery path",
    )
    ap.add_argument(
        "--tol-factor",
        type=float,
        default=3.0,
        help="pass if stream MSE <= factor * per-system MEDIAN one-shot MSE + tol-abs",
    )
    ap.add_argument("--tol-abs", type=float, default=0.05)
    ap.add_argument("--seed", type=int, default=0)
    return ap


def service_spec(args, dims, dt: float, *, ckpt_dir=None, ckpt_period: int = 0):
    """The stream-mode RecoverySpec the parsed flags describe, for a fleet
    of library shape ``dims = (n_state, n_input, order)`` sampled at ``dt``."""
    from repro import api
    from repro.core.stream import StreamConfig

    n_state, n_input, order = dims
    scfg = StreamConfig(
        buf_len=args.buf_len,
        window=args.window,
        stride=args.stride,
        chunk=args.chunk,
        steps_per_tick=args.steps_per_tick,
        lr=args.lr,
        delta_tol=args.delta_tol,
        min_steps=args.min_steps,
        max_steps=args.max_steps,
    )
    return api.RecoverySpec(
        state_dim=n_state,
        input_dim=n_input,
        order=order,
        hidden=args.hidden,
        dense_hidden=2 * args.hidden,
        dt=dt,
        encoder=args.encoder,
        precision="int8_pwl" if args.quant else "fp32",
        fused=args.fused,
        mode="stream",
        lr=args.lr,
        seed=args.seed,
        n_slots=args.slots,
        stream=scfg,
        # the loose tick flags are a thin mapping onto TickSpec: geometry
        # (steps_per_tick/ema) mirrors the StreamConfig above, the kernel
        # choice is the only new degree of freedom
        tick=api.TickSpec(
            steps_per_tick=args.steps_per_tick,
            tick_kernel=args.tick_kernel,
            control=args.control,
            queue_capacity=args.queue_capacity or max(args.streams, 1),
            snapshot_period=args.snapshot_period,
            checkpoint_period=ckpt_period,
            checkpoint_dir=ckpt_dir,
        ),
        mesh_slots=args.mesh,
    )


def main(argv: list[str] | None = None) -> int:
    args = build_parser().parse_args(argv)
    if args.virtual_devices:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.virtual_devices} "
            + os.environ.get("XLA_FLAGS", "")
        ).strip()
    if args.mesh > 1 and not args.plan:
        raise SystemExit("--mesh requires --plan (the sharded service is plan-compiled)")
    if args.audit != "off" and not args.plan:
        raise SystemExit("--audit requires --plan (only compiled plans are auditable)")
    if args.tune != "off" and not args.plan:
        raise SystemExit("--tune requires --plan (only compiled plans are tunable)")
    if args.tick_kernel != "composite" and not args.plan:
        raise SystemExit(
            "--tick-kernel requires --plan (the tick program is plan-compiled; "
            "the legacy service binds the composite tick internally)"
        )
    if args.control == "device" and not args.plan:
        raise SystemExit(
            "--control device requires --plan (the control-plane programs are "
            "plan-compiled; the legacy service is host-driven)"
        )
    if args.chaos_kill_shard >= 0 and not args.plan:
        raise SystemExit(
            "--chaos-kill-shard requires --plan (the supervisor recompiles the "
            "plan on the surviving mesh)"
        )

    # jax loads HERE, after the virtual-device environment is pinned
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    # the recovery math is float32: on TPU, XLA's default multiplies float32
    # matrices in one bfloat16 pass, which moves a stream's convergence far
    # enough to fail the baseline check below
    with jax.default_matmul_precision("float32"):
        return _serve(args)


def _serve(args) -> int:
    """Serve the fleet the flags describe, then hold every recovered stream
    to the one-shot batch baseline; 0 when all are within tolerance."""
    from repro import api
    from repro.core.stream import RecoveryService
    from repro.data.dynamics import embed_true_coef

    names = [s.strip() for s in args.systems.split(",") if s.strip()]
    # enough samples that max_steps' worth of ticks never wraps mid-stream
    n_samples = args.buf_len + args.chunk * (args.max_steps // args.steps_per_tick + 2)
    specs, ys, us, (n_state, n_input, order) = build_stream_fleet(
        names, args.streams, n_samples, noise=args.noise, seed=args.seed
    )
    ckpt_dir, ckpt_period = args.checkpoint_dir, args.checkpoint_period
    if args.chaos_kill_shard >= 0:
        # the chaos path needs snapshots to restore from: default a temp
        # directory + a 2-tick cadence when the flags don't pin them
        import tempfile

        ckpt_dir = ckpt_dir or tempfile.mkdtemp(prefix="serve_mr_ckpt_")
        ckpt_period = ckpt_period or 2
    spec = service_spec(
        args, (n_state, n_input, order), specs[0].dt, ckpt_dir=ckpt_dir, ckpt_period=ckpt_period
    )
    scfg = spec.stream
    supervisor = None
    if args.chaos_kill_shard >= 0:
        from repro.runtime import ServiceSupervisor, kill_shard_once

        supervisor = ServiceSupervisor(
            spec,
            ckpt_dir,
            checkpoint_period=ckpt_period,
            max_restarts=args.max_restarts,
            chaos=kill_shard_once(args.chaos_kill_shard),
        )
        service = supervisor.service
        print(f"[serve_mr] plan lowering: {supervisor.plan.lowering}")
    elif args.plan:
        plan = api.compile_plan(spec, audit=args.audit, tune=args.tune)
        service = plan.make_service()
        print(f"[serve_mr] plan lowering: {plan.lowering}")
    else:
        # legacy construction path (deprecated; kept for compatibility) —
        # same declarative record, direct service construction
        service = RecoveryService(
            spec.to_mr_config(), scfg, args.slots, seed=args.seed, quant=args.quant
        )
    cfg = service.cfg
    print(
        f"[serve_mr] streams={args.streams} slots={args.slots} "
        f"K={args.steps_per_tick} windows/slot={scfg.n_windows} "
        f"library={cfg.n_terms}x{cfg.state_dim} encoder={args.encoder} "
        f"fused={args.fused} quant={args.quant} mesh={args.mesh if args.plan else 1}"
    )
    if supervisor is not None:
        t0 = time.time()
        summary = supervisor.serve(ys, us if n_input else None, max_ticks=args.max_ticks)
        service = supervisor.service
        results = summary["results"]
        stats = {"ticks": summary["ticks"], "wall_s": time.time() - t0}
        tick_ms = [t for h in supervisor.history for t in h["tick_ms"]]
        straggler_flags = summary["straggler_flags"]
        print(
            f"[serve_mr] chaos: {summary['restarts']} restart(s), final mesh "
            f"{summary['final_mesh']}, recovered_streams_fraction="
            f"{summary['recovered_streams_fraction']:.2f}"
        )
    else:
        stats = run_service(service, ys, us, args.max_ticks)
        results = service.results
        tick_ms = service.tick_ms
        straggler_flags = service.straggler_flags
    n_done = len(results)
    print(
        f"[serve_mr] {n_done}/{args.streams} streams recovered in {stats['ticks']} ticks "
        f"({stats['wall_s']:.1f}s, {stats['ticks'] / max(stats['wall_s'], 1e-9):.1f} ticks/s)"
    )
    if tick_ms:
        print(
            f"[serve_mr] tick latency: p50={float(np.percentile(tick_ms, 50)):.1f}ms "
            f"p99={float(np.percentile(tick_ms, 99)):.1f}ms; "
            f"stragglers={','.join(straggler_flags) or 'none'}"
        )
    if service.sync_log:
        print(
            f"[serve_mr] host boundary ({args.control if args.plan else 'host'} "
            f"control plane): {service.counters['host_syncs']} syncs, "
            f"{service.counters['reshards']} reshards; "
            f"median {float(np.median(service.sync_log)):.1f} syncs/tick"
        )
    if n_done < args.streams:
        print(f"[serve_mr] FAIL: {args.streams - n_done} streams never recovered")
        return 1

    # one-shot baseline: a batch-mode plan over each stream's initial history,
    # same step budget — the quality bar streaming ingestion must not fall below
    import dataclasses

    from repro.core.library import denormalize_theta
    from repro.data.windows import make_windows

    yw_b, uw_b, norms = [], [], []
    for i, sysspec in enumerate(specs):
        hist_y = ys[i, : scfg.buf_len, : sysspec.state_dim]
        hist_u = us[i, : scfg.buf_len] if n_input else None
        yw, uw, norm = make_windows(hist_y, hist_u, window=scfg.window, stride=scfg.stride)
        yw = np.pad(yw, ((0, 0), (0, 0), (0, n_state - sysspec.state_dim)))
        yw_b.append(yw)
        if n_input:
            uw_b.append(uw if uw is not None else np.zeros(yw.shape[:2] + (n_input,), np.float32))
        norms.append(norm)
    base_spec = dataclasses.replace(
        spec,
        mode="batch",
        precision="fp32",
        steps=scfg.max_steps,
        stream=None,
        tick=None,
        mesh_slots=1,
    )
    base_plan = api.compile_plan(base_spec)
    t0 = time.time()
    theta_base = np.asarray(
        base_plan.run_batch(np.stack(yw_b), np.stack(uw_b) if n_input else None)
    )
    print(f"[serve_mr] one-shot batch-plan baseline: {time.time() - t0:.1f}s")

    n_vars = n_state + n_input
    mse_srv, mse_base = [], []
    for i, sysspec in enumerate(specs):
        truth = embed_true_coef(sysspec, n_state, n_input, order)
        res = results[i]
        th_srv = denormalize_theta(
            res.theta, res.mean, res.scale, n_vars=n_vars, order=order, n_state=n_state
        )
        th_base = denormalize_theta(
            theta_base[i],
            norms[i]["mean"],
            norms[i]["scale"],
            n_vars=n_vars,
            order=order,
            n_state=n_state,
        )
        mse_srv.append(_theta_mse(th_srv, truth))
        mse_base.append(_theta_mse(th_base, truth))
    # tolerance anchors on the PER-SYSTEM MEDIAN baseline: one-shot MSE on a
    # chaotic system spreads ~10x across noise draws (measured 3.6-46 for
    # lorenz), so a per-stream anchor flips the check on a single lucky
    # baseline draw even when the streaming estimates are tightly clustered
    med_base = {
        s.name: float(np.median([b for sp, b in zip(specs, mse_base) if sp.name == s.name]))
        for s in specs
    }
    failures = 0
    for i, sysspec in enumerate(specs):
        res = results[i]
        mse_s, mse_b = mse_srv[i], mse_base[i]
        tol = args.tol_factor * med_base[sysspec.name] + args.tol_abs
        ok = mse_s <= tol
        failures += not ok
        print(
            f"  stream {i:3d} {sysspec.name:22s} mse={mse_s:8.4f} "
            f"baseline={mse_b:8.4f} tol={tol:8.4f} steps={res.steps:4d} "
            f"{res.reason:9s} {'ok' if ok else 'FAIL'}"
        )
    if failures:
        print(f"[serve_mr] FAIL: {failures}/{args.streams} streams above baseline tolerance")
        return 1
    print(f"[serve_mr] OK: all {args.streams} streams within baseline tolerance")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
