"""Bring-up smoke: the streaming model-recovery service on one TPU chip.

Drives the service's main path through the entry points a user calls —
``RecoverySpec`` -> ``compile_plan`` -> ``RecoveryService`` (core/stream.py,
kernels/mr_step) and ``launch/serve_mr.main`` — with weights and data made
from fixed seeds, checks every result against the repo's own references,
and prints one JSON object as its last line. Run it from the repository root
on a machine with a TPU:

    python chip_smoke.py           # phases A, B and C on one chip
    python chip_smoke.py --mesh4   # only the 4-chip slot mesh against 1 chip

Phases (one chip):

A  Deployment-width stream fleet: fused GRU, fp32, hidden 128, head 256,
   1024 slots serving lorenz / damped_oscillator / controlled_pendulum
   tenants. The banked and the composite tick each run two training ticks
   (the second evicts every stream on its step budget): their trained
   params must be bitwise equal and their Theta readouts equal at the
   fp32 kernel-parity tolerance. A serve tick (no training) of the fused
   spec is checked against the same spec with ``fused=False``.
B  ``launch/serve_mr.main`` on its CI acceptance scenario
   (``--plan --fused --streams 12 --slots 4``): every stream must land
   within the one-shot baseline tolerance.
C  The paper's headline path: batch-mode LTC recovery, fused, against the
   same spec with ``fused=False``. After 4 steps Theta must agree at the
   kernel-parity tolerance (the same math). After 300 steps the two
   trainings have drifted apart (Mosaic and XLA round differently, and
   300 optimizer steps amplify it), so there each system's recovered
   coefficients are scored against the true ones, as ``serve_mr`` scores
   a stream: the fused MSE must be within 3x the unfused MSE + 0.05.

``--mesh4`` runs only the phase-A fleet with ``mesh_slots=4`` and with
``mesh_slots=1`` and compares per-stream Theta between them.

Every plan must lower to Pallas on the chip: ``plan.lowering.dispatch`` is
``"pallas"`` and the compiled program holds a ``tpu_custom_call``. Every
phase multiplies at float32 matmul precision, as ``serve_mr`` does: on TPU,
XLA's default rounds float32 matmul operands to bfloat16, so fused and
unfused (Mosaic and XLA) would not compute the same math. A failed
check raises, so the script exits non-zero and prints no result; without a
TPU it exits non-zero at once, naming the platform JAX found. Times printed
on the way are smoke readings, not metrics.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
FLEET = ("lorenz", "damped_oscillator", "controlled_pendulum")
N_SLOTS, HIDDEN = 1024, 128  # phase-A deployment widths (head = 2 * hidden)
STEPS_PER_TICK = 8
PARITY = dict(atol=1e-4, rtol=1e-4)  # fp32 kernel-parity bound of the kernel tests
PARITY_STEPS, RECOVERY_STEPS = 4, 300  # phase C's two batch runs
TOL_FACTOR, TOL_ABS = 3.0, 0.05  # serve_mr's default recovery tolerance
SERVE_MR_CI = ["--plan", "--fused", "--streams", "12", "--slots", "4"]

_T0 = time.perf_counter()


def note(msg: str) -> None:
    print(f"[chip_smoke {time.perf_counter() - _T0:7.1f}s] {msg}", flush=True)


def require_tpu(n_chips: int):
    """The devices JAX found; exits non-zero unless they are ``n_chips``+ TPUs."""
    import jax

    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        raise SystemExit(
            f"chip_smoke: needs a TPU, but JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); nothing was run"
        )
    if len(devices) < n_chips:
        raise SystemExit(f"chip_smoke: needs {n_chips} TPU chips, JAX found {len(devices)}")
    return devices


def check(cond: bool, what: str) -> None:
    if not cond:
        raise AssertionError(f"chip_smoke check failed: {what}")
    note(f"ok: {what}")


def compiled_text(plan, program, *args) -> str:
    """Optimized HLO of a plan program (a partial over a jitted function),
    traced as the service calls it: inside the plan's slot mesh, if any."""
    from repro.core.stream import SLOT_RULES
    from repro.parallel import use_mesh_rules

    on_mesh = plan.mesh is not None
    with use_mesh_rules(plan.mesh, SLOT_RULES) if on_mesh else contextlib.nullcontext():
        return program.func.lower(*args, **program.keywords).compile().as_text()


def require_kernels(plan, program, *args) -> None:
    """The plan lowered to Pallas and its compiled program holds the kernel."""
    check(plan.lowering.dispatch == "pallas", f"{plan.spec.encoder} plan dispatch is 'pallas'")
    text = compiled_text(plan, program, *args)
    check("tpu_custom_call" in text, "compiled program has tpu_custom_call")


# ---------------------------------------------------------------------------
# phase A: the deployment-width stream fleet
# ---------------------------------------------------------------------------
def fleet_spec(
    n_slots: int,
    hidden: int,
    *,
    fused: bool = True,
    steps_per_tick: int = STEPS_PER_TICK,
    tick_kernel: str = "composite",
    mesh_slots: int = 1,
):
    """The phase-A stream spec. Training ticks spend every stream's step
    budget in two ticks, so the second tick evicts the whole fleet; a serve
    spec (``steps_per_tick=0``) never evicts."""
    from repro import api
    from repro.core.stream import StreamConfig

    budget = 2 * steps_per_tick or 1
    scfg = StreamConfig(steps_per_tick=steps_per_tick, min_steps=budget, max_steps=budget)
    return api.RecoverySpec(
        state_dim=3,
        input_dim=1,
        order=2,
        hidden=hidden,
        dense_hidden=2 * hidden,
        dt=0.01,
        encoder="gru",
        fused=fused,
        mode="stream",
        n_slots=n_slots,
        stream=scfg,
        tick=api.TickSpec(steps_per_tick=steps_per_tick, tick_kernel=tick_kernel),
        mesh_slots=mesh_slots,
    )


def make_fleet(n_streams: int, scfg, n_ticks: int = 2):
    from repro.launch.serve_mr import build_stream_fleet

    n_samples = scfg.buf_len + scfg.chunk * n_ticks
    _, ys, us, dims = build_stream_fleet(list(FLEET), n_streams, n_samples)
    assert dims == (3, 1, 2), dims  # the library shape fleet_spec declares
    return ys, us


def start_service(plan, ys, us):
    """A service with every stream submitted and admitted (slot s <- stream s)."""
    service = plan.make_service()
    L = plan.scfg.buf_len
    for i in range(ys.shape[0]):
        service.submit(i, ys[i, :L], us[i, :L])
    service.fill_slots()
    return service


def tick(service, ys, us, t: int) -> dict:
    """Tick ``t``: each live slot ingests its stream's next chunk."""
    import numpy as np

    L, C = service.scfg.buf_len, service.scfg.chunk
    sids = np.asarray(service.slot_streams())
    live = (sids >= 0)[:, None, None]
    rows = np.maximum(sids, 0)
    window = slice(L + t * C, L + (t + 1) * C)
    t0 = time.perf_counter()
    info = service.tick_once(ys[rows, window] * live, us[rows, window] * live)
    info["seconds"] = time.perf_counter() - t0
    return info


def tick_args(service):
    import jax
    import jax.numpy as jnp

    S, C = service.n_slots, service.scfg.chunk
    new_y = jnp.zeros((S, C, service.cfg.state_dim), jnp.float32)
    new_u = jnp.zeros((S, C, service.cfg.input_dim), jnp.float32)
    return service.state, new_y, new_u, jax.random.key(0)


def theta_by_stream(service):
    """Per-stream Theta of the live slots: {stream_id: [n_terms, n]}."""
    import numpy as np

    theta = np.asarray(service.state.theta)
    return {int(s): theta[i] for i, s in enumerate(np.asarray(service.state.stream_id)) if s >= 0}


def max_diff(a: dict, b: dict) -> float:
    import numpy as np

    check(a.keys() == b.keys() and len(a) > 0, f"same {len(a)} streams on both sides")
    return max(float(np.abs(a[k] - b[k]).max()) for k in a)


def require_close(a: dict, b: dict, what: str) -> None:
    import numpy as np

    d = max_diff(a, b)
    for k in a:
        np.testing.assert_allclose(a[k], b[k], **PARITY, err_msg=f"{what}: stream {k}")
    note(f"ok: {what}: max |dTheta| = {d!r} within atol=rtol=1e-4")


def run_training_fleet(spec, ys, us, *, kernel_check: bool = True):
    """Compile, admit the fleet and run its two training ticks.

    Returns (plan, service, Theta after tick 1, Theta of the evicted streams).
    """
    from repro import api

    t0 = time.perf_counter()
    plan = api.compile_plan(spec)
    service = start_service(plan, ys, us)
    note(f"{plan.lowering.tick_kernel} tick, mesh {spec.mesh_slots}: admitted "
         f"{ys.shape[0]} streams ({time.perf_counter() - t0:.1f}s, smoke reading)")
    if kernel_check:
        require_kernels(plan, plan.tick, *tick_args(service))
    t1 = tick(service, ys, us, 0)
    after_first = theta_by_stream(service)
    t2 = tick(service, ys, us, 1)
    check(len(t2["evicted"]) == ys.shape[0], f"tick 2 evicted all {ys.shape[0]} streams")
    note(
        f"{plan.lowering.tick_kernel} tick (bank {plan.lowering.tick_slots_per_bank}): "
        f"tick 1 {t1['seconds']:.3f}s incl. compile, tick 2 {t2['seconds']:.3f}s "
        f"incl. eviction (smoke readings)"
    )
    evicted = {r.stream_id: r.theta for r in service.results.values()}
    return plan, service, after_first, evicted


def phase_a(dev, n_slots: int = N_SLOTS, hidden: int = HIDDEN, kernel_check: bool = True):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro import api
    from repro.core.merinda import init_mr

    spec = fleet_spec(n_slots, hidden)
    n_params = sum(x.size for x in jax.tree.leaves(init_mr(jax.random.key(0), spec.to_mr_config())))
    note(
        f"A: {n_slots} slots x {n_params} params/slot; with the AdamW moments "
        f"{3 * 4 * n_params / 1e6:.2f} MB/slot, {3 * 4 * n_params * n_slots / 1e9:.2f} GB of "
        f"slot state (reckoned)"
    )
    ys, us = make_fleet(n_slots, spec.stream)

    # banked against composite: the same training program, two readout kernels
    _, composite, c1, c_evicted = run_training_fleet(spec, ys, us, kernel_check=kernel_check)
    banked_spec = dataclasses.replace(
        spec, tick=dataclasses.replace(spec.tick, tick_kernel="banked")
    )
    plan_b, banked, b1, b_evicted = run_training_fleet(
        banked_spec, ys, us, kernel_check=kernel_check
    )
    check(plan_b.lowering.tick_kernel == "banked", "banked tick resolved")
    same = jax.tree.map(
        lambda a, b: bool(jnp.array_equal(a, b)), composite.state.params, banked.state.params
    )
    check(all(jax.tree.leaves(same)), "trained params bitwise equal, banked vs composite")
    require_close(b1, c1, "banked vs composite Theta after tick 1")
    require_close(b_evicted, c_evicted, "banked vs composite Theta of evicted streams")
    check(np.isfinite(np.stack(list(c_evicted.values()))).all(), "evicted Theta finite")
    state_bytes = sum(x.nbytes for x in jax.tree.leaves(composite.state))
    note(f"A: slot state on device {state_bytes / 1e9:.3f} GB per service")
    del composite, banked

    # the fused serve tick against the unfused stage sequence
    readouts = {}
    for fused in (True, False):
        serve_spec = fleet_spec(n_slots, hidden, fused=fused, steps_per_tick=0)
        plan = api.compile_plan(serve_spec)
        service = start_service(plan, ys, us)
        if fused and kernel_check:
            require_kernels(plan, plan.tick, *tick_args(service))
        tick(service, ys, us, 0)
        readouts[fused] = theta_by_stream(service)
        del service
    require_close(readouts[True], readouts[False], "fused vs unfused serve-tick Theta")
    if dev.platform == "tpu":
        peak = dev.memory_stats()["peak_bytes_in_use"]
        note(f"A: peak_bytes_in_use {peak} ({peak / 1e9:.3f} GB)")


# ---------------------------------------------------------------------------
# phase B: the serve_mr entry point on its CI acceptance scenario
# ---------------------------------------------------------------------------
def phase_b(argv=SERVE_MR_CI, kernel_check: bool = True):
    from repro import api
    from repro.data.dynamics import get_system
    from repro.launch import serve_mr

    args = serve_mr.build_parser().parse_args(argv)
    systems = [get_system(n) for n in args.systems.split(",")]
    dims = tuple(max(getattr(s, f) for s in systems) for f in ("state_dim", "input_dim", "order"))
    if kernel_check:
        plan = api.compile_plan(serve_mr.service_spec(args, dims, systems[0].dt))
        require_kernels(plan, plan.tick, *tick_args(plan.make_service()))
    t0 = time.perf_counter()
    rc = serve_mr.main(list(argv))
    check(rc == 0, f"serve_mr {' '.join(argv)}: every stream within the baseline tolerance")
    note(f"B: serve_mr ran in {time.perf_counter() - t0:.1f}s (smoke reading)")


# ---------------------------------------------------------------------------
# phase C: batch-mode fused LTC recovery against the unfused stage sequence
# ---------------------------------------------------------------------------
def recovery_mse(thetas, norms, cfg) -> list[float]:
    """Per-system MSE of recovered Theta against the true coefficients, in
    physical units — ``serve_mr``'s score of a recovered stream."""
    import numpy as np

    from repro.core.library import denormalize_theta
    from repro.data.dynamics import embed_true_coef, get_system

    mse = []
    for name, theta, norm in zip(FLEET, thetas, norms):
        phys = denormalize_theta(
            theta,
            norm["mean"],
            norm["scale"],
            n_vars=cfg.state_dim + cfg.input_dim,
            order=cfg.order,
            n_state=cfg.state_dim,
        )
        truth = embed_true_coef(get_system(name), cfg.state_dim, cfg.input_dim, cfg.order)
        mse.append(float(np.mean((phys - truth) ** 2)))
    return mse


def phase_c(
    hidden: int = HIDDEN,
    steps: tuple[int, int] = (PARITY_STEPS, RECOVERY_STEPS),
    kernel_check: bool = True,
):
    import jax
    import numpy as np

    from repro import api
    from repro.core import engine

    ys_b, us_b, norms, cfg = engine.stack_systems(list(FLEET))
    spec = api.RecoverySpec(
        state_dim=cfg.state_dim,
        input_dim=cfg.input_dim,
        order=cfg.order,
        hidden=hidden,
        dense_hidden=2 * hidden,
        dt=cfg.dt,
        encoder="ltc",
        fused=True,
        mode="batch",
        batch_size=64,
    )
    thetas = {}
    for n_steps in steps:
        for fused in (True, False):
            plan = api.compile_plan(dataclasses.replace(spec, fused=fused, steps=n_steps))
            if fused and kernel_check:
                keys = engine.system_keys(spec.seed, ys_b.shape[0])
                require_kernels(plan, plan.programs["recover_many"], ys_b, us_b, keys, spec.lr)
            t0 = time.perf_counter()
            theta = np.asarray(jax.block_until_ready(plan.run_batch(ys_b, us_b)))
            thetas[n_steps, fused] = theta
            note(f"C: fused={fused} {n_steps} steps x {ys_b.shape[0]} systems "
                 f"{time.perf_counter() - t0:.1f}s incl. compile (smoke reading)")
            check(np.isfinite(theta).all(), f"C: LTC Theta finite (fused={fused})")
    short, long = steps
    require_close(
        dict(zip(FLEET, thetas[short, True])),
        dict(zip(FLEET, thetas[short, False])),
        f"C: fused vs unfused LTC Theta after {short} steps",
    )
    drift = float(np.abs(thetas[long, True] - thetas[long, False]).max())
    note(f"C: after {long} steps max |dTheta| = {drift!r}, max |Theta| = "
         f"{float(np.abs(thetas[long, False]).max())!r} (fused vs unfused)")
    fused_mse, unfused_mse = (recovery_mse(thetas[long, f], norms, cfg) for f in (True, False))
    for name, mf, mu in zip(FLEET, fused_mse, unfused_mse):
        check(
            mf <= TOL_FACTOR * mu + TOL_ABS,
            f"C: {name} fused LTC recovery mse {mf!r} <= 3 x unfused {mu!r} + 0.05",
        )


# ---------------------------------------------------------------------------
# --mesh4: the sharded slot axis on four chips against one
# ---------------------------------------------------------------------------
def phase_mesh4(n_slots: int = N_SLOTS, hidden: int = HIDDEN, kernel_check: bool = True):
    spec1 = fleet_spec(n_slots, hidden)
    ys, us = make_fleet(n_slots, spec1.stream)
    runs = {}
    for mesh in (4, 1):
        spec = dataclasses.replace(spec1, mesh_slots=mesh)
        plan, service, first, evicted = run_training_fleet(spec, ys, us, kernel_check=kernel_check)
        if mesh > 1:
            check("slots" in str(service.state.theta.sharding), "slot state sharded over the mesh")
        runs[mesh] = (first, evicted)
        del service
    require_close(runs[4][0], runs[1][0], "mesh 4 vs mesh 1 Theta after tick 1")
    require_close(runs[4][1], runs[1][1], "mesh 4 vs mesh 1 Theta of evicted streams")


def main(argv: list[str] | None = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n", 1)[0])
    ap.add_argument(
        "--mesh4",
        action="store_true",
        help="run only the phase-A fleet sharded over 4 chips, against 1 chip",
    )
    args = ap.parse_args(argv)
    devices = require_tpu(4 if args.mesh4 else 1)
    dev = devices[0]
    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    note(f"device {dev.device_kind} x {len(devices)}; compile cache {enable_compile_cache()}")
    with jax.default_matmul_precision("float32"):
        if args.mesh4:
            phase_mesh4()
        else:
            phase_a(dev)
            phase_b()
            phase_c()
    device = {"platform": dev.platform, "kind": dev.device_kind, "count": len(devices)}
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
