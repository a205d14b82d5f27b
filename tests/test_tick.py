"""Banked one-kernel service tick (kernels/mr_step/tick.py + TickSpec).

Pins the mr_tick kernel family against the ref.py oracle (fp32 + int8/PWL,
sweep over encoder x input_dim x slots_per_bank), the plan-level
banked-vs-composite service parity (params bitwise, theta/delta <= 1e-5),
the packed-status host-sync drop, TickSpec validation and "auto" kernel
resolution through the tick-level VMEM residency model, and the tick-level
R2 audit cell.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.api import RecoverySpec, TickSpec
from repro.core import stream
from repro.core.merinda import MRConfig, init_mr
from repro.core.stream import StreamConfig
from repro.data.dynamics import generate_trajectory
from repro.kernels.mr_step import tiling
from repro.kernels.mr_step.tick import mr_tick, tick_supported

# serve-only geometry: 3 windows per buffer, no optimizer steps in the tick
TCFG = StreamConfig(
    buf_len=16, window=8, stride=4, chunk=4, steps_per_tick=0, min_steps=10**9, max_steps=10**9
)
BASE = dict(state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01)


def _mr_cfg(encoder="gru", m=0):
    return MRConfig(input_dim=m, encoder=encoder, **BASE)


def _tick_inputs(cfg, scfg, S, seed=0):
    """Random slot-stacked operands for a direct mr_tick call."""
    key = jax.random.key(seed)
    keys = jax.random.split(key, S + 7)
    params = jax.vmap(lambda k: init_mr(k, cfg))(keys[:S])
    n, m, L, C = cfg.state_dim, cfg.input_dim, scfg.buf_len, scfg.chunk
    buf_y = jax.random.normal(keys[S], (S, L, n))
    buf_u = jax.random.normal(keys[S + 1], (S, L, m))
    new_y = jax.random.normal(keys[S + 2], (S, C, n))
    new_u = jax.random.normal(keys[S + 3], (S, C, m))
    mean = jax.random.normal(keys[S + 4], (S, n)) * 0.1
    scale = jax.random.uniform(keys[S + 5], (S, n), minval=0.5, maxval=1.5)
    theta_prev = jax.random.normal(keys[S + 6], (S, cfg.n_terms, n)) * 0.3
    seed_flags = jnp.asarray([True, False] * (S // 2))
    active = jnp.asarray([True] * (S - 1) + [False])
    return params, buf_y, buf_u, new_y, new_u, mean, scale, theta_prev, seed_flags, active


def _run_tick(cfg, scfg, S, *, quant=False, slots_per_bank=1, **dispatch):
    ops = _tick_inputs(cfg, scfg, S)
    return mr_tick(
        ops[0], cfg, scfg, *ops[1:], quant=quant, slots_per_bank=slots_per_bank, **dispatch
    )


# ---------------------------------------------------------------------------
# kernel vs reference oracle: fp32 sweep over encoder x input_dim x bank size
# ---------------------------------------------------------------------------
@pytest.mark.parametrize(
    "encoder,m,spb",
    [
        ("gru", 0, 1),
        ("gru", 2, 2),
        ("gru", 0, 4),
        ("gru_flow", 0, 2),
        ("gru_flow", 2, 1),
    ],
)
def test_mr_tick_interpret_matches_reference(encoder, m, spb):
    cfg = _mr_cfg(encoder, m)
    ref = _run_tick(cfg, TCFG, 4, slots_per_bank=spb, force_reference=True)
    ker = _run_tick(cfg, TCFG, 4, slots_per_bank=spb, interpret=True)
    for r, k, name in zip(ref, ker, ("buf_y", "buf_u", "theta", "delta")):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r), atol=1e-5, err_msg=name)


def test_mr_tick_inactive_slot_reports_inf_delta():
    cfg = _mr_cfg()
    *_, delta = _run_tick(cfg, TCFG, 4, interpret=True)
    assert np.isinf(np.asarray(delta)[-1])  # _tick_inputs deactivates the last slot
    assert np.isfinite(np.asarray(delta)[:-1]).all()


def test_mr_tick_rolls_buffers():
    cfg = _mr_cfg(m=2)
    ops = _tick_inputs(cfg, TCFG, 4)
    buf_y2, buf_u2, _, _ = mr_tick(ops[0], cfg, TCFG, *ops[1:], interpret=True)
    C = TCFG.chunk
    np.testing.assert_allclose(np.asarray(buf_y2[:, :-C]), np.asarray(ops[1][:, C:]), atol=0)
    np.testing.assert_allclose(np.asarray(buf_y2[:, -C:]), np.asarray(ops[3]), atol=0)
    np.testing.assert_allclose(np.asarray(buf_u2[:, -C:]), np.asarray(ops[4]), atol=0)


# ---------------------------------------------------------------------------
# int8/PWL serving twin
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("m,spb", [(0, 1), (2, 2)])
def test_mr_tick_int8_interpret_matches_reference(m, spb):
    cfg = _mr_cfg("gru", m)
    ref = _run_tick(cfg, TCFG, 4, quant=True, slots_per_bank=spb, force_reference=True)
    ker = _run_tick(cfg, TCFG, 4, quant=True, slots_per_bank=spb, interpret=True)
    for r, k, name in zip(ref, ker, ("buf_y", "buf_u", "theta", "delta")):
        np.testing.assert_allclose(np.asarray(k), np.asarray(r), atol=1e-5, err_msg=name)


def test_mr_tick_int8_tracks_fp32():
    cfg = _mr_cfg("gru")
    theta_f = np.asarray(_run_tick(cfg, TCFG, 4, interpret=True)[2])
    theta_q = np.asarray(_run_tick(cfg, TCFG, 4, quant=True, interpret=True)[2])
    assert np.max(np.abs(theta_q - theta_f)) < 0.25  # int8+PWL vs fp32 readout


def test_mr_tick_rejects_unsupported_family():
    assert not tick_supported(MRConfig(encoder="ltc", **BASE))
    assert tick_supported(_mr_cfg("gru_flow"))
    assert not tick_supported(_mr_cfg("gru_flow"), int8=True)  # PWL = standard gru only
    with pytest.raises(ValueError, match="GRU"):
        _run_tick(MRConfig(encoder="ltc", **BASE), TCFG, 4, force_reference=True)


# ---------------------------------------------------------------------------
# plan-level parity: banked vs composite service, lockstep ticks
# ---------------------------------------------------------------------------
SCFG = StreamConfig(
    buf_len=32, window=8, stride=8, chunk=8, steps_per_tick=0, min_steps=10**9, max_steps=10**9
)


def _spec(**overrides):
    base = dict(mode="stream", n_slots=2, stream=SCFG, encoder="gru", seed=0, **BASE)
    base.update(overrides)
    return RecoverySpec(**base)


def _tick_for(scfg):
    return lambda kernel: TickSpec(
        steps_per_tick=scfg.steps_per_tick, ema_decay=scfg.ema, tick_kernel=kernel
    )


@pytest.fixture(scope="module")
def lorenz():
    _, ys, _ = generate_trajectory("lorenz", n_samples=200)
    return ys


@pytest.mark.parametrize("k", [0, 2])
def test_banked_matches_composite_service(lorenz, k):
    """Same spec, same data: the banked tick's params stay bitwise the
    composite tick's (K > 0 reuses its training scan verbatim) and the
    one-kernel serving segment reproduces theta/delta to 1e-5."""
    scfg = dataclasses.replace(SCFG, steps_per_tick=k)
    services = {}
    for kernel in ("banked", "composite"):
        spec = _spec(stream=scfg, tick=_tick_for(scfg)(kernel))
        svc = api.compile_plan(spec).make_service()
        for sid in range(2):
            svc.submit(sid, lorenz[sid : sid + scfg.buf_len])
        svc.fill_slots()
        services[kernel] = svc
    for t in range(3):
        idx = scfg.buf_len + t * scfg.chunk + np.arange(scfg.chunk)
        chunk = np.repeat(lorenz[idx][None], 2, axis=0)
        info_b = services["banked"].tick_once(chunk)
        info_c = services["composite"].tick_once(chunk)
        np.testing.assert_allclose(info_b["delta"], info_c["delta"], atol=1e-5)
    sb, sc = services["banked"].state, services["composite"].state
    for lb, lc in zip(jax.tree.leaves(sb.params), jax.tree.leaves(sc.params)):
        np.testing.assert_array_equal(np.asarray(lb), np.asarray(lc))
    np.testing.assert_allclose(np.asarray(sb.theta), np.asarray(sc.theta), atol=1e-5)
    np.testing.assert_array_equal(np.asarray(sb.steps), np.asarray(sc.steps))


def test_banked_tick_single_host_sync(lorenz):
    """The packed [delta, loss, steps, active] status makes a steady-state
    banked tick ONE host readback; the composite tick reads each SlotState
    leaf separately (the 5.17-syncs/tick ROADMAP baseline)."""
    logs = {}
    for kernel in ("banked", "composite"):
        spec = _spec(tick=_tick_for(SCFG)(kernel))
        svc = api.compile_plan(spec).make_service()
        for sid in range(2):
            svc.submit(sid, lorenz[sid : sid + SCFG.buf_len])
        svc.fill_slots()
        for t in range(4):
            idx = SCFG.buf_len + t * SCFG.chunk + np.arange(SCFG.chunk)
            svc.tick_once(np.repeat(lorenz[idx][None], 2, axis=0))
        logs[kernel] = svc.sync_log[1:]  # tick 0 compiles; steady state after
    assert max(logs["banked"]) <= 2, logs
    assert min(logs["composite"]) >= 4, logs
    assert max(logs["banked"]) < min(logs["composite"])


# ---------------------------------------------------------------------------
# TickSpec validation + "auto" resolution through the VMEM residency model
# ---------------------------------------------------------------------------
def test_tick_spec_validates_literals():
    with pytest.raises(ValueError, match="tick_kernel"):
        TickSpec(tick_kernel="bankd")
    with pytest.raises(ValueError, match="steps_per_tick"):
        TickSpec(steps_per_tick=-1)
    with pytest.raises(ValueError, match="ema_decay"):
        TickSpec(ema_decay=1.0)
    TickSpec(steps_per_tick=0)  # pure serve tick is a valid request


def test_tick_spec_requires_stream_mode():
    with pytest.raises(ValueError, match="tick= requires mode='stream'"):
        RecoverySpec(mode="batch", batch_size=8, tick=TickSpec(), **BASE, encoder="gru")


def test_tick_spec_conflict_with_stream_config():
    with pytest.raises(ValueError, match="tick conflict"):
        _spec(tick=TickSpec(steps_per_tick=SCFG.steps_per_tick + 1))


def test_plan_records_tick_lowering():
    plan = api.compile_plan(_spec())  # tick=None -> composite default
    assert plan.lowering.tick_kernel == "composite"
    assert plan.lowering.tick_slots_per_bank is None

    plan = api.compile_plan(_spec(tick=_tick_for(SCFG)("banked")))
    assert plan.lowering.tick_kernel == "banked"
    assert plan.lowering.tick_slots_per_bank >= 1
    assert 2 % plan.lowering.tick_slots_per_bank == 0


def test_auto_resolves_banked_for_gru_composite_for_ltc():
    plan = api.compile_plan(_spec(tick=_tick_for(SCFG)("auto")))
    assert plan.lowering.tick_kernel == "banked"  # gru fits the tiny shapes

    plan = api.compile_plan(_spec(encoder="ltc", tick=_tick_for(SCFG)("auto")))
    assert plan.lowering.tick_kernel == "composite"
    assert plan.lowering.tick_slots_per_bank is None


def test_explicit_banked_on_ltc_raises():
    with pytest.raises(ValueError, match="GRU-family"):
        api.compile_plan(_spec(encoder="ltc", tick=_tick_for(SCFG)("banked")))


def test_tiny_budget_auto_falls_back_explicit_runs_at_bank_one():
    tiny = dict(block_b="auto", vmem_budget_bytes=1024)
    plan = api.compile_plan(_spec(tick=_tick_for(SCFG)("auto"), **tiny))
    assert plan.lowering.tick_kernel == "composite"  # nothing fits: heuristic declines

    plan = api.compile_plan(_spec(tick=_tick_for(SCFG)("banked"), **tiny))
    assert plan.lowering.tick_kernel == "banked"  # explicit request overrides
    assert plan.lowering.tick_slots_per_bank == 1


def test_plan_tick_program_property():
    plan = api.compile_plan(_spec(tick=_tick_for(SCFG)("banked")))
    assert callable(plan.tick)
    offline = api.compile_plan(RecoverySpec(encoder="gru", **BASE))
    with pytest.raises(ValueError):
        _ = offline.tick


# ---------------------------------------------------------------------------
# tick-level VMEM residency model
# ---------------------------------------------------------------------------
def test_tick_vmem_bytes_monotonic_in_bank_size():
    cfg = _mr_cfg()
    sizes = [tiling.tick_vmem_bytes(cfg, TCFG, slots_per_bank=s) for s in (1, 2, 4)]
    assert sizes[0] < sizes[1] < sizes[2]
    q = tiling.tick_vmem_bytes(cfg, TCFG, slots_per_bank=2, int8=True)
    assert q < sizes[1]  # int8 weights shrink the resident bank


@pytest.mark.parametrize("int8", [False, True])
def test_tick_vmem_footprint_bounds_residency(int8):
    """The VMEM a grid step allocates (tile-padded, double-buffered blocks)
    is never below the bank's logical residency, and grows with the bank."""
    cfg = _mr_cfg()
    for s in (1, 2, 4):
        foot = tiling.tick_vmem_footprint(cfg, TCFG, slots_per_bank=s, int8=int8)
        assert foot >= tiling.tick_vmem_bytes(cfg, TCFG, slots_per_bank=s, int8=int8)
    one, two = (
        tiling.tick_vmem_footprint(cfg, TCFG, slots_per_bank=s, int8=int8) for s in (1, 2)
    )
    assert one < two < 2 * one + 1  # per-slot blocks scale; the scratch is shared


def test_auto_slots_per_bank_policy():
    cfg = _mr_cfg()
    assert tiling.auto_slots_per_bank(cfg, TCFG, 8, None) == 8  # no budget: whole shard
    spb = tiling.auto_slots_per_bank(cfg, TCFG, 8, 10**9)
    assert spb >= 1 and 8 % spb == 0
    assert tiling.auto_slots_per_bank(cfg, TCFG, 8, 64) == 0  # nothing fits


# ---------------------------------------------------------------------------
# audit: the banked K=0 tick program carries a tick-level R2 residency cell
# ---------------------------------------------------------------------------
def test_banked_plan_passes_audit_with_tick_residency_cell():
    spec = _spec(tick=_tick_for(SCFG)("banked"))
    plan = api.compile_plan(spec, audit="error")  # any finding raises
    assert plan.lowering.audit.startswith("pass")
    assert "R2" in plan.lowering.audit


# ---------------------------------------------------------------------------
# device-resident control plane (core/control.py): host-queue parity + syncs
# ---------------------------------------------------------------------------
# budget-only eviction (delta_tol=0): the two planes run the SAME tick math
# but as differently-fused XLA programs, so float-identical convergence
# deltas are not guaranteed near a tolerance — the lockstep comparison pins
# occupancy/steps/reason exactly and theta to 1e-5 instead.
CCFG = StreamConfig(
    buf_len=32,
    window=8,
    stride=8,
    chunk=8,
    steps_per_tick=8,
    min_steps=16,
    max_steps=16,
    delta_tol=0.0,
)


def _control_spec(control, scfg=CCFG, **overrides):
    base = dict(
        mode="stream",
        n_slots=2,
        stream=scfg,
        encoder="gru",
        seed=0,
        tick=TickSpec(
            steps_per_tick=scfg.steps_per_tick,
            control=control,
            queue_capacity=8,
            snapshot_period=1,
            warm_capacity=8,
        ),
        **BASE,
    )
    base.update(overrides)
    return RecoverySpec(**base)


def test_tick_spec_validates_control_plane_fields():
    with pytest.raises(ValueError, match="control"):
        TickSpec(control="fpga")
    with pytest.raises(ValueError, match="queue_capacity"):
        TickSpec(queue_capacity=0)
    with pytest.raises(ValueError, match="snapshot_period"):
        TickSpec(snapshot_period=0)
    with pytest.raises(ValueError, match="warm_capacity"):
        TickSpec(warm_capacity=0)


def test_plan_records_control_plane_lowering():
    low_d = api.compile_plan(_control_spec("device")).lowering
    assert low_d.control_plane == "device"
    assert low_d.tick_queue_capacity == 8
    assert low_d.tick_snapshot_period == 1
    assert low_d.warm_capacity == 8
    low_h = api.compile_plan(_control_spec("host")).lowering
    assert low_h.control_plane == "host"
    assert low_h.tick_queue_capacity is None
    assert low_h.tick_snapshot_period is None


def test_device_control_matches_host_queue_lockstep(lorenz):
    """Randomized admission/eviction traffic through both control planes in
    lockstep: same slot occupancy, same eviction (tick, id, steps, reason),
    per-stream theta to 1e-5 — including a warm-start resubmission wave."""
    rng = np.random.default_rng(7)
    n_streams, slots = 6, 2
    data = np.stack(
        [
            np.roll(lorenz, -int(rng.integers(0, 64)), axis=0)
            + rng.normal(0.0, 0.01, lorenz.shape)
            for _ in range(n_streams)
        ]
    ).astype(np.float32)
    arrivals = {0: [0, 1, 2], 2: [3], 3: [4], 5: [5]}  # rng-drawn, then frozen
    t_total = data.shape[1]

    def run_traffic(svc, resubmit=()):
        cursors = dict.fromkeys(range(n_streams), CCFG.buf_len)
        slot_maps, evictions = [], []
        for sid in resubmit:
            svc.submit(sid, data[sid, : CCFG.buf_len])
        svc.fill_slots()
        t = 0
        while (not svc.done or t in arrivals) and t < 40:
            if not resubmit:
                for sid in arrivals.get(t, ()):
                    svc.submit(sid, data[sid, : CCFG.buf_len])
                    svc.fill_slots()
            chunk = np.zeros((slots, CCFG.chunk, 3), np.float32)
            for s, sid in enumerate(svc.slot_streams()):
                if sid < 0:
                    continue
                idx = (cursors[sid] + np.arange(CCFG.chunk)) % t_total
                chunk[s] = data[sid, idx]
                cursors[sid] += CCFG.chunk
            info = svc.tick_once(chunk)
            slot_maps.append(tuple(int(s) for s in svc.slot_streams()))
            evictions.extend((t, r.stream_id, r.steps, r.reason) for r in info["evicted"])
            t += 1
        return slot_maps, evictions

    services, traces = {}, {}
    for control in ("host", "device"):
        svc = api.compile_plan(_control_spec(control)).make_service()
        traces[control] = run_traffic(svc)
        services[control] = svc
    assert traces["device"] == traces["host"]
    assert services["device"].done and services["host"].done
    res_h, res_d = services["host"].results, services["device"].results
    assert set(res_d) == set(res_h) == set(range(n_streams))
    for sid in range(n_streams):
        assert (res_d[sid].steps, res_d[sid].reason) == (res_h[sid].steps, res_h[sid].reason)
        np.testing.assert_allclose(res_d[sid].theta, res_h[sid].theta, atol=1e-5)
        np.testing.assert_allclose(res_d[sid].mean, res_h[sid].mean, atol=1e-6)
    # warm-start resubmission (below LRU/warm-cache capacity): both planes
    # must serve the cached evicted params, not a cold restart
    for control in ("host", "device"):
        traces[control] = run_traffic(services[control], resubmit=(0, 1))
    assert traces["device"] == traces["host"]
    for sid in (0, 1):
        np.testing.assert_allclose(
            services["device"].results[sid].theta,
            services["host"].results[sid].theta,
            atol=1e-5,
        )


def test_device_queue_backpressure_typed(lorenz):
    """Pressure never raises: a full shard ring spills to the bounded host
    overflow queue (OVERFLOW), a full overflow REJECTs, and overflowed
    arrivals drain back into the ring (and complete) as capacity frees."""
    svc = api.compile_plan(
        _control_spec(
            "device",
            tick=TickSpec(
                steps_per_tick=8, control="device", queue_capacity=2, overflow_capacity=1
            ),
        )
    ).make_service()
    hist = lorenz[: CCFG.buf_len]
    assert svc.submit(0, hist).status is stream.SubmitStatus.ENQUEUED
    assert svc.submit(1, hist).status is stream.SubmitStatus.ENQUEUED
    r2 = svc.submit(2, hist)
    assert r2.status is stream.SubmitStatus.OVERFLOW and r2.accepted
    r3 = svc.submit(3, hist)
    assert r3.status is stream.SubmitStatus.REJECTED and not r3.accepted
    assert 3 not in svc._pending  # nothing retained for a rejected stream
    chunk = np.repeat(lorenz[CCFG.buf_len : CCFG.buf_len + CCFG.chunk][None], 2, axis=0)
    svc.fill_slots()
    for _ in range(12):
        if svc.done:
            break
        svc.tick_once(chunk)
    assert set(svc.results) == {0, 1, 2}  # the overflowed stream completed too


@pytest.mark.parametrize("control", ["host", "device"])
def test_priority_preempts_cold_slot(lorenz, control):
    """A higher-tier arrival displaces the lowest-tier COLD slot (steps <
    min_steps) on both control planes: the victim re-enters the queue with
    its live buffers and still completes, so no stream is lost."""
    svc = api.compile_plan(_control_spec(control)).make_service()
    hist = lorenz[: CCFG.buf_len]
    for sid in (0, 1):
        svc.submit(sid, hist)
    svc.fill_slots()
    assert sorted(svc.slot_streams()) == [0, 1]
    assert svc.submit(2, hist, priority=3).accepted
    chunk = np.repeat(lorenz[CCFG.buf_len : CCFG.buf_len + CCFG.chunk][None], 2, axis=0)
    svc.tick_once(chunk)
    # one tick in, both residents are cold (8 < min_steps=16): victim policy
    # picks the lowest (tier, slot) — slot 0 — and the tier-3 arrival lands
    assert svc.slot_streams() == [2, 1]
    for _ in range(12):
        if svc.done:
            break
        svc.tick_once(chunk)
    assert set(svc.results) == {0, 1, 2}
    assert all(r.reason == "budget" for r in svc.results.values())


def test_device_queue_ring_wraps(lorenz):
    """Capacity-2 ring admits two waves of two: the second wave's writes wrap
    the ring head and still admit/complete the right streams."""
    svc = api.compile_plan(
        _control_spec("device", tick=TickSpec(steps_per_tick=8, control="device", queue_capacity=2))
    ).make_service()
    for sid in (0, 1):
        svc.submit(sid, lorenz[: CCFG.buf_len])
    svc.fill_slots()  # snapshot reconciles: ring is empty again
    for sid in (2, 3):
        svc.submit(sid, lorenz[sid : sid + CCFG.buf_len])
    chunk = np.repeat(lorenz[CCFG.buf_len : CCFG.buf_len + CCFG.chunk][None], 2, axis=0)
    for _ in range(8):
        if svc.done:
            break
        svc.tick_once(chunk)
    assert set(svc.results) == {0, 1, 2, 3}
    assert all(r.steps == CCFG.max_steps for r in svc.results.values())


def test_host_warm_registry_bounded(lorenz):
    """Satellite: the host-path warm-start registry is a bounded LRU sized by
    TickSpec.warm_capacity, not an unbounded dict."""
    svc = api.compile_plan(
        _control_spec(
            "host",
            n_slots=1,
            tick=TickSpec(steps_per_tick=8, control="host", warm_capacity=2),
        )
    ).make_service()
    assert svc.warm_capacity == 2
    for sid in range(3):
        svc.submit(sid, lorenz[sid : sid + CCFG.buf_len])
    svc.fill_slots()
    chunk = lorenz[CCFG.buf_len : CCFG.buf_len + CCFG.chunk][None]
    for _ in range(8):
        if svc.done:
            break
        svc.tick_once(chunk)
    assert set(svc.results) == {0, 1, 2}
    assert list(svc.warm) == [1, 2]  # LRU: stream 0's entry was evicted


def test_device_snapshot_period_steady_state_zero_syncs(lorenz):
    """With snapshot_period=4 and no evictions, only every 4th tick reads
    anything back (status + event drain); the median steady-state tick is
    ZERO host syncs and the service stays queryable from cached views."""
    scfg = dataclasses.replace(CCFG, min_steps=10**9, max_steps=10**9)
    svc = api.compile_plan(
        _control_spec(
            "device",
            scfg=scfg,
            tick=TickSpec(steps_per_tick=8, control="device", snapshot_period=4),
        )
    ).make_service()
    for sid in (0, 1):
        svc.submit(sid, lorenz[: scfg.buf_len])
    svc.fill_slots()
    chunk = np.repeat(lorenz[scfg.buf_len : scfg.buf_len + scfg.chunk][None], 2, axis=0)
    for _ in range(8):
        svc.tick_once(chunk)
    syncs0 = svc.counters["host_syncs"]
    assert list(svc.slot_streams()) == [0, 1]  # served from the snapshot view
    assert svc.done is False  # no eager active-mask readback (satellite fix)
    assert svc.counters["host_syncs"] == syncs0
    assert svc.counters["reshards"] == 0
    log = svc.sync_log[1:]  # tick 0 pays compile-adjacent snapshot timing
    assert float(np.median(log)) == 0.0
    assert all(s == 0 for i, s in enumerate(log, start=2) if i % 4 != 0), log
