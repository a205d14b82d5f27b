"""repro.api: the declarative RecoverySpec -> compile_plan -> RecoveryPlan surface.

Pins the redesign's contract: spec validation fails at compile time (never
mid-trace), each execution mode reproduces its legacy entry point exactly
(train_mr / recover_many / RecoveryService, fp32 and int8), the lowering
record resolves block_b against a VMEM budget, and a 2-virtual-device mesh
shards SlotState without changing the numerics.
"""

from __future__ import annotations

import jax.numpy as jnp
import numpy as np
import pytest

from repro import api
from repro.core import engine, stream
from repro.core.merinda import MRConfig, train_mr
from repro.core.stream import RecoveryService, StreamConfig
from repro.data.dynamics import generate_trajectory
from repro.data.windows import make_windows
from tests.conftest import run_devices

SCFG = StreamConfig(
    buf_len=48, window=12, stride=6, chunk=8, steps_per_tick=8, min_steps=16, max_steps=64
)


def small_spec(**overrides) -> api.RecoverySpec:
    base = dict(state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01, encoder="gru")
    base.update(overrides)
    return api.RecoverySpec(**base)


@pytest.fixture(scope="module")
def lorenz_windows():
    _, ys, _ = generate_trajectory("lorenz", n_samples=300)
    yw, _, norm = make_windows(ys, None, window=12, stride=6)
    return jnp.asarray(yw), norm


@pytest.fixture(scope="module")
def lorenz_raw():
    _, ys, _ = generate_trajectory("lorenz", n_samples=400)
    return ys


# ---------------------------------------------------------------------------
# spec validation: bad requests fail at construction / compile time
# ---------------------------------------------------------------------------
def test_spec_literal_validation():
    with pytest.raises(ValueError, match="mode"):
        small_spec(mode="streaming")
    with pytest.raises(ValueError, match="precision"):
        small_spec(precision="fp16")
    with pytest.raises(ValueError, match="block_b"):
        small_spec(block_b="automatic")
    with pytest.raises(ValueError, match="vmem_budget_bytes"):
        small_spec(block_b=32, vmem_budget_bytes=1 << 20)
    with pytest.raises(ValueError, match="divide"):
        small_spec(mode="stream", n_slots=3, mesh_slots=2)
    with pytest.raises(ValueError, match="mesh_slots"):
        small_spec(mode="offline", mesh_slots=2)


def test_compile_validation_unknown_encoder():
    with pytest.raises(ValueError, match="unknown encoder"):
        api.compile_plan(small_spec(encoder="gru_typo"))


@pytest.mark.parametrize("encoder", ["ltc", "node"])
def test_compile_validation_fused_substep_families_lower(encoder):
    """fused=True is legal for every registry encoder now: the multi-substep
    families lower to their fused-solver mr_step variants with no new call
    sites (Lowering.dispatch routes through the kernel family)."""
    plan = api.compile_plan(small_spec(encoder=encoder, fused=True))
    assert plan.lowering.fused
    assert plan.lowering.dispatch in ("pallas", "reference")
    assert plan.cfg.fused


def test_compile_validation_fused_requires_fusable():
    """A custom registry row without an mr_step lowering still fails
    eagerly at compile time with the actionable fusable list."""
    from repro.core import encoders

    row = encoders.EncoderSpec(
        name="mean_pool_nofuse_api",
        init=lambda key, d_in, hidden, dtype=None: {},
        encode=lambda p, cfg, xs: xs.mean(axis=1),
        flow=None,
        fusable=False,
        kernel=False,
    )
    encoders.register_encoder(row)
    try:
        with pytest.raises(ValueError, match="fusable"):
            api.compile_plan(small_spec(encoder="mean_pool_nofuse_api", fused=True))
    finally:
        encoders._REGISTRY.pop("mean_pool_nofuse_api", None)


@pytest.mark.parametrize("encoder", ["gru_flow", "node"])
def test_compile_validation_int8_requires_pwl_mappable_cell(encoder):
    """int8 + flow encoder (and int8 + node) is a genuinely unsupported
    combo: no PWL mapping exists, so it still raises the actionable list."""
    with pytest.raises(ValueError, match="int8_pwl"):
        api.compile_plan(small_spec(encoder=encoder, precision="int8_pwl"))


def test_compile_int8_ltc_serving_lowers():
    """The LTC substep cell is sigmoid-only, so its fixed-point fused stage
    exists and int8_pwl serving compiles."""
    plan = api.compile_plan(small_spec(encoder="ltc", precision="int8_pwl"))
    assert plan.lowering.quant_serving


def test_compile_validation_mesh_exceeds_devices():
    # the test process holds exactly one CPU device (see conftest)
    with pytest.raises(ValueError, match="device"):
        api.compile_plan(small_spec(mode="stream", n_slots=4, mesh_slots=4))


def test_mode_mismatch_raises(lorenz_windows):
    yw, _ = lorenz_windows
    plan = api.compile_plan(small_spec(mode="offline", steps=2))
    with pytest.raises(ValueError, match="mode"):
        plan.run_batch(yw[None])
    with pytest.raises(ValueError, match="mode"):
        plan.make_service()


def test_legacy_entry_points_validate_eagerly(lorenz_windows):
    """The deprecated wrappers + service fail BEFORE tracing on a fused
    request with a non-fusable encoder (no silent unfused fallback)."""
    from repro.core import encoders

    yw, _ = lorenz_windows
    row = encoders.EncoderSpec(
        name="mean_pool_nofuse_legacy",
        init=lambda key, d_in, hidden, dtype=None: {},
        encode=lambda p, cfg, xs: xs.mean(axis=1),
        flow=None,
        fusable=False,
        kernel=False,
    )
    encoders.register_encoder(row)
    try:
        cfg = MRConfig(
            state_dim=3,
            order=2,
            hidden=8,
            dense_hidden=16,
            dt=0.01,
            encoder="mean_pool_nofuse_legacy",
            fused=True,
        )
        with pytest.raises(ValueError, match="fusable"):
            engine.train_mr_scan(cfg, yw, steps=1)
        with pytest.raises(ValueError, match="fusable"):
            engine.recover_many(cfg, yw[None], steps=1)
        with pytest.raises(ValueError, match="fusable"):
            RecoveryService(cfg, SCFG, n_slots=1)
    finally:
        encoders._REGISTRY.pop("mean_pool_nofuse_legacy", None)


def test_legacy_entry_points_warn_deprecated_once(lorenz_windows):
    """The deprecated wrappers warn ONCE per process, not per call — the
    service-tick/benchmark loops call them hundreds of times."""
    import warnings

    from repro.deprecation import reset_warned

    yw, _ = lorenz_windows
    cfg = MRConfig(state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01, encoder="gru")
    reset_warned()
    try:
        with warnings.catch_warnings(record=True) as caught:
            warnings.simplefilter("always")
            for _ in range(3):
                engine.train_mr_scan(cfg, yw, steps=1)
                RecoveryService(cfg, SCFG, n_slots=1)
        dep = [w for w in caught if issubclass(w.category, DeprecationWarning)]
        assert len(dep) == 2, [str(w.message) for w in dep]  # one per entry point
    finally:
        reset_warned()


# ---------------------------------------------------------------------------
# block_b lowering
# ---------------------------------------------------------------------------
def test_block_b_auto_resolves_against_budget():
    spec = small_spec(
        mode="batch", batch_size=32, fused=True, block_b="auto", vmem_budget_bytes=6000
    )
    plan = api.compile_plan(spec)
    bb = plan.lowering.block_b
    assert bb is not None and 32 % bb == 0 and bb < 32
    assert plan.lowering.vmem_bytes is not None
    assert plan.lowering.vmem_bytes <= 6000
    assert plan.cfg.block_b == bb  # the tile reaches the fused kernel config


def test_block_b_auto_without_budget_detects_device_budget():
    """No explicit vmem_budget_bytes: the budget is auto-detected from the
    device (platform table; CPU resolves the v4/v5 default) and recorded in
    the lowering. The tiny config fits, so the tile stays full-batch."""
    from repro.kernels.mr_step import tiling

    plan = api.compile_plan(small_spec(mode="batch", batch_size=32, fused=True, block_b="auto"))
    assert plan.lowering.block_b is None  # full batch fits the detected budget
    assert plan.lowering.vmem_budget_bytes == tiling.detect_vmem_budget()
    assert plan.lowering.vmem_bytes <= plan.lowering.vmem_budget_bytes


def test_block_b_auto_explicit_budget_overrides_detection():
    spec = small_spec(
        mode="batch", batch_size=32, fused=True, block_b="auto", vmem_budget_bytes=6000
    )
    plan = api.compile_plan(spec)
    assert plan.lowering.vmem_budget_bytes == 6000  # override wins, recorded


def test_detect_vmem_budget_platform_table():
    from repro.kernels.mr_step import tiling

    class FakeDev:
        device_kind = "TPU v6e"

        def memory_stats(self):
            return {}

    assert tiling.detect_vmem_budget(FakeDev()) == int(32 * 1024 * 1024 * 0.5)

    class StatsDev:
        device_kind = "weird"

        def memory_stats(self):
            return {"vmem_size_bytes": 4 * 1024 * 1024}

    assert tiling.detect_vmem_budget(StatsDev()) == int(4 * 1024 * 1024 * 0.5)


@pytest.mark.parametrize("platform", ["tpu", "cpu"])
def test_vmem_budget_unknown_kind_raises_only_on_tpu(platform):
    """A TPU whose device_kind the platform table lacks has no known VMEM
    budget: resolving one raises instead of guessing. Off TPU the labelled
    v4/v5 default keeps CPU runs deterministic."""
    from repro.kernels.mr_step import tiling

    class UnknownDev:
        device_kind = "TPU v99 hypothetical"

        def memory_stats(self):
            return {}

    UnknownDev.platform = platform
    if platform == "tpu":
        with pytest.raises(ValueError, match="PLATFORM_VMEM_BYTES"):
            tiling.resolve_vmem_budget(UnknownDev())
    else:
        assert tiling.resolve_vmem_budget(UnknownDev()) == (
            int(tiling.VMEM_BYTES_PER_CORE * tiling.VMEM_BUDGET_FRACTION),
            "default",
        )


@pytest.mark.parametrize("encoder", ["ltc", "node"])
def test_substep_vmem_model_and_auto_tile(encoder):
    """config_vmem_bytes dispatches to the substep-cell residency models and
    the auto tile budgets against them (block_b="auto" stays correct)."""
    from repro.kernels.mr_step import tiling

    cfg = small_spec(encoder=encoder, fused=True, hidden=64, dense_hidden=128).to_mr_config()
    full = tiling.config_vmem_bytes(cfg, 32)
    tiled = tiling.config_vmem_bytes(cfg, 32, block_b=8)
    assert tiled < full  # activation rows tile; weights stay resident
    # residency is substep-count-invariant: the kernels reuse one working set
    import dataclasses

    cfg12 = dataclasses.replace(cfg, ltc_substeps=12)
    assert tiling.config_vmem_bytes(cfg12, 32) == full
    budget = tiled
    bb = tiling.auto_block_b(cfg, 32, budget)
    assert bb is not None and 32 % bb == 0
    assert tiling.config_vmem_bytes(cfg, 32, block_b=bb) <= budget
    plan = api.compile_plan(
        small_spec(
            encoder=encoder,
            fused=True,
            hidden=64,
            dense_hidden=128,
            mode="batch",
            batch_size=32,
            block_b="auto",
            vmem_budget_bytes=budget,
        )
    )
    assert plan.lowering.block_b == bb


def test_block_b_must_divide_compile_time_batch():
    scfg = StreamConfig(buf_len=32, window=8, stride=8, chunk=8)  # n_windows = 4
    with pytest.raises(ValueError, match="divide"):
        api.compile_plan(
            small_spec(mode="stream", n_slots=2, stream=scfg, fused=True, block_b=3)
        )


def test_stream_lr_conflict_rejected():
    # the StreamConfig copies govern the tick; a diverging spec value would
    # be silently dropped, so the spec refuses to construct
    with pytest.raises(ValueError, match="lr"):
        small_spec(mode="stream", stream=SCFG, lr=1e-2)
    # no stream= given: the spec's lr/batch_size flow into the StreamConfig
    scfg = small_spec(mode="stream", lr=1e-2, batch_size=4).stream_config()
    assert scfg.lr == 1e-2 and scfg.batch_size == 4


def test_auto_block_b_walks_divisors_not_halvings():
    from repro.kernels.mr_step import tiling

    cfg = small_spec(fused=True).to_mr_config()
    # batch=50: halving from 25 hits non-divisor 12; the divisor walk must
    # still find 10 when the budget fits a 10-row tile but not a 25-row one
    budget = tiling.config_vmem_bytes(cfg, 50, block_b=10)
    assert tiling.config_vmem_bytes(cfg, 50, block_b=25) > budget
    assert tiling.auto_block_b(cfg, 50, budget) == 10


def test_auto_block_b_prefers_largest_fitting_divisor():
    from repro.kernels.mr_step import tiling

    cfg = small_spec(fused=True).to_mr_config()
    # batch=48 ladder: None, 24, 16, 12, 8. Budget fits a 16-row tile but
    # not 24 — the walk must stop at 16, never settle for a smaller divisor
    budget = tiling.config_vmem_bytes(cfg, 48, block_b=16)
    assert tiling.config_vmem_bytes(cfg, 48, block_b=24) > budget
    assert tiling.auto_block_b(cfg, 48, budget) == 16


def test_auto_block_b_non_power_of_two_batch_reaches_small_divisors():
    from repro.kernels.mr_step import tiling

    cfg = small_spec(fused=True).to_mr_config()
    # batch=12 has NO divisor in [min_block=8, 12): the old walk enumerated
    # an empty ladder and returned None (= full batch) even with the budget
    # blown; the shared block_b_candidates ladder now carries the degraded
    # sub-min_block tail, so a 6-row tile that fits is found
    assert tiling.block_b_candidates(12) == [None, 6, 4, 3, 2, 1]
    budget = tiling.config_vmem_bytes(cfg, 12, block_b=6)
    assert tiling.config_vmem_bytes(cfg, 12) > budget
    assert tiling.auto_block_b(cfg, 12, budget) == 6


def test_vmem_model_matches_bench_stagemap():
    from benchmarks.bench_stagemap import _vmem_bytes
    from repro.kernels.mr_step import tiling

    kw = dict(int8=False, n_seg=0, block_b=64)
    assert _vmem_bytes(256, 8, 64, 128, 32, **kw) == tiling.vmem_bytes(256, 8, 64, 128, 32, **kw)


# ---------------------------------------------------------------------------
# parity with the legacy entry points
# ---------------------------------------------------------------------------
def test_offline_parity_with_train_mr(lorenz_windows):
    yw, norm = lorenz_windows
    spec = small_spec(mode="offline", steps=20, batch_size=16, lr=3e-3, seed=0)
    plan = api.compile_plan(spec)
    params, metrics = plan.run_offline(yw, norm=norm)
    params_l, hist = train_mr(
        plan.cfg,
        yw,
        None,
        steps=20,
        lr=3e-3,
        seed=0,
        batch_size=16,
        log_every=10,
        norm=norm,
    )
    np.testing.assert_array_equal(np.asarray(params.head_w2), np.asarray(params_l.head_w2))
    assert float(metrics["recon_mse"][10]) == pytest.approx(hist[1]["recon_mse"])


def test_batch_parity_with_recover_many(lorenz_windows):
    yw, _ = lorenz_windows
    spec = small_spec(mode="batch", steps=12, batch_size=16, seed=3, n_active=8)
    plan = api.compile_plan(spec)
    theta = plan.run_batch(yw[None])
    theta_l = engine.recover_many(plan.cfg, yw[None], steps=12, batch_size=16, seed=3, n_active=8)
    np.testing.assert_array_equal(np.asarray(theta), np.asarray(theta_l))
    assert theta.shape == (1, plan.cfg.n_terms, 3)


def test_int8_readout_parity(lorenz_windows):
    yw, _ = lorenz_windows
    spec = small_spec(mode="offline", steps=30, batch_size=16, precision="int8_pwl")
    plan = api.compile_plan(spec)
    assert plan.lowering.quant_serving and plan.lowering.dispatch == "reference"
    params, _ = plan.run_offline(yw)
    theta = plan.readout(params, yw)
    theta_l = np.asarray(stream.readout_theta(params, plan.cfg, yw, quant=True))
    np.testing.assert_array_equal(theta, theta_l)


@pytest.mark.parametrize("encoder", ["gru", "ltc", "node"])
def test_fused_plan_runs_and_matches_unfused(lorenz_windows, encoder):
    yw, _ = lorenz_windows
    fused = api.compile_plan(
        small_spec(mode="offline", steps=15, batch_size=16, encoder=encoder, fused=True)
    )
    unfused = api.compile_plan(small_spec(mode="offline", steps=15, batch_size=16, encoder=encoder))
    assert fused.lowering.fused and fused.lowering.dispatch == "reference"
    pf, mf = fused.run_offline(yw)
    pu, mu = unfused.run_offline(yw)
    # fused reference math == unfused stage sequence (same program structure)
    np.testing.assert_allclose(np.asarray(mf["recon_mse"]), np.asarray(mu["recon_mse"]), atol=1e-5)


def test_stream_plan_matches_legacy_service(lorenz_raw):
    ys = lorenz_raw
    spec = small_spec(mode="stream", n_slots=2, stream=SCFG, seed=0)
    plan = api.compile_plan(spec)
    svc_p = plan.make_service()
    cfg = MRConfig(state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01, encoder="gru")
    svc_l = RecoveryService(cfg, SCFG, n_slots=2, seed=0)
    for svc in (svc_p, svc_l):
        for sid in range(2):
            svc.submit(sid, ys[sid : sid + SCFG.buf_len])
        svc.fill_slots()
    for t in range(3):
        idx = SCFG.buf_len + t * SCFG.chunk + np.arange(SCFG.chunk)
        chunk = np.repeat(ys[idx][None], 2, axis=0)
        info_p = svc_p.tick_once(chunk)
        info_l = svc_l.tick_once(chunk)
    np.testing.assert_array_equal(np.asarray(svc_p.state.theta), np.asarray(svc_l.state.theta))
    np.testing.assert_array_equal(info_p["delta"], info_l["delta"])


# ---------------------------------------------------------------------------
# sharded SlotState: 2 virtual devices, parity with the trivial mesh
# ---------------------------------------------------------------------------
def test_sharded_slots_parity_two_devices():
    run_devices(
        """
        import numpy as np
        from repro import api
        from repro.core.stream import StreamConfig
        from repro.data.dynamics import generate_trajectory

        _, ys, _ = generate_trajectory("lorenz", n_samples=200)
        scfg = StreamConfig(buf_len=32, window=8, stride=8, chunk=8,
                            steps_per_tick=4, min_steps=10**9, max_steps=10**9)

        def run(mesh_slots):
            spec = api.RecoverySpec(
                state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01,
                encoder="gru", mode="stream", n_slots=2, stream=scfg,
                mesh_slots=mesh_slots,
            )
            plan = api.compile_plan(spec)
            svc = plan.make_service()
            for i in range(2):
                svc.submit(i, ys[i : i + 32])
            svc.fill_slots()
            for t in range(3):
                idx = 32 + t * 8 + np.arange(8)
                svc.tick_once(np.repeat(ys[idx][None], 2, axis=0))
            return svc

        svc1, svc2 = run(1), run(2)
        sh = str(svc2.state.theta.sharding)
        assert "slots" in sh, sh  # actually sharded over the mesh axis
        d = np.abs(np.asarray(svc2.state.theta) - np.asarray(svc1.state.theta)).max()
        assert d < 1e-5, d
        assert np.isfinite(np.asarray(svc2.state.loss)).all()
        print("PASS")
        """,
        n_devices=2,
    )


def test_sharded_slots_eviction_parity_two_devices():
    """Eviction on a sharded slot axis: ``slot_result`` gathers the slot from
    the sharded state in one program, with one host read per eviction, and
    the evicted answers and warm params match the trivial mesh's."""
    run_devices(
        """
        import jax
        import numpy as np
        from repro import api
        from repro.core.stream import StreamConfig
        from repro.data.dynamics import generate_trajectory

        _, ys, _ = generate_trajectory("lorenz", n_samples=200)
        scfg = StreamConfig(buf_len=32, window=8, stride=8, chunk=8,
                            steps_per_tick=4, min_steps=8, max_steps=8, delta_tol=0.0)

        def run(mesh_slots):
            spec = api.RecoverySpec(
                state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01,
                encoder="gru", mode="stream", n_slots=2, stream=scfg,
                mesh_slots=mesh_slots,
            )
            svc = api.compile_plan(spec).make_service()
            for i in range(4):
                svc.submit(i, ys[i : i + 32])
            svc.fill_slots()
            out = []
            for t in range(4):
                idx = 32 + t * 8 + np.arange(8)
                info = svc.tick_once(np.repeat(ys[idx][None], 2, axis=0))
                out += info["evicted"]
                assert svc.sync_log[-1] == 4 + len(info["evicted"]), svc.sync_log
            return svc, out

        (svc1, ev1), (svc2, ev2) = run(1), run(2)
        assert "slots" in str(svc2.state.theta.sharding)
        assert [r.stream_id for r in ev2] == [r.stream_id for r in ev1] == [0, 1, 2, 3]
        for a, b in zip(ev1, ev2):
            assert a.steps == b.steps == 8
            assert np.abs(a.theta - b.theta).max() < 1e-5
            np.testing.assert_array_equal(a.mean, b.mean)
            np.testing.assert_array_equal(a.scale, b.scale)
        for sid in range(4):
            for p1, p2 in zip(jax.tree.leaves(svc1.warm[sid]), jax.tree.leaves(svc2.warm[sid])):
                assert np.abs(np.asarray(p1) - np.asarray(p2)).max() < 1e-5
        print("PASS")
        """,
        n_devices=2,
    )
