"""core/stream: slot-based streaming recovery service.

Pins the serving path end to end: device-side windowing helpers, slot
admission/eviction round-trips through the shared pytree, warm-start
re-admission (fewer steps / lower loss than cold start on the same data),
and int8-encoder readout parity with the f32 path.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import stream
from repro.core.merinda import MRConfig, init_mr
from repro.core.stream import RecoveryService, StreamConfig
from repro.data.dynamics import generate_trajectory
from repro.data.windows import make_windows, n_buffer_windows, roll_buffer, window_views
from repro.optim import adamw_init

CFG = MRConfig(state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01, encoder="gru")
SCFG = StreamConfig(
    buf_len=48, window=12, stride=6, chunk=8, steps_per_tick=8, min_steps=16, max_steps=64
)


@pytest.fixture(scope="module")
def lorenz():
    _, ys, _ = generate_trajectory("lorenz", n_samples=400)
    return ys


def _chunks(ys, start, n_slots):
    idx = (start + np.arange(SCFG.chunk)) % len(ys)
    return np.repeat(ys[idx][None], n_slots, axis=0)


# ---------------------------------------------------------------------------
# device-side windowing helpers
# ---------------------------------------------------------------------------
def test_window_views_matches_make_windows(lorenz):
    buf = lorenz[: SCFG.buf_len]
    yw_np, _, _ = make_windows(buf, None, window=SCFG.window, stride=SCFG.stride, normalize=False)
    yw_dev = window_views(jnp.asarray(buf), SCFG.window, SCFG.stride)
    assert yw_dev.shape[0] == n_buffer_windows(SCFG.buf_len, SCFG.window, SCFG.stride)
    np.testing.assert_allclose(np.asarray(yw_dev), yw_np, atol=1e-7)


def test_window_views_batched(lorenz):
    bufs = jnp.asarray(np.stack([lorenz[:48], lorenz[8:56]]))
    yw = window_views(bufs, SCFG.window, SCFG.stride)
    assert yw.shape == (2, n_buffer_windows(48, 12, 6), 12, 3)
    np.testing.assert_allclose(
        np.asarray(yw[1]), np.asarray(window_views(bufs[1], SCFG.window, SCFG.stride)), atol=0
    )


def test_roll_buffer_drops_oldest():
    buf = jnp.arange(12, dtype=jnp.float32).reshape(6, 2)[None]
    new = jnp.full((1, 2, 2), 99.0)
    out = roll_buffer(buf, new)
    assert out.shape == buf.shape
    np.testing.assert_allclose(np.asarray(out[0, :4]), np.asarray(buf[0, 2:]))
    np.testing.assert_allclose(np.asarray(out[0, 4:]), 99.0)


# ---------------------------------------------------------------------------
# admission / eviction round-trip
# ---------------------------------------------------------------------------
def test_admission_eviction_roundtrip(lorenz):
    svc = RecoveryService(CFG, SCFG, n_slots=2, seed=0)
    for sid in range(3):
        svc.submit(sid, lorenz[sid : sid + SCFG.buf_len])
    assert svc.fill_slots() == [0, 1]
    assert svc.slot_streams() == [0, 1]
    assert np.asarray(svc.state.active).all()

    # admission wrote each stream's history into ITS slot only
    np.testing.assert_allclose(np.asarray(svc.state.buf_y[0]), lorenz[:48], atol=1e-6)
    np.testing.assert_allclose(np.asarray(svc.state.buf_y[1]), lorenz[1:49], atol=1e-6)

    p1_before = np.asarray(svc.state.params.head_w1[1])
    cursor = 0
    while not ({0, 1} <= set(svc.results)) and svc.ticks < 20:
        svc.tick_once(_chunks(lorenz, SCFG.buf_len + cursor, 2))
        cursor += SCFG.chunk
        if svc.ticks == 1:
            # ticking trains BOTH slots (params moved) and keeps ids stable
            assert svc.slot_streams() == [0, 1]
            assert not np.allclose(np.asarray(svc.state.params.head_w1[1]), p1_before)
    # max_steps=64 at K=8 forces eviction by tick 8; stream 2 takes a freed
    # slot immediately, the other freed slot deactivates (queue drained)
    assert {0, 1} <= set(svc.results)
    assert 2 in svc.slot_streams()
    assert sorted(svc.slot_streams()) == [-1, 2]
    # evicted streams land in the warm-start registry with a recorded result
    assert {0, 1} <= set(svc.warm)
    for sid in (0, 1):
        res = svc.results[sid]
        assert res.theta.shape == (CFG.n_terms, CFG.state_dim)
        assert np.isfinite(res.theta).all()
        assert res.steps >= SCFG.min_steps
        assert res.reason in ("converged", "budget")
    # draining the queue: once all streams finish, slots deactivate
    while not svc.done and svc.ticks < 40:
        svc.tick_once(_chunks(lorenz, SCFG.buf_len + cursor, 2))
        cursor += SCFG.chunk
    assert svc.done
    assert svc.slot_streams() == [-1, -1]
    assert len(svc.results) == 3


def test_admission_preserves_other_slots(lorenz):
    svc = RecoveryService(CFG, SCFG, n_slots=2, seed=1)
    svc.submit(0, lorenz[:48])
    svc.submit(1, lorenz[5:53])
    svc.fill_slots()
    buf1 = np.asarray(svc.state.buf_y[1])
    w1 = np.asarray(svc.state.params.head_w1[1])
    # admit a new stream into slot 0 only
    svc.submit(9, lorenz[10:58])
    svc._admit_into(0)
    assert svc.slot_streams() == [9, 1]
    np.testing.assert_allclose(np.asarray(svc.state.buf_y[1]), buf1, atol=0)
    np.testing.assert_allclose(np.asarray(svc.state.params.head_w1[1]), w1, atol=0)
    # the admitted slot was fully reset
    assert float(np.asarray(svc.state.delta[0])) == np.inf
    assert int(np.asarray(svc.state.steps[0])) == 0


# ---------------------------------------------------------------------------
# compiled churn path: cold start and one-read eviction
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("seed", [0, 7, 2**31 + 5])
def test_cold_start_program_matches_eager(seed):
    """The compiled cold start gives the eager init_mr + adamw_init: params to
    1e-6 relative, moments and step exactly zero."""
    key = jax.random.fold_in(jax.random.key(seed), 1000 + 3)
    params, opt = stream.cold_start(key, CFG)
    ref = init_mr(key, CFG)
    for got, want in zip(jax.tree.leaves(params), jax.tree.leaves(ref)):
        got, want = np.asarray(got), np.asarray(want)
        assert got.shape == want.shape and got.dtype == want.dtype
        assert np.abs(got - want).max() <= 1e-6 * max(np.abs(want).max(), 1e-30)
    ref_opt = adamw_init(ref)
    assert jax.tree.structure(opt) == jax.tree.structure(ref_opt)
    assert int(opt.step) == 0
    for leaf in jax.tree.leaves((opt.m, opt.v)):
        assert leaf.dtype == jnp.float32 and not np.asarray(leaf).any()


def test_cold_start_compiles_once(lorenz):
    """After the first admission, further admissions reuse the one compiled
    cold start: the jitted function's cache holds a single entry."""
    stream.cold_start_program.clear_cache()
    svc = RecoveryService(CFG, SCFG, n_slots=1, seed=0)
    svc.submit(0, lorenz[:48])
    svc.fill_slots()
    assert stream.cold_start_program._cache_size() == 1
    for sid in range(1, 9):
        svc.submit(sid, lorenz[sid : sid + 48])
        svc._admit_into(0)
    assert svc.slot_streams() == [8]
    assert stream.cold_start_program._cache_size() == 1


def _five_reads(st, slot):
    """A slot's answer read leaf by leaf, as eviction did before slot_result."""
    return dict(
        stream_id=int(np.asarray(st.stream_id[slot])),
        theta=np.asarray(st.theta[slot]),
        mean=np.asarray(st.mean[slot]),
        scale=np.asarray(st.scale[slot]),
        steps=int(np.asarray(st.steps[slot])),
    )


def test_evict_reads_slot_once(lorenz):
    """On a service with evictions, each eviction is one host read: the
    StreamResult matches the leaf-by-leaf reads of the slot, the warm
    registry holds the slot's params, and a tick's syncs are the 4 status
    reads of the composite tick plus one per eviction."""
    svc = RecoveryService(CFG, SCFG, n_slots=2, seed=0)
    for sid in range(6):
        svc.submit(sid, lorenz[3 * sid : 3 * sid + SCFG.buf_len])
    svc.fill_slots()
    cursor, evictions = SCFG.buf_len, 0
    for _ in range(12):
        info = svc.tick_once(_chunks(lorenz, cursor, 2))
        cursor += SCFG.chunk
        evictions += len(info["evicted"])
        assert svc.sync_log[-1] == 4 + len(info["evicted"])
    assert evictions >= 2

    # one direct eviction, against the five separate reads
    slot = 1
    st = svc.state
    want = _five_reads(st, slot)
    want_params = [np.asarray(a[slot]) for a in jax.tree.leaves(st.params)]
    syncs = svc.counters["host_syncs"]
    res = svc._evict(slot, "budget")
    assert svc.counters["host_syncs"] == syncs + 1
    assert res.stream_id == want["stream_id"] and res.steps == want["steps"]
    assert res.reason == "budget"
    for k in ("theta", "mean", "scale"):
        np.testing.assert_array_equal(getattr(res, k), want[k])
    warm = svc.warm[res.stream_id]
    for got, exp in zip(jax.tree.leaves(warm), want_params):
        np.testing.assert_array_equal(np.asarray(got), exp)
    assert svc.results[res.stream_id] is res


def test_slot_result_one_program_for_all_slots(lorenz):
    """slot_result takes the slot as a traced index: every slot is served by
    one compiled program, and the state is left intact (not donated)."""
    svc = RecoveryService(CFG, SCFG, n_slots=3, seed=2)
    for sid in range(3):
        svc.submit(10 + sid, lorenz[sid : sid + SCFG.buf_len])
    svc.fill_slots()
    stream.slot_result.clear_cache()
    for slot in range(3):
        fields, params = stream.slot_result(svc.state, jnp.int32(slot))
        assert int(fields["stream_id"]) == 10 + slot
        np.testing.assert_array_equal(
            np.asarray(params.head_w1), np.asarray(svc.state.params.head_w1[slot])
        )
    assert stream.slot_result._cache_size() == 1
    assert np.asarray(svc.state.active).all()


# ---------------------------------------------------------------------------
# warm start
# ---------------------------------------------------------------------------
def test_warm_start_beats_cold_start(lorenz):
    """A re-admitted stream resumes from its evicted params: after the same
    few ticks on the same data it must sit at a lower loss than cold start."""

    def run_ticks(svc, n):
        losses = []
        cursor = SCFG.buf_len
        for _ in range(n):
            info = svc.tick_once(_chunks(lorenz, cursor, 1))
            cursor += SCFG.chunk
            losses.append(float(info["loss"][0]))
        return losses

    scfg = SCFG  # max_steps=64 -> evicts after 8 ticks
    cold = RecoveryService(CFG, scfg, n_slots=1, seed=3)
    cold.submit(7, lorenz[:48])
    cold.fill_slots()
    cold_losses = run_ticks(cold, 8)
    assert 7 in cold.results  # budget eviction happened; params in registry

    # same service, same stream id re-submitted -> warm start from registry
    cold.submit(7, lorenz[:48])
    cold.fill_slots()
    warm_losses = run_ticks(cold, 2)

    # fresh service, same data, cold init observed over the same 2 ticks
    fresh = RecoveryService(CFG, scfg, n_slots=1, seed=3)
    fresh.submit(7, lorenz[:48])
    fresh.fill_slots()
    fresh_losses = run_ticks(fresh, 2)

    assert warm_losses[-1] < fresh_losses[-1], (warm_losses, fresh_losses)
    # warm start resumes near the evicted loss level, far below loss at init
    assert warm_losses[0] < cold_losses[0], (warm_losses, cold_losses)


# ---------------------------------------------------------------------------
# int8 serving readout
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def trained(lorenz):
    from repro.core import engine

    yw, _, _ = make_windows(lorenz, None, window=SCFG.window, stride=SCFG.stride)
    params, _ = engine.train_mr_scan(CFG, jnp.asarray(yw), steps=100, lr=3e-3)
    return params, jnp.asarray(yw)


def test_int8_readout_parity(trained):
    """The int8/PWL kernel readout must track the f32 encoder within
    quantization tolerance — and must actually quantize (nonzero gap)."""
    params, yw = trained
    th_f32 = np.asarray(stream.readout_theta(params, CFG, yw))
    th_int8 = np.asarray(stream.readout_theta(params, CFG, yw, quant=True))
    assert np.isfinite(th_int8).all()
    rel = np.linalg.norm(th_int8 - th_f32) / (np.linalg.norm(th_f32) + 1e-9)
    assert rel < 0.05, rel
    assert np.abs(th_int8 - th_f32).max() < 0.1
    assert np.abs(th_int8 - th_f32).max() > 1e-7  # not silently running f32


def test_int8_readout_requires_gru(trained):
    params, yw = trained
    cfg_flow = MRConfig(state_dim=3, order=2, hidden=8, dense_hidden=16, dt=0.01)
    with pytest.raises(ValueError, match="encoder='gru'"):
        stream.readout_theta(params, cfg_flow, yw, quant=True)


def test_quant_service_eviction_readout(lorenz):
    """--quant service: evicted results flow through the int8 kernel path."""
    svc = RecoveryService(CFG, SCFG, n_slots=1, seed=0, quant=True)
    svc.submit(0, lorenz[:48])
    svc.fill_slots()
    cursor = SCFG.buf_len
    while not svc.done and svc.ticks < 12:
        svc.tick_once(_chunks(lorenz, cursor, 1))
        cursor += SCFG.chunk
    res = svc.results[0]
    assert np.isfinite(res.theta).all()
    assert res.theta.shape == (CFG.n_terms, CFG.state_dim)
