"""The main-path Pallas kernels compile for a TPU v5e chip.

Interpret mode (every other kernel test) runs the kernel bodies on the CPU
and cannot see Mosaic's rules: block shapes whose last two dims are neither
the full array dims nor (8, 128)-aligned, scalar stores to VMEM, VMEM
overflow. Here each kernel is compiled by the TPU compiler for a described
(not attached) ``v5e:2x2`` topology, at the widths of the deployment-size
stream fleet that ``chip_smoke.py`` serves (GRU hidden 128, head 256, the
lorenz/damped-oscillator/controlled-pendulum library: 3 states + 1 input,
45 coefficients; 32-step windows, 17 windows per slot, 8-slot tick banks
streamed over a 32-slot grid),
and the compiled program must hold the Mosaic kernel (``tpu_custom_call``).
Two tests compile the whole service tick: with its slot axis sharded over
two described chips, and at a tiny size to find the encoder's backward under
its device scope in the compiled program's metadata.

The topology is described inside a fixture — never at import — so every
test worker collects the same tests and only the worker running this file
loads the TPU compiler.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.gru_scan import kernel as gru_k
from repro.kernels.mr_step import kernel as mr_k
from repro.kernels.mr_step import tick as tick_k

B, T, D, H, DH, KO = 17, 32, 4, 128, 256, 45  # windows, window, n+m, hidden, head, coefs
S, BANK, L, C, N_STATE, N_IN = 32, 8, 160, 16, 3, 1  # slots, bank, ring, chunk, states, inputs
N_SEG = 16
I8 = jnp.int8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a described chip's compiles cannot be read back from the persistent
    # cache: keep them out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiled_text(fn, shapes, sharding):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in shapes]
    return jax.jit(fn).lower(*args).compile().as_text()


def _f(*shape):
    return shape, jnp.float32


def _i8(*shape):
    return shape, I8


_HEAD = [_f(H, DH), _f(DH), _f(DH, KO), _f(KO)]
_HEAD_I8 = [_i8(H, DH), _f(DH), _f(DH), _i8(DH, KO), _f(KO), _f(KO)]
_TAB = _f(2, N_SEG)

MR_STEP = {
    "gru": (
        lambda *a: mr_k.mr_step_pallas(*a, flow=False),
        [_f(B, T, D), _f(B, H), _f(D, 3 * H), _f(H, 3 * H), _f(3 * H), _f(H), _f(T), *_HEAD],
    ),
    "gru_flow": (
        lambda *a: mr_k.mr_step_pallas(*a, flow=True),
        [_f(B, T, D), _f(B, H), _f(D, 3 * H), _f(H, 3 * H), _f(3 * H), _f(H), _f(T), *_HEAD],
    ),
    "gru_int8": (
        mr_k.mr_step_pallas_int8,
        [_f(B, T, D), _f(B, H), _i8(D, 3 * H), _i8(H, 3 * H), _f(3 * H), _f(3 * H), _f(3 * H)]
        + [_f(T), _TAB, _TAB, *_HEAD_I8],
    ),
    "ltc": (
        mr_k.mr_step_ltc_pallas,
        [_f(B, T, D), _f(B, H), _f(D, H), _f(H, H), _f(H), _f(H), _f(H), *_HEAD],
    ),
    "ltc_int8": (
        mr_k.mr_step_ltc_pallas_int8,
        [_f(B, T, D), _f(B, H), _i8(D, H), _f(H), _i8(H, H), _f(H), _f(H), _f(H), _f(H), _TAB]
        + _HEAD_I8,
    ),
    "node": (
        mr_k.mr_step_node_pallas,
        [_f(B, T, D), _f(B, H), _f(H, H), _f(H), _f(H, H), _f(H), _f(D, H), _f(H), *_HEAD],
    ),
}


@pytest.mark.parametrize("variant", sorted(MR_STEP))
def test_mr_step_compiles_for_v5e(one_chip, variant):
    fn, shapes = MR_STEP[variant]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


def test_mr_step_vmapped_over_slots_compiles_for_v5e(one_chip):
    """The composite service tick vmaps the fused step over the slot axis."""
    fn, shapes = MR_STEP["gru"]
    slotted = [((S, *s), dt) for s, dt in shapes]
    assert "tpu_custom_call" in _compiled_text(jax.vmap(fn), slotted, one_chip)


_TICK_STATS = [_f(S, L, N_STATE), _f(S, C, N_STATE), _f(S, N_STATE), _f(S, N_STATE)]
_TICK_STATS += [_f(S, KO), _f(S, 1), _f(S, 1)]
_TICK_U = [_f(S, L, N_IN), _f(S, C, N_IN)]
_TICK_KW = dict(window=T, stride=8, ema=0.9, slots_per_bank=BANK)

MR_TICK = {
    "fp32": (
        lambda *a: tick_k.mr_tick_pallas(*a, flow=False, **_TICK_KW),
        _TICK_STATS
        + [_f(S, D, 3 * H), _f(S, H, 3 * H), _f(S, 3 * H), _f(S, H)]
        + [_f(S, H, DH), _f(S, DH), _f(S, DH, KO), _f(S, KO)]
        + _TICK_U,
    ),
    "int8": (
        lambda *a: tick_k.mr_tick_pallas_int8(*a, **_TICK_KW),
        _TICK_STATS
        + [_i8(S, D, 3 * H), _i8(S, H, 3 * H), _f(S, 3 * H), _f(S, 3 * H), _f(S, 3 * H)]
        + [_TAB, _TAB, _i8(S, H, DH), _f(S, DH), _f(S, DH), _i8(S, DH, KO), _f(S, KO)]
        + [_f(S, KO)]
        + _TICK_U,
    ),
}


@pytest.mark.parametrize("precision", sorted(MR_TICK))
def test_mr_tick_bank8_compiles_for_v5e(one_chip, precision):
    fn, shapes = MR_TICK[precision]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


GRU_SCAN = {
    "fp32": (
        gru_k.gru_scan_pallas,
        [_f(B, T, D), _f(B, H), _f(D, 3 * H), _f(H, 3 * H), _f(3 * H), _f(H), _f(T)],
    ),
    "int8": (
        gru_k.gru_scan_pallas_int8,
        [_f(B, T, D), _f(B, H), _i8(D, 3 * H), _i8(H, 3 * H), _f(3 * H), _f(3 * H), _f(3 * H)]
        + [_f(T), _TAB, _TAB],
    ),
}


@pytest.mark.parametrize("precision", sorted(GRU_SCAN))
def test_gru_scan_compiles_for_v5e(one_chip, precision):
    fn, shapes = GRU_SCAN[precision]
    assert "tpu_custom_call" in _compiled_text(fn, shapes, one_chip)


@pytest.mark.parametrize("tick_kernel", ["composite", "banked"])
def test_slot_mesh_tick_compiles_for_v5e(topo, monkeypatch, tick_kernel):
    """On a slot axis sharded over two chips, the K=8 training tick's Mosaic
    kernels run per shard: XLA cannot partition a Pallas call, so the tick
    must hand each chip its own slots (``core/stream._slot_local``). The
    service traces its programs inside the slot mesh; so does this test."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro import api
    from repro.core import stream as sm
    from repro.core.stream import StreamConfig
    from repro.kernels import runtime as rt
    from repro.parallel import make_mesh, use_mesh_rules

    # off a chip the dispatch policy picks the reference: steer it to the
    # compiled kernels the chip would run
    monkeypatch.setattr(rt, "on_tpu", lambda: True)
    monkeypatch.setattr(
        rt,
        "resolve_dispatch",
        lambda force_reference=False, interpret=None, backend=None: (
            rt.Dispatch.REFERENCE if force_reference else rt.Dispatch.KERNEL
        ),
    )
    n_slots = 2 * BANK
    spec = api.RecoverySpec(
        state_dim=N_STATE,
        input_dim=N_IN,
        order=2,
        hidden=H,
        dense_hidden=DH,
        dt=0.01,
        encoder="gru",
        fused=True,
        mode="stream",
        n_slots=n_slots,
        stream=StreamConfig(steps_per_tick=8),
    )
    cfg, scfg = spec.to_mr_config(), spec.stream_config()
    mesh = make_mesh((2,), ("slots",), devices=topo.devices)
    slots, rep = NamedSharding(mesh, P("slots")), NamedSharding(mesh, P())
    state = jax.eval_shape(lambda k: sm.init_slots(k, cfg, scfg, n_slots), jax.random.key(0))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=slots), state)
    new_y = jax.ShapeDtypeStruct((n_slots, scfg.chunk, N_STATE), jnp.float32, sharding=rep)
    new_u = jax.ShapeDtypeStruct((n_slots, scfg.chunk, N_IN), jnp.float32, sharding=rep)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=rep)
    with use_mesh_rules(mesh, sm.SLOT_RULES):
        if tick_kernel == "banked":
            lowered = sm.tick_banked.lower(
                state, new_y, new_u, key, cfg=cfg, scfg=scfg, slots_per_bank=BANK
            )
        else:
            lowered = sm.tick.lower(state, new_y, new_u, key, cfg=cfg, scfg=scfg)
    assert "tpu_custom_call" in lowered.compile().as_text()


@pytest.mark.parametrize("encoder", ["gru", "ltc"])
def test_encoder_backward_runs_under_its_device_scope(one_chip, monkeypatch, encoder):
    """The fused training tick, compiled for the chip at a tiny size: the
    custom VJP's backward (reference replay and transpose) carries the
    device scope ``mr.encoder_bwd`` in its ``op_name`` metadata, which is
    what a profiler trace reads it by; the forward kernel does not."""
    import re

    from repro import api
    from repro.core import stream as sm
    from repro.core.stream import StreamConfig
    from repro.kernels import runtime as rt
    from repro.kernels.mr_step.ops import ENCODER_BWD_SCOPE

    monkeypatch.setattr(rt, "on_tpu", lambda: True)
    monkeypatch.setattr(
        rt,
        "resolve_dispatch",
        lambda force_reference=False, interpret=None, backend=None: (
            rt.Dispatch.REFERENCE if force_reference else rt.Dispatch.KERNEL
        ),
    )
    n_slots = 4
    spec = api.RecoverySpec(
        state_dim=N_STATE,
        input_dim=N_IN,
        order=2,
        hidden=8,
        dense_hidden=16,
        dt=0.01,
        encoder=encoder,
        fused=True,
        mode="stream",
        n_slots=n_slots,
        stream=StreamConfig(steps_per_tick=1, buf_len=48, window=16, stride=8, chunk=8),
    )
    cfg, scfg = spec.to_mr_config(), spec.stream_config()
    state = jax.eval_shape(lambda k: sm.init_slots(k, cfg, scfg, n_slots), jax.random.key(0))
    state = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip), state)
    new_y = jax.ShapeDtypeStruct((n_slots, scfg.chunk, N_STATE), jnp.float32, sharding=one_chip)
    new_u = jax.ShapeDtypeStruct((n_slots, scfg.chunk, N_IN), jnp.float32, sharding=one_chip)
    key = jax.ShapeDtypeStruct((), jax.random.key(0).dtype, sharding=one_chip)
    text = sm.tick.lower(state, new_y, new_u, key, cfg=cfg, scfg=scfg).compile().as_text()
    op_names = re.findall(r'op_name="([^"]*)"', text)
    assert sum(ENCODER_BWD_SCOPE in n for n in op_names) > 10
    kernels = [
        line
        for line in text.splitlines()
        if 'custom_call_target="tpu_custom_call"' in line
        and line.lstrip().startswith("%mr_step_fused")
    ]
    assert kernels  # the training step's forward and the readout
    for line in kernels:
        name = re.search(r'op_name="([^"]*)"', line).group(1)
        assert ENCODER_BWD_SCOPE not in name, name
