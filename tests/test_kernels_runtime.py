"""kernels/runtime: the one pallas_call constructor (spec pairs, SMEM
operands, compiler params), the dispatch policy, and interpret-vs-reference
parity for the kernel families routed through it."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import runtime as rt


# --- the one pallas_call constructor ----------------------------------------
def test_compiler_params_resolves_new_spelling():
    assert isinstance(rt.compiler_params(), pltpu.CompilerParams)


def test_compiler_params_builds_on_installed_jax():
    p = rt.compiler_params(dimension_semantics=(rt.PARALLEL, rt.ARBITRARY))
    assert tuple(p.dimension_semantics) == (rt.PARALLEL, rt.ARBITRARY)


def test_block_spec_builds_on_installed_jax():
    spec = rt.block_spec((8, 128), lambda i: (i, 0))
    assert tuple(spec.block_shape) == (8, 128)


def test_smem_spec_places_operand_in_smem():
    assert rt.smem_spec().memory_space == pltpu.SMEM


def test_pallas_call_squeezed_time_major_stream_and_smem_scalars():
    """The streaming layout every scan kernel uses: a time-major operand
    read one ``(None, bb, D)`` block (time dim squeezed) per grid step, plus
    per-step scalars read from an SMEM operand — under the interpreter it
    must equal the plain jnp expression."""
    T, B, D, bb = 5, 16, 128, 8

    def kernel(x_ref, dt_ref, o_ref):
        o_ref[...] = x_ref[...] * dt_ref[pl.program_id(1), 0]

    x = jax.random.normal(jax.random.key(0), (T, B, D), jnp.float32)
    dts = jnp.arange(1.0, T + 1.0, dtype=jnp.float32).reshape(T, 1)
    out = rt.pallas_call(
        kernel,
        grid=(B // bb, T),
        in_specs=[((None, bb, D), lambda ib, t: (t, ib, 0)), rt.smem_spec()],
        out_specs=((None, bb, D), lambda ib, t: (t, ib, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, D), jnp.float32),
        dimension_semantics=(rt.PARALLEL, rt.ARBITRARY),
        interpret=True,
    )(x, dts)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(x * dts[:, :, None]))


# --- dispatch policy ---------------------------------------------------------
def test_dispatch_force_reference_wins_everywhere():
    for backend in ("cpu", "tpu", "gpu"):
        for interp in (None, False, True):
            assert rt.resolve_dispatch(True, interp, backend=backend) is rt.Dispatch.REFERENCE


def test_dispatch_tpu_runs_kernel():
    assert rt.resolve_dispatch(False, None, backend="tpu") is rt.Dispatch.KERNEL
    assert rt.resolve_dispatch(False, True, backend="tpu") is rt.Dispatch.KERNEL


def test_dispatch_cpu_interpret_vs_reference():
    assert rt.resolve_dispatch(False, True, backend="cpu") is rt.Dispatch.INTERPRET
    assert rt.resolve_dispatch(False, None, backend="cpu") is rt.Dispatch.REFERENCE
    assert rt.resolve_dispatch(False, False, backend="cpu") is rt.Dispatch.REFERENCE


# --- interpret-vs-reference parity through the runtime ------------------------
def test_gru_interpret_matches_reference():
    from repro.core.neural_flow import gru_scan_ref, init_gru
    from repro.kernels.gru_scan.ops import gru_scan

    key = jax.random.key(0)
    p = init_gru(key, 4, 16)
    xs = jax.random.normal(key, (2, 9, 4), jnp.float32)
    h0 = jax.random.normal(jax.random.key(1), (2, 16), jnp.float32) * 0.1
    _, hs_r = gru_scan_ref(p, xs, h0, flow=True)
    _, hs_k = gru_scan(p, xs, h0, flow=True, interpret=True)
    np.testing.assert_allclose(np.asarray(hs_k), np.asarray(hs_r), atol=2e-5, rtol=2e-5)


def test_flash_interpret_matches_reference():
    from repro.kernels.flash_attention.ops import flash_attention

    key = jax.random.key(2)
    q = jax.random.normal(key, (1, 64, 2, 32), jnp.float32)
    k = jax.random.normal(jax.random.key(3), (1, 64, 2, 32), jnp.float32)
    v = jax.random.normal(jax.random.key(4), (1, 64, 2, 32), jnp.float32)
    out_k = flash_attention(q, k, v, causal=True, interpret=True, block_q=32, block_k=32)
    out_r = flash_attention(q, k, v, causal=True, force_reference=True)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_r), atol=2e-5, rtol=2e-5)


def test_ssd_interpret_matches_reference():
    from repro.kernels.ssd_scan.ops import ssd_scan

    key = jax.random.key(5)
    B, T, H, P, G, N = 1, 64, 2, 8, 1, 4
    x = jax.random.normal(key, (B, T, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(jax.random.key(6), (B, T, H))) * 0.1
    A = -jax.nn.softplus(jax.random.normal(jax.random.key(7), (H,)))
    bm = jax.random.normal(jax.random.key(8), (B, T, G, N), jnp.float32)
    cm = jax.random.normal(jax.random.key(9), (B, T, G, N), jnp.float32)
    D = jax.random.normal(jax.random.key(10), (H,), jnp.float32)
    y_k, s_k = ssd_scan(x, dt, A, bm, cm, D, chunk=32, interpret=True)
    y_r, s_r = ssd_scan(x, dt, A, bm, cm, D, chunk=32, force_reference=True)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r), atol=2e-4, rtol=2e-4)
    np.testing.assert_allclose(np.asarray(s_k), np.asarray(s_r), atol=2e-4, rtol=2e-4)
