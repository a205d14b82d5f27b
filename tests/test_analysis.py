"""HLO analyzer: trip counts, dot flops, collective wire model, RS detection."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.analysis.hlo import analyze_module, roofline_terms

from conftest import run_devices


def test_scan_equals_unroll_flops():
    """The whole reason this analyzer exists (see analysis/hlo.py docstring)."""

    def f_scan(x, w):
        def body(c, _):
            return jnp.tanh(c @ w), None

        return jax.lax.scan(body, x, None, length=8)[0]

    def f_unroll(x, w):
        for _ in range(8):
            x = jnp.tanh(x @ w)
        return x

    x = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    w = jax.ShapeDtypeStruct((256, 256), jnp.float32)
    expect = 8 * 2 * 256**3
    got = {}
    for name, f in (("scan", f_scan), ("unroll", f_unroll)):
        txt = jax.jit(f).lower(x, w).compile().as_text()
        got[name] = analyze_module(txt, 1).flops
    assert got["scan"] == got["unroll"] == expect, got


def test_dot_flops_counts_batch_dims_once():
    """Batched dot: flops = 2 * prod(result dims) * prod(contracting dims).

    The batch dims already appear in the result-shape product, so the lhs
    contracting product must EXCLUDE lhs_batch_dims — re-multiplying them
    overcounts by the batch size. Hand-computed einsum cases, one and two
    batch dims."""
    a = jnp.zeros((4, 3, 5), jnp.float32)
    b = jnp.zeros((4, 5, 7), jnp.float32)
    txt = jax.jit(lambda x, y: jnp.einsum("bij,bjk->bik", x, y)).lower(a, b).compile().as_text()
    assert analyze_module(txt, 1).flops == 2 * (4 * 3 * 7) * 5

    a = jnp.zeros((2, 3, 4, 5), jnp.float32)
    b = jnp.zeros((2, 3, 5, 6), jnp.float32)
    txt = (
        jax.jit(lambda x, y: jnp.einsum("abij,abjk->abik", x, y)).lower(a, b).compile().as_text()
    )
    assert analyze_module(txt, 1).flops == 2 * (2 * 3 * 4 * 6) * 5


def test_nested_scan_trip_product():
    def f(x, w):
        def outer(c, _):
            def inner(ci, _):
                return ci @ w, None

            return jax.lax.scan(inner, c, None, length=3)[0], None

        return jax.lax.scan(outer, x, None, length=5)[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    w = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    txt = jax.jit(f).lower(x, w).compile().as_text()
    assert analyze_module(txt, 1).flops == 15 * 2 * 64**3


def test_collective_wire_model():
    """psum of [N] over 8 devices: AR wire = 2*B*(n-1)/n per device."""
    run_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.analysis.hlo import analyze_module
        from repro.parallel import make_mesh
        mesh = make_mesh((8,), ("m",))
        def f(x, w):  # contract the sharded dim -> one all-reduce
            return x @ w
        x = jax.ShapeDtypeStruct((64, 512), jnp.float32)
        w = jax.ShapeDtypeStruct((512, 64), jnp.float32)
        c = jax.jit(f,
            in_shardings=(NamedSharding(mesh, P(None, "m")), NamedSharding(mesh, P("m", None))),
            out_shardings=NamedSharding(mesh, P())).lower(x, w).compile()
        a = analyze_module(c.as_text(), 8)
        B = 64 * 64 * 4
        assert a.collective_ops.get("all-reduce", 0) >= 1
        expect = 2 * B * 7 / 8
        assert abs(a.collective_wire_bytes - expect) / expect < 0.01, \
            (a.collective_wire_bytes, expect)
        print("PASS")
        """,
        n_devices=8,
    )


def test_reduce_scatter_recognition():
    """AR + 1/n slice (CPU lowering) must be costed as reduce-scatter (TPU)."""
    run_devices(
        """
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.analysis.hlo import analyze_module
        from repro.parallel import make_mesh
        mesh = make_mesh((8,), ("m",))
        def f(x, w):
            y = x @ w  # partial over m
            return jax.lax.with_sharding_constraint(
                y, NamedSharding(mesh, P("m", None)))  # sharded output
        x = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        w = jax.ShapeDtypeStruct((512, 512), jnp.float32)
        c = jax.jit(f,
            in_shardings=(NamedSharding(mesh, P(None, "m")), NamedSharding(mesh, P("m", None)))
            ).lower(x, w).compile()
        a = analyze_module(c.as_text(), 8)
        assert a.collective_ops.get("reduce-scatter", 0) >= 1, a.collective_ops
        assert a.collective_ops.get("all-reduce", 0) == 0, a.collective_ops
        B = 512 * 512 * 4
        expect = B * 7 / 8
        assert abs(a.collective_wire_bytes - expect) / expect < 0.01
        print("PASS")
        """,
        n_devices=8,
    )


def test_roofline_terms_bottleneck():
    r = roofline_terms(1e12, 1e9, 1e8, model_flops_global=5e11, n_devices=1)
    assert r.bottleneck == "compute"
    assert abs(r.useful_ratio - 0.5) < 1e-9
    r2 = roofline_terms(1e10, 1e12, 1e8)
    assert r2.bottleneck == "memory"
    r3 = roofline_terms(1e10, 1e9, 1e12)
    assert r3.bottleneck == "collective"


def test_kernel_adjusted_ssd_roofline():
    """The fused-kernel memory term must beat the XLA path and leave the
    cell compute-bound (EXPERIMENTS.md §Perf cell 3, reproducible in code)."""
    import pytest

    from benchmarks.roofline import ART, kernel_adjusted_ssd

    if not (ART / "mamba2-130m__train_4k__single__fsdp2d.json").exists():
        pytest.skip("fsdp2d variant artifact not generated")
    k = kernel_adjusted_ssd()
    assert k["t_memory_kernel"] < 0.25 * k["t_memory_xla"]
    assert abs(k["dominant_after"] - k["t_compute"]) < 1e-9  # compute-bound


def test_kernel_adjusted_flash_roofline():
    """Flash kernel must cut the prefill memory term (EXPERIMENTS §Perf)."""
    import pytest

    from benchmarks.roofline import ART, kernel_adjusted_flash

    if not (ART / "minitron-8b__prefill_32k__single.json").exists():
        pytest.skip("dry-run artifact not generated")
    k = kernel_adjusted_flash()
    assert k["t_memory_kernel"] < 0.6 * k["t_memory_xla"]
    assert k["dominant_after"] < k["dominant_before"]


def test_scanned_mr_step_trip_count_recovery():
    """While-loop trip-count recovery on a REAL scanned mr_step program.

    Doubling the window length T doubles the fused stage's scan trips, so
    the analyzer's flop total must scale ~2x — it only can if the while
    loop's trip count was actually recovered (trip=1 fallback would give a
    ~1x ratio)."""
    from repro.core.merinda import MRConfig, init_mr
    from repro.kernels.mr_step import ops as mr_ops

    cfg = MRConfig(state_dim=2, hidden=8, dense_hidden=16, encoder="gru", fused=True)
    params = init_mr(jax.random.key(0), cfg)
    flops = {}
    for T in (8, 16):
        xs = jax.ShapeDtypeStruct((4, T, cfg.state_dim), jnp.float32)
        step = jax.jit(lambda p, x: mr_ops.mr_step(p, cfg, x))
        flops[T] = analyze_module(step.lower(params, xs).compile().as_text(), 1).flops
    ratio = flops[16] / flops[8]
    assert 1.8 <= ratio <= 2.2, (flops, ratio)


def test_nonconstant_trip_count_degrades_gracefully():
    """A while loop whose bound is a TRACED value has no recoverable trip
    count; the analyzer must not crash and must fall back to trip >= 1."""

    def f(x, n):
        def cond(c):
            return c[1] < n

        def body(c):
            return (jnp.tanh(c[0] @ c[0]), c[1] + 1)

        return jax.lax.while_loop(cond, body, (x, jnp.int32(0)))[0]

    x = jax.ShapeDtypeStruct((64, 64), jnp.float32)
    n = jax.ShapeDtypeStruct((), jnp.int32)
    txt = jax.jit(f).lower(x, n).compile().as_text()
    a = analyze_module(txt, 1)
    # one loop-body matmul counted at least once (conservative trip=1)
    assert a.flops >= 2 * 64**3, a.flops


def test_fusion_byte_model_smaller_than_naive():
    """Chained elementwise ops must not each pay full tensor traffic."""

    def f(x):
        for _ in range(16):
            x = jnp.tanh(x) * 1.01 + 0.1
        return x

    x = jax.ShapeDtypeStruct((1024, 1024), jnp.float32)
    txt = jax.jit(f).lower(x).compile().as_text()
    a = analyze_module(txt, 1)
    naive = 16 * 2 * 1024 * 1024 * 4
    # fused estimate should be well under one read+write per op
    assert a.hbm_bytes < naive / 2, (a.hbm_bytes, naive)
