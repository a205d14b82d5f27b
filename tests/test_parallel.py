"""Distribution layer: sharded steps, pipeline parallelism, compression.

Multi-device behaviour runs in subprocesses (conftest.run_devices) so the
main pytest process keeps the real single-device backend.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from conftest import run_devices


def test_sharded_train_step_matches_single_device():
    """Same batch + params: loss on a (2,2) mesh == loss on 1 device."""
    run_devices(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.configs.base import get_config, ShapeConfig
        from repro.models import model as M
        from repro.parallel import rules as rules_mod
        from repro.parallel.steps import make_train_step, train_state_specs, TrainState
        from repro.models.params import materialize

        cfg = get_config("qwen2.5-3b", smoke=True)
        shape = ShapeConfig("t", 32, 4, "train")
        key = jax.random.key(0)
        params = materialize(key, train_state_specs(cfg).params)
        batch = {
            "tokens": jax.random.randint(jax.random.key(1), (4, 32), 0, cfg.vocab_size, jnp.int32),
            "labels": jax.random.randint(jax.random.key(2), (4, 32), 0, cfg.vocab_size, jnp.int32),
        }
        # single-device reference
        loss_ref, _ = jax.jit(lambda p, b: M.train_loss(p, b, cfg))(params, batch)

        mesh = rules_mod.make_mesh((2, 2), ("data", "model"))
        rules = rules_mod.DEFAULT_RULES
        with rules_mod.use_mesh_rules(mesh, rules):
            jitted, state_sh, batch_sh, _ = make_train_step(cfg, shape, mesh, rules, donate=False)
            zeros = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
            state = TrainState(params=params, m=zeros, v=jax.tree.map(jnp.copy, zeros),
                               step=jnp.zeros((), jnp.int32))
            state = jax.device_put(state, state_sh)
            b = jax.device_put(batch, batch_sh)
            new_state, metrics = jitted(state, b)
        assert abs(float(metrics["loss"]) - float(loss_ref)) < 0.05, \
            (float(metrics["loss"]), float(loss_ref))
        # params actually updated
        delta = max(float(jnp.max(jnp.abs(a.astype(jnp.float32) - b2.astype(jnp.float32))))
                    for a, b2 in zip(jax.tree.leaves(params), jax.tree.leaves(new_state.params)))
        assert delta > 0
        print("PASS")
        """,
        n_devices=4,
    )


def test_pipeline_matches_sequential():
    run_devices(
        """
        import jax, jax.numpy as jnp, numpy as np
        from repro.parallel.pipeline import pipeline_spmd, make_pp_mesh, bubble_fraction
        L, D, M, mb = 8, 16, 6, 4
        key = jax.random.key(0)
        ws = jax.random.normal(key, (L, D, D)) * (1.0 / D**0.5)
        layer_fn = lambda lp, x: jnp.tanh(x @ lp)
        x = jax.random.normal(key, (M, mb, D))
        mesh = make_pp_mesh(4, 1)
        y_pp = pipeline_spmd(layer_fn, ws, x, mesh)
        def seq(w, xm):
            return jax.lax.scan(lambda c, wi: (jnp.tanh(c @ wi), None), xm, w)[0]
        y_ref = jax.vmap(lambda xm: seq(ws, xm))(x)
        np.testing.assert_allclose(np.asarray(y_pp), np.asarray(y_ref), atol=1e-6)
        g_pp = jax.grad(lambda w: jnp.sum(pipeline_spmd(layer_fn, w, x, mesh)**2))(ws)
        g_ref = jax.grad(lambda w: jnp.sum(jax.vmap(lambda xm: seq(w, xm))(x)**2))(ws)
        np.testing.assert_allclose(np.asarray(g_pp), np.asarray(g_ref), atol=1e-5)
        assert abs(bubble_fraction(4, 6) - 3/9) < 1e-9
        print("PASS")
        """,
        n_devices=4,
    )


def test_grad_compression_int8_error_feedback():
    """Compressed psum with error feedback: bias vanishes across steps."""
    run_devices(
        """
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import PartitionSpec as P
        from repro.optim.compression import compress_reduce_grads, init_error_buffers
        from repro.parallel import make_mesh
        mesh = make_mesh((4,), ("pod",))
        g_global = jax.random.normal(jax.random.key(0), (4, 64, 8))  # per-pod grads
        mean_ref = jnp.mean(g_global, axis=0)

        def body(g, e):
            out, e2 = compress_reduce_grads({"w": g[0]}, {"w": e[0]}, "pod")
            return out["w"], e2["w"]

        fn = jax.shard_map(body, mesh=mesh, in_specs=(P("pod"), P("pod")),
                           out_specs=(P(), P("pod")), check_vma=False)
        # one step: quantization error bounded
        e0 = jnp.zeros_like(g_global)
        red1, e1 = fn(g_global, e0)
        amax = float(jnp.max(jnp.abs(g_global)))
        assert float(jnp.max(jnp.abs(red1 - mean_ref))) < amax / 127.0 + 1e-5
        # error feedback: same grads re-sent -> accumulated mean converges
        acc = jnp.zeros_like(mean_ref); e = e0
        for i in range(8):
            r, e = fn(g_global, e)
            acc = acc + r
        drift = float(jnp.max(jnp.abs(acc / 8 - mean_ref)))
        assert drift < amax / 127.0 / 2, drift
        print("PASS")
        """,
        n_devices=4,
    )


def test_multislice_compressed_training_matches_uncompressed():
    """Host-driven cross-slice int8+EF exchange: training stays on track.

    Two simulated slices train a small MR head; the compressed run must track
    the uncompressed run's loss closely (error feedback removes the bias).
    """
    from repro.runtime.multislice import MultiSliceTrainer

    key = jax.random.key(0)
    W = jax.random.normal(key, (8, 4)) * 0.5  # ground-truth linear map

    def make_batch(seed):
        k = jax.random.key(seed)
        x = jax.random.normal(k, (32, 8))
        return x, x @ W + 0.01 * jax.random.normal(k, (32, 4))

    def loss_fn(params, batch):
        x, y = batch
        return jnp.mean((x @ params - y) ** 2)

    grad_fn = jax.jit(jax.value_and_grad(loss_fn))

    def update_fn(params, opt_state, grads):
        return params - 0.1 * grads, opt_state

    results = {}
    for compress in (False, True):
        params = jnp.zeros((8, 4))
        tr = MultiSliceTrainer(grad_fn, update_fn, n_slices=2, compress=compress)
        losses = []
        for step in range(40):
            batches = [make_batch(step * 2), make_batch(step * 2 + 1)]
            params, _, loss = tr.step(params, None, batches)
            losses.append(loss)
        results[compress] = (losses, params)
    l_u, p_u = results[False]
    l_c, p_c = results[True]
    assert l_c[-1] < 0.05 * l_c[0], l_c[-1]  # converges
    assert abs(l_c[-1] - l_u[-1]) < 0.02, (l_c[-1], l_u[-1])  # tracks full-precision
    assert float(jnp.max(jnp.abs(p_c - p_u))) < 0.05


def test_multipod_train_step_compiles():
    """(pod, data, model) mesh train step lowers + compiles (pure GSPMD)."""
    run_devices(
        """
        import jax, jax.numpy as jnp
        from repro.configs.base import get_config, ShapeConfig
        from repro.parallel import rules as rules_mod
        from repro.parallel.steps import make_train_step
        cfg = get_config("qwen2.5-3b", smoke=True)
        shape = ShapeConfig("t", 32, 8, "train")
        mesh = rules_mod.make_mesh((2, 2, 2), ("pod", "data", "model"))
        rules = rules_mod.DEFAULT_RULES
        with rules_mod.use_mesh_rules(mesh, rules):
            jitted, state_sh, batch_sh, abstract_args = make_train_step(
                cfg, shape, mesh, rules, donate=False)
            compiled = jitted.lower(*abstract_args).compile()
        txt = compiled.as_text()
        assert "all-reduce" in txt or "reduce-scatter" in txt
        print("PASS")
        """,
        n_devices=8,
        timeout=560,
    )
