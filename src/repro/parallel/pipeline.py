"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

The layer stack [L, ...] is split into S contiguous stages (stage s owns
layers [s*L/S, (s+1)*L/S)). Execution runs inside ``shard_map`` over the
stage axis: every device holds only its stage's weights, activations move
stage->stage with ``jax.lax.ppermute`` (collective_permute on the wire — the
cheapest collective: one neighbor hop per microbatch per stage boundary).

Schedule: classic GPipe. M microbatches flow through S stages in M + S - 1
ticks; the bubble fraction is (S-1)/(M+S-1). Backward is obtained by JAX AD
through the scan + ppermute (ppermute's transpose is the reverse permute),
which reproduces the standard reverse-schedule wave.

This is an optional execution mode (``--mesh pp`` in the launcher): the
production 40-cell grid uses DP x TP (see DESIGN.md §5); PP becomes necessary
when layer weights no longer fit a TP group, and the same stage axis extends
to (pod, stage, data, model) at real scale.

API:
    pipeline_spmd(layer_fn, stacked, x_mb, mesh) -> y_mb
        layer_fn(lp, x) -> x        one layer's forward
        stacked: pytree, leaves [L, ...]
        x_mb:    [M, mb, S, D]      microbatched activations
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

from repro.parallel.rules import make_mesh


def split_stages(stacked, n_stages: int):
    """[L, ...] leaves -> [S, L/S, ...] (stage-major) for stage sharding."""

    def f(a):
        L = a.shape[0]
        assert L % n_stages == 0, f"{L} layers not divisible into {n_stages} stages"
        return a.reshape(n_stages, L // n_stages, *a.shape[1:])

    return jax.tree.map(f, stacked)


def pipeline_spmd(layer_fn, stacked, x_mb: jnp.ndarray, mesh: Mesh, axis: str = "stage"):
    """Run x_mb [M, mb, ...] through the stage-split stack. Returns [M, mb, ...].

    Correctness contract (tested): equals the sequential application of all L
    layers to each microbatch, for forward AND gradients.
    """
    S = mesh.shape[axis]
    M = x_mb.shape[0]
    staged = split_stages(stacked, S)  # [S, L/S, ...]

    def per_stage(stage_params, xs):
        # stage_params: [1, L/S, ...] (this stage's slice); xs: [M, mb, ...]
        stage_params = jax.tree.map(lambda a: a[0], stage_params)
        idx = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)
        outs = jnp.zeros_like(xs)

        def apply_stage(x):
            def body(c, lp):
                return layer_fn(lp, c), None

            y, _ = jax.lax.scan(body, x, stage_params)
            return y

        fwd = [(i, (i + 1) % S) for i in range(S)]

        def tick(carry, t):
            state, outs = carry
            # stage 0 ingests microbatch t while t < M
            take = jnp.clip(t, 0, M - 1)
            inject = jnp.where((idx == 0) & (t < M), 1.0, 0.0).astype(xs.dtype)
            keep = jnp.where(idx == 0, 0.0, 1.0).astype(xs.dtype)
            state = inject * xs[take] + keep * state
            state = apply_stage(state)
            # last stage emits microbatch t - (S-1) when valid
            out_i = jnp.clip(t - (S - 1), 0, M - 1)
            emit = ((idx == S - 1) & (t >= S - 1)).astype(xs.dtype)
            outs = jax.lax.dynamic_update_slice(
                outs,
                (emit * state + (1 - emit) * jax.lax.dynamic_slice(
                    outs, (out_i,) + (0,) * len(mb_shape), (1,) + mb_shape
                )[0])[None],
                (out_i,) + (0,) * len(mb_shape),
            )
            # hand activations to the next stage
            state = jax.lax.ppermute(state, axis, fwd)
            return (state, outs), None

        (state, outs), _ = jax.lax.scan(tick, (state, outs), jnp.arange(M + S - 1))
        # outputs live on the last stage; broadcast to every stage so the
        # caller (loss on replicated head) sees the full tensor
        outs = jax.lax.psum(jnp.where(idx == S - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    in_specs = (
        jax.tree.map(lambda _: P(axis), staged),
        P(),  # microbatches replicated across stages
    )
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False)
    return fn(staged, x_mb)


def make_pp_mesh(n_stages: int = 4, data: int = 1):
    """(stage, data) mesh for the pipeline execution mode."""
    return make_mesh((n_stages, data), ("stage", "data"))


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)
