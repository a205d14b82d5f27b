"""Distributed LM training driver.

Wires together: configs registry -> sharded train step (parallel/steps.py) ->
deterministic data pipeline (data/pipeline.py) -> supervisor with
checkpoint/restart + elastic re-mesh (runtime/supervisor.py).

On the CPU container this trains REDUCED (smoke) configs for real — the same
code path the production mesh would run; pass --full only on a TPU slice.

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --steps 60 --batch 8 --seq 128 --data 2 --model 2

Failure drill (kills a "host" mid-run, supervisor re-meshes + restores):

    PYTHONPATH=src python -m repro.launch.train --arch qwen2.5-3b \
        --steps 40 --chaos-step 20 --data 2 --model 2

Model-recovery mode (the paper's workload, scan-jitted engine — one compiled
program for the whole run; comma-separate systems to recover a fleet in one
vmapped call via core/engine.recover_many):

    PYTHONPATH=src python -m repro.launch.train \
        --recover lorenz,damped_oscillator,controlled_pendulum --steps 300
"""

from __future__ import annotations

import argparse
import logging
import time

import jax
import numpy as np

from repro.launch.compile_cache import enable_compile_cache


def run_recover(systems: list[str], steps: int, lr: float) -> int:
    """Streaming-recovery driver: one vmapped scan-jitted program recovers
    coefficients for every requested system (core/engine.py)."""
    from repro.core import engine
    from repro.core.library import denormalize_theta

    t0 = time.time()
    ys_b, us_b, norms, cfg = engine.stack_systems(systems)
    thetas = engine.recover_many(cfg, ys_b, us_b, steps=steps, lr=lr, batch_size=64)
    thetas = np.asarray(jax.block_until_ready(thetas))
    dt = time.time() - t0
    print(
        f"[recover] {len(systems)} systems x {steps} steps in {dt:.1f}s "
        f"(one compiled program; library order {cfg.order}, {cfg.n_terms} terms)"
    )
    for name, th, norm in zip(systems, thetas, norms):
        # report in PHYSICAL units — spurious terms can hide in z-scored
        # coordinates (see merinda.recover_physical_coefficients)
        th_phys = denormalize_theta(
            th,
            norm["mean"],
            norm["scale"],
            n_vars=cfg.state_dim + cfg.input_dim,
            order=cfg.order,
            n_state=cfg.state_dim,
        )
        nz = int((np.abs(th_phys) > 0.05).sum())
        print(f"  {name:22s} |theta|_max={np.abs(th_phys).max():.3f} active_terms~{nz}")
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2.5-3b")
    ap.add_argument("--recover", default=None, metavar="SYS[,SYS...]",
                    help="model-recovery mode: comma-separated systems from "
                         "data/dynamics.SYSTEMS (skips LM training entirely)")
    ap.add_argument("--full", action="store_true", help="full config (TPU only)")
    ap.add_argument("--steps", type=int, default=60)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--data", type=int, default=1)
    ap.add_argument("--model", type=int, default=1)
    ap.add_argument("--lr", type=float, default=None,
                    help="default 3e-4 (LM training) / 3e-3 (--recover mode)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--save-every", type=int, default=20)
    ap.add_argument("--chaos-step", type=int, default=0, help="simulate failure at step")
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--rules", default="default",
                    help="sharding rules variant (parallel/rules.RULE_VARIANTS)")
    args = ap.parse_args()
    enable_compile_cache()

    if args.recover:
        systems = [s.strip() for s in args.recover.split(",") if s.strip()]
        # float32 recovery math, as serve_mr runs it (TPU's default would
        # multiply float32 matrices in one bfloat16 pass)
        with jax.default_matmul_precision("float32"):
            return run_recover(systems, args.steps, args.lr if args.lr is not None else 3e-3)

    logging.basicConfig(level=logging.INFO, format="%(name)s %(message)s")
    from repro.configs.base import ShapeConfig, get_config
    from repro.data.pipeline import PipelineConfig, SyntheticLM, device_put_batch
    from repro.parallel import rules as rules_mod
    from repro.parallel.steps import make_train_step, train_state_specs
    from repro.models.params import materialize
    from repro.runtime import SimulatedFailure, Supervisor
    from repro.runtime.elastic import plan_mesh
    from repro.runtime.supervisor import SupervisorConfig

    cfg = get_config(args.arch, smoke=not args.full)
    shape = ShapeConfig("cli", args.seq, args.batch, "train")
    pipe = SyntheticLM(
        PipelineConfig(vocab_size=cfg.vocab_size, seq_len=args.seq, global_batch=args.batch)
    )

    def build_step(mesh):
        rules = rules_mod.RULE_VARIANTS[args.rules]
        with rules_mod.use_mesh_rules(mesh, rules):
            jitted, state_sh, batch_sh, _ = make_train_step(
                cfg, shape, mesh, rules,
                lr=args.lr if args.lr is not None else 3e-4, donate=False
            )

        def init_state():
            from repro.parallel.steps import TrainState
            import jax.numpy as jnp

            specs = train_state_specs(cfg)
            key = jax.random.key(0)
            with rules_mod.use_mesh_rules(mesh, rules):
                params = materialize(key, specs.params)
                zeros_like = jax.tree.map(lambda a: jnp.zeros(a.shape, jnp.float32), params)
                state = TrainState(
                    params=params,
                    m=zeros_like,
                    v=jax.tree.map(jnp.copy, zeros_like),
                    step=jnp.zeros((), jnp.int32),
                )
                state = jax.device_put(state, state_sh)
            return state

        def step_fn(state, batch):
            with rules_mod.use_mesh_rules(mesh, rules):
                batch = device_put_batch(batch, batch_sh)
                return jitted(state, batch)

        return step_fn, None, init_state  # shardings=None: save/restore re-places

    def next_batch(step, mesh):
        return pipe.batch_at(step)

    chaos = None
    if args.chaos_step:
        fired = {"done": False}

        def chaos(step):
            if step == args.chaos_step and not fired["done"]:
                fired["done"] = True
                raise SimulatedFailure(n_lost=len(jax.devices()) // 2)

    sup = Supervisor(
        build_step,
        next_batch,
        args.ckpt_dir,
        SupervisorConfig(max_steps=args.steps, save_every=args.save_every),
        chaos=chaos,
    )
    plan = plan_mesh(len(jax.devices()), model=args.model, max_data=args.data)
    t0 = time.time()
    result = sup.run(plan)
    dt = time.time() - t0

    losses = [h["loss"] for h in result["history"] if np.isfinite(h["loss"])]
    print(
        f"[train] arch={args.arch} steps={result['final_step']} "
        f"restarts={result['restarts']} mesh={result['final_mesh']} "
        f"loss {losses[0]:.3f} -> {losses[-1]:.3f} ({dt:.0f}s)"
    )
    for h in result["history"][:: max(1, args.log_every)]:
        print(f"  step {h['step']:4d} mesh={h['mesh']} loss={h['loss']:.4f} {h['t']*1e3:.0f}ms")
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
