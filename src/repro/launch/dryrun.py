import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST be the first two lines, before ANY jax import: jax locks the device
#   count at first init. Only the dry-run sees 512 placeholder host devices;
#   smoke tests and benches see the real device count.

"""Multi-pod dry-run: prove the distribution config is coherent without TPUs.

For every (architecture x input-shape) cell this lowers + compiles the
appropriate step (train_step / prefill_step / serve_step) against abstract
ShapeDtypeStruct inputs on the production mesh:

    single-pod:  (data=16, model=16)          256 chips
    multi-pod:   (pod=2, data=16, model=16)   512 chips

and records, per cell:
    - memory_analysis()     bytes-per-device (proves the cell fits HBM)
    - cost_analysis()       per-device HLO FLOPs / bytes accessed
    - collective stats      parsed from the post-SPMD HLO (analysis/hlo.py)
    - roofline terms        compute / memory / collective seconds + bottleneck

Usage:
    python -m repro.launch.dryrun --arch minitron-8b --shape train_4k
    python -m repro.launch.dryrun --all --mesh both --jobs 4
    python -m repro.launch.dryrun --arch mamba2-130m --shape train_4k \
        --variant remat_dots          # perf-hillclimb variants (see VARIANTS)

Results land in artifacts/dryrun/<arch>__<shape>__<mesh>[__<variant>].json;
EXPERIMENTS.md tables are generated from these via benchmarks/roofline.py.
"""

import argparse
import dataclasses
import json
import multiprocessing as mp
import pathlib
import subprocess
import sys
import time
import traceback

ART = pathlib.Path(__file__).resolve().parents[3] / "artifacts" / "dryrun"


# ---------------------------------------------------------------------------
# perf-iteration variants (EXPERIMENTS.md §Perf). "baseline" is paper-faithful
# defaults; the others are single-axis changes so before/after is attributable.
# ---------------------------------------------------------------------------
def _apply_variant(cfg, variant: str):
    """Return (cfg', step_kwargs) for a named variant.

    Compound variants compose with '+': e.g. ``mb2+logit_chunk``.
    """
    kw: dict = {}
    if variant == "baseline":
        return cfg, kw
    if "+" in variant:
        for part in variant.split("+"):
            cfg, kw_part = _apply_variant(cfg, part)
            kw.update(kw_part)
        return cfg, kw
    if variant == "remat_dots":
        return dataclasses.replace(cfg, remat="dots"), kw
    if variant == "remat_none":
        return dataclasses.replace(cfg, remat="none"), kw
    if variant == "logit_chunk":
        return dataclasses.replace(cfg, logit_chunk=8), kw
    if variant == "attn_chunk_2k":
        return dataclasses.replace(cfg, attn_chunk=2048), kw
    if variant == "attn_chunk_4k":
        return dataclasses.replace(cfg, attn_chunk=4096), kw
    if variant.startswith("mb"):  # microbatched grad accumulation (mb2, mb4...)
        kw["microbatch"] = int(variant[2:])
        return cfg, kw
    if variant.startswith("ssm_chunk_"):
        n = int(variant.rsplit("_", 1)[1])
        ssm = dataclasses.replace(cfg.ssm, chunk=n)
        return dataclasses.replace(cfg, ssm=ssm), kw
    if variant == "unscan":
        return dataclasses.replace(cfg, scan_layers=False), kw
    raise ValueError(f"unknown variant {variant!r}")


def run_cell(
    arch: str,
    shape_name: str,
    mesh_kind: str,
    variant: str = "baseline",
    rules_name: str = "default",
    out_dir: pathlib.Path = ART,
    verbose: bool = True,
) -> dict:
    """Lower + compile one cell; write the JSON record; return it."""
    import jax  # noqa: F401  # deferred side effect: XLA_FLAGS already set at module import

    from repro.analysis.hlo import analyze_module, roofline_terms
    from repro.configs.base import get_config, get_shape, shape_applicable
    from repro.launch.mesh import make_production_mesh
    from repro.parallel import rules as rules_mod
    from repro.parallel.steps import make_step_for_shape

    t0 = time.time()
    record: dict = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_kind,
        "variant": variant,
        "rules": rules_name,
    }
    out_dir.mkdir(parents=True, exist_ok=True)
    tag = f"{arch}__{shape_name}__{mesh_kind}" + (
        f"__{variant}" if variant != "baseline" else ""
    ) + (f"__{rules_name}" if rules_name != "default" else "")
    out_path = out_dir / f"{tag}.json"

    ok, reason = shape_applicable(arch, shape_name)
    if not ok:
        record.update(status="skipped", reason=reason)
        out_path.write_text(json.dumps(record, indent=1))
        if verbose:
            print(f"[dryrun] {tag}: SKIPPED ({reason})")
        return record

    try:
        cfg = get_config(arch)
        shape = get_shape(shape_name)
        cfg, step_kw = _apply_variant(cfg, variant)
        mesh = make_production_mesh(multi_pod=(mesh_kind == "multi"))
        n_dev = mesh.devices.size
        rules = rules_mod.RULE_VARIANTS[rules_name]

        with rules_mod.use_mesh_rules(mesh, rules):
            jitted, abstract_args = make_step_for_shape(cfg, shape, mesh, rules, **step_kw)
            lowered = jitted.lower(*abstract_args)
            compiled = lowered.compile()

        mem = compiled.memory_analysis()
        cost = compiled.cost_analysis() or {}
        hlo = compiled.as_text()
        # trip-count-aware HLO analysis (analysis/hlo.py) — raw cost_analysis()
        # counts scan bodies once, under-reporting L-layer models by ~L x.
        # f32_as_bf16 corrects CPU float-normalization (see analyzer docstring).
        costs = analyze_module(hlo, n_dev, f32_as_bf16=(cfg.dtype == "bfloat16"))

        # model FLOPs: 6*N_active*D for train, 2*N_active*D per generated/scored token
        n_active = cfg.n_active_params()
        tokens = shape.global_batch * (1 if shape.mode == "decode" else shape.seq_len)
        mf = (6 if shape.mode == "train" else 2) * n_active * tokens

        rf = roofline_terms(
            flops_per_dev=costs.flops,
            hbm_bytes_per_dev=costs.hbm_bytes,
            coll_wire_bytes_per_dev=costs.collective_wire_bytes,
            model_flops_global=float(mf),
            n_devices=n_dev,
        )
        record.update(
            status="ok",
            n_devices=n_dev,
            seconds_to_compile=round(time.time() - t0, 1),
            memory={
                "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
                "output_bytes": getattr(mem, "output_size_in_bytes", 0),
                "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
                "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", 0),
                # donated inputs alias outputs (train: state, decode: cache),
                # so live bytes = temps + max(args, outputs), not their sum
                "peak_bytes_per_device": (
                    max(
                        getattr(mem, "argument_size_in_bytes", 0),
                        getattr(mem, "output_size_in_bytes", 0),
                    )
                    + getattr(mem, "temp_size_in_bytes", 0)
                ),
            },
            cost={k: float(v) for k, v in cost.items() if isinstance(v, (int, float))},
            collectives={
                "ops": {k: int(v) for k, v in costs.collective_ops.items()},
                "operand_bytes": costs.collective_operand_bytes,
                "wire_bytes_per_device": costs.collective_wire_bytes,
            },
            roofline=rf.as_dict(),
            n_params=cfg.n_params(),
            n_active_params=n_active,
        )
        if verbose:
            hbm_gib = record["memory"]["peak_bytes_per_device"] / 2**30
            print(
                f"[dryrun] {tag}: OK {record['seconds_to_compile']}s "
                f"mem/dev={hbm_gib:.2f}GiB bottleneck={rf.bottleneck} "
                f"(tc={rf.t_compute*1e3:.2f}ms tm={rf.t_memory*1e3:.2f}ms "
                f"tl={rf.t_collective*1e3:.2f}ms)"
            )
    except Exception as e:  # record the failure — it's a bug to fix, not to hide
        record.update(status="error", error=f"{type(e).__name__}: {e}",
                      traceback=traceback.format_exc()[-4000:])
        if verbose:
            print(f"[dryrun] {tag}: ERROR {type(e).__name__}: {e}")
    out_path.write_text(json.dumps(record, indent=1))
    return record


def _cells(archs, shapes, meshes):
    from repro.configs.base import ARCH_IDS, SHAPES

    archs = archs or [a for a in ARCH_IDS if a != "merinda-gru"]
    shapes = shapes or list(SHAPES)
    return [(a, s, m) for a in archs for s in shapes for m in meshes]


def _run_subprocess(cell_args) -> tuple[str, bool]:
    """Run one cell in a fresh interpreter (isolation: one compile per proc)."""
    arch, shape, mesh, variant, rules_name = cell_args
    cmd = [
        sys.executable,
        "-m",
        "repro.launch.dryrun",
        "--arch",
        arch,
        "--shape",
        shape,
        "--mesh",
        mesh,
        "--variant",
        variant,
        "--rules",
        rules_name,
    ]
    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).resolve().parents[2])
    p = subprocess.run(cmd, capture_output=True, text=True, env=env)
    tail = (p.stdout + p.stderr).strip().splitlines()
    msg = tail[-1] if tail else ""
    return f"{arch}__{shape}__{mesh}", p.returncode == 0 and "ERROR" not in msg


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", action="append", help="architecture id (repeatable)")
    ap.add_argument("--shape", action="append", help="shape name (repeatable)")
    ap.add_argument("--mesh", default="single", choices=("single", "multi", "both"))
    ap.add_argument("--variant", default="baseline")
    ap.add_argument("--rules", default="default", help="sharding rule variant")
    ap.add_argument("--all", action="store_true", help="all 40 cells")
    ap.add_argument("--jobs", type=int, default=1, help="parallel subprocesses for --all")
    ap.add_argument("--force", action="store_true", help="recompute existing results")
    args = ap.parse_args()

    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]

    if args.all or (args.arch and len(args.arch) + len(args.shape or "xxxx") > 2):
        cells = _cells(args.arch, args.shape, meshes)
        todo = []
        for a, s, m in cells:
            tag = f"{a}__{s}__{m}" + (f"__{args.variant}" if args.variant != "baseline" else "")
            path = ART / f"{tag}.json"
            if path.exists() and not args.force:
                prev = json.loads(path.read_text())
                if prev.get("status") in ("ok", "skipped"):
                    continue
            todo.append((a, s, m, args.variant, args.rules))
        print(f"[dryrun] {len(todo)} cells to run ({len(cells) - len(todo)} cached)")
        failures = []
        with mp.Pool(args.jobs) as pool:
            for tag, _ok in pool.imap_unordered(_run_subprocess, todo):
                rec = (
                    json.loads((ART / f"{tag}.json").read_text())
                    if (ART / f"{tag}.json").exists()
                    else {}
                )
                status = rec.get("status", "missing")
                print(f"  {tag}: {status}")
                if status not in ("ok", "skipped"):
                    failures.append(tag)
        if failures:
            print(f"[dryrun] FAILURES: {failures}")
            return 1
        print("[dryrun] all cells ok")
        return 0

    rec = run_cell(
        args.arch[0] if args.arch else "minitron-8b",
        args.shape[0] if args.shape else "train_4k",
        meshes[0],
        variant=args.variant,
        rules_name=args.rules,
    )
    return 0 if rec.get("status") in ("ok", "skipped") else 1


if __name__ == "__main__":
    sys.exit(main())
