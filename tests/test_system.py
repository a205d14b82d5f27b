"""End-to-end behaviour: training drivers, serving driver, dry-run machinery."""

from __future__ import annotations

import json
import pathlib

import numpy as np
import pytest

from conftest import REPO, SRC, run_devices


def test_lm_training_reduces_loss():
    """examples-grade run: reduced qwen on synthetic LM data, loss must fall."""
    run_devices(
        """
        import sys, tempfile
        sys.argv = ["train", "--arch", "qwen2.5-3b", "--steps", "25",
                    "--batch", "8", "--seq", "64", "--data", "2", "--model", "2",
                    "--save-every", "0", "--ckpt-dir", tempfile.mkdtemp()]
        from repro.launch.train import main
        assert main() == 0
        print("PASS")
        """,
        n_devices=4,
        timeout=560,
    )


def test_serving_driver_completes_all_requests():
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = SRC
    cmd = [sys.executable, "-m", "repro.launch.serve", "--arch", "qwen2.5-3b"]
    cmd += ["--requests", "6", "--slots", "2", "--prompt-len", "8"]
    cmd += ["--max-new", "6", "--cache-len", "32"]
    p = subprocess.run(cmd, capture_output=True, text=True, env=env, timeout=560, cwd=REPO)
    assert p.returncode == 0, p.stderr[-2000:]
    assert "requests=6" in p.stdout


def test_dryrun_machinery_small_mesh():
    """The dry-run entry point end-to-end on a 16-device toy mesh."""
    run_devices(
        """
        import json, pathlib, tempfile, jax
        import repro.launch.mesh as mesh_mod
        from repro.parallel import make_mesh
        mesh_mod.make_production_mesh = lambda multi_pod=False: (
            make_mesh((2, 2, 2), ("pod", "data", "model")) if multi_pod
            else make_mesh((2, 2), ("data", "model")))
        import repro.configs.base as B
        # smoke dims + tiny shape so the cell compiles in seconds
        B.SHAPES["train_4k"] = B.ShapeConfig("train_4k", 64, 8, "train")
        real_get = B.get_config
        B.get_config = lambda name, smoke=False: real_get(name, smoke=True)
        import repro.launch.dryrun as DR
        DR.get_config = B.get_config  # run_cell imports inside the function
        out = pathlib.Path(tempfile.mkdtemp())
        rec = DR.run_cell("qwen2.5-3b", "train_4k", "single", out_dir=out)
        assert rec["status"] == "ok", rec.get("error")
        assert rec["roofline"]["flops_per_dev"] > 0
        assert rec["memory"]["peak_bytes_per_device"] > 0
        rec2 = DR.run_cell("qwen2.5-3b", "train_4k", "multi", out_dir=out)
        assert rec2["status"] == "ok", rec2.get("error")
        print("PASS")
        """,
        n_devices=16,
        timeout=560,
    )


def test_dryrun_artifacts_complete():
    """The committed 80-cell dry-run results: every cell ok or justified skip."""
    art = pathlib.Path(REPO) / "artifacts" / "dryrun"
    if not art.exists():
        pytest.skip("dry-run artifacts not generated yet")
    from repro.configs.base import ARCH_IDS, SHAPES, shape_applicable

    archs = [a for a in ARCH_IDS if a != "merinda-gru"]
    missing, bad = [], []
    for arch in archs:
        for shape in SHAPES:
            for mesh in ("single", "multi"):
                p = art / f"{arch}__{shape}__{mesh}.json"
                if not p.exists():
                    missing.append(p.name)
                    continue
                r = json.loads(p.read_text())
                ok, _ = shape_applicable(arch, shape)
                want = ("ok",) if ok else ("skipped",)
                if r["status"] not in want:
                    bad.append((p.name, r["status"], r.get("error", "")[:100]))
    assert not missing, missing[:5]
    assert not bad, bad[:5]


def test_mr_end_to_end_quickstart():
    """The quickstart path: generate -> train MERINDA -> recover -> prune."""
    import jax.numpy as jnp

    from repro.core.merinda import MRConfig, recover_coefficients, train_mr
    from repro.data.dynamics import generate_trajectory
    from repro.data.windows import make_windows

    ts, ys, us = generate_trajectory("lotka_volterra")
    yw, uw, norm = make_windows(ys, us, window=32, stride=4)
    cfg = MRConfig(state_dim=2, order=2, hidden=32, dense_hidden=64, dt=0.05)
    params, hist = train_mr(cfg, jnp.asarray(yw), None, steps=120, lr=3e-3,
                            batch_size=64, log_every=119)
    assert hist[-1]["recon_mse"] < 0.1, hist
    theta = recover_coefficients(params, cfg, jnp.asarray(yw), None, n_active=4)
    assert int((np.abs(np.asarray(theta)) > 0).sum()) <= 4


@pytest.mark.parametrize("from_env", [True, False])
def test_compile_cache_dir(monkeypatch, tmp_path, from_env):
    """Entry points keep JAX's compile cache in $JAX_COMPILATION_CACHE_DIR
    when set, else at the fixed <checkout>/.jax_cache — never elsewhere."""
    import jax

    from repro.launch.compile_cache import enable_compile_cache

    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    was = jax.config.jax_compilation_cache_dir
    try:
        path = enable_compile_cache()
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    assert path == (str(tmp_path) if from_env else str(pathlib.Path(REPO) / ".jax_cache"))


def test_serve_mr_serves_at_float32_matmul_precision(monkeypatch):
    """serve_mr runs the recovery math at float32 matmul precision, which the
    CPU computes anyway and a TPU only when asked: at its default (one
    bfloat16 pass) a stream of the acceptance scenario converged early,
    outside the baseline tolerance."""
    import jax

    from repro.launch import compile_cache, serve_mr

    seen = []
    monkeypatch.setattr(compile_cache, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(
        serve_mr, "_serve", lambda args: seen.append(jax.config.jax_default_matmul_precision)
    )
    serve_mr.main(["--streams", "1"])
    assert seen == ["float32"]
    assert jax.config.jax_default_matmul_precision is None  # nothing leaks past main
