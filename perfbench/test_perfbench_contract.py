"""BENCHMARK.json holds together: names, units, files found by name, and ``moves``.

Also: a run that finds no TPU, or that starts in a directory holding only the
benchmark's own files, exits non-zero and prints no result.
"""

from __future__ import annotations

import json
import os
import re
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")


def cell_reports(workload: str, kind: str) -> set[str]:
    return {m["name"] for m in BENCH[kind] if workload in m.get("workloads", [workload])}


def test_names_units_and_keys():
    names = [x["name"] for k in ("configs", "workloads", "end_to_end", "per_layer")
             for x in BENCH[k]]
    assert len(names) == len(set(names))
    for name in names:
        assert NAME.match(name), name
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert UNIT.match(m["unit"]) and m["better"] in ("lower", "higher"), m
    for m in BENCH["end_to_end"]:
        assert m["source"] in ("host_clock", "device_trace") and 0.01 <= m["bound"] <= 0.25
    for wl in BENCH["workloads"]:
        assert wl["name"] == f"{wl['config']}.{wl['traffic']}" and wl["chips"] in (1, 4)
        assert len(wl["why"]) <= 200
    assert 1 <= BENCH["run_seconds"] <= 51


def test_every_file_is_found_by_name():
    for c in BENCH["configs"]:
        cfg = json.loads((ROOT / c["file"]).read_text())
        assert cfg["name"] == c["name"]
        assert (HERE / "reference" / f"{cfg['encoder']}.py").exists()
        for system in cfg["systems"]:
            assert (HERE / "systems" / f"{system}.json").exists()
    for wl in BENCH["workloads"]:
        assert (HERE / "traffic" / f"{wl['traffic']}.json").exists()
        limits = json.loads((HERE / "limits" / f"{wl['name']}.json").read_text())["limits"]
        assert limits and all(v > 0 for v in limits.values())
    import run

    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert run.reader_path(HERE, m["name"]).exists(), m["name"]


@pytest.mark.parametrize("workload", [wl["name"] for wl in BENCH["workloads"]])
def test_each_cell_reports_what_its_layer_metrics_move(workload):
    e2e = cell_reports(workload, "end_to_end")
    layer = cell_reports(workload, "per_layer")
    assert "setup_s" in e2e and len(e2e) >= 2 and layer
    moves = {m["name"]: m["moves"] for m in BENCH["per_layer"]}
    for name in layer:
        assert moves[name] in e2e, (workload, name, moves[name])


def _run(cwd: Path, env: dict) -> subprocess.CompletedProcess:
    return subprocess.run(
        [sys.executable, "perfbench/run.py", "--workload", BENCH["workloads"][0]["name"],
         "--seed", "3000000001", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_tpu_exits_nonzero_without_a_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _run(ROOT, env)
    assert p.returncode != 0
    assert not p.stdout.strip(), p.stdout[-2000:]


def test_bare_directory_exits_nonzero_without_a_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(HERE, tmp_path / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"} | {"JAX_PLATFORMS": "cpu"}
    p = _run(tmp_path, env)
    assert p.returncode != 0
    assert not p.stdout.strip(), p.stdout[-2000:]


def test_unknown_device_kind_is_an_error():
    import flops

    assert flops.peaks("TPU v5 lite")["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        flops.peaks("TPU v9 imaginary")
