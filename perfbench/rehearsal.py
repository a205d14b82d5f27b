"""A tiny copy of the benchmark for CPU rehearsals (the tests beside this file).

``tiny_root(tmp)`` copies ``BENCHMARK.json`` and this directory's files into
``tmp`` and then only adds files, as a later change that brings a cell or a
metric would: a tiny GRU and a tiny LTC configuration (widths cut to fit a
test), two tiny traffic mixes, their limits (the chip cells' own), and one
more end-to-end metric reader. Nothing that was copied is edited.

The test files import the fixtures ``tiny`` and ``no_compile_cache`` from
here (a ``conftest.py`` beside them would shadow the repository's own
``tests/conftest.py`` module of the same name).
"""

from __future__ import annotations

import json
import shutil
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent

TINY = dict(n_slots=4, hidden=8, dense_hidden=16, buf_len=48, window=16, stride=8, chunk=8,
            min_steps=16, max_steps=32)
TRAFFIC = {
    "serve": dict(samples_per_stream=200, check_slots=2, trace_seconds=0.2),
    "backlog": dict(samples_per_stream=200, check_slots=2, trace_seconds=0.2, admit_groups=2,
                    backlog_streams=64),
}
ADDED_METRIC = '''"""Ticks completed per second of the window (added by a rehearsal)."""


def read(run):
    return len(run.rec.tick_s) / run.rec.window_s
'''


def tiny_root(tmp: Path) -> Path:
    """A benchmark root with tiny cells ``tiny_<gru|ltc>.tiny_<serve|backlog>``."""
    tmp = Path(tmp)
    shutil.copy(ROOT / "BENCHMARK.json", tmp / "BENCHMARK.json")
    shutil.copytree(HERE, tmp / "perfbench", ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads((tmp / "BENCHMARK.json").read_text())
    pb = tmp / "perfbench"
    for cfg_name, real in (("tiny_gru", "gru_fleet"), ("tiny_ltc", "ltc_fleet")):
        cfg = json.loads((pb / "configs" / f"{real}.json").read_text()) | TINY
        cfg["name"] = cfg_name
        (pb / "configs" / f"{cfg_name}.json").write_text(json.dumps(cfg, indent=1))
        bench["configs"].append(dict(
            name=cfg_name, source=cfg["source"], file=f"perfbench/configs/{cfg_name}.json",
            reduced=["n_slots"], why="CPU rehearsal: widths cut to run in a test"))
        for mix, extra in TRAFFIC.items():
            wl = f"{cfg_name}.tiny_{mix}"
            # the chip cell this tiny one stands for: its own, else the GRU one of the mix
            cell = f"{real}.{mix}"
            if not (pb / "limits" / f"{cell}.json").exists():
                cell = f"gru_fleet.{mix}"
            traffic = json.loads((pb / "traffic" / f"{mix}.json").read_text()) | extra
            (pb / "traffic" / f"tiny_{mix}.json").write_text(json.dumps(traffic))
            shutil.copy(pb / "limits" / f"{cell}.json", pb / "limits" / f"{wl}.json")
            bench["workloads"].append(dict(name=wl, config=cfg_name, traffic=f"tiny_{mix}",
                                           chips=1, why="CPU rehearsal"))
            for m in bench["end_to_end"] + bench["per_layer"]:
                if cell in m.get("workloads", []):
                    m["workloads"].append(wl)
    (pb / "metrics" / "ticks_per_s.py").write_text(ADDED_METRIC)
    bench["end_to_end"].append(dict(name="ticks_per_s", unit="ticks/s", better="higher",
                                    bound=0.25, source="host_clock"))
    (tmp / "BENCHMARK.json").write_text(json.dumps(bench, indent=1))
    return tmp


@pytest.fixture(scope="module")
def tiny(tmp_path_factory):
    """A temporary benchmark root holding the tiny rehearsal cells."""
    return tiny_root(tmp_path_factory.mktemp("bench"))


@pytest.fixture(autouse=True)
def no_compile_cache(monkeypatch):
    """Rehearsals leave the process's compilation cache settings alone."""
    import run

    monkeypatch.setattr(run, "enable_cache", lambda: None)
