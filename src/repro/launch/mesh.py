"""Production mesh factory.

A FUNCTION, not a module-level constant — importing this module never touches
jax device state. The dry-run entry point (dryrun.py) sets
XLA_FLAGS=--xla_force_host_platform_device_count=512 before any jax import;
everything else sees the real device count.
"""

from __future__ import annotations

from repro.parallel import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
