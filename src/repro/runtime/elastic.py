"""Elastic mesh planning: largest healthy mesh after failures.

Policy (documented in DESIGN.md §5): shrink the DATA axis first — model/TP
degree is dictated by per-layer weight shapes and changing it reshapes every
compiled program, while data-parallel width only rescales throughput. Pods
drop next (a whole pod lost); the model axis is preserved unless fewer than
``model`` devices survive.

``plan_mesh`` is pure (unit-testable); ``build_mesh`` materializes it.
"""

from __future__ import annotations

import dataclasses

import jax

from repro.parallel import make_mesh


@dataclasses.dataclass(frozen=True)
class MeshPlan:
    shape: tuple[int, ...]
    axes: tuple[str, ...]

    @property
    def n_devices(self) -> int:
        n = 1
        for s in self.shape:
            n *= s
        return n


def _pow2_floor(n: int) -> int:
    p = 1
    while p * 2 <= n:
        p *= 2
    return p


def plan_mesh(
    n_available: int,
    model: int = 16,
    max_data: int = 16,
    pods: int = 1,
) -> MeshPlan:
    """Largest (pod, data, model) mesh fitting n_available devices.

    data is kept a power of two (keeps global batch divisible and collectives
    ring-friendly); model is preserved if at all possible.
    """
    if n_available < 1:
        raise ValueError("no devices")
    model_eff = model
    while model_eff > n_available:
        model_eff //= 2
    per_pod_target = max_data * model_eff
    pods_eff = max(1, min(pods, n_available // per_pod_target))
    data = _pow2_floor(max(1, n_available // (pods_eff * model_eff)))
    data = min(data, max_data)
    if pods_eff > 1:
        return MeshPlan((pods_eff, data, model_eff), ("pod", "data", "model"))
    return MeshPlan((data, model_eff), ("data", "model"))


def plan_mesh_slots(n_available: int, n_slots: int) -> MeshPlan:
    """Largest 1-D ``("slots",)`` mesh fitting n_available devices.

    The serving mesh shards the slot axis, so the device count must divide
    ``n_slots`` (shard_slots requires equal per-shard slot counts). Picks the
    largest divisor of n_slots that fits — after a shard failure the service
    restores onto this plan (runtime/resilience.py).
    """
    if n_available < 1:
        raise ValueError("no devices")
    if n_slots < 1:
        raise ValueError("no slots")
    d = min(n_available, n_slots)
    while n_slots % d:
        d -= 1
    return MeshPlan((d,), ("slots",))


def shrink_plan(current: MeshPlan, n_failed: int) -> MeshPlan:
    """Re-plan after n_failed devices drop out of the current mesh."""
    return plan_mesh(
        current.n_devices - n_failed,
        model=current.shape[-1],
        max_data=current.shape[-2],
        pods=current.shape[0] if len(current.shape) == 3 else 1,
    )


def build_mesh(plan: MeshPlan, devices=None):
    devices = devices if devices is not None else jax.devices()
    n = plan.n_devices
    if n > len(devices):
        raise ValueError(f"plan needs {n} devices, have {len(devices)}")
    return make_mesh(plan.shape, plan.axes, devices=devices)
