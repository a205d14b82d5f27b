"""Serving resilience: periodic service snapshots + supervised restart.

The training side already has the full stack — atomic async checkpoints
(checkpoint/checkpoint.py), elastic mesh re-planning (runtime/elastic.py),
heartbeats/stragglers (runtime/heartbeat.py) and a supervised restart loop
(runtime/supervisor.py). This module is the SERVING analogue for the
slot-streaming recovery service (core/stream.py):

- :class:`ServiceCheckpointer` — every ``period`` ticks, stage the whole
  service image (SlotState, the device-resident ControlState, the warm-start
  LRU, the tick counter and any supervisor extras) and hand it to
  ``CheckpointManager`` for an async, atomic, CRC-checked write. Restore
  ``device_put``s every slot/control leaf with the CURRENT plan's shardings,
  so a snapshot written on a ("slots",)-mesh of 2 restores onto the shrunken
  1-device plan — reshard-on-restore for the serving state.
- :class:`ServiceSupervisor` — owns the serve loop. On a shard failure
  (:class:`~repro.runtime.supervisor.SimulatedFailure` from a chaos hook) it
  waits out the in-flight snapshot write, drops the lost devices, re-plans
  the slot mesh on the survivors (``plan_mesh_slots``), recompiles the plan
  (``api.compile_plan``), restores the latest snapshot onto the new mesh and
  re-submits every stream the restored image does not already hold — no
  stream is lost, at worst a stream replays the ticks since the snapshot.

Restore rewinds ``service.ticks`` to the snapshot tick, and every tick's
randomness is ``fold_in(key, ticks)``, so a same-mesh restore replays the
exact pre-failure trajectory (tests pin bitwise SlotState/ControlState
round-trip parity). The ControlState is restored only when the shard count
survives unchanged (its leaves are shaped [shards, ...]); on a re-mesh the
queues restart empty and the supervisor re-submits the queued streams.
"""

from __future__ import annotations

import dataclasses
import json
import logging
import pathlib
from typing import Any, Callable

import jax
import numpy as np

from repro.checkpoint import CheckpointManager
from repro.checkpoint.checkpoint import _flatten, _logical_view, restore_checkpoint
from repro.runtime.elastic import plan_mesh_slots
from repro.runtime.supervisor import SimulatedFailure

log = logging.getLogger("repro.resilience")


def _slot_shardings(tree, mesh):
    """Per-leaf NamedSharding over the ("slots",) axis — the same placement
    rule ``shard_slots``/``shard_control`` pin, applied at restore time."""
    from repro.core.stream import SLOT_RULES
    from repro.parallel import named_sharding

    def one(leaf):
        axes = ("slots",) + (None,) * (leaf.ndim - 1)
        return named_sharding(mesh, leaf.shape, axes, SLOT_RULES)

    return jax.tree.map(one, tree)


class ServiceCheckpointer:
    """Periodic async snapshots of a RecoveryService; restore with resharding.

    Attached by ``RecoveryPlan.make_service`` when the TickSpec carries
    ``checkpoint_period``/``checkpoint_dir``; ``RecoveryService.tick_once``
    calls :meth:`after_tick` every tick (a no-op off the period).

    ``extra`` is a host-side dict of arrays snapshotted alongside the
    service image — the supervisor keeps its stream cursors there so a
    restart resumes feeding each stream where the snapshot left off.
    """

    def __init__(self, root: str, period: int, keep: int = 3):
        self.period = int(period)
        self.manager = CheckpointManager(root, keep=keep, save_every=self.period)
        self.extra: dict[str, np.ndarray] = {}

    # -- save ---------------------------------------------------------------
    def _stage(self, service) -> dict:
        tree: dict[str, Any] = {"slots": service.state, "ticks": np.int64(service.ticks)}
        if service.control is not None:
            tree["control"] = service.control
        # warm-start LRU: one params subtree per entry + the LRU order, so a
        # restored service serves the same warm hits the failed one would
        tree["warm"] = {str(sid): params for sid, params in service.warm.items()}
        tree["warm_order"] = np.asarray(list(service.warm.keys()), np.int64)
        for k, v in self.extra.items():
            tree[f"extra/{k}"] = np.asarray(v)
        return tree

    def after_tick(self, service):
        """Snapshot when the tick counter crosses the period (else no-op —
        a steady-state tick pays nothing, keeping the zero-readback gate)."""
        if self.period <= 0 or service.ticks % self.period:
            return
        self.save(service)

    def save(self, service):
        """Stage device->host now (one counted sync), write async (span
        ``mr.checkpoint``)."""
        with jax.profiler.TraceAnnotation("mr.checkpoint"):
            tree = self._stage(service)
            service.counters["host_syncs"] += 1
            self.manager.maybe_save(service.ticks, tree, mesh=service.mesh, force=True)

    def wait(self):
        self.manager.wait()

    # -- restore ------------------------------------------------------------
    def restore_into(self, service) -> dict | None:
        """Restore the latest snapshot into a FRESH service, resharding every
        slot/control leaf onto the service's current mesh.

        Returns ``{"step", "resident", "queued", "extra"}`` (None when no
        snapshot exists). The ControlState is taken only when its shard count
        matches the restoring plan; otherwise the queues restart empty and
        ``queued`` is what the caller must re-submit.
        """
        self.manager.wait()
        step = self.manager.latest()
        if step is None:
            return None
        d = pathlib.Path(self.manager.root) / f"step_{step:08d}"
        manifest = json.loads((d / "manifest.json").read_text())
        leaves = manifest["leaves"]

        like: dict[str, Any] = {"slots": service.state}
        shardings: dict[str, Any] | None = None
        if service.mesh is not None:
            shardings = {"slots": _slot_shardings(service.state, service.mesh)}
        take_control = False
        if service.control is not None:
            ctl_flat = _flatten(service.control)
            take_control = all(
                f"control/{k}" in leaves
                and leaves[f"control/{k}"]["shape"] == list(v.shape)
                for k, v in ctl_flat
            )
            if take_control:
                like["control"] = service.control
                if shardings is not None:
                    shardings["control"] = _slot_shardings(service.control, service.mesh)

        expect_axes = ("slots",) if service.mesh is not None else None
        restored, _ = restore_checkpoint(
            self.manager.root, step, like, shardings, expect_axes=expect_axes
        )
        service.state = restored["slots"]
        if take_control:
            service.control = restored["control"]
        service.ticks = int(np.load(d / leaves["ticks"]["file"]))

        self._restore_warm(service, d, leaves)
        extra = {
            k[len("extra/") :]: np.load(d / meta["file"])
            for k, meta in leaves.items()
            if k.startswith("extra/")
        }
        resident, queued = self._rebuild_views(service, take_control)
        log.info(
            "restored service snapshot step=%d (%d resident, %d queued, control=%s)",
            step,
            len(resident),
            len(queued),
            "restored" if take_control else "reset",
        )
        return {"step": step, "resident": resident, "queued": queued, "extra": extra}

    def _restore_warm(self, service, d: pathlib.Path, leaves: dict):
        from repro.core.stream import cold_start

        order_meta = leaves.get("warm_order")
        if order_meta is None:
            return
        warm_order = [int(s) for s in np.load(d / order_meta["file"])]
        if not warm_order:
            return
        template, _ = cold_start(jax.random.fold_in(service.key, 0), service.cfg)
        tpaths = _flatten(template)
        treedef = jax.tree_util.tree_structure(template)
        for sid in warm_order:
            vals = []
            for pkey, _leaf in tpaths:
                meta = leaves.get(f"warm/{sid}/{pkey}")
                if meta is None:
                    vals = None
                    break
                vals.append(
                    jax.numpy.asarray(_logical_view(np.load(d / meta["file"]), meta["dtype"]))
                )
            if vals is not None:
                service.warm[sid] = treedef.unflatten(vals)
        while len(service.warm) > service.warm_capacity:
            service.warm.popitem(last=False)

    @staticmethod
    def _rebuild_views(service, take_control: bool) -> tuple[set[int], set[int]]:
        """Refresh the host-side caches from the restored image (restore-time
        readbacks — the running service never repeats them)."""
        sid_view = np.asarray(service.state.stream_id)
        service._active_view = np.asarray(service.state.active, bool).copy()
        service._slot_view = sid_view.astype(np.int64)
        service._delta_view = np.asarray(service.state.delta, np.float32).copy()
        service._loss_view = np.asarray(service.state.loss, np.float32).copy()
        service._steps_view = np.asarray(service.state.steps).astype(np.int64)
        resident = {int(i) for i in sid_view if i >= 0}
        queued: set[int] = set()
        if service.control_plane is not None:
            service._inflight = [set() for _ in range(service.control_plane.shards)]
            if take_control:
                for row, ids in enumerate(np.asarray(service.control.q_ids)):
                    for sid in ids:
                        if sid >= 0:
                            service._inflight[row].add(int(sid))
                            queued.add(int(sid))
            service._pending = resident | queued
            service._seen_done = set()
            service._ticks_since_snapshot = 0
        return resident, queued


def replan_spec(spec, n_available: int):
    """Shrink a stream RecoverySpec's slot mesh onto ``n_available`` devices
    (largest divisor of n_slots that fits — ``plan_mesh_slots``)."""
    plan = plan_mesh_slots(n_available, spec.n_slots)
    return dataclasses.replace(spec, mesh_slots=plan.shape[0])


def kill_shard_once(at_tick: int, n_lost: int = 1) -> Callable[[int], None]:
    """Chaos hook: lose ``n_lost`` device(s) at the first tick >= at_tick
    (fires exactly once; the supervisor's restart must absorb it)."""
    state = {"fired": False}

    def chaos(tick: int):
        if not state["fired"] and tick >= at_tick:
            state["fired"] = True
            raise SimulatedFailure(n_lost)

    return chaos


class ServiceSupervisor:
    """Drives a streaming RecoverySpec through shard failures.

    Owns the serve loop (the chunk-routing pattern of
    ``launch/serve_mr.run_service``) plus the restart path: on a
    :class:`SimulatedFailure` it re-plans the slot mesh on the surviving
    devices, recompiles the plan, restores the latest service snapshot with
    resharding and re-submits any stream the restored image dropped.
    ``chaos(tick)`` may raise SimulatedFailure (tests / chaos configs).
    """

    def __init__(
        self,
        spec,
        ckpt_dir: str,
        checkpoint_period: int = 4,
        max_restarts: int = 4,
        chaos: Callable[[int], None] | None = None,
        devices: list | None = None,
        keep: int = 3,
    ):
        if spec.mode != "stream":
            raise ValueError(f"ServiceSupervisor serves stream plans, got mode={spec.mode!r}")
        self.base_spec = spec
        self.ckpt_dir = str(ckpt_dir)
        self.checkpoint_period = int(checkpoint_period)
        self.max_restarts = int(max_restarts)
        self.chaos = chaos
        self.devices = list(devices if devices is not None else jax.devices())
        self.keep = keep
        self.restarts = 0
        self.history: list[dict] = []  # per-incarnation stats
        self.spec = self.plan = self.service = None
        self._compile(len(self.devices))

    def _compile(self, n_available: int):
        from repro.api.plan import compile_plan

        spec = replan_spec(self.base_spec, n_available)
        tspec = dataclasses.replace(
            spec.tick_spec(),
            checkpoint_period=self.checkpoint_period,
            checkpoint_dir=self.ckpt_dir,
        )
        self.spec = spec = dataclasses.replace(spec, tick=tspec)
        self.plan = compile_plan(spec)
        self.service = self.plan.make_service()
        return self.service

    def _incarnation_stats(self) -> dict:
        svc = self.service
        return {
            "ticks": svc.ticks,
            "tick_ms": list(svc.tick_ms),
            "counters": dict(svc.counters),
            "sync_log": list(svc.sync_log),
            "mesh_shape": tuple(self.plan.lowering.mesh_shape),
        }

    def serve(self, ys: np.ndarray, us: np.ndarray | None = None, max_ticks: int = 400) -> dict:
        """Feed every stream through the service until all recover (or the
        tick budget runs out), absorbing injected shard failures.

        ys [R, T_total, n] / us [R, T_total, m]; cursors wrap modulo T_total
        (a slow or replayed stream never starves). Returns the summary dict
        (results, recovered_streams_fraction, restarts, tick latencies).
        """
        svc = self.service
        n_streams, t_total = ys.shape[:2]
        if us is None:
            us = np.zeros(ys.shape[:2] + (svc.cfg.input_dim,), np.float32)
        L = svc.scfg.buf_len
        results: dict[int, Any] = {}
        cursors = {i: L for i in range(n_streams)}
        for i in range(n_streams):
            svc.submit(i, ys[i, :L], us[i, :L])
        svc.fill_slots()
        total_ticks = 0
        while len(results) < n_streams and total_ticks < max_ticks:
            try:
                if self.chaos is not None:
                    self.chaos(total_ticks)
                svc = self.service
                slots, chunk = svc.n_slots, svc.scfg.chunk
                chunks_y = np.zeros((slots, chunk, svc.cfg.state_dim), np.float32)
                chunks_u = np.zeros((slots, chunk, svc.cfg.input_dim), np.float32)
                for s, sid in enumerate(svc.slot_streams()):
                    if sid < 0:
                        continue
                    idx = (cursors[sid] + np.arange(chunk)) % t_total
                    chunks_y[s] = ys[sid, idx]
                    chunks_u[s] = us[sid, idx]
                    cursors[sid] += chunk
                if svc.checkpointer is not None:
                    # stamp cursors BEFORE the tick: a snapshot taken inside
                    # tick_once then restores a consistent (state, cursor) pair
                    svc.checkpointer.extra["cursors"] = np.asarray(
                        [cursors[i] for i in range(n_streams)], np.int64
                    )
                svc.tick_once(chunks_y, chunks_u)
                total_ticks += 1
                results.update(svc.results)
            except SimulatedFailure as e:
                results.update(self.service.results)
                self._recover(e, ys, us, cursors, results, t_total)
        self.history.append(self._incarnation_stats())
        results.update(self.service.results)
        all_ms = [t for h in self.history for t in h["tick_ms"]]
        return {
            "results": results,
            "ticks": total_ticks,
            "restarts": self.restarts,
            "recovered_streams_fraction": len(results) / max(n_streams, 1),
            "p50_tick_ms": float(np.percentile(all_ms, 50)) if all_ms else 0.0,
            "p99_tick_ms": float(np.percentile(all_ms, 99)) if all_ms else 0.0,
            "final_mesh": tuple(self.plan.lowering.mesh_shape),
            "counters": {
                k: sum(h["counters"][k] for h in self.history)
                for k in ("host_syncs", "reshards")
            },
        }

    def _recover(self, e: SimulatedFailure, ys, us, cursors, results, t_total: int):
        self.restarts += 1
        if self.restarts > self.max_restarts:
            raise RuntimeError("restart budget exhausted") from e
        if e.n_lost >= len(self.devices):
            raise RuntimeError("no surviving devices") from e
        old = self.service
        if old.checkpointer is not None:
            old.checkpointer.wait()  # never restore a torn in-flight write
        self.history.append(self._incarnation_stats())
        log.warning("shard failure (%s); re-meshing on survivors", e)
        # surviving devices: drop from the tail (the lost shard's chips)
        self.devices = self.devices[: len(self.devices) - e.n_lost]
        svc = self._compile(len(self.devices))
        info = svc.checkpointer.restore_into(svc) if svc.checkpointer is not None else None
        safe: set[int] = set()
        if info is not None:
            safe = info["resident"] | info["queued"]
            saved = info["extra"].get("cursors")
            if saved is not None:
                for i in range(min(len(cursors), len(saved))):
                    cursors[i] = int(saved[i])
        else:
            # failed before the first snapshot: every stream restarts from
            # its initial history
            for i in cursors:
                cursors[i] = svc.scfg.buf_len
        L = svc.scfg.buf_len
        for sid in sorted(cursors):
            if sid in results or sid in svc.results or sid in safe:
                continue
            idx = (cursors[sid] - L + np.arange(L)) % t_total
            svc.submit(sid, ys[sid, idx], us[sid, idx])
        svc.fill_slots()
