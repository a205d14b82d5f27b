"""One cell's run: set-up, the measured window, and what it leaves for the checks.

The program is driven only through its public path:
``RecoverySpec`` -> ``compile_plan`` -> ``plan.make_service`` ->
``submit`` / ``fill_slots`` / ``tick_once``. Everything that belongs to a
configuration or a traffic mix comes from their data files; the loop below
is the one general driver both mixes run through.

The window is a closed loop, ticks back to back. Each tick's input is one
vectorised gather over trajectories made at set-up from the seed, every shape
the window uses has been run during set-up, and garbage left by set-up is
collected once and frozen (``gc.freeze``), as long-running servers do. The
window counts the compiles and the garbage collections that still happen in
it; stalls the program causes itself stay in it.
"""

from __future__ import annotations

import collections
import dataclasses
import gc
import time

import numpy as np

BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
TRACE = "/jax/core/compile/jaxpr_trace_duration"
LOWER = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class CompileWatch:
    """Counts JAX traces and backend compiles (or cache loads) and their seconds."""

    def __init__(self):
        import jax

        self.counts = {BACKEND_COMPILE: 0, TRACE: 0, LOWER: 0}
        self.seconds = dict.fromkeys(self.counts, 0.0)
        self.names = collections.Counter()  # (event, function) -> count
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, fun_name: str = "", **_):
        if event in self.counts:
            self.counts[event] += 1
            self.seconds[event] += duration
            self.names[event.rsplit("/", 1)[-1].split("_duration")[0], fun_name] += 1

    def snapshot(self) -> tuple[dict, dict]:
        return dict(self.counts), dict(self.seconds)

    def close(self):
        import jax

        jax.monitoring.unregister_event_duration_listener(self._on)


class GCWatch:
    """Times every garbage collection while armed."""

    def __init__(self):
        self.count, self.seconds, self._t0, self.armed = 0, 0.0, None, False
        gc.callbacks.append(self._on)

    def _on(self, phase: str, info: dict):
        if not self.armed:
            return
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.count += 1
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None

    def close(self):
        gc.callbacks.remove(self._on)


def build_spec(cfg: dict, traffic: dict, service_seed: int):
    from repro import api
    from repro.core.stream import StreamConfig

    scfg = StreamConfig(
        buf_len=cfg["buf_len"],
        window=cfg["window"],
        stride=cfg["stride"],
        chunk=cfg["chunk"],
        steps_per_tick=traffic["steps_per_tick"],
        lr=cfg["lr"],
        ema=cfg["ema"],
        delta_tol=cfg["delta_tol"],
        min_steps=cfg["min_steps"],
        max_steps=cfg["max_steps"],
    )
    return api.RecoverySpec(
        state_dim=cfg["state_dim"],
        input_dim=cfg["input_dim"],
        order=cfg["order"],
        hidden=cfg["hidden"],
        dense_hidden=cfg["dense_hidden"],
        dt=cfg["dt"],
        ltc_substeps=cfg["ltc_substeps"],
        lambda_sparse=cfg["lambda_sparse"],
        recon_weight=cfg["recon_weight"],
        encoder=cfg["encoder"],
        fused=cfg["fused"],
        mode="stream",
        lr=cfg["lr"],
        seed=service_seed,
        n_slots=cfg["n_slots"],
        stream=scfg,
    )


@dataclasses.dataclass
class Record:
    """What a run leaves for the metric readers and the checks."""

    chunk: int
    phases: dict = dataclasses.field(default_factory=dict)
    setup_s: float = 0.0
    window_s: float = 0.0
    tick_s: list = dataclasses.field(default_factory=list)
    evicted: list = dataclasses.field(default_factory=list)  # (tick, StreamResult, in window)
    host_syncs: list = dataclasses.field(default_factory=list)
    window_compiles: int = 0
    window_traces: int = 0
    window_compiled: dict = dataclasses.field(default_factory=dict)  # (event, fn) -> count
    gc_count: int = 0
    gc_ms: float = 0.0
    live_slots: int = 0
    trace: object = None  # devtrace.Reduction of the traced part of the window
    traced_ticks: int = 0
    memory_peak_bytes: int | None = None
    # for the checks
    slot_stream0: object = None  # slot -> stream after the first admission
    first: dict = dataclasses.field(default_factory=dict)  # the training check's ticks
    recycled: dict = dataclasses.field(default_factory=dict)  # streams refilled in the window
    theta_log: dict = dataclasses.field(default_factory=dict)  # slot -> [(tick, theta)]
    ticks_total: int = 0


def as_dict(tree):
    """A model's parameters (NamedTuples of arrays) as nested dicts, by field name."""
    if hasattr(tree, "_asdict"):
        return {k: as_dict(v) for k, v in tree._asdict().items()}
    return tree


def leaf_norms(tree, rows) -> np.ndarray:
    """[len(rows), n_leaves] norms of each leaf of the slots ``rows``, leaves in
    sorted-name order."""
    import jax

    leaves = jax.tree.leaves(as_dict(tree))
    return np.stack(
        [np.sqrt((np.asarray(x[rows], np.float64) ** 2).reshape(len(rows), -1).sum(1))
         for x in leaves], axis=1)


def rows_host(tree, rows):
    import jax

    return jax.tree.map(lambda x: np.asarray(x[rows]), as_dict(tree))


class Driver:
    """Set-up and window of one cell, through the service's public calls."""

    def __init__(self, cfg: dict, traffic: dict, fleet, order: np.ndarray, spec,
                 check_slots: np.ndarray, n_first: int):
        self.cfg, self.traffic, self.fleet, self.spec = cfg, traffic, fleet, spec
        self.S, self.C, self.L = cfg["n_slots"], cfg["chunk"], cfg["buf_len"]
        self.rec = Record(self.C)
        self.slot_stream = np.full(self.S, -1, np.int64)
        self.cursor = np.zeros(self.S, np.int64)
        self.admitted_at = np.zeros(self.S, np.int64)  # tick after which the slot's stream came
        self.order = order  # stream ids in arrival order
        self.next_stream = 0
        self.window_open = None  # tick count when the window opened
        self.ticks = 0
        self.check_slots = check_slots
        self.n_first = n_first  # ticks whose state the training check follows
        K = traffic["steps_per_tick"]
        self.budget_ticks = -(-cfg["max_steps"] // K) + 1 if K else 0
        self.plan = self.service = None
        self.in_window = False
        self.theta_failed = 0  # window Theta readbacks of live slots that are not finite

    # -- set-up ----------------------------------------------------------------
    def compile(self, service_seed: int):
        from repro import api

        self.plan = api.compile_plan(self.spec)
        self.service = self.plan.make_service(service_seed)

    def submit(self, n: int):
        for _ in range(n):
            sid = int(self.order[self.next_stream])
            hy, hu = self.fleet.history(sid, self.L)
            self.service.submit(sid, hy, hu)
            self.next_stream += 1

    def fill(self):
        self.service.fill_slots()
        self._route()

    def _route(self):
        """Re-read the slot map; a slot whose stream changed restarts its cursor."""
        streams = np.asarray(self.service.slot_streams(), np.int64)
        changed = streams != self.slot_stream
        self.cursor[changed] = self.L
        self.admitted_at[changed] = self.ticks
        self.slot_stream = streams

    def tick(self, read_theta: bool):
        import jax

        with jax.profiler.TraceAnnotation("bench.gather"):
            cy, cu = self.fleet.chunks(self.slot_stream, self.cursor, self.C)
        with jax.profiler.TraceAnnotation("bench.tick_once"):
            info = self.service.tick_once(cy, cu)
        self.ticks += 1
        self.cursor += self.C
        if read_theta:
            with jax.profiler.TraceAnnotation("bench.readback"):
                theta = np.asarray(self.service.state.theta)
            if self.in_window:
                finite = np.isfinite(theta).reshape(self.S, -1).all(axis=1)
                self.theta_failed += int((~finite & (self.slot_stream >= 0)).sum())
            for slot in self.check_slots:
                self.rec.theta_log.setdefault(int(slot), []).append(
                    (self.ticks, theta[slot].copy()))
        if info["evicted"]:
            with jax.profiler.TraceAnnotation("bench.route"):
                self._route()
        return info

    def setup(self, first_only: bool = False):
        """Admission in staggered groups, warm ticks, then the eviction flow.
        ``first_only`` stops after the training check's first ticks.

        In a training mix the first ``n_first`` ticks of the first admitted
        group are recorded for the training check: each tick's loss and Theta
        readout, AdamW's first moment after tick 1 and the parameters' change
        after the last.
        """
        tr = self.traffic
        groups, every = tr["admit_groups"], tr["admit_every"]
        sizes = np.diff(np.linspace(0, self.S, groups + 1).round().astype(int))
        admit_s, g, evictions = 0.0, 0, 0
        first, p0, losses, thetas = None, None, [], []
        while True:
            if g < groups and self.ticks % every == 0:
                t0 = time.perf_counter()
                self.submit(int(sizes[g]))
                self.fill()
                g += 1
                if g == groups and tr["backlog_streams"]:
                    self.submit(tr["backlog_streams"])
                    self.fill()
                admit_s += time.perf_counter() - t0
            if self.ticks == 0:
                self.rec.slot_stream0 = self.slot_stream.copy()
                first = np.flatnonzero(self.slot_stream >= 0)
                if self.n_first:
                    p0 = rows_host(self.service.state.params, first)
            info = self.tick(read_theta=tr["read_theta"])
            if self.ticks <= self.n_first:
                st = self.service.state
                losses.append(np.asarray(st.loss)[first])
                thetas.append(np.asarray(st.theta)[first])
                if self.ticks == 1:
                    self.rec.first["moment"] = leaf_norms(st.opt.m, first)
                if self.ticks == self.n_first:
                    import jax

                    now = rows_host(st.params, first)
                    self.rec.first["change"] = leaf_norms(
                        jax.tree.map(lambda a, b: a - b, now, p0), np.arange(len(first)))
                    self.rec.first["loss"] = np.stack(losses, axis=1)
                    self.rec.first["theta"] = np.stack(thetas, axis=1)
                    self.rec.first["streams"] = self.rec.slot_stream0[first]
                    p0 = None
                    if first_only:
                        return
            evictions += len(info["evicted"])
            for res in info["evicted"]:
                self.rec.evicted.append((self.ticks, res, False))
            done = g >= groups and self.ticks >= groups * every + tr["warm_ticks"]
            if done and evictions >= tr["min_setup_evictions"] and self.ticks >= self.n_first:
                break
            if self.ticks > groups * every + tr["warm_ticks"] + self.budget_ticks:
                break  # every stream overran its step budget: the window shows the fault
        self.rec.phases["admit_s"] = admit_s
        self.rec.live_slots = int((self.slot_stream >= 0).sum())

    # -- the window --------------------------------------------------------------
    def window(self, seconds: float, gcw: GCWatch, watch: CompileWatch, trace_dir=None,
               trace_seconds: float = 0.0):
        import jax

        tr = self.traffic
        gc.collect()
        gc.freeze()
        counts0, _ = watch.snapshot()
        names0 = watch.names.copy()
        syncs0 = len(self.service.sync_log)
        self.window_open = self.ticks
        gcw.armed = True
        self.in_window = True
        tracing = trace_dir is not None
        t_open = time.perf_counter()
        if tracing:
            # host spans only: the Python tracer would record every call of the loop
            options = jax.profiler.ProfileOptions()
            options.python_tracer_level = 0
            jax.profiler.start_trace(str(trace_dir), profiler_options=options)
        traced_ticks = 0
        while True:
            t0 = time.perf_counter()
            info = self.tick(read_theta=tr["read_theta"])
            t1 = time.perf_counter()
            self.rec.tick_s.append(t1 - t0)
            for res in info["evicted"]:
                self.rec.evicted.append((self.ticks, res, True))
            if tracing:
                traced_ticks += 1
                if t1 - t_open >= trace_seconds:
                    jax.profiler.stop_trace()
                    tracing = False
            if t1 - t_open >= seconds:
                break
        self.rec.window_s = t1 - t_open
        gcw.armed = False
        self.in_window = False
        counts1, _ = watch.snapshot()
        self.rec.window_compiles = counts1[BACKEND_COMPILE] - counts0[BACKEND_COMPILE]
        self.rec.window_traces = counts1[TRACE] - counts0[TRACE]
        self.rec.window_compiled = dict((watch.names - names0).most_common(6))
        self.rec.gc_count, self.rec.gc_ms = gcw.count, gcw.seconds * 1e3
        self.rec.host_syncs = list(self.service.sync_log[syncs0:])
        self.rec.traced_ticks = traced_ticks
        self.rec.ticks_total = self.ticks
        gc.unfreeze()

    def read_recycled(self, rng: np.random.Generator, n: int):
        """After the window: the state of a seed-drawn sample of at most ``n``
        slots that the window refilled, with the fleet full, and whose new
        stream has trained 1 to ``n_first`` ticks. The training check replays
        each of these streams from its own admission."""
        age = self.ticks - self.admitted_at
        pool = np.flatnonzero((self.slot_stream >= 0) & (self.admitted_at > self.window_open)
                              & (age >= 1) & (age <= self.n_first))
        if not n or not len(pool):
            return
        rows = np.sort(rng.choice(pool, min(n, len(pool)), replace=False))
        st = self.service.state
        self.rec.recycled = dict(
            slots=rows,
            streams=self.slot_stream[rows],
            ages=age[rows],
            loss=np.asarray(st.loss[rows]),
            theta=np.asarray(st.theta[rows]),
            steps=np.asarray(st.steps[rows]),
            mean=np.asarray(st.mean[rows]),
            scale=np.asarray(st.scale[rows]),
            moment=leaf_norms(st.opt.m, rows),
            params=rows_host(st.params, rows),
        )
