"""compile_plan: lower a RecoverySpec into an executable RecoveryPlan.

MERINDA's central claim is compile-once / stream-forever: all execution
decisions are made at setup time, after which recovery is a fixed dataflow
with no per-step decisions. ``compile_plan`` is the host-side compiler for
that story. It takes one declarative :class:`RecoverySpec` and produces a
:class:`RecoveryPlan` holding

- the resolved :class:`Lowering` record — every decision that used to be
  scattered across call sites (``fused``, ``use_kernel``-era encoder
  backends, quantized serving, the ``block_b`` batch tile, backend
  dispatch) in ONE place;
- the jitted, donated programs for the spec's execution mode (the engine's
  epoch scan, the vmapped multi-system recovery, the streaming tick);
- for stream mode, a device mesh over the slot axis — ``SlotState`` is
  sharded across it (``parallel.make_mesh`` + the ``parallel/`` rule table),
  with ``mesh_slots=1`` degenerating to the single-device path — so one
  service scales past a single chip's VMEM/HBM.

Compile-time failures are ValueErrors raised here (unknown encoder, fused
with a non-fusable family, int8 serving with a flow encoder, mesh larger
than the device count) — never mid-trace errors inside a jitted scan.

The legacy entry points (``merinda.train_mr``, ``engine.train_mr_scan``,
``engine.recover_many``, direct ``RecoveryService`` construction) remain as
deprecated wrappers that build a spec internally and run through a plan.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.api.spec import RecoverySpec
from repro.core import encoders, engine
from repro.core import stream as stream_mod
from repro.core.merinda import MRConfig, init_mr, prune_theta
from repro.core.stream import RecoveryService, StreamConfig
from repro.kernels import runtime as rt
from repro.kernels.mr_step import tiling
from repro.optim import adamw_init
from repro.parallel import make_mesh


@dataclasses.dataclass(frozen=True)
class Lowering:
    """Every resolved execution decision, in one record.

    ``dispatch`` names where the per-window recovery stage executes:
    ``"pallas"`` (compiled kernel on TPU), ``"reference"`` (identical math
    as pure JAX off-TPU — what kernel-backed and fused requests resolve to
    on CPU/GPU), or ``"xla"`` (plain lax.scan encoders that never route
    through a kernel family).
    """

    encoder: str
    fused: bool
    kernel: bool  # encoder row routes through a Pallas kernel family
    dispatch: str  # "pallas" | "reference" | "xla"
    quant_serving: bool  # int8/PWL fused readout at serving time
    qat: bool  # fixed-point fake-quant during training
    block_b: int | None  # resolved fused-stage batch tile (None = full batch)
    vmem_bytes: int | None  # modeled fused-stage VMEM residency at block_b
    vmem_budget_bytes: int | None  # resolved budget the "auto" tile fit into
    mesh_shape: tuple[int, ...]  # device mesh over the slot axis (stream mode)
    # which source resolved vmem_budget_bytes: "explicit" (spec override),
    # "memory_stats", "platform:<key>" or "default" (tiling.resolve_vmem_budget)
    vmem_budget_source: str | None = None
    # measured-cost autotuner (analysis/tuner.py): the scan-unroll factor the
    # resolved lowering carries; how the lowering was chosen ("static" |
    # "measured" | "measured:cached", None = untuned static policy); the
    # on-disk cache key the measured decision persists under; and the chosen
    # candidate's cost evidence — the VMEM model's predicted residency vs the
    # per-input-step HBM traffic parsed from the candidate's own compiled HLO
    # (the figure the R2 audit re-measures a tuned plan against, with
    # tiling.TUNED_RESIDENCY_BAND)
    substep_unroll: int = 1
    tuned: str | None = None
    tune_cache_key: str | None = None
    predicted_bytes: int | None = None
    measured_bytes: float | None = None
    audit: str | None = None  # audit verdict stamp ("pass:R1,R3,..."/"fail:R2")
    # stream mode: the resolved tick structure — "banked" (one-kernel mr_tick
    # serving segment) or "composite" (stage-sequence tick), and the bank size
    # the tick-level VMEM model settled on (None for composite)
    tick_kernel: str | None = None
    tick_slots_per_bank: int | None = None
    # stream mode: the resolved control plane ("host" reference orchestrator
    # or "device" zero-readback tick, core/control.py) and the capacities
    # baked into the compiled control-state shapes (None outside stream mode;
    # queue/snapshot fields None on the host plane, which has no rings)
    control_plane: str | None = None
    tick_queue_capacity: int | None = None
    tick_snapshot_period: int | None = None
    warm_capacity: int | None = None
    # stream mode: service resilience (runtime/resilience.py) — snapshot
    # cadence/destination for the SlotState+ControlState checkpointer (0/None
    # = checkpointing off) and the bounded host overflow queue that backs the
    # typed submit() backpressure signal
    checkpoint_period: int | None = None
    checkpoint_dir: str | None = None
    overflow_capacity: int | None = None


class RecoveryPlan:
    """A compiled recovery dataflow: spec + lowering + jitted programs.

    Built by :func:`compile_plan`; consumers call the mode's run method and
    never re-make execution decisions:

    - ``run_offline(ys, us, norm)``  -> (params, metrics)     [mode=offline]
    - ``run_batch(ys_batch, us_b)``  -> theta [S, n_terms, n] [mode=batch]
    - ``make_service(seed)``         -> RecoveryService       [mode=stream]
    - ``readout(params, yw, uw)``    -> theta through the spec's precision
    """

    def __init__(
        self,
        spec: RecoverySpec,
        cfg: MRConfig,
        scfg: StreamConfig,
        lowering: Lowering,
        mesh,
        programs: dict,
    ):
        self.spec = spec
        self.cfg = cfg
        self.scfg = scfg
        self.lowering = lowering
        self.mesh = mesh  # jax Mesh over ("slots",) or None (trivial mesh)
        self.programs = programs  # name -> jitted donated program

    def _require_mode(self, mode: str):
        if self.spec.mode != mode:
            raise ValueError(
                f"this plan was compiled for mode={self.spec.mode!r}; "
                f"recompile with RecoverySpec(mode={mode!r})"
            )

    # -- offline: one system, one compiled training run ----------------------
    def run_offline(
        self, ys: jnp.ndarray, us: jnp.ndarray | None = None, norm: dict | None = None
    ) -> tuple:
        """Train one system's recovery model: ys [N, T, n] -> (params, metrics).

        One donated lax.scan program over all optimizer steps (the engine's
        epoch scan); ``norm`` applies the L1 penalty in physical units.
        """
        self._require_mode("offline")
        key = jax.random.key(self.spec.seed)
        params = init_mr(key, self.cfg)
        opt_state = adamw_init(params)
        phys = engine.make_phys(self.cfg, norm)
        params, _, metrics = self.programs["epoch"](
            params, opt_state, ys, us, key, self.spec.lr, phys
        )
        return params, metrics

    # -- batch: a fleet of systems, one vmapped program -----------------------
    def run_batch(self, ys_batch: jnp.ndarray, us_batch: jnp.ndarray | None = None) -> jnp.ndarray:
        """Recover S distinct systems in one compiled vmapped call.

        ys_batch [S, N, T, n] -> theta_batch [S, n_terms, n] (normalized
        coordinates; pruned to ``spec.n_active`` when set).
        """
        self._require_mode("batch")
        keys = engine.system_keys(self.spec.seed, ys_batch.shape[0])
        return self.programs["recover_many"](ys_batch, us_batch, keys, self.spec.lr)

    # -- stream: the slot-based online service --------------------------------
    @property
    def tick(self):
        """The compiled tick program (stream mode): ``(state, new_y, new_u,
        key)`` with cfg/scfg/kernel choice pre-bound. Composite returns the
        next SlotState; banked returns ``(state, status[S, 4])`` — the packed
        per-slot ``[delta, loss, steps, active]`` read back in one sync."""
        self._require_mode("stream")
        return self.programs["tick"]

    def make_service(self, seed: int | None = None) -> RecoveryService:
        """The online multi-tenant service, with SlotState sharded over the
        plan's mesh (trivial on mesh_slots=1). On ``control="device"`` the
        service also carries the compiled ControlPlane (core/control.py): the
        zero-readback tick, enqueue, pump and snapshot-drain programs."""
        self._require_mode("stream")
        control = None
        if self.lowering.control_plane == "device":
            from repro.core import control as control_mod

            control = control_mod.ControlPlane(
                queue_capacity=self.lowering.tick_queue_capacity,
                snapshot_period=self.lowering.tick_snapshot_period,
                warm_capacity=self.lowering.warm_capacity,
                shards=self.spec.mesh_slots,
                tick=self.programs["tick_device"],
                enqueue=self.programs["enqueue"],
                pump=self.programs["pump"],
                drain=self.programs["drain"],
            )
        service = RecoveryService(
            self.cfg,
            self.scfg,
            self.spec.n_slots,
            seed=self.spec.seed if seed is None else seed,
            quant=self.lowering.quant_serving,
            mesh=self.mesh,
            tick_program=self.programs["tick"],
            control=control,
            warm_capacity=self.lowering.warm_capacity or 32,
            overflow_capacity=self.lowering.overflow_capacity
            if self.lowering.overflow_capacity is not None
            else 16,
        )
        if self.lowering.checkpoint_period and self.lowering.checkpoint_dir:
            # lazy import: resilience pulls checkpoint/elastic; plan.py stays
            # importable without them on the critical path
            from repro.runtime.resilience import ServiceCheckpointer

            service.checkpointer = ServiceCheckpointer(
                self.lowering.checkpoint_dir,
                period=self.lowering.checkpoint_period,
            )
        return service

    # -- readout: the spec's serving precision --------------------------------
    def readout(
        self,
        params,
        yw: jnp.ndarray,
        uw: jnp.ndarray | None = None,
        norm: dict | None = None,
        n_active: int | None = None,
    ) -> np.ndarray:
        """Aggregate Theta through the spec's serving precision.

        fp32 runs the (possibly fused) forward; int8_pwl serves through the
        fused fixed-point stage (kernels/mr_step int8). ``norm`` maps the
        result back to physical units; ``n_active`` (default: the spec's)
        magnitude-prunes.
        """
        theta = stream_mod.readout_theta(
            params, self.cfg, yw, uw, quant=self.lowering.quant_serving
        )
        theta = np.asarray(theta)
        if norm is not None:
            from repro.core.library import denormalize_theta

            theta = denormalize_theta(
                theta,
                norm["mean"],
                norm["scale"],
                n_vars=self.cfg.state_dim + self.cfg.input_dim,
                order=self.cfg.order,
                n_state=self.cfg.state_dim,
            )
        n_active = self.spec.n_active if n_active is None else n_active
        if n_active is not None:
            theta = prune_theta(theta, n_active)
        return theta


def _resolve_lowering(
    spec: RecoverySpec, row: encoders.EncoderSpec, tune_report=None
) -> Lowering:
    """All execution decisions for one spec, resolved once.

    ``tune_report`` (analysis/tuner.TuneReport, from ``compile_plan``'s
    ``tune=`` modes) replaces the static policy with the tuner's winning
    candidate: the fused/unfused dispatch, the batch tile and the scan-unroll
    factor come from the candidate, and its cost evidence (predicted vs
    measured per-step bytes, the cache key) is stamped into the record.
    """
    quant_serving = spec.precision == "int8_pwl"
    chosen = tune_report.chosen.candidate if tune_report is not None else None
    fused = chosen.fused if chosen is not None else spec.fused
    routes_kernel = fused or row.kernel or quant_serving
    if routes_kernel:
        dispatch = "pallas" if rt.on_tpu() else "reference"
    else:
        dispatch = "xla"
    block_b, vmem, budget, budget_src = None, None, None, None
    if chosen is not None and fused:
        batch = _compile_time_batch(spec)
        block_b = chosen.block_b
        budget, budget_src = tune_report.budget_bytes, tune_report.budget_source
        if batch is not None:
            vmem = tiling.config_vmem_bytes(spec.to_mr_config(), batch, block_b=block_b)
    elif spec.fused:
        batch = _compile_time_batch(spec)
        if spec.block_b == "auto":
            # explicit override wins; otherwise the budget is auto-detected
            # from the local device (platform table + memory_stats when the
            # runtime exposes a VMEM figure) — ROADMAP "auto-detect the
            # budget" item. The resolved figure AND which source produced it
            # land in the Lowering record.
            if spec.vmem_budget_bytes is not None:
                budget, budget_src = spec.vmem_budget_bytes, "explicit"
            else:
                budget, budget_src = tiling.resolve_vmem_budget()
            block_b = tiling.auto_block_b(spec.to_mr_config(), batch, budget)
        elif isinstance(spec.block_b, int):
            if batch is not None and batch % spec.block_b != 0:
                # the kernel would silently drop a non-dividing tile at run
                # time (ops._legal_block_b) while this record claimed it —
                # a validatable request fails HERE like every other one
                raise ValueError(
                    f"block_b={spec.block_b} does not divide the compile-time "
                    f"batch ({batch}); the fused kernel requires B % block_b == 0"
                )
            block_b = spec.block_b
        if batch is not None:
            vmem = tiling.config_vmem_bytes(spec.to_mr_config(), batch, block_b=block_b)
    tuned = cache_key = predicted = measured = None
    if tune_report is not None:
        tuned = "measured:cached" if tune_report.cache_hit else tune_report.mode
        cache_key = tune_report.cache_key
        predicted = tune_report.chosen.predicted_bytes
        measured = tune_report.chosen.parsed_bytes
    return Lowering(
        encoder=spec.encoder,
        fused=fused,
        kernel=row.kernel,
        dispatch=dispatch,
        quant_serving=quant_serving,
        qat=spec.qat is not None,
        block_b=block_b,
        vmem_bytes=vmem,
        vmem_budget_bytes=budget,
        mesh_shape=(spec.mesh_slots,) if spec.mode == "stream" else (),
        vmem_budget_source=budget_src,
        substep_unroll=chosen.substep_unroll if chosen is not None else spec.substep_unroll,
        tuned=tuned,
        tune_cache_key=cache_key,
        predicted_bytes=predicted,
        measured_bytes=measured,
    )


def _resolve_tick_kernel(
    spec: RecoverySpec, cfg: MRConfig, scfg: StreamConfig, lowering: Lowering
) -> tuple[str, int | None]:
    """Resolve ``TickSpec.tick_kernel`` -> ("banked"|"composite", slots_per_bank).

    ``"composite"`` short-circuits (the bitwise-stable default). ``"banked"``
    is an explicit request: an unsupported family is a compile-time
    ValueError, and a budget the model can't fit still runs at bank size 1
    (the user overrode the heuristic). ``"auto"`` picks banked only when the
    family supports it AND ``tiling.auto_slots_per_bank`` finds a bank size
    whose residency fits the resolved VMEM budget — otherwise composite.
    The int8 serving twin is engaged only for pure serve ticks
    (``steps_per_tick == 0`` with int8_pwl serving), matching what the
    compiled program will actually run.
    """
    from repro.kernels.mr_step import tick as tick_mod

    requested = spec.tick_spec().tick_kernel
    if requested == "composite":
        return "composite", None
    quant_tick = lowering.quant_serving and scfg.steps_per_tick == 0
    supported = tick_mod.tick_supported(cfg, int8=quant_tick)
    if not supported:
        if requested == "banked":
            raise ValueError(
                f"tick_kernel='banked' requires a GRU-family encoder "
                f"(kernels/mr_step/tick.py banks the gru cell); got "
                f"encoder={spec.encoder!r} — use 'composite' or 'auto'"
            )
        return "composite", None
    if spec.vmem_budget_bytes is not None:
        budget = spec.vmem_budget_bytes
    else:
        budget, _ = tiling.resolve_vmem_budget()
    local_slots = spec.n_slots // spec.mesh_slots  # the per-device slot shard
    spb = tiling.auto_slots_per_bank(cfg, scfg, local_slots, budget, int8=quant_tick)
    if spb < 1:
        if requested == "banked":
            return "banked", 1  # explicit request: run anyway, smallest bank
        return "composite", None
    return "banked", spb


def _compile_time_batch(spec: RecoverySpec) -> int | None:
    """The fused-stage batch dimension knowable at compile time.

    stream: windows per slot (the tick's per-slot forward batch);
    offline/batch: the optimizer minibatch when configured, else unknown
    (None) — the auto tile then falls back to full batch, the documented
    no-budget behaviour.
    """
    if spec.mode == "stream":
        return spec.stream_config().n_windows
    return spec.batch_size


AUDIT_MODES = ("off", "warn", "error")
TUNE_MODES = ("off", "static", "measured")


def compile_plan(spec: RecoverySpec, audit: str = "off", tune: str = "off") -> RecoveryPlan:
    """Validate + lower a RecoverySpec; see the module docstring.

    ``audit`` runs the static HLO-contract auditor (analysis/audit.py) over
    the compiled programs: ``"off"`` skips it, ``"warn"`` emits a warning
    per finding, ``"error"`` raises :class:`repro.analysis.audit.AuditError`
    on any finding. Either audited mode stamps the verdict into
    ``plan.lowering.audit``.

    ``tune`` closes the loop from HLO cost analysis to the lowering choice
    (analysis/tuner.py): ``"off"`` keeps the static policy, ``"static"``
    replays the candidate table through the VMEM model only (no extra
    compiles — the decision matches the static policy, the evidence is
    recorded), ``"measured"`` lowers every candidate, scores the optimized
    HLO against ``Compiled.cost_analysis()`` and picks the roofline winner.
    Measured decisions persist in an on-disk cache keyed by (spec
    fingerprint, device kind, mesh shape), so a warm recompile performs ZERO
    candidate lowerings — the chosen candidate and its cost evidence land in
    ``plan.lowering`` (``tuned``, ``tune_cache_key``, ``predicted_bytes``,
    ``measured_bytes``).
    """
    if audit not in AUDIT_MODES:
        raise ValueError(f"audit must be one of {AUDIT_MODES}, got {audit!r}")
    if tune not in TUNE_MODES:
        raise ValueError(f"tune must be one of {TUNE_MODES}, got {tune!r}")
    row = encoders.get_encoder(spec.encoder)  # unknown name fails here
    if spec.precision == "int8_pwl" and not row.int8:
        raise ValueError(
            f"precision='int8_pwl' serves through a fixed-point fused stage, "
            f"implemented for the families with a PWL activation mapping "
            f"({encoders.int8_names()}); got {spec.encoder!r}"
        )
    if spec.qat is not None and row.flow is None:
        raise ValueError(
            f"qat (fixed-point fake-quant) is implemented for the GRU families, "
            f"got encoder={spec.encoder!r}"
        )
    tune_report = None
    if tune != "off":
        # lazy import: the tuner pulls hlo/encoders/merinda; plan.py stays
        # cheap to import and tune="off" pays nothing
        from repro.analysis import tuner as tuner_mod

        tune_report = tuner_mod.tune(spec, mode=tune)
    lowering = _resolve_lowering(spec, row, tune_report)
    cfg = spec.to_mr_config(block_b=lowering.block_b, substep_unroll=lowering.substep_unroll)
    if cfg.fused != lowering.fused:
        # the tuner may flip the fused dispatch (identical math, different
        # lowering) for families that implement both paths
        cfg = dataclasses.replace(cfg, fused=lowering.fused)
    # ONE source of truth for encoder-level invariants (registered name,
    # fused x fusable) — the same check the legacy entry points run
    encoders.validate_config(cfg)
    scfg = spec.stream_config()

    mesh = None
    if spec.mode == "stream" and spec.mesh_slots > 1:
        n_dev = len(jax.devices())
        if spec.mesh_slots > n_dev:
            raise ValueError(
                f"mesh_slots={spec.mesh_slots} exceeds the {n_dev} visible "
                f"device(s); set XLA_FLAGS=--xla_force_host_platform_device_count "
                f"for CPU virtual devices"
            )
        mesh = make_mesh((spec.mesh_slots,), ("slots",))

    # the jitted donated programs for this spec's mode — static arguments are
    # bound NOW so every later call hits the same executable
    programs: dict = {}
    if spec.mode == "offline":
        programs["epoch"] = functools.partial(
            engine.run_epoch, cfg=cfg, steps=spec.steps, batch_size=spec.batch_size
        )
    elif spec.mode == "batch":
        programs["recover_many"] = functools.partial(
            engine._recover_many_jit,
            cfg=cfg,
            steps=spec.steps,
            batch_size=spec.batch_size,
            n_active=spec.n_active,
        )
    else:  # stream
        tick_kernel, spb = _resolve_tick_kernel(spec, cfg, scfg, lowering)
        if (
            tune_report is not None
            and tick_kernel == "banked"
            and tune_report.chosen_tick is not None
            and tune_report.chosen_tick.candidate.slots_per_bank
        ):
            # the measured tick search ranked the bank sizes; its winner
            # replaces the static auto_slots_per_bank choice
            spb = tune_report.chosen_tick.candidate.slots_per_bank
        tspec = spec.tick_spec()
        lowering = dataclasses.replace(
            lowering,
            tick_kernel=tick_kernel,
            tick_slots_per_bank=spb,
            control_plane=tspec.control,
            tick_queue_capacity=tspec.queue_capacity if tspec.control == "device" else None,
            tick_snapshot_period=tspec.snapshot_period if tspec.control == "device" else None,
            warm_capacity=tspec.warm_capacity,
            checkpoint_period=tspec.checkpoint_period,
            checkpoint_dir=tspec.checkpoint_dir,
            overflow_capacity=tspec.overflow_capacity,
        )
        quant_tick = lowering.quant_serving and scfg.steps_per_tick == 0
        if tick_kernel == "banked":
            programs["tick"] = functools.partial(
                stream_mod.tick_banked,
                cfg=cfg,
                scfg=scfg,
                quant=quant_tick,
                slots_per_bank=spb,
            )
        else:
            programs["tick"] = functools.partial(stream_mod.tick, cfg=cfg, scfg=scfg)
        if tspec.control == "device":
            # the zero-readback control-plane programs (core/control.py):
            # all statics bound NOW so every later call hits one executable
            from repro.core import control as control_mod

            programs["tick_device"] = functools.partial(
                control_mod.tick_device,
                cfg=cfg,
                scfg=scfg,
                kernel=tick_kernel,
                quant=quant_tick,
                slots_per_bank=spb or 1,
                shards=spec.mesh_slots,
            )
            programs["enqueue"] = control_mod.enqueue
            programs["pump"] = functools.partial(control_mod.pump, shards=spec.mesh_slots)
            programs["drain"] = control_mod.drain_events
    plan = RecoveryPlan(spec, cfg, scfg, lowering, mesh, programs)

    if audit != "off":
        # lazy import: the auditor pulls engine/stream/kernels; rules/hlo
        # stay importable without jax and plan.py stays cheap to import
        from repro.analysis import audit as audit_mod

        report = audit_mod.audit_plan(plan)
        plan.lowering = dataclasses.replace(lowering, audit=report.verdict)
        if report.findings:
            if audit == "error":
                raise audit_mod.AuditError(report)
            import warnings

            for f in report.findings:
                warnings.warn(f"plan audit: {f}", stacklevel=2)
    return plan
