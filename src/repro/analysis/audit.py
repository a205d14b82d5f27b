"""Plan auditor: static HLO-contract verification for compiled RecoveryPlans.

``audit_plan`` lowers each of a compiled plan's jitted programs to OPTIMIZED
HLO (``.lower(...).compile().as_text()`` — what XLA actually emitted) and
holds the text to the hardware contracts in ``analysis/rules.py``:

    R1 donation, R2 VMEM-model residency, R3 host-transfer hygiene,
    R4 int8 weight transport, R5 sharded-tick collective census.

The auditor owns the lowering recipe per program (which concrete shapes to
trace with, which arguments are donated, which weights are contracted s8);
the rules stay pure text->Findings functions. ``compile_plan(spec,
audit="warn"|"error")`` runs this at plan-compile time and stamps the
verdict into ``plan.lowering.audit``; violations raise :class:`AuditError`
under ``"error"`` and ``warnings.warn`` under ``"warn"``.

CLI (the CI ``audit-matrix`` job):

    python -m repro.analysis.audit --matrix \\
        --error-rules R1,R3,R4 --warn-rules R2,R5 --json findings.json

compiles the full encoder x fused x quant spec matrix (tiny stream shapes)
including the device-resident control-plane cells, audits every cell, runs
the 2-virtual-device mesh cells in subprocesses (R5 needs >1 device), and
exits nonzero on any error-rule finding.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import json
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp

from repro.analysis import rules as R
from repro.core import encoders, engine
from repro.core import stream as stream_mod
from repro.core.merinda import init_mr
from repro.core.quant import make_sigmoid_table, make_tanh_table, quantize_int8
from repro.kernels.mr_step import ref as mr_ref
from repro.kernels.mr_step import tiling
from repro.optim import adamw_init
from repro.parallel.rules import predict_tick_collectives, use_mesh_rules

DEFAULT_RULES = ("R1", "R2", "R3", "R4", "R5")

#: host-transfer substrings the tick program may legitimately contain: NONE.
#: All host syncs of the service live in RecoveryService.tick_once (counted
#: in sync_log); the compiled tick itself must stay on device.
DEFAULT_TICK_ALLOWLIST: tuple[str, ...] = ()


class AuditError(ValueError):
    """A compiled plan violated its hardware contract (audit="error")."""

    def __init__(self, report: "AuditReport"):
        self.report = report
        lines = "\n".join(f"  {f}" for f in report.findings)
        super().__init__(f"plan audit failed with {len(report.findings)} finding(s):\n{lines}")


@dataclasses.dataclass
class AuditReport:
    """Outcome of one ``audit_plan`` run: findings + what was actually checked."""

    findings: list[R.Finding]
    checked: dict[str, list[str]]  # rule id -> programs it ran over

    @property
    def ok(self) -> bool:
        return not self.findings

    @property
    def verdict(self) -> str:
        """Compact stamp for plan.lowering.audit: "pass:R1,R3" / "fail:R1"."""
        if self.ok:
            return "pass:" + ",".join(sorted(self.checked))
        return "fail:" + ",".join(sorted({f.rule for f in self.findings}))

    def to_json(self) -> dict:
        return {
            "verdict": self.verdict,
            "checked": self.checked,
            "findings": [dataclasses.asdict(f) for f in self.findings],
        }


def _compiled_text(lowered) -> str:
    return lowered.compile().as_text()


def _fused_batch(plan) -> int:
    """The fused-stage batch the plan was tiled for (mirrors plan.py)."""
    if plan.spec.mode == "stream":
        return plan.scfg.n_windows
    return plan.spec.batch_size or 16


def _fused_step_text(plan) -> tuple[str, int]:
    """Lower the plan's fused per-window stage; returns (hlo text, T steps)."""
    from repro.kernels.mr_step import ops as mr_ops

    cfg = plan.cfg
    B = _fused_batch(plan)
    T = plan.scfg.window if plan.spec.mode == "stream" else 32
    params = init_mr(jax.random.key(0), cfg)
    xs = jnp.zeros((B, T, cfg.state_dim + cfg.input_dim), jnp.float32)
    block_b = plan.lowering.block_b
    fn = jax.jit(lambda p, x: mr_ops.mr_step(p, cfg, x, block_b=block_b))
    return _compiled_text(fn.lower(params, xs)), T


def _serving_weight_text(plan) -> tuple[str, dict[str, str]]:
    """Lower the int8 serving stage at KERNEL SIGNATURE level: weights are
    quantized OUTSIDE the program and enter as s8 parameters (the transport
    contract R4 checks). Returns (hlo text, weight name -> dtype contract).

    ``mr_step_int8`` itself quantizes float params on the fly inside the jit
    (a convenience for the reference path); production serving caches the
    int8 tensors and calls the kernel signature — which is what a dtype
    audit must hold to, so that is what gets lowered here.
    """
    cfg = plan.cfg
    family = encoders.get_encoder(cfg.encoder).family
    B = _fused_batch(plan)
    T = plan.scfg.window if plan.spec.mode == "stream" else 32
    params = init_mr(jax.random.key(0), cfg)
    xs = jnp.zeros((B, T, cfg.state_dim + cfg.input_dim), jnp.float32)
    h0 = jnp.zeros((B, cfg.hidden), jnp.float32)
    w1q = quantize_int8(params.head_w1, axis=-1)
    w2q = quantize_int8(params.head_w2, axis=-1)
    sig_t = make_sigmoid_table(16)

    if family == "ltc":
        enc = params.encoder
        w_inq = quantize_int8(enc.w_in, axis=-1)
        w_recq = quantize_int8(enc.w_rec, axis=-1)

        def serve(
            xs,
            h0,
            w_inq,
            w_in_s,
            w_recq,
            w_rec_s,
            bias,
            a,
            inv_tau,
            w1q,
            w1_s,
            b1,
            w2q,
            w2_s,
            b2,
        ):
            args = (xs, h0, w_inq, w_in_s, w_recq, w_rec_s, bias, a, inv_tau)
            head = (w1q, w1_s, b1, w2q, w2_s, b2)
            return mr_ref.mr_step_ltc_int8_reference(
                *args, *head, sig_t, dt=cfg.dt, n_substeps=cfg.ltc_substeps
            )

        lowered = jax.jit(serve).lower(
            xs,
            h0,
            w_inq.values,
            w_inq.scale,
            w_recq.values,
            w_recq.scale,
            enc.bias,
            enc.a,
            enc.inv_tau,
            w1q.values,
            w1q.scale,
            params.head_b1,
            w2q.values,
            w2q.scale,
            params.head_b2,
        )
        weights = {"w_inq": "s8", "w_recq": "s8", "w1q": "s8", "w2q": "s8"}
        return _compiled_text(lowered), weights

    # gru family (the standard cell; flow families are float-serving)
    d_in = cfg.state_dim + cfg.input_dim
    wxq = quantize_int8(params.encoder.w[:d_in], axis=-1)
    whq = quantize_int8(params.encoder.w[d_in:], axis=-1)
    tanh_t = make_tanh_table(16)
    dts = jnp.ones((T,), jnp.float32)

    def serve(xs, h0, wxq, whq, wx_s, wh_s, b, dts, w1q, w1_s, b1, w2q, w2_s, b2):
        gate = (xs, h0, wxq, whq, wx_s, wh_s, b, dts)
        head = (w1q, w1_s, b1, w2q, w2_s, b2)
        return mr_ref.mr_step_int8_reference(*gate, *head, sig_t, tanh_t)

    lowered = jax.jit(serve).lower(
        xs,
        h0,
        wxq.values,
        whq.values,
        wxq.scale,
        whq.scale,
        params.encoder.b,
        dts,
        w1q.values,
        w1q.scale,
        params.head_b1,
        w2q.values,
        w2q.scale,
        params.head_b2,
    )
    weights = {"wxq": "s8", "whq": "s8", "w1q": "s8", "w2q": "s8"}
    return _compiled_text(lowered), weights


def audit_plan(
    plan,
    *,
    rules: tuple[str, ...] = DEFAULT_RULES,
    host_allowlist: tuple[str, ...] = DEFAULT_TICK_ALLOWLIST,
) -> AuditReport:
    """Audit every program of a compiled RecoveryPlan; see module docstring.

    Which rules run depends on the plan: R1/R3 on the mode's donated program
    (tick / epoch; the batch program declares no donation, by design), R2
    only for fused lowerings, R4 only for int8 serving, R5 only on meshed
    stream plans (a 1-device census is vacuously collective-free).
    """
    spec, cfg, scfg = plan.spec, plan.cfg, plan.scfg
    findings: list[R.Finding] = []
    checked: dict[str, list[str]] = {}

    def run(rule: str, program: str, fn, *args, **kw):
        if rule not in rules:
            return
        checked.setdefault(rule, []).append(program)
        findings.extend(fn(program, *args, **kw))

    key = jax.random.key(0)
    if spec.mode == "stream":
        state = stream_mod.init_slots(key, cfg, scfg, spec.n_slots)
        if plan.mesh is not None:
            state = stream_mod.shard_slots(state, plan.mesh)
        new_y = jnp.zeros((spec.n_slots, scfg.chunk, cfg.state_dim), jnp.float32)
        new_u = jnp.zeros((spec.n_slots, scfg.chunk, cfg.input_dim), jnp.float32)
        banked_tick = plan.lowering.tick_kernel == "banked"
        quant_tick = plan.lowering.quant_serving and scfg.steps_per_tick == 0

        def mesh_ctx():
            # programs are traced as the service calls them: inside the
            # plan's slot mesh, where the tick's kernel section runs per shard
            if plan.mesh is None:
                return contextlib.nullcontext()
            return use_mesh_rules(plan.mesh, stream_mod.SLOT_RULES)

        with mesh_ctx():
            if banked_tick:
                lowered = stream_mod.tick_banked.lower(
                    state,
                    new_y,
                    new_u,
                    key,
                    cfg=cfg,
                    scfg=scfg,
                    quant=quant_tick,
                    slots_per_bank=plan.lowering.tick_slots_per_bank or 1,
                )
            else:
                lowered = stream_mod.tick.lower(state, new_y, new_u, key, cfg=cfg, scfg=scfg)
        text = _compiled_text(lowered)
        run("R1", "tick", R.check_donation, text, ("state",))
        run("R3", "tick", R.check_host_transfers, text, host_allowlist)
        if banked_tick and not scfg.steps_per_tick:
            # K=0 serve tick: the compiled program IS the banked mr_tick
            # serving segment, so its traffic is held to the tick-level VMEM
            # model directly (training ticks bury the kernel inside the scan
            # program, where per-step attribution is the scan's, not the
            # tick kernel's)
            local_slots = spec.n_slots // max(spec.mesh_slots, 1)
            predicted = tiling.tick_vmem_bytes(
                cfg, scfg, slots_per_bank=local_slots, int8=quant_tick
            )
            run(
                "R2",
                "tick_banked",
                R.check_residency,
                text,
                predicted,
                scfg.window,
                tiling.TICK_RESIDENCY_BAND,
                family=encoders.get_encoder(cfg.encoder).family,
            )
        if plan.mesh is not None:
            n_dev = int(plan.mesh.devices.size)
            predicted = predict_tick_collectives(plan.mesh)
            run("R5", "tick", R.check_collectives, text, n_dev, predicted)
        if plan.lowering.control_plane == "device":
            # the device-resident control-plane program (core/control.py):
            # tick + eviction mask + queue refill + warm gather fused into one
            # donated program. R1 holds BOTH trees' donation, R3 pins zero
            # host transfers (the zero-readback claim, statically), and R5
            # holds the sharded control plane to the EMPTY collective census
            # (admission/refill must stay shard-local).
            from repro.core import control as control_mod

            shards = max(spec.mesh_slots, 1)
            control = control_mod.init_control(
                key,
                cfg,
                scfg,
                spec.n_slots,
                shards=shards,
                queue_capacity=plan.lowering.tick_queue_capacity,
                warm_capacity=plan.lowering.warm_capacity,
                snapshot_period=plan.lowering.tick_snapshot_period,
            )
            if plan.mesh is not None:
                control = control_mod.shard_control(control, plan.mesh)
            with mesh_ctx():
                lowered = control_mod.tick_device.lower(
                    state,
                    control,
                    new_y,
                    new_u,
                    key,
                    cfg=cfg,
                    scfg=scfg,
                    kernel=plan.lowering.tick_kernel,
                    quant=quant_tick,
                    slots_per_bank=plan.lowering.tick_slots_per_bank or 1,
                    shards=shards,
                )
            text = _compiled_text(lowered)
            run("R1", "tick_device", R.check_donation, text, ("state", "control"))
            run("R3", "tick_device", R.check_host_transfers, text, host_allowlist)
            if plan.mesh is not None:
                n_dev = int(plan.mesh.devices.size)
                predicted = predict_tick_collectives(plan.mesh)
                run("R5", "tick_device", R.check_collectives, text, n_dev, predicted)
    elif spec.mode == "offline":
        params = init_mr(key, cfg)
        opt = adamw_init(params)
        N = max(spec.batch_size or 8, 4)
        ys = jnp.zeros((N, scfg.window, cfg.state_dim), jnp.float32)
        us = jnp.zeros((N, scfg.window, cfg.input_dim), jnp.float32) if cfg.input_dim else None
        lowered = engine.run_epoch.lower(
            params,
            opt,
            ys,
            us,
            key,
            spec.lr,
            None,
            cfg=cfg,
            steps=spec.steps,
            batch_size=spec.batch_size,
        )
        text = _compiled_text(lowered)
        run("R1", "epoch", R.check_donation, text, ("params", "opt_state"))
        run("R3", "epoch", R.check_host_transfers, text, host_allowlist)

    if plan.lowering.fused:
        text, T = _fused_step_text(plan)
        family = encoders.get_encoder(cfg.encoder).family
        if plan.lowering.measured_bytes is not None:
            # a measured-tuned plan carries the per-step traffic the tuner
            # parsed from the chosen candidate's own compiled HLO; the audit
            # re-measures against THAT figure (self-consistency of two parses
            # of the same program) in the much tighter tuned band, not the
            # static residency model
            band = tiling.TUNED_RESIDENCY_BAND
            predicted = plan.lowering.measured_bytes
        else:
            band = tiling.residency_tolerance(family)
            predicted = plan.lowering.vmem_bytes or tiling.config_vmem_bytes(
                cfg, _fused_batch(plan), block_b=plan.lowering.block_b
            )
        run("R2", "fused_step", R.check_residency, text, predicted, T, band, family=family)
        run("R3", "fused_step", R.check_host_transfers, text, host_allowlist)

    if plan.lowering.quant_serving:
        text, weights = _serving_weight_text(plan)
        run("R4", "serving_int8", R.check_weight_dtypes, text, weights)
        run("R3", "serving_int8", R.check_host_transfers, text, host_allowlist)

    return AuditReport(findings=findings, checked=checked)


# ---------------------------------------------------------------------------
# --matrix CLI (the CI audit-matrix job)
# ---------------------------------------------------------------------------

# tiny stream shapes: 2 windows of 8 per tick, 2 slots — enough structure to
# exercise every contract, small enough that the full matrix compiles on a
# CPU CI runner in minutes
_TINY = dict(state_dim=2, order=2, hidden=8, dense_hidden=16, mode="stream", n_slots=2)
_TINY_STREAM = dict(buf_len=16, window=8, stride=8, chunk=8, steps_per_tick=2)


def _matrix_specs():
    """Every encoder x fused x quant cell as a (label, RecoverySpec) pair."""
    from repro.api.spec import RecoverySpec, TickSpec
    from repro.core.stream import StreamConfig

    cells = []
    for name in encoders.encoder_names():
        row = encoders.get_encoder(name)
        for fused in (False, True):
            if fused and not row.fusable:
                continue
            for quant in (False, True) if row.int8 else (False,):
                label = f"{name}:fused={int(fused)}:int8={int(quant)}"
                spec = RecoverySpec(
                    encoder=name,
                    precision="int8_pwl" if quant else "fp32",
                    fused=fused,
                    stream=StreamConfig(**_TINY_STREAM),
                    **_TINY,
                )
                cells.append((label, spec))
    # banked one-kernel tick cells (kernels/mr_step/tick.py): the supporting
    # GRU families with a training tick, the K=0 serve tick — where R2 runs
    # against the tick program's own OPTIMIZED HLO — and its int8 serve twin
    banked = [
        ("gru:tick=banked", "gru", 2, "fp32"),
        ("gru_flow:tick=banked", "gru_flow", 2, "fp32"),
        ("gru:tick=banked:K=0", "gru", 0, "fp32"),
        ("gru:tick=banked:K=0:int8=1", "gru", 0, "int8_pwl"),
    ]
    for label, name, k, precision in banked:
        spec = RecoverySpec(
            encoder=name,
            precision=precision,
            stream=StreamConfig(**{**_TINY_STREAM, "steps_per_tick": k}),
            tick=TickSpec(steps_per_tick=k, tick_kernel="banked"),
            **_TINY,
        )
        cells.append((label, spec))
    # device-resident control-plane cells (core/control.py): the fused
    # tick + eviction + refill + warm-gather program, over both tick bodies
    # (R1 donation on both trees, R3 zero host transfers; the sharded R5
    # census runs in the mesh cells below)
    k = _TINY_STREAM["steps_per_tick"]
    for label, tick_kernel in (
        ("gru:control=device", "composite"),
        ("gru:tick=banked:control=device", "banked"),
    ):
        spec = RecoverySpec(
            encoder="gru",
            stream=StreamConfig(**_TINY_STREAM),
            tick=TickSpec(
                steps_per_tick=k,
                tick_kernel=tick_kernel,
                control="device",
                queue_capacity=2,
                snapshot_period=2,
                warm_capacity=4,
            ),
            **_TINY,
        )
        cells.append((label, spec))
    return cells


def _run_mesh_cell(
    n_devices: int,
    rules: tuple[str, ...],
    tick_kernel: str = "composite",
    control: str = "host",
) -> dict:
    """Audit one slot-sharded plan under ``n_devices`` CPU virtual devices.

    XLA_FLAGS must be set before jax initializes, so the meshed cell runs in
    a subprocess (same pattern as tests/conftest.run_devices).
    ``tick_kernel`` picks the tick structure the sharded cell compiles
    ("banked" runs R1/R3/R5 against the banked tick program's HLO);
    ``control="device"`` audits the device-resident control-plane program
    (R5's empty census then covers the sharded queues/refill/warm gather).
    """
    snippet = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count={n_devices}"
        )
        import json
        from repro.analysis import audit as audit_mod
        from repro.api.plan import compile_plan
        from repro.api.spec import RecoverySpec, TickSpec
        from repro.core.stream import StreamConfig

        spec = RecoverySpec(
            encoder="gru", fused=True, mesh_slots={n_devices},
            stream=StreamConfig(**{_TINY_STREAM!r}),
            tick=TickSpec(
                steps_per_tick={_TINY_STREAM["steps_per_tick"]!r},
                tick_kernel={tick_kernel!r},
                control={control!r},
                queue_capacity=2, snapshot_period=2, warm_capacity=4,
            ),
            **{_TINY!r},
        )
        report = audit_mod.audit_plan(compile_plan(spec), rules={rules!r})
        print("AUDITCELL " + json.dumps(report.to_json()))
        """
    )
    return _run_cpu_cell(snippet)


def _run_restored_cell(survivors: int, rules: tuple[str, ...]) -> dict:
    """Audit the plan the ServiceSupervisor compiles AFTER an elastic re-mesh.

    The chaos-recovery path (runtime/resilience.py) re-plans the slot mesh on
    the surviving devices and recompiles before restoring the snapshot; that
    RESTORED plan must honor the same HLO contracts as the original. The
    subprocess pins ``2 * survivors`` virtual devices, builds the original
    device-control spec at mesh ``2 * survivors``, drops half the devices,
    re-plans via ``replan_spec``, recompiles, and audits the restored plan —
    so R5's collective census still runs against a real multi-device mesh
    (shrinking all the way to 1 device would make it vacuous).
    """
    n_devices = 2 * survivors
    stream_cfg = {**_TINY_STREAM}
    tiny = {**_TINY, "n_slots": n_devices}  # mesh_slots must divide n_slots
    snippet = textwrap.dedent(
        f"""
        import os
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "")
            + " --xla_force_host_platform_device_count={n_devices}"
        )
        import json
        from repro.analysis import audit as audit_mod
        from repro.api.plan import compile_plan
        from repro.api.spec import RecoverySpec, TickSpec
        from repro.core.stream import StreamConfig
        from repro.runtime import replan_spec

        spec = RecoverySpec(
            encoder="gru", fused=True, mesh_slots={n_devices},
            stream=StreamConfig(**{stream_cfg!r}),
            tick=TickSpec(
                steps_per_tick={stream_cfg["steps_per_tick"]!r},
                control="device",
                queue_capacity=2, snapshot_period=2, warm_capacity=4,
            ),
            **{tiny!r},
        )
        respec = replan_spec(spec, {survivors})
        assert respec.mesh_slots == {survivors}, respec.mesh_slots
        report = audit_mod.audit_plan(compile_plan(respec), rules={rules!r})
        print("AUDITCELL " + json.dumps(report.to_json()))
        """
    )
    return _run_cpu_cell(snippet)


def _run_cpu_cell(snippet: str) -> dict:
    """Run one meshed audit cell in a child interpreter on CPU virtual devices.

    The child is a CPU rehearsal by design (``JAX_PLATFORMS=cpu`` in its own
    environment): a parent that has touched an accelerator holds it, and a
    child reaching for the same chip would fail or hang. A child that prints
    no AUDITCELL line crashed: its verdict is ``"infra-error"``.
    """
    env = dict(os.environ)
    src_root = os.path.dirname(os.path.dirname(os.path.dirname(__file__)))
    env["PYTHONPATH"] = os.pathsep.join(p for p in (src_root, env.get("PYTHONPATH", "")) if p)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run(
        [sys.executable, "-c", snippet],
        capture_output=True,
        text=True,
        env=env,
        timeout=560,
        check=False,
    )
    for line in proc.stdout.splitlines():
        if line.startswith("AUDITCELL "):
            return json.loads(line.split(" ", 1)[1])
    return {
        "verdict": "infra-error",
        "checked": {},
        "findings": [],
        "stderr": proc.stderr[-2000:],
    }


def _parse_rules(arg: str) -> tuple[str, ...]:
    out = tuple(r.strip() for r in arg.split(",") if r.strip())
    unknown = [r for r in out if r not in R.RULES]
    if unknown:
        raise SystemExit(f"unknown rule id(s) {unknown}; known: {sorted(R.RULES)}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.audit",
        description="Static HLO-contract audit of compiled RecoveryPlans.",
    )
    ap.add_argument(
        "--matrix",
        action="store_true",
        help="audit the full encoder x fused x quant spec matrix",
    )
    ap.add_argument(
        "--error-rules",
        default="R1,R2,R3,R4,R5",
        type=_parse_rules,
        help="comma-separated rules whose findings fail the run (exit 1)",
    )
    ap.add_argument(
        "--warn-rules",
        default="",
        type=_parse_rules,
        help="comma-separated rules whose findings only warn",
    )
    ap.add_argument("--json", default=None, help="write all cells + findings here")
    ap.add_argument(
        "--mesh-devices",
        type=int,
        default=2,
        help="CPU virtual devices for the sharded-mesh cell (0 = skip R5 mesh cell)",
    )
    args = ap.parse_args(argv)
    if not args.matrix:
        ap.error("nothing to do: pass --matrix")
    active = tuple(dict.fromkeys(args.error_rules + args.warn_rules))

    from repro.api.plan import compile_plan

    cells, n_err, n_warn = [], 0, 0
    for label, spec in _matrix_specs():
        report = audit_plan(compile_plan(spec), rules=active)
        cell = {"cell": label, **report.to_json()}
        cells.append(cell)
        for f in report.findings:
            if f.rule in args.error_rules:
                n_err += 1
                print(f"ERROR {label} {f}")
            else:
                n_warn += 1
                print(f"WARN  {label} {f}")
        print(f"{label}: {report.verdict}")

    def ingest_subprocess_cell(label: str, cell: dict) -> None:
        nonlocal n_err, n_warn
        cells.append({"cell": label, **cell})
        if cell["verdict"] == "infra-error":
            # a cell that crashed checked nothing: the matrix cannot pass
            n_err += 1
            print(f"ERROR {label} mesh cell failed to run:\n{cell.get('stderr', '')}")
            return
        for f in cell["findings"]:
            rule = f["rule"]
            line = f"[{rule}] {f['program']}: {f['message']}"
            if rule in args.error_rules:
                n_err += 1
                print(f"ERROR {label} {line}")
            else:
                n_warn += 1
                print(f"WARN  {label} {line}")
        print(f"{label}: {cell['verdict']}")

    if args.mesh_devices and "R5" in active:
        mesh_cells = [
            (f"gru:fused=1:mesh={args.mesh_devices}", "composite", "host"),
            (f"gru:tick=banked:mesh={args.mesh_devices}", "banked", "host"),
            (
                f"gru:control=device:mesh={args.mesh_devices}",
                "composite",
                "device",
            ),
        ]
        for label, tick_kernel, control in mesh_cells:
            cell = _run_mesh_cell(
                args.mesh_devices, active, tick_kernel=tick_kernel, control=control
            )
            ingest_subprocess_cell(label, cell)
        # restored-plan cell: the plan the supervisor recompiles after an
        # elastic re-mesh (mesh 2N -> N via replan_spec) must pass the same
        # contracts as a first-compile plan — recovery may not relax R1
        # donation, R3 zero host transfers, or the R5 collective census
        label = f"gru:control=device:restored:mesh={2 * args.mesh_devices}->{args.mesh_devices}"
        ingest_subprocess_cell(label, _run_restored_cell(args.mesh_devices, active))

    if args.json:
        with open(args.json, "w") as fh:
            json.dump({"rules": R.RULES, "cells": cells}, fh, indent=2)
        print(f"wrote {args.json} ({len(cells)} cells)")
    print(f"audit matrix: {len(cells)} cells, {n_err} error(s), {n_warn} warning(s)")
    return 1 if n_err else 0


if __name__ == "__main__":
    sys.exit(main())
