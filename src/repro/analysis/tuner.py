"""Measured-cost autotuner: close the loop from HLO cost to lowering choice.

The static lowering policy (``tiling.auto_block_b`` / ``auto_slots_per_bank``)
trusts the hand-written VMEM residency model. This module makes the decision
EMPIRICAL: given a :class:`~repro.api.spec.RecoverySpec` it

1. enumerates candidate lowerings from the SAME generators the static path
   walks (``tiling.block_b_candidates`` batch tiles, fused-vs-unfused where
   the encoder family supports both, the substep-scan unroll factor of the
   multi-substep families, ``tiling.slots_per_bank_candidates`` bank sizes
   for a banked stream tick);
2. lowers each candidate's per-window stage to OPTIMIZED HLO and scores it
   with the trip-count-aware parse (``analysis/hlo.analyze_module``) —
   per-input-step HBM bytes and FLOPs — cross-checked against XLA's own
   ``Compiled.cost_analysis()`` figures;
3. ranks candidates by the roofline time estimate (bytes/HBM_BW vs
   flops/PEAK_FLOPS, whichever binds), preferring candidates that fit the
   VMEM budget and whose measured traffic lands inside the R2 residency band
   of the static prediction, and optionally refines the top-k with timed
   micro-runs;
4. returns a ranked :class:`TuneReport` with predicted-vs-measured bytes and
   flops per candidate, and persists the decision in an on-disk cache keyed
   by (spec fingerprint, device kind, mesh shape) so a warm
   ``compile_plan(spec, tune="measured")`` pays ZERO search cost.

``compile_plan(spec, tune="off"|"static"|"measured")`` is the integration
point (api/plan.py): the chosen candidate and its cost evidence are stamped
into ``plan.lowering`` (``tuned``, ``tune_cache_key``, ``predicted_bytes``,
``measured_bytes``).

CLI::

    python -m repro.analysis.tuner --what-if --encoder ltc --fused \\
        --batch 48 --vmem-budget 40000          # replay the candidate table
    python -m repro.analysis.tuner --smoke --json TUNE_report.json

``--what-if`` prints the ranked table and explains the decision (why
block_b=16 beat 24 on this device); ``--smoke`` is the CI tune-smoke step:
two specs tuned cold then recompiled warm, asserting the warm pass hits the
cache with zero lowered candidates.
"""

from __future__ import annotations

import argparse
import dataclasses
import hashlib
import json
import os
import sys
import time
import warnings
from pathlib import Path

from repro.analysis import hlo as H
from repro.kernels.mr_step import tiling

TUNER_VERSION = 1  # bump to invalidate every cached decision

TUNE_MODES = ("off", "static", "measured")

#: hard cap on lowered candidates per tune() call: each candidate costs one
#: XLA compile, and the divisor ladder of a large batch is long. Candidates
#: past the cap are dropped FROM THE MEASURED SET ONLY (the static scores
#: still cover them) and the drop is recorded in TuneReport.n_dropped.
MAX_LOWERED = 12


# ---------------------------------------------------------------------------
# candidates
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class Candidate:
    """One point in the lowering design space.

    ``stage="step"`` tunes the fused per-window stage (block_b x fused x
    substep_unroll); ``stage="tick"`` tunes the banked service tick's bank
    size (``slots_per_bank``) — the two searches are independent because the
    two programs are.
    """

    block_b: int | None = None
    fused: bool = False
    substep_unroll: int = 1
    stage: str = "step"  # "step" | "tick"
    slots_per_bank: int | None = None

    def label(self) -> str:
        if self.stage == "tick":
            return f"tick:spb={self.slots_per_bank}"
        bits = [f"block_b={self.block_b}", "fused" if self.fused else "unfused"]
        if self.substep_unroll != 1:
            bits.append(f"unroll={self.substep_unroll}")
        return ":".join(bits)


@dataclasses.dataclass
class ScoredCandidate:
    """One candidate with its cost evidence (predicted vs measured)."""

    candidate: Candidate
    predicted_bytes: int  # static VMEM residency model (tiling.py)
    fits_budget: bool
    parsed_bytes: float | None = None  # analyze_module per-input-step HBM traffic
    parsed_flops: float | None = None
    xla_bytes: float | None = None  # Compiled.cost_analysis() cross-check
    xla_flops: float | None = None
    t_step_us: float | None = None  # roofline per-step time estimate
    in_band: bool = True  # parsed/predicted inside the R2 residency band
    measured_us: float | None = None  # timed micro-run (refine_topk only)

    def to_json(self) -> dict:
        d = dataclasses.asdict(self)
        d["candidate"] = dataclasses.asdict(self.candidate)
        return d

    @classmethod
    def from_json(cls, d: dict) -> "ScoredCandidate":
        d = dict(d)
        d["candidate"] = Candidate(**d["candidate"])
        return cls(**d)


@dataclasses.dataclass
class TuneReport:
    """Outcome of one tune() call: the ranked table + the decision."""

    cache_key: str
    spec_fingerprint: str
    device_kind: str
    mesh_shape: tuple[int, ...]
    mode: str  # "static" | "measured"
    candidates: list[ScoredCandidate]  # ranked, best first (step stage)
    chosen: ScoredCandidate
    tick_candidates: list[ScoredCandidate] = dataclasses.field(default_factory=list)
    chosen_tick: ScoredCandidate | None = None
    cache_hit: bool = False
    n_lowered: int = 0  # candidate lowerings performed THIS call (0 on warm)
    n_dropped: int = 0  # candidates past MAX_LOWERED (static scores only)
    budget_bytes: int | None = None
    budget_source: str | None = None

    def to_json(self) -> dict:
        return {
            "version": TUNER_VERSION,
            "cache_key": self.cache_key,
            "spec_fingerprint": self.spec_fingerprint,
            "device_kind": self.device_kind,
            "mesh_shape": list(self.mesh_shape),
            "mode": self.mode,
            "candidates": [s.to_json() for s in self.candidates],
            "chosen": self.chosen.to_json(),
            "tick_candidates": [s.to_json() for s in self.tick_candidates],
            "chosen_tick": self.chosen_tick.to_json() if self.chosen_tick else None,
            "cache_hit": self.cache_hit,
            "n_lowered": self.n_lowered,
            "n_dropped": self.n_dropped,
            "budget_bytes": self.budget_bytes,
            "budget_source": self.budget_source,
        }


# ---------------------------------------------------------------------------
# fingerprint + cache
# ---------------------------------------------------------------------------
def spec_fingerprint(spec) -> str:
    """Deterministic digest of every spec field (nested configs included)."""
    blob = json.dumps(dataclasses.asdict(spec), sort_keys=True, default=str)
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def device_kind() -> str:
    import jax

    devs = jax.local_devices()
    return devs[0].device_kind if devs else "unknown"


def tune_cache_key(spec, kind: str | None = None, mesh_shape: tuple[int, ...] | None = None) -> str:
    """Cache key = (spec fingerprint, device kind, mesh shape, tuner version).

    Any spec field change (hidden_dim bump, new window geometry) changes the
    fingerprint and therefore misses the cache; so does moving the plan to a
    different device kind or mesh.
    """
    kind = device_kind() if kind is None else kind
    if mesh_shape is None:
        mesh_shape = (spec.mesh_slots,) if spec.mode == "stream" else ()
    blob = f"{spec_fingerprint(spec)}|{kind}|{','.join(map(str, mesh_shape))}|v{TUNER_VERSION}"
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


def cache_dir() -> Path:
    """On-disk tuning cache root: $REPRO_TUNE_CACHE or ~/.cache/repro/tune."""
    env = os.environ.get("REPRO_TUNE_CACHE")
    if env:
        return Path(env)
    return Path.home() / ".cache" / "repro" / "tune"


def _cache_load(path: Path, key: str) -> dict | None:
    """A cached decision, or None (missing / corrupted / stale version)."""
    try:
        with open(path) as fh:
            doc = json.load(fh)
    except FileNotFoundError:
        return None
    except (json.JSONDecodeError, OSError, UnicodeDecodeError) as e:
        warnings.warn(
            f"tuning cache {path} is corrupted ({e}); falling back to a fresh search",
            stacklevel=3,
        )
        return None
    if (
        not isinstance(doc, dict)
        or doc.get("version") != TUNER_VERSION
        or doc.get("cache_key") != key
    ):
        return None
    try:
        # validate the payload shape eagerly so a truncated-but-valid-JSON
        # file degrades to a fresh search, not a crash downstream
        ScoredCandidate.from_json(doc["chosen"])
        [ScoredCandidate.from_json(d) for d in doc["candidates"]]
    except (KeyError, TypeError) as e:
        warnings.warn(
            f"tuning cache {path} has an unreadable payload ({e}); "
            f"falling back to a fresh search",
            stacklevel=3,
        )
        return None
    return doc


def _cache_store(path: Path, doc: dict) -> None:
    path.parent.mkdir(parents=True, exist_ok=True)
    tmp = path.with_suffix(".tmp")
    with open(tmp, "w") as fh:
        json.dump(doc, fh, indent=2, sort_keys=True)
        fh.write("\n")
    os.replace(tmp, path)  # atomic on POSIX: a reader never sees a torn file


# ---------------------------------------------------------------------------
# enumeration
# ---------------------------------------------------------------------------
def _step_batch(spec) -> int | None:
    """The fused-stage batch knowable at compile time (mirrors api/plan.py)."""
    if spec.mode == "stream":
        return spec.stream_config().n_windows
    return spec.batch_size


def _step_window(spec) -> int:
    return spec.stream_config().window if spec.mode == "stream" else 32


def enumerate_candidates(spec) -> list[Candidate]:
    """The step-stage design space for one spec, static-policy point first.

    Axes: batch tile (``tiling.block_b_candidates``; pinned when the spec
    carries an explicit int), fused-vs-unfused (both only when the family is
    fusable and the spec is float — int8 serving and QAT pin the kernel
    path), and the substep-scan unroll factor (multi-substep families only).
    The list is deterministic and deduplicated; the candidate matching the
    spec's own static lowering always leads, so the measured set (capped at
    MAX_LOWERED) can never lose the baseline it must beat.
    """
    from repro.core import encoders

    row = encoders.get_encoder(spec.encoder)
    batch = _step_batch(spec)

    if isinstance(spec.block_b, int):
        tiles: list[int | None] = [spec.block_b]
    elif spec.block_b == "auto" and batch is not None:
        tiles = tiling.block_b_candidates(batch)
    else:
        tiles = [None]  # batch unknown at compile time: only full batch is legal

    if row.fusable and spec.precision == "fp32" and spec.qat is None:
        fused_opts = [spec.fused, not spec.fused]
    else:
        fused_opts = [spec.fused]

    if row.family in ("ltc", "node"):
        unrolls = sorted({1, 2, spec.ltc_substeps})
    else:
        unrolls = [1]
    if spec.substep_unroll not in unrolls:
        unrolls = sorted({spec.substep_unroll, *unrolls})

    out: list[Candidate] = []
    for fused in fused_opts:
        for bb in tiles if fused else [None]:  # block_b tiles the FUSED stage only
            for u in unrolls:
                out.append(Candidate(block_b=bb, fused=fused, substep_unroll=u))
    # the static-policy point leads (see docstring)
    static = static_candidate(spec)
    out = [static] + [c for c in out if c != static]
    return out


def static_candidate(spec, budget: int | None = None) -> Candidate:
    """The candidate the static policy (auto_block_b + the spec) would pick."""
    batch = _step_batch(spec)
    bb: int | None
    if isinstance(spec.block_b, int):
        bb = spec.block_b
    elif spec.block_b == "auto" and spec.fused:
        if budget is None:
            budget = (
                spec.vmem_budget_bytes
                if spec.vmem_budget_bytes is not None
                else tiling.detect_vmem_budget()
            )
        bb = tiling.auto_block_b(spec.to_mr_config(), batch, budget)
    else:
        bb = None
    return Candidate(block_b=bb, fused=spec.fused, substep_unroll=spec.substep_unroll)


def enumerate_tick_candidates(spec) -> list[Candidate]:
    """Bank sizes for the banked stream tick (empty off-stream / unsupported)."""
    if spec.mode != "stream":
        return []
    requested = spec.tick_spec().tick_kernel
    if requested not in ("banked", "auto"):
        return []
    from repro.kernels.mr_step import tick as tick_mod

    cfg = spec.to_mr_config()
    scfg = spec.stream_config()
    quant_tick = spec.precision == "int8_pwl" and scfg.steps_per_tick == 0
    if not tick_mod.tick_supported(cfg, int8=quant_tick):
        return []
    local_slots = spec.n_slots // spec.mesh_slots
    return [
        Candidate(stage="tick", slots_per_bank=spb)
        for spb in tiling.slots_per_bank_candidates(local_slots)
    ]


# ---------------------------------------------------------------------------
# scoring
# ---------------------------------------------------------------------------
def _candidate_cfg(spec, cand: Candidate):
    cfg = spec.to_mr_config(block_b=cand.block_b, substep_unroll=cand.substep_unroll)
    if cfg.fused != cand.fused:
        cfg = dataclasses.replace(cfg, fused=cand.fused)
    return cfg


def _lower_step(spec, cand: Candidate):
    """Compile one step-stage candidate; returns (Compiled, hlo text, T)."""
    import jax
    import jax.numpy as jnp

    from repro.core.merinda import init_mr, mr_forward

    cfg = _candidate_cfg(spec, cand)
    B = _step_batch(spec) or 16
    T = _step_window(spec)
    params = init_mr(jax.random.key(0), cfg)
    ys = jnp.zeros((B, T, cfg.state_dim), jnp.float32)
    us = jnp.zeros((B, T, cfg.input_dim), jnp.float32) if cfg.input_dim else None
    fn = jax.jit(lambda p, y, u: mr_forward(p, cfg, y, u))
    compiled = fn.lower(params, ys, us).compile()
    return compiled, compiled.as_text(), T, (params, ys, us)


def _lower_tick(spec, cand: Candidate):
    """Compile one tick-stage candidate; returns (Compiled, hlo text, T)."""
    import functools

    import jax
    import jax.numpy as jnp

    from repro.core import stream as stream_mod

    cfg = spec.to_mr_config()
    scfg = spec.stream_config()
    quant_tick = spec.precision == "int8_pwl" and scfg.steps_per_tick == 0
    key = jax.random.key(0)
    state = stream_mod.init_slots(key, cfg, scfg, spec.n_slots)
    new_y = jnp.zeros((spec.n_slots, scfg.chunk, cfg.state_dim), jnp.float32)
    new_u = jnp.zeros((spec.n_slots, scfg.chunk, cfg.input_dim), jnp.float32)
    fn = jax.jit(
        functools.partial(
            stream_mod.tick_banked,
            cfg=cfg,
            scfg=scfg,
            quant=quant_tick,
            slots_per_bank=cand.slots_per_bank or 1,
        )
    )
    compiled = fn.lower(state, new_y, new_u, key).compile()
    return compiled, compiled.as_text(), scfg.window, None


def _xla_costs(compiled) -> tuple[float | None, float | None]:
    """(flops, bytes accessed) from Compiled.cost_analysis(), defensively.

    A backend that raises or reports nothing degrades to (None, None) — the
    parse-based score is the primary signal, this is the cross-check.
    """
    try:
        cost = compiled.cost_analysis()
    except Exception:
        return None, None
    if not isinstance(cost, dict):
        return None, None
    return cost.get("flops"), cost.get("bytes accessed")


def _roofline_us(flops: float, bytes_: float) -> float:
    """Per-step roofline time in microseconds: the binding term wins."""
    return max(flops / H.PEAK_FLOPS, bytes_ / H.HBM_BW) * 1e6


def _predicted_bytes(spec, cand: Candidate) -> int:
    if cand.stage == "tick":
        return tiling.tick_vmem_bytes(
            spec.to_mr_config(),
            spec.stream_config(),
            slots_per_bank=cand.slots_per_bank or 1,
            int8=spec.precision == "int8_pwl" and spec.stream_config().steps_per_tick == 0,
        )
    return tiling.config_vmem_bytes(
        _candidate_cfg(spec, cand), _step_batch(spec) or 16, block_b=cand.block_b
    )


def score_candidate(
    spec, cand: Candidate, budget: int | None, *, lower: bool = True
) -> ScoredCandidate:
    """Static prediction always; parsed + XLA measurement when ``lower``."""
    predicted = _predicted_bytes(spec, cand)
    fits = budget is None or predicted <= budget
    sc = ScoredCandidate(candidate=cand, predicted_bytes=predicted, fits_budget=fits)
    if not lower:
        return sc
    compiled, text, T, _ = (_lower_tick if cand.stage == "tick" else _lower_step)(spec, cand)
    costs = H.analyze_module(text, 1)
    sc.parsed_bytes = costs.hbm_bytes / max(T, 1)
    sc.parsed_flops = costs.flops / max(T, 1)
    xf, xb = _xla_costs(compiled)
    sc.xla_flops = xf / max(T, 1) if xf is not None else None
    sc.xla_bytes = xb / max(T, 1) if xb is not None else None
    sc.t_step_us = _roofline_us(sc.parsed_flops, sc.parsed_bytes)
    if cand.stage == "tick":
        lo, hi = tiling.TICK_RESIDENCY_BAND
    else:
        from repro.core import encoders

        lo, hi = tiling.residency_tolerance(encoders.get_encoder(spec.encoder).family)
    ratio = sc.parsed_bytes / max(predicted, 1)
    sc.in_band = lo <= ratio <= hi
    return sc


def _rank_key(sc: ScoredCandidate):
    """Deterministic ranking: budget-fitting in-band candidates first, then
    the roofline estimate (micro-run time when refined), with a fixed
    structural tie-break so identical scores order identically everywhere."""
    c = sc.candidate
    t = sc.measured_us if sc.measured_us is not None else sc.t_step_us
    return (
        not sc.fits_budget,
        not sc.in_band,
        round(t, 4) if t is not None else float("inf"),
        -(c.block_b or 1 << 30),  # larger tile preferred at equal cost
        c.substep_unroll,  # least unrolling at equal cost
        not c.fused,
        -(c.slots_per_bank or 0),
    )


def _time_compiled(compiled, args, *, repeats: int = 3) -> float:
    """Best-of-N wall time of one compiled call, in microseconds."""
    import jax

    flat = [a for a in args if a is not None] if args else []
    best = float("inf")
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = compiled(*flat)
        jax.block_until_ready(out)
        best = min(best, (time.perf_counter() - t0) * 1e6)
    return best


# ---------------------------------------------------------------------------
# the tuner
# ---------------------------------------------------------------------------
def tune(
    spec,
    mode: str = "measured",
    *,
    cache: bool = True,
    cache_root: Path | str | None = None,
    refine_topk: int = 0,
) -> TuneReport:
    """Pick the best lowering for ``spec``; see the module docstring.

    ``mode="static"`` scores the candidate table with the VMEM model only
    (no lowering, no cache) and chooses exactly what the static policy
    chooses — the table is the what-if evidence. ``mode="measured"`` lowers
    every candidate (up to MAX_LOWERED), scores the optimized HLO, and
    caches the decision; a warm call returns the cached report with
    ``cache_hit=True`` and ``n_lowered=0``. ``refine_topk`` times the top-k
    step candidates with micro-runs and re-ranks (opt-in: wall times are
    machine-dependent, so compile_plan never sets it).
    """
    if mode not in ("static", "measured"):
        raise ValueError(f"tune mode must be 'static' or 'measured', got {mode!r}")
    kind = device_kind()
    mesh_shape = (spec.mesh_slots,) if spec.mode == "stream" else ()
    fingerprint = spec_fingerprint(spec)
    key = tune_cache_key(spec, kind, mesh_shape)
    if spec.vmem_budget_bytes is not None:
        budget, budget_src = spec.vmem_budget_bytes, "explicit"
    else:
        budget, budget_src = tiling.resolve_vmem_budget()

    cands = enumerate_candidates(spec)
    tick_cands = enumerate_tick_candidates(spec)

    if mode == "static":
        scored = [score_candidate(spec, c, budget, lower=False) for c in cands]
        tick_scored = [score_candidate(spec, c, budget, lower=False) for c in tick_cands]
        chosen_c = static_candidate(spec, budget)
        chosen = next(s for s in scored if s.candidate == chosen_c)
        chosen_tick = next((s for s in tick_scored if s.fits_budget), None)
        return TuneReport(
            cache_key=key,
            spec_fingerprint=fingerprint,
            device_kind=kind,
            mesh_shape=mesh_shape,
            mode=mode,
            candidates=scored,
            chosen=chosen,
            tick_candidates=tick_scored,
            chosen_tick=chosen_tick,
            budget_bytes=budget,
            budget_source=budget_src,
        )

    cpath = Path(cache_root) if cache_root is not None else cache_dir()
    cpath = cpath / f"{key}.json"
    if cache:
        doc = _cache_load(cpath, key)
        if doc is not None:
            return TuneReport(
                cache_key=key,
                spec_fingerprint=fingerprint,
                device_kind=kind,
                mesh_shape=mesh_shape,
                mode="measured",
                candidates=[ScoredCandidate.from_json(d) for d in doc["candidates"]],
                chosen=ScoredCandidate.from_json(doc["chosen"]),
                tick_candidates=[ScoredCandidate.from_json(d) for d in doc["tick_candidates"]],
                chosen_tick=ScoredCandidate.from_json(doc["chosen_tick"])
                if doc.get("chosen_tick")
                else None,
                cache_hit=True,
                n_lowered=0,
                n_dropped=doc.get("n_dropped", 0),
                budget_bytes=doc.get("budget_bytes"),
                budget_source=doc.get("budget_source"),
            )

    lowered_set = cands[:MAX_LOWERED]
    dropped = cands[MAX_LOWERED:]
    scored = [score_candidate(spec, c, budget, lower=True) for c in lowered_set]
    scored += [score_candidate(spec, c, budget, lower=False) for c in dropped]
    n_lowered = len(lowered_set)
    if refine_topk > 0:
        for sc in sorted(scored, key=_rank_key)[:refine_topk]:
            if sc.candidate.stage != "step" or sc.t_step_us is None:
                continue
            compiled, _, _, args = _lower_step(spec, sc.candidate)
            sc.measured_us = _time_compiled(compiled, args)
    scored.sort(key=_rank_key)
    chosen = scored[0]

    tick_scored = [score_candidate(spec, c, budget, lower=True) for c in tick_cands]
    n_lowered += len(tick_cands)
    tick_scored.sort(key=_rank_key)
    chosen_tick = tick_scored[0] if tick_scored else None

    report = TuneReport(
        cache_key=key,
        spec_fingerprint=fingerprint,
        device_kind=kind,
        mesh_shape=mesh_shape,
        mode="measured",
        candidates=scored,
        chosen=chosen,
        tick_candidates=tick_scored,
        chosen_tick=chosen_tick,
        cache_hit=False,
        n_lowered=n_lowered,
        n_dropped=len(dropped),
        budget_bytes=budget,
        budget_source=budget_src,
    )
    if cache:
        _cache_store(cpath, report.to_json())
    return report


# ---------------------------------------------------------------------------
# what-if / smoke CLI
# ---------------------------------------------------------------------------
def _fmt_bytes(x: float | None) -> str:
    if x is None:
        return "-"
    return f"{x / 1024:.1f}K" if x >= 1024 else f"{x:.0f}"


def explain(report: TuneReport) -> str:
    """Human-readable replay of the decision (the --what-if body)."""
    lines = [
        f"tune[{report.mode}] key={report.cache_key} device={report.device_kind} "
        f"mesh={report.mesh_shape or '()'} budget={_fmt_bytes(report.budget_bytes)} "
        f"({report.budget_source}) cache_hit={report.cache_hit} "
        f"lowered={report.n_lowered} dropped={report.n_dropped}",
        f"{'rank':<4} {'candidate':<32} {'pred_B':>8} {'meas_B/step':>11} "
        f"{'flops/step':>10} {'xla_B/step':>10} {'t_us':>8} fit band",
    ]
    winners = {report.chosen.candidate}
    if report.chosen_tick is not None:
        winners.add(report.chosen_tick.candidate)
    for i, sc in enumerate(report.candidates + report.tick_candidates):
        mark = "*" if sc.candidate in winners else " "
        t_str = f"{sc.t_step_us:.2f}" if sc.t_step_us is not None else "-"
        lines.append(
            f"{mark}{i:<3} {sc.candidate.label():<32} {_fmt_bytes(sc.predicted_bytes):>8} "
            f"{_fmt_bytes(sc.parsed_bytes):>11} {_fmt_bytes(sc.parsed_flops):>10} "
            f"{_fmt_bytes(sc.xla_bytes):>10} {t_str:>8} "
            f"{'y' if sc.fits_budget else 'N'}   {'y' if sc.in_band else 'N'}"
        )
    ch = report.chosen
    runners = [s for s in report.candidates if s is not ch]
    if runners and ch.t_step_us is not None and runners[0].t_step_us is not None:
        ru = runners[0]
        why = []
        if ch.fits_budget and not ru.fits_budget:
            why.append(f"it fits the budget ({_fmt_bytes(ch.predicted_bytes)} resident)")
        if ch.in_band and not ru.in_band:
            why.append("its measured traffic matches the residency model")
        if ru.t_step_us > (ch.t_step_us or 0):
            why.append(
                f"its roofline step time is {ru.t_step_us / max(ch.t_step_us, 1e-9):.2f}x "
                f"lower ({ch.t_step_us:.2f}us vs {ru.t_step_us:.2f}us)"
            )
        if why:
            lines.append(
                f"chose {ch.candidate.label()} over {ru.candidate.label()}: " + "; ".join(why)
            )
    return "\n".join(lines)


def _spec_from_args(args) -> "object":
    from repro.api.spec import RecoverySpec

    kw = dict(
        state_dim=args.state_dim,
        hidden=args.hidden,
        encoder=args.encoder,
        fused=args.fused,
        block_b="auto",
        mode=args.mode,
    )
    if args.vmem_budget:
        kw["vmem_budget_bytes"] = args.vmem_budget
    if args.mode in ("offline", "batch"):
        kw["batch_size"] = args.batch
    return RecoverySpec(**kw)


def _smoke_specs():
    from repro.api.spec import RecoverySpec

    return [
        (
            "gru_flow:fused:b16",
            RecoverySpec(
                state_dim=2, hidden=8, dense_hidden=16, encoder="gru_flow",
                fused=True, block_b="auto", mode="batch", batch_size=16, steps=4,
            ),
        ),
        (
            "ltc:fused:b12",
            RecoverySpec(
                state_dim=2, hidden=8, dense_hidden=16, encoder="ltc", ltc_substeps=4,
                fused=True, block_b="auto", mode="batch", batch_size=12, steps=4,
            ),
        ),
    ]


def _run_smoke(args) -> int:
    """CI tune-smoke: cold tune two specs, then assert the warm path is free."""
    from repro.api import plan as plan_mod

    reports = {}
    for label, spec in _smoke_specs():
        cold = plan_mod.compile_plan(spec, tune="measured")
        if cold.lowering.tuned not in ("measured", "measured:cached"):
            print(f"FAIL {label}: cold compile not tuned ({cold.lowering.tuned})")
            return 1
        warm = plan_mod.compile_plan(spec, tune="measured")
        if warm.lowering.tuned != "measured:cached":
            print(f"FAIL {label}: warm compile missed the cache ({warm.lowering.tuned})")
            return 1
        warm_report = tune(spec, mode="measured")
        if not warm_report.cache_hit or warm_report.n_lowered != 0:
            print(
                f"FAIL {label}: warm tune lowered {warm_report.n_lowered} candidates "
                f"(cache_hit={warm_report.cache_hit})"
            )
            return 1
        if warm.lowering.block_b != cold.lowering.block_b:
            print(f"FAIL {label}: warm choice diverged from cold")
            return 1
        reports[label] = warm_report.to_json()
        print(f"ok {label}: chosen={warm_report.chosen.candidate.label()} warm n_lowered=0")
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(reports, fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json}")
    print("tune-smoke: warm compiles hit the cache with zero lowered candidates")
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(
        prog="python -m repro.analysis.tuner",
        description="Measured-cost autotuner: replay / explain lowering decisions.",
    )
    ap.add_argument("--what-if", action="store_true", help="print the ranked candidate table")
    ap.add_argument("--smoke", action="store_true", help="CI tune-smoke (two specs, warm assert)")
    ap.add_argument("--tune", default="measured", choices=("static", "measured"))
    ap.add_argument("--encoder", default="gru_flow")
    ap.add_argument("--state-dim", type=int, default=2)
    ap.add_argument("--hidden", type=int, default=16)
    ap.add_argument("--batch", type=int, default=32)
    ap.add_argument("--mode", default="batch", choices=("offline", "batch", "stream"))
    ap.add_argument("--fused", action="store_true")
    ap.add_argument("--vmem-budget", type=int, default=0, help="explicit VMEM budget in bytes")
    ap.add_argument("--no-cache", action="store_true", help="ignore + don't write the cache")
    ap.add_argument("--measure-topk", type=int, default=0, help="micro-run the top-k candidates")
    ap.add_argument("--cache-dir", default=None, help="override the tuning cache root")
    ap.add_argument("--json", default=None, help="write the TuneReport here")
    args = ap.parse_args(argv)
    if args.cache_dir:
        os.environ["REPRO_TUNE_CACHE"] = args.cache_dir
    if args.smoke:
        return _run_smoke(args)
    if not args.what_if:
        ap.error("nothing to do: pass --what-if or --smoke")
    spec = _spec_from_args(args)
    report = tune(spec, mode=args.tune, cache=not args.no_cache, refine_topk=args.measure_topk)
    print(explain(report))
    if args.json:
        with open(args.json, "w") as fh:
            json.dump(report.to_json(), fh, indent=2, sort_keys=True)
            fh.write("\n")
        print(f"wrote {args.json}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
