"""VMEM residency model + batch-tile (``block_b``) auto-selection.

One model of what the stage-fused ``mr_step`` kernels pin in VMEM — encoder
weights, head weights, the per-tile activation blocks, the per-substep
working set of the multi-substep cells, PWL tables when int8 — shared by
two consumers:

- ``benchmarks/bench_stagemap._vmem_bytes`` (the paper Table 7 analogue)
  delegates here, so the design-space sweep and the runtime tiling decision
  can never disagree about residency;
- ``repro.api.compile_plan`` resolves ``RecoverySpec.block_b="auto"`` by
  walking the divisor tiles of the batch and picking the largest one whose
  residency fits the VMEM budget (the ROADMAP "pick block_b from
  ``_vmem_bytes`` against the VMEM budget" item). The budget is the spec's
  explicit ``vmem_budget_bytes`` when given, else :func:`detect_vmem_budget`
  resolves it from the local device (platform table + ``memory_stats()``
  when the runtime exposes a VMEM figure).

``config_vmem_bytes`` dispatches on the encoder family: the GRU(-flow)
model (``vmem_bytes``), the LTC fused-solver model (``ltc_vmem_bytes``) or
the NODE/ODE-RNN model (``node_vmem_bytes``). The numbers mirror each
kernel's actual BlockSpecs (kernel.py): weights are resident across the
whole grid, activations are tiled by ``block_b`` rows, and the substep
loops REUSE their temporaries (residency is substep-count-invariant — the
kernels unroll the loop over one working set, they do not allocate K
copies).
"""

from __future__ import annotations

# ~16 MB of VMEM per TPU core (v4/v5 family); the auto policy budgets
# against a fraction of this, never the constant directly.
VMEM_BYTES_PER_CORE = 16 * 1024 * 1024

# Conservative fraction of raw VMEM the auto tile may claim: Mosaic
# double-buffers the streamed x_t blocks and needs headroom for spills, so
# budgeting the full physical size would thrash.
VMEM_BUDGET_FRACTION = 0.5

# device_kind substring -> VMEM bytes/core (first match wins, checked in
# order). Every currently-shipping TPU core carries 16 MiB of VMEM except
# Trillium-class parts. A TPU whose kind is missing here is an error (its
# budget is unknown, so any tile chosen for it would be a guess); non-TPU
# hosts (CPU tests) fall back to the v4/v5 figure so they resolve the same
# budget a v5e deployment would.
PLATFORM_VMEM_BYTES: tuple[tuple[str, int], ...] = (
    ("v6", 32 * 1024 * 1024),  # Trillium
    ("v5", VMEM_BYTES_PER_CORE),
    ("v4", VMEM_BYTES_PER_CORE),
)


def resolve_vmem_budget(device=None, *, fraction: float = VMEM_BUDGET_FRACTION) -> tuple[int, str]:
    """(budget bytes, source) for the local accelerator's fused-stage VMEM.

    Resolution order: ``device.memory_stats()``'s VMEM figure when the
    runtime exposes one (source ``"memory_stats"``), else the platform table
    keyed on ``device_kind`` (source ``"platform:<key>"``), else — off TPU
    only — the v4/v5 default (source ``"default"``); a TPU kind missing from
    the table raises. The result is ``fraction`` of the raw size (headroom
    for Mosaic double-buffering). Deterministic on CPU: no entry matches, so
    the default applies. The source string lands in
    ``plan.lowering.vmem_budget_source`` so an R2 residency finding is
    attributable to the budget that produced the tile.
    """
    import jax

    if device is None:
        devices = jax.local_devices()
        device = devices[0] if devices else None
    size, source = None, "default"
    if device is not None:
        stats_fn = getattr(device, "memory_stats", None)
        if callable(stats_fn):
            try:
                stats = stats_fn() or {}
            except Exception:  # backends without stats raise, not return {}
                stats = {}
            size = stats.get("vmem_size_bytes")
            if size is not None:
                source = "memory_stats"
        if size is None:
            kind = (getattr(device, "device_kind", "") or "").lower()
            for key, nbytes in PLATFORM_VMEM_BYTES:
                if key in kind:
                    size, source = nbytes, f"platform:{key}"
                    break
    if size is None:
        if getattr(device, "platform", None) == "tpu":
            raise ValueError(
                f"no VMEM figure for TPU device_kind {device.device_kind!r}: "
                "add it to tiling.PLATFORM_VMEM_BYTES"
            )
        size = VMEM_BYTES_PER_CORE
    return int(size * fraction), source


def detect_vmem_budget(device=None, *, fraction: float = VMEM_BUDGET_FRACTION) -> int:
    """Usable fused-stage VMEM budget in bytes (see resolve_vmem_budget)."""
    return resolve_vmem_budget(device, fraction=fraction)[0]


# Per-family tolerance bands for the R2 residency audit (analysis/rules.py):
# the parsed per-input-step traffic of the compiled fused stage, divided by
# this model's predicted residency, must land inside [lo, hi]. The bands are
# wide on purpose — the CPU lowering re-streams weights per scan trip where
# the kernel holds them resident, and the NODE field does two H x H mats per
# Euler substep — so they catch an order-of-magnitude model drift (a new
# resident buffer the model misses, a dropped term) without flaking on
# backend lowering details. Measured per-step ratios on the CPU lowering:
# gru 1.40, ltc 1.34, node 3.25.
RESIDENCY_BANDS: dict[str, tuple[float, float]] = {
    "gru": (0.25, 8.0),
    "ltc": (0.25, 8.0),
    "node": (0.25, 16.0),
}


def residency_tolerance(family: str) -> tuple[float, float]:
    """(lo, hi) acceptance band for parsed-per-step/predicted residency."""
    return RESIDENCY_BANDS.get(family, RESIDENCY_BANDS["gru"])


# R2 band for a MEASURED-tuned plan: the tuner stamped the parsed per-step
# traffic of the chosen candidate's own compiled HLO into
# ``plan.lowering.measured_bytes``, so the audit re-measures against that
# figure instead of the static residency model. Self-consistency of two
# parses of the same program tolerates only lowering drift (batch geometry of
# the audited program vs the tuned one), hence much tighter than the
# per-family model bands above.
TUNED_RESIDENCY_BAND: tuple[float, float] = (0.5, 2.0)


def vmem_bytes(
    B: int,
    D: int,
    H: int,
    Dh: int = 128,
    K: int = 32,
    *,
    int8: bool,
    n_seg: int,
    block_b: int,
    fused: bool = True,
) -> int:
    """Exact VMEM residency of the fused kernel's BlockSpecs (kernel.py).

    ``block_b=0`` means the full batch is one tile. ``fused=False`` models
    the bare gru_scan kernel (no head residency) — the configuration the
    unfused two-dispatch pipeline runs.
    """
    wbytes = 1 if int8 else 4
    bb = block_b or B
    vm = (D * 3 * H + H * 3 * H) * wbytes  # resident gate weights
    vm += 3 * H * 4 * (3 if int8 else 1)  # bias (+2 scale rows when int8)
    vm += bb * D * 4 + bb * H * 4 * 2  # x_t block + h scratch + h_t/out tile
    vm += H * 4 + 4  # time_scale + dt
    if int8:
        vm += 2 * 2 * n_seg * 4  # sigmoid/tanh PWL tables (slopes+intercepts)
    if fused:
        # head weights are VMEM-resident next to the gate weights
        vm += (H * Dh + Dh * K) * wbytes  # w1 + w2
        vm += (Dh + K) * 4  # b1 + b2
        vm += bb * K * 4  # out tile (theta ++ shifts)
        if int8:
            vm += (Dh + K) * 4  # per-channel dequant scale rows
    return vm


def _head_vmem_bytes(H: int, Dh: int, K: int, bb: int, *, int8: bool) -> int:
    """Head-stage residency shared by every fused variant (see vmem_bytes)."""
    wbytes = 1 if int8 else 4
    vm = (H * Dh + Dh * K) * wbytes  # w1 + w2, resident
    vm += (Dh + K) * 4  # b1 + b2
    vm += bb * K * 4  # out tile (theta ++ shifts)
    if int8:
        vm += (Dh + K) * 4  # per-channel dequant scale rows
    return vm


def ltc_vmem_bytes(
    B: int,
    D: int,
    H: int,
    Dh: int = 128,
    K: int = 32,
    *,
    int8: bool,
    n_seg: int,
    block_b: int,
    n_substeps: int = 6,
) -> int:
    """VMEM residency of the fused multi-substep LTC kernel's BlockSpecs.

    ``n_substeps`` does NOT scale the residency: the unrolled substep loop
    reuses one [bb, H] working set (drive is loop-invariant, f/num/den are
    rewritten every substep) — which is exactly why the fused variant fits
    where K separate XLA substep dispatches would each re-stream operands.
    """
    del n_substeps  # residency is substep-count-invariant (see docstring)
    wbytes = 1 if int8 else 4
    bb = block_b or B
    vm = (D * H + H * H) * wbytes  # w_in + w_rec, resident
    vm += 3 * H * 4  # bias + a + inv_tau rows
    if int8:
        vm += 2 * H * 4  # per-channel dequant scale rows (w_in, w_rec)
        vm += 2 * n_seg * 4  # sigmoid PWL table (slopes + intercepts)
    vm += bb * D * 4  # x_t block
    vm += bb * H * 4 * 2  # h scratch + the per-substep drive/f working set
    vm += _head_vmem_bytes(H, Dh, K, bb, int8=int8)
    return vm


def node_vmem_bytes(
    B: int,
    D: int,
    H: int,
    Dh: int = 128,
    K: int = 32,
    *,
    block_b: int,
    n_substeps: int = 6,
) -> int:
    """VMEM residency of the fused multi-substep NODE (ODE-RNN) kernel.

    fp32 only (no int8 variant: the tanh-MLP vector field has no PWL
    serving mapping). Substep temporaries are reused (see ltc_vmem_bytes).
    """
    del n_substeps
    vm = (2 * H * H + D * H) * 4  # w_f1 + w_f2 + w_in, resident
    vm += 3 * H * 4  # b_f1 + b_f2 + b_in rows
    bb = block_b or B
    vm += bb * D * 4  # x_t block
    vm += bb * H * 4 * 2  # h scratch + the per-substep z working set
    vm += _head_vmem_bytes(H, Dh, K, bb, int8=False)
    return vm


def _encoder_family(name: str) -> str:
    """The mr_step kernel family a registry row lowers to (see EncoderSpec)."""
    from repro.core import encoders

    try:
        return encoders.get_encoder(name).family
    except ValueError:
        return "gru"  # unregistered name: the model the default rows use


def config_vmem_bytes(cfg, batch: int, *, block_b: int | None = None, n_seg: int = 16) -> int:
    """Residency of the fused stage for one ``MRConfig`` at a given batch.

    Dispatches on the registry row's ``family`` — the SAME field
    ``kernels/mr_step/ops.py`` dispatches the kernels on — so
    ``block_b="auto"`` budgets against the variant the config actually
    lowers to.
    """
    family = _encoder_family(cfg.encoder)
    D = cfg.state_dim + cfg.input_dim
    K = cfg.n_coef + cfg.n_shifts
    if family == "ltc":
        return ltc_vmem_bytes(
            batch,
            D,
            cfg.hidden,
            cfg.dense_hidden,
            K,
            int8=cfg.quant is not None,
            n_seg=n_seg,
            block_b=block_b or 0,
            n_substeps=cfg.ltc_substeps,
        )
    if family == "node":
        return node_vmem_bytes(
            batch,
            D,
            cfg.hidden,
            cfg.dense_hidden,
            K,
            block_b=block_b or 0,
            n_substeps=cfg.ltc_substeps,
        )
    return vmem_bytes(
        batch,
        D,
        cfg.hidden,
        cfg.dense_hidden,
        K,
        int8=cfg.quant is not None,
        n_seg=n_seg,
        block_b=block_b or 0,
    )


def block_b_candidates(batch: int | None, *, min_block: int = 8) -> list[int | None]:
    """Every legal batch tile for ``batch``, largest residency first.

    The SHARED candidate enumeration behind both lowering paths: the static
    heuristic (:func:`auto_block_b`) and the measured-cost autotuner
    (``analysis/tuner.py``) walk this exact list, so the two can never
    disagree about which tiles exist. ``None`` (full batch, no tiling) leads;
    the proper divisors >= ``min_block`` follow in descending order; divisors
    BELOW ``min_block`` trail as a degraded tail — they are legal (kernel.py
    only asserts divisibility) but waste lane occupancy, so they are only
    reached when nothing larger exists (the non-power-of-two batches whose
    divisor ladder skips the [min_block, batch) range entirely, e.g.
    batch=12 with min_block=8).
    """
    if batch is None or batch < 1:
        return [None]
    preferred = [d for d in range(batch - 1, min_block - 1, -1) if batch % d == 0]
    degraded = [d for d in range(min(min_block, batch) - 1, 0, -1) if batch % d == 0]
    return [None, *preferred, *degraded]


def auto_block_b(
    cfg,
    batch: int | None,
    vmem_budget_bytes: int | None,
    *,
    min_block: int = 8,
    n_seg: int = 16,
) -> int | None:
    """Largest batch tile whose fused-stage residency fits the VMEM budget.

    Walks :func:`block_b_candidates` — full batch first, then the proper
    divisors of ``batch`` from largest to smallest (the tile must divide the
    batch exactly; kernel.py asserts ``B % block_b == 0``) — and returns the
    FIRST (largest) candidate that fits, so the choice is order-independent
    of how the divisors were generated. ``None`` (= full batch, no tiling)
    when no budget is configured OR the batch is unknown at compile time OR
    the full batch already fits. When nothing fits, the smallest enumerated
    tile is returned — including the sub-``min_block`` divisors of
    non-power-of-two batches (batch=12 has no divisor >= 8; the old walk
    returned None = full batch there even with the budget blown) — so a
    too-tight budget degrades to maximum tiling instead of failing.
    """
    if vmem_budget_bytes is None or batch is None:
        return None  # documented fallback: full batch
    candidates = block_b_candidates(batch, min_block=min_block)
    for bb in candidates:
        if config_vmem_bytes(cfg, batch, block_b=bb, n_seg=n_seg) <= vmem_budget_bytes:
            return bb  # largest fitting tile: first hit walking downward
    # nothing fits: maximum tiling — the smallest preferred divisor, or the
    # LARGEST degraded one (smaller only shrinks occupancy, not residency
    # headroom, once below min_block)
    preferred = [bb for bb in candidates if bb is not None and bb >= min_block]
    if preferred:
        return preferred[-1]
    degraded = [bb for bb in candidates if bb is not None]
    return degraded[0] if degraded else None


# ---------------------------------------------------------------------------
# banked one-kernel tick (kernels/mr_step/tick.py): slots-per-bank residency
# ---------------------------------------------------------------------------
# R2 acceptance band for the banked tick program: parsed per-window-step
# traffic of the compiled serve tick vs tick_vmem_bytes with every local
# slot resident (the CPU lowering re-streams the whole working set per scan
# trip). Wide for the same reason as RESIDENCY_BANDS; measured per-step
# ratios on the CPU lowering (tiny audit-matrix shapes): 0.97 fp32 gru,
# 1.87 int8/PWL (dequant widens the parsed traffic vs the s8 residency).
TICK_RESIDENCY_BAND: tuple[float, float] = (0.25, 8.0)


def tick_vmem_bytes(cfg, scfg, *, slots_per_bank: int = 1, int8: bool = False, n_seg: int = 16) -> int:
    """VMEM residency of one ``mr_tick`` bank (tick.py BlockSpecs).

    Everything a bank pins at once: the slots' ring buffers (in + rolled
    out), the tick chunk, the materialized window set, the hidden state for
    all windows of the bank's slots, and the per-slot gate + head weights.
    Window count does scale the working set (all N windows of a slot run as
    one batch through the unrolled substeps), which is why the bank size is
    the budget knob compile_plan resolves.
    """
    n, m = cfg.state_dim, cfg.input_dim
    D, H, Dh = n + m, cfg.hidden, cfg.dense_hidden
    Ko = cfg.n_coef + cfg.n_shifts
    L, C, T, N = scfg.buf_len, scfg.chunk, scfg.window, scfg.n_windows
    wbytes = 1 if int8 else 4
    per_slot = L * (n + m) * 4 * 2  # ring buffer block in + rolled out
    per_slot += C * (n + m) * 4  # tick chunk
    per_slot += N * T * D * 4  # materialized window set
    per_slot += N * H * 4  # hidden state across the unrolled substeps
    per_slot += (D + H) * 3 * H * wbytes + 3 * H * 4  # gate weights + bias
    per_slot += H * 4  # time_scale (fp32) / spare scale row (int8)
    per_slot += (H * Dh + Dh * Ko) * wbytes + (Dh + Ko) * 4  # head weights
    per_slot += 2 * n * 4  # frozen mean/scale rows
    per_slot += cfg.n_coef * 4 * 2 + 3 * 4  # theta in/out + seed/active/delta
    if int8:
        per_slot += (2 * 3 * H + Dh + Ko) * 4  # per-channel dequant scales
    vm = slots_per_bank * per_slot
    if int8:
        vm += 2 * 2 * n_seg * 4  # shared sigmoid/tanh PWL tables
    return vm


def _vmem_block_bytes(rows: int, cols: int, itemsize: int = 4) -> int:
    """One ``[rows, cols]`` block as Mosaic lays it out in VMEM: columns pad
    to 128 lanes; multi-row blocks pad rows to the sublane tile (8 rows of
    32-bit values, 32 of int8), single-row blocks take one row."""
    sub = 1 if rows == 1 else 8 * 4 // itemsize
    return -(-rows // sub) * sub * -(-cols // 128) * 128 * itemsize


def tick_vmem_footprint(
    cfg, scfg, *, slots_per_bank: int = 1, int8: bool = False, n_seg: int = 16
) -> int:
    """VMEM one ``mr_tick`` grid step allocates: what must fit the budget.

    :func:`tick_vmem_bytes` counts the bank's logical residency (the figure
    audit rule R2 holds the compiled traffic to). The kernel's VMEM is
    larger: every per-slot block of tick.py's BlockSpecs is padded to
    Mosaic's tiles — a ``[L, 3]`` ring buffer occupies ``[L, 128]`` lanes —
    and Mosaic double-buffers every streamed input and output block so bank
    ``i + 1`` moves while bank ``i`` computes. This model counts both, plus
    the window scratch and the shared PWL tables. For the deployment-width
    fleet of ``chip_smoke.py`` it reckons 1.64 MB per slot where the v5e
    compiler reported 21.6 MB scoped VMEM for a 16-slot bank (1.35 MB per
    slot): conservative, so a bank it admits fits.
    """
    n, m = cfg.state_dim, cfg.input_dim
    D, H, Dh = n + m, cfg.hidden, cfg.dense_hidden
    Ko = cfg.n_coef + cfg.n_shifts
    L, C = scfg.buf_len, scfg.chunk
    w = 1 if int8 else 4
    blocks = [(L, n), (C, n), (1, n), (1, n), (1, cfg.n_coef), (1, 1), (1, 1)]
    blocks += [(D, 3 * H, w), (H, 3 * H, w), (H, Dh, w), (Dh, Ko, w)]  # weights
    if int8:  # scale + bias rows: gates, head layer 1, head layer 2
        blocks += [(1, 3 * H)] * 3 + [(1, Dh)] * 2 + [(1, Ko)] * 2
    else:  # bias + time-scale rows, head biases
        blocks += [(1, 3 * H), (1, H), (1, Dh), (1, Ko)]
    blocks += [(L, n), (1, cfg.n_coef), (1, 1)]  # outputs: buffer, Theta, delta
    if m:
        blocks += [(L, m), (C, m), (L, m)]  # input ring in + chunk + rolled out
    per_slot = 2 * sum(_vmem_block_bytes(*b) for b in blocks)  # double-buffered
    vm = slots_per_bank * per_slot + _vmem_block_bytes(L, D)  # + window scratch
    if int8:
        vm += 2 * 2 * _vmem_block_bytes(2, n_seg)  # sigmoid/tanh PWL tables
    return vm


def slots_per_bank_candidates(n_slots: int) -> list[int]:
    """Every legal bank size for ``n_slots``, largest residency first.

    The shared enumeration behind :func:`auto_slots_per_bank` and the
    measured-cost autotuner's tick-stage search (``analysis/tuner.py``):
    the divisors of ``n_slots`` from all-in-one-bank down to 1.
    """
    if n_slots < 1:
        return []
    return sorted((d for d in range(1, n_slots + 1) if n_slots % d == 0), reverse=True)


def auto_slots_per_bank(
    cfg, scfg, n_slots: int, vmem_budget_bytes: int | None, *, int8: bool = False
) -> int:
    """Largest divisor of ``n_slots`` whose banked-tick VMEM footprint fits.

    Walks :func:`slots_per_bank_candidates` from largest (all slots in one
    bank — no grid streaming at all) down to 1, against
    :func:`tick_vmem_footprint`; returns 0 when even a single slot's blocks
    exceed the budget — the caller (``compile_plan``
    resolving ``tick_kernel="auto"``) falls back to the composite tick then.
    With no budget configured the full slot set is one bank, mirroring
    auto_block_b.
    """
    if n_slots < 1:
        return 0
    if vmem_budget_bytes is None:
        return n_slots
    for bank in slots_per_bank_candidates(n_slots):
        if tick_vmem_footprint(cfg, scfg, slots_per_bank=bank, int8=int8) <= vmem_budget_bytes:
            return bank
    return 0
