"""Slot-based, continuously-batched online model-recovery service.

The paper's headline property is "one setup, then continuous streaming":
configure the pipeline once, then recovery updates flow with no per-step
launch or synchronization overhead (the FPGA dataflow claim). This module is
the serving-system analogue for a FLEET of dynamical-system streams:

- N slots each hold one stream's ring-buffer window, warm-started MERINDA
  params and optimizer state inside ONE shared pytree (SlotState);
- every tick executes a single donated, jit-cached program (``tick``) that
  rolls new observations into every slot's buffer (data/windows.py),
  re-windows and re-normalizes device-side, runs K scan-jitted recovery
  steps per slot via a vmapped train loop, and reads out per-slot
  coefficient estimates + their tick-over-tick delta;
- slots whose coefficient delta falls below threshold are EVICTED and the
  next queued stream is ADMITTED into the freed slot via
  ``dynamic_update_slice`` — the same admission structure as the LM decode
  service in launch/serve.py, applied to model recovery;
- evicted params are kept in a warm-start registry, so a returning stream
  resumes from its previous model instead of a cold init.

``RecoveryService`` is the host-side orchestrator (queue, eviction policy,
warm-start registry); everything numerical stays inside compiled programs.

With a ``mesh`` (built by ``repro.api.compile_plan`` from
``RecoverySpec.mesh_slots``), every SlotState leaf's slot axis is SHARDED
across a ``("slots",)`` device mesh (``shard_slots``): the fused stage makes
per-slot cost uniform, so the even slot split is a balanced shard map and
one service scales past a single chip's VMEM/HBM. The single-device path is
the trivial mesh (``mesh=None``); numerics are identical either way
(tests/test_api.py pins 2-virtual-device parity).

The per-window recovery stage itself is merinda.mr_forward, so the service
inherits the stage-fused dataflow for free: an ``MRConfig(fused=True)``
routes every tick's encode + norm + head through the single fused
kernels/mr_step stage (one dispatch, VMEM-resident hidden state) — the same
code path the engine's epoch scan and serve_mr --fused use. The int8
readout (``readout_theta(..., quant=True)``) serves converged coefficients
through the fused fixed-point stage (kernels/mr_step int8 + PWL: quantized
gate AND head weights) — the paper's serving configuration end to end.
"""

from __future__ import annotations

import collections
import contextlib
import dataclasses
import enum
import functools
import time
from typing import Any, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import encoders
from repro.core.engine import WARMUP_STEPS
from repro.core.merinda import (
    MRConfig,
    MRParams,
    init_mr,
    mr_forward,
    mr_train_step,
)
from repro.data.windows import buffer_stats, n_buffer_windows, roll_buffer, window_views
from repro.optim import adamw_init
from repro.parallel import named_sharding, use_mesh_rules

# Slot-axis sharding rule table for the parallel/ spec resolver: the leading
# (slot) axis of every SlotState leaf shards over the "slots" mesh axis; the
# divisibility fallback in partition_spec replicates any leaf whose slot
# count doesn't divide the mesh, so an odd configuration degrades safely
# instead of forcing GSPMD padding.
SLOT_RULES: dict[str, list[tuple[str, ...]]] = {"slots": [("slots",)]}


def _slot_local(fn):
    """``fn`` (batched over the leading slot axis of every argument and
    result) run on each device's own slots when a slot mesh is active.

    Mosaic kernels cannot be partitioned automatically, so on a sharded slot
    axis the tick's per-slot section runs under ``shard_map``: each device
    computes the slots it holds, the same per-slot math as on one device,
    with no collective. Off a mesh (or on the trivial one) ``fn`` is
    returned unchanged.
    """
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.shape.get("slots", 1) == 1:
        return fn
    slots = jax.sharding.PartitionSpec("slots")
    return jax.shard_map(fn, in_specs=slots, out_specs=slots, check_vma=False)


@dataclasses.dataclass(frozen=True)
class StreamConfig:
    """Static service configuration (hashable: usable as a jit static arg)."""

    buf_len: int = 160  # ring-buffer length L (observations per slot)
    window: int = 32  # T: window length fed to the encoder
    stride: int = 8  # window stride over the buffer
    chunk: int = 16  # C: new observations ingested per tick
    steps_per_tick: int = 8  # K: optimizer steps per slot per tick (0 = serve-only)
    lr: float = 3e-3
    batch_size: int | None = None  # windows per step (None = all N windows)
    ema: float = 0.9  # smoothing for the per-tick Theta readout
    delta_tol: float = 0.015  # relative coefficient-delta eviction threshold
    min_steps: int = 128  # no eviction before this many optimizer steps
    max_steps: int = 400  # unconditional eviction budget per stream

    def __post_init__(self):
        if self.window > self.buf_len:
            raise ValueError(f"window {self.window} exceeds buf_len {self.buf_len}")
        if self.chunk > self.buf_len:
            # roll_buffer would silently GROW the buffer past buf_len and
            # every static shape downstream (admit, n_windows) would be wrong
            raise ValueError(f"chunk {self.chunk} exceeds buf_len {self.buf_len}")
        if self.stride < 1 or self.chunk < 1:
            raise ValueError("stride and chunk must be >= 1")
        if self.steps_per_tick < 0:
            # 0 is a pure serve/monitor tick: ingest + readout, no training
            raise ValueError("steps_per_tick must be >= 0")

    @property
    def n_windows(self) -> int:
        return n_buffer_windows(self.buf_len, self.window, self.stride)


class SlotState(NamedTuple):
    """One shared pytree for all S slots (every leaf has leading axis S)."""

    params: Any  # MRParams, leaves [S, ...]
    opt: Any  # AdamWState, leaves [S, ...]
    buf_y: jnp.ndarray  # [S, L, n] raw observations (ring buffer)
    buf_u: jnp.ndarray  # [S, L, m] exogenous inputs (m may be 0)
    theta: jnp.ndarray  # [S, n_terms, n] last readout (normalized coords)
    delta: jnp.ndarray  # [S] relative theta change at the last tick
    loss: jnp.ndarray  # [S] last-step reconstruction MSE
    mean: jnp.ndarray  # [S, n] normalization stats FROZEN at admission
    scale: jnp.ndarray  # [S, n]
    steps: jnp.ndarray  # [S] int32 optimizer steps since admission
    active: jnp.ndarray  # [S] bool
    stream_id: jnp.ndarray  # [S] int32 (-1 = empty slot)


def shard_slots(state: SlotState, mesh) -> SlotState:
    """Shard every SlotState leaf's slot axis across ``mesh`` ("slots" axis).

    The fused stage makes per-slot cost uniform, so an even slot split IS the
    balanced shard map — one service then scales past a single chip's
    VMEM/HBM. Placement goes through the ``parallel/`` rule table
    (``named_sharding`` + SLOT_RULES) so the mesh-shim and divisibility
    safety properties apply; the jitted ``tick``/``admit`` programs see the
    sharded pytree as inputs and XLA's SPMD partitioner keeps every per-slot
    computation on the slot's device.
    """

    def put(leaf):
        axes = ("slots",) + (None,) * (leaf.ndim - 1)
        return jax.device_put(leaf, named_sharding(mesh, leaf.shape, axes, SLOT_RULES))

    return jax.tree.map(put, state)


@functools.partial(jax.jit, static_argnames=("cfg",))
def cold_start_program(key: jax.Array, cfg: MRConfig) -> tuple[MRParams, Any]:
    """``init_mr`` + ``adamw_init`` as one compiled program (one per config)."""
    params = init_mr(key, cfg)
    return params, adamw_init(params)


# the warm path's optimizer reset: one compiled program per params shape
opt_init = jax.jit(adamw_init)


def cold_start(key: jax.Array, cfg: MRConfig) -> tuple[MRParams, Any]:
    """Fresh (params, opt_state) for one admission (span ``mr.cold_start``).

    One dispatch of ``cold_start_program``: the per-stream admission path
    traces nothing after the first stream of a config."""
    with jax.profiler.TraceAnnotation("mr.cold_start"):
        return cold_start_program(key, cfg)


def init_slots(key: jax.Array, cfg: MRConfig, scfg: StreamConfig, n_slots: int) -> SlotState:
    """All-empty service state: per-slot fresh params, inactive slots."""
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_slots))
    params = jax.vmap(lambda k: init_mr(k, cfg))(keys)
    opt = jax.vmap(adamw_init)(params)
    n, m = cfg.state_dim, cfg.input_dim
    return SlotState(
        params=params,
        opt=opt,
        buf_y=jnp.zeros((n_slots, scfg.buf_len, n), jnp.float32),
        buf_u=jnp.zeros((n_slots, scfg.buf_len, m), jnp.float32),
        theta=jnp.zeros((n_slots, cfg.n_terms, n), jnp.float32),
        delta=jnp.full((n_slots,), jnp.inf, jnp.float32),
        loss=jnp.full((n_slots,), jnp.inf, jnp.float32),
        mean=jnp.zeros((n_slots, n), jnp.float32),
        scale=jnp.ones((n_slots, n), jnp.float32),
        steps=jnp.zeros((n_slots,), jnp.int32),
        active=jnp.zeros((n_slots,), bool),
        stream_id=jnp.full((n_slots,), -1, jnp.int32),
    )


def _write_slot(tree: Any, slot: jnp.ndarray, one: Any) -> Any:
    """Write one slot's entry (leading axis) across a whole pytree."""

    def wr(full, new):
        new = jnp.asarray(new, full.dtype)
        return jax.lax.dynamic_update_slice_in_dim(full, new[None], slot, axis=0)

    return jax.tree.map(wr, tree, one)


@functools.partial(jax.jit, donate_argnums=(0,))
def admit(
    state: SlotState,
    slot: jnp.ndarray,  # scalar int32 (traced: one program serves all slots)
    stream_id: jnp.ndarray,
    buf_y: jnp.ndarray,  # [L, n] initial history
    buf_u: jnp.ndarray,  # [L, m]
    params: MRParams,  # cold init or warm-start tree (single slot)
    opt: Any,
) -> SlotState:
    """Admit one stream into ``slot`` (dynamic_update_slice across the pytree).

    Normalization stats are computed from the admission history and FROZEN
    for the stream's lifetime: re-estimating them as the buffer slides would
    wobble the coefficient basis under the optimizer every tick (a moving
    target Theta has to chase) and make the EMA readout mix estimates from
    different coordinate systems.
    """
    n_terms, n = state.theta.shape[1:]
    mean, scale = buffer_stats(buf_y)
    return SlotState(
        params=_write_slot(state.params, slot, params),
        opt=_write_slot(state.opt, slot, opt),
        buf_y=_write_slot(state.buf_y, slot, buf_y),
        buf_u=_write_slot(state.buf_u, slot, buf_u),
        theta=_write_slot(state.theta, slot, jnp.zeros((n_terms, n))),
        delta=_write_slot(state.delta, slot, jnp.inf),
        loss=_write_slot(state.loss, slot, jnp.inf),
        mean=_write_slot(state.mean, slot, mean[0]),
        scale=_write_slot(state.scale, slot, scale[0]),
        steps=_write_slot(state.steps, slot, jnp.zeros((), jnp.int32)),
        active=_write_slot(state.active, slot, jnp.ones((), bool)),
        stream_id=_write_slot(state.stream_id, slot, stream_id),
    )


@jax.jit
def slot_result(state: SlotState, slot: jnp.ndarray) -> tuple[dict, MRParams]:
    """One slot's answer gathered in one program (the state is not donated).

    Returns ``(fields, params)``: ``fields`` holds the slot's ``stream_id``,
    ``theta``, ``mean``, ``scale`` and ``steps`` for a single host read;
    ``params`` is the slot's parameter tree, left on the device. ``slot`` is
    traced, so one program serves every slot, as ``admit``'s does.
    """

    def one(leaf):
        return jax.lax.dynamic_index_in_dim(leaf, slot, axis=0, keepdims=False)

    fields = {
        k: one(getattr(state, k)) for k in ("stream_id", "theta", "mean", "scale", "steps")
    }
    return fields, jax.tree.map(one, state.params)


@functools.partial(jax.jit, donate_argnums=(0,))
def deactivate(state: SlotState, slot: jnp.ndarray) -> SlotState:
    """Mark a slot empty (no queued stream to admit)."""
    return state._replace(
        active=_write_slot(state.active, slot, jnp.zeros((), bool)),
        stream_id=_write_slot(state.stream_id, slot, jnp.full((), -1, jnp.int32)),
    )


def _slot_windows(buf_y, buf_u, mean, scale, scfg: StreamConfig):
    """Normalize a buffer (frozen admission stats) and window it."""
    yw = window_views((buf_y - mean) / scale, scfg.window, scfg.stride)
    uw = window_views(buf_u, scfg.window, scfg.stride)
    return yw, uw


def _recover_steps(params, opt, yw, uw, key, steps0, *, cfg: MRConfig, scfg: StreamConfig):
    """K optimizer steps on one slot's windows (scan body; vmapped in tick)."""
    n_win = yw.shape[0]
    bs = scfg.batch_size or n_win
    sample = bs < n_win

    def body(carry, j):
        p, o = carry
        if sample:
            sub = jax.random.fold_in(key, j)
            idx = jax.random.randint(sub, (bs,), 0, n_win)
            yb, ub = jnp.take(yw, idx, axis=0), jnp.take(uw, idx, axis=0)
        else:
            yb, ub = yw, uw
        # linear warmup then inverse-sqrt decay: the decay makes the Theta
        # readout settle so the coefficient-delta eviction signal converges
        # (constant lr keeps the estimate jittering above any useful tol)
        frac = (steps0 + j + 1.0) / WARMUP_STEPS
        lr_t = scfg.lr * jnp.minimum(frac, jax.lax.rsqrt(frac))
        p, o, aux = mr_train_step(p, o, cfg, yb, ub, lr_t, None)
        return (p, o), aux["recon_mse"]

    (params, opt), recon = jax.lax.scan(body, (params, opt), jnp.arange(scfg.steps_per_tick))
    theta, _ = mr_forward(params, cfg, yw, uw)
    return params, opt, theta.mean(axis=0), recon[-1]


def _train_slots(state: SlotState, yw, uw, key, *, cfg: MRConfig, scfg: StreamConfig):
    """K recovery steps for every slot: (params, opt, Theta, last recon MSE).

    Each slot's sampling key folds in its GLOBAL slot index, so a slot trains
    identically whichever device of the slot mesh holds it."""
    n_slots = yw.shape[0]
    keys = jax.vmap(lambda i: jax.random.fold_in(key, i))(jnp.arange(n_slots))
    return _slot_local(
        jax.vmap(lambda p, o, y, u, k, s: _recover_steps(p, o, y, u, k, s, cfg=cfg, scfg=scfg))
    )(state.params, state.opt, yw, uw, keys, state.steps)


def _tick_impl(
    state: SlotState,
    new_y: jnp.ndarray,
    new_u: jnp.ndarray,
    key: jax.Array,
    *,
    cfg: MRConfig,
    scfg: StreamConfig,
) -> SlotState:
    """Composite tick body (un-jitted: ``tick`` wraps it; the device-resident
    control-plane program in core/control.py inlines it ahead of the on-device
    eviction/refill section so both paths trace the identical tick math)."""
    buf_y = roll_buffer(state.buf_y, new_y)
    buf_u = roll_buffer(state.buf_u, new_u)
    yw, uw = jax.vmap(lambda y, u, mu, sd: _slot_windows(y, u, mu, sd, scfg))(
        buf_y, buf_u, state.mean, state.scale
    )

    if scfg.steps_per_tick:
        params, opt, theta, recon = _train_slots(state, yw, uw, key, cfg=cfg, scfg=scfg)
        loss = jnp.where(state.active, recon, jnp.inf)
    else:
        # serve/monitor tick: no optimizer steps, readout only
        params, opt, loss = state.params, state.opt, state.loss
        theta = _slot_local(
            jax.vmap(lambda p, y, u: mr_forward(p, cfg, y, u)[0].mean(axis=0))
        )(params, yw, uw)

    # EMA-smoothed readout: the window set (and its normalization) shifts a
    # little every tick, so the raw per-tick Theta jitters even after the
    # model has converged; the EMA is what the delta threshold watches.
    # The first tick after admission seeds the EMA directly (a fresh slot is
    # at step 0 with its delta still at the admission-time inf).
    seed = (state.steps == 0) & jnp.isinf(state.delta)
    theta = jnp.where(
        seed[:, None, None],
        theta,
        scfg.ema * state.theta + (1.0 - scfg.ema) * theta,
    )
    # relative coefficient delta: |Theta| grows toward its asymptote long
    # after the loss plateaus, so an absolute threshold never fires at a
    # scale-free setting — normalize by the current coefficient magnitude
    change = jnp.max(jnp.abs(theta - state.theta), axis=(1, 2))
    delta = change / (jnp.max(jnp.abs(theta), axis=(1, 2)) + 1e-3)
    delta = jnp.where(state.active, delta, jnp.inf)
    return state._replace(
        params=params,
        opt=opt,
        buf_y=buf_y,
        buf_u=buf_u,
        theta=theta,
        delta=delta,
        loss=loss,
        steps=state.steps + scfg.steps_per_tick,
    )


@functools.partial(jax.jit, static_argnames=("cfg", "scfg"), donate_argnums=(0,))
def tick(
    state: SlotState,
    new_y: jnp.ndarray,  # [S, C, n] fresh observations (zeros for idle slots)
    new_u: jnp.ndarray,  # [S, C, m]
    key: jax.Array,
    *,
    cfg: MRConfig,
    scfg: StreamConfig,
) -> SlotState:
    """One service tick: ingest + K recovery steps + readout, for ALL slots.

    A single compiled program (jit-cached across the whole run): ring-buffer
    roll, per-slot re-normalization and windowing, the vmapped K-step train
    scan and the coefficient readout all execute device-side with zero
    per-slot or per-step dispatch — the service-level analogue of the
    paper's "one setup, continuous streaming" pipeline.
    """
    return _tick_impl(state, new_y, new_u, key, cfg=cfg, scfg=scfg)


def pack_status(state: SlotState) -> jnp.ndarray:
    """Pack the per-slot eviction scalars into ONE [S, 4] array
    (``[delta, loss, steps, active]``) so a whole service status costs a
    single host readback — the banked tick and the device-resident control
    plane both return it instead of individual SlotState leaves."""
    return jnp.stack(
        [
            state.delta,
            state.loss,
            state.steps.astype(jnp.float32),
            state.active.astype(jnp.float32),
        ],
        axis=-1,
    )


def _tick_banked_impl(
    state: SlotState,
    new_y: jnp.ndarray,
    new_u: jnp.ndarray,
    key: jax.Array,
    *,
    cfg: MRConfig,
    scfg: StreamConfig,
    quant: bool = False,
    slots_per_bank: int = 1,
) -> tuple[SlotState, jnp.ndarray]:
    """Banked tick body (un-jitted; see ``_tick_impl`` for why it exists)."""
    from repro.kernels.mr_step.tick import mr_tick

    if scfg.steps_per_tick:
        buf_y = roll_buffer(state.buf_y, new_y)
        buf_u = roll_buffer(state.buf_u, new_u)
        yw, uw = jax.vmap(lambda y, u, mu, sd: _slot_windows(y, u, mu, sd, scfg))(
            buf_y, buf_u, state.mean, state.scale
        )
        # the in-scan forward readout is unused here (the banked kernel reads
        # out below, from the post-training params) — XLA dead-code-eliminates
        # it, leaving exactly the composite tick's training program
        params, opt, _, recon = _train_slots(state, yw, uw, key, cfg=cfg, scfg=scfg)
        loss = jnp.where(state.active, recon, jnp.inf)
    else:
        params, opt, loss = state.params, state.opt, state.loss

    seed = (state.steps == 0) & jnp.isinf(state.delta)

    def serve(params, *slot_arrays):
        return mr_tick(params, cfg, scfg, *slot_arrays, quant=quant, slots_per_bank=slots_per_bank)

    buf_y, buf_u, theta, delta = _slot_local(serve)(
        params,
        state.buf_y,
        state.buf_u,
        new_y,
        new_u,
        state.mean,
        state.scale,
        state.theta,
        seed,
        state.active,
    )
    delta = jnp.where(state.active, delta, jnp.inf)
    steps = state.steps + scfg.steps_per_tick
    state = state._replace(
        params=params,
        opt=opt,
        buf_y=buf_y,
        buf_u=buf_u,
        theta=theta,
        delta=delta,
        loss=loss,
        steps=steps,
    )
    return state, pack_status(state)


@functools.partial(
    jax.jit, static_argnames=("cfg", "scfg", "quant", "slots_per_bank"), donate_argnums=(0,)
)
def tick_banked(
    state: SlotState,
    new_y: jnp.ndarray,  # [S, C, n]
    new_u: jnp.ndarray,  # [S, C, m]
    key: jax.Array,
    *,
    cfg: MRConfig,
    scfg: StreamConfig,
    quant: bool = False,
    slots_per_bank: int = 1,
) -> tuple[SlotState, jnp.ndarray]:
    """Banked one-kernel tick: same contract as ``tick``, plus packed status.

    The training segment (K > 0) is BITWISE the composite tick's — the same
    vmapped ``_recover_steps`` scan — but the whole serving segment (ring
    ingest, window substeps, head, EMA Theta readout, delta) collapses into
    one slot-banked ``mr_tick`` program (kernels/mr_step/tick.py) instead of
    the composite stage sequence. Returns ``(state, status)`` where status
    packs ``[delta, loss, steps, active]`` per slot into one [S, 4] array so
    ``RecoveryService.tick_once`` needs a single host readback per tick.
    ``quant`` serves the readout through the int8/PWL twin (K = 0 monitor
    ticks: the serving configuration).
    """
    return _tick_banked_impl(
        state, new_y, new_u, key, cfg=cfg, scfg=scfg, quant=quant, slots_per_bank=slots_per_bank
    )


def readout_theta(
    params: MRParams,
    cfg: MRConfig,
    yw: jnp.ndarray,  # [N, T, n] normalized windows
    uw: jnp.ndarray | None = None,
    quant: bool = False,
) -> jnp.ndarray:
    """Serving readout: mean-over-windows Theta (normalized coordinates).

    quant=True serves through the stage-FUSED fixed-point step
    (kernels/mr_step int8: int8 cell + head weights with per-channel scales,
    PWL activations; interpret mode off-TPU) — the paper's serving
    configuration as one kernel. Requires an encoder whose cell has a PWL
    mapping: 'gru' (paper Eq. 12-15) or 'ltc' (sigmoid-only substep).
    """
    if not quant:
        theta, _ = mr_forward(params, cfg, yw, uw)
        return theta.mean(axis=0)
    from repro.kernels.mr_step.ops import mr_step_int8

    xs = yw if uw is None or uw.shape[-1] == 0 else jnp.concatenate([yw, uw], axis=-1)
    theta, _ = mr_step_int8(params, cfg, xs, interpret=True)
    return theta.mean(axis=0)


class StreamResult(NamedTuple):
    """Host-side record for one completed stream."""

    stream_id: int
    theta: np.ndarray  # [n_terms, n] normalized coordinates
    mean: np.ndarray  # [n] buffer stats for denormalization
    scale: np.ndarray  # [n]
    steps: int
    reason: str  # "converged" | "budget"


class SubmitStatus(enum.Enum):
    """Typed admission backpressure signal returned by ``submit``.

    ENQUEUED — the stream is queued (host deque or a device shard ring) and
    will be admitted as capacity frees. OVERFLOW — every device ring was
    full; the stream sits in the bounded host-side overflow queue and drains
    into a ring at the next snapshot/fill with free capacity. REJECTED — the
    overflow queue is also full; the caller must retry later (nothing was
    retained). ``submit`` never raises on pressure.
    """

    ENQUEUED = "enqueued"
    OVERFLOW = "overflow"
    REJECTED = "rejected"


class SubmitResult(NamedTuple):
    """What ``submit`` did with one stream (see :class:`SubmitStatus`)."""

    status: SubmitStatus
    stream_id: int
    shard: int | None = None  # device ring the stream landed in (ENQUEUED)

    @property
    def accepted(self) -> bool:
        return self.status is not SubmitStatus.REJECTED


class RecoveryService:
    """Host orchestrator: admission queue, eviction policy, warm-start registry.

    All numerics run inside the compiled ``tick``/``admit`` programs; this
    class only moves O(slots) scalars across the host boundary per tick.

    Two control planes (``control=`` — a ``control.ControlPlane`` record built
    by the plan — selects the device-resident one):

    - **host** (the reference): admission pops a ``collections.deque``,
      eviction decisions read per-slot scalars back each tick and each
      admission runs the ``admit`` program (plus a reshard on a mesh). Kept
      bitwise-stable — the device path is locked against it.
    - **device**: the queue, the eviction mask, the refill and the warm-start
      lookup all live inside ONE donated tick program
      (``control.tick_device``); the host only enqueues arrivals and drains a
      packed status snapshot + event log every ``snapshot_period`` ticks.
      Between arrivals and snapshots a tick is ZERO host readbacks and zero
      reshards (the slot shard is never re-pinned).
    """

    def __init__(
        self,
        cfg: MRConfig,
        scfg: StreamConfig,
        n_slots: int,
        seed: int = 0,
        quant: bool = False,
        mesh=None,
        tick_program=None,
        control=None,
        warm_capacity: int = 32,
        overflow_capacity: int = 16,
    ):
        encoders.validate_config(cfg)  # fused x fusable fails HERE, not mid-trace
        self.cfg, self.scfg, self.n_slots = cfg, scfg, n_slots
        self.quant = quant
        self.mesh = mesh  # jax Mesh over ("slots",) | None = single device
        # Host-boundary accounting for the mesh-scaling work (phase 2 of the
        # ROADMAP multi-device item): every device->host readback is a sync
        # point the sharded service pays ACROSS the mesh, and every re-pin of
        # the slot shard after admission is a reshard. bench_stream reports
        # these per tick so the per-device-admission redesign has a baseline.
        self.counters = {"host_syncs": 0, "reshards": 0}
        # per-tick host-sync deltas (appended by tick_once): the first tick
        # compiles and the eviction/admission ticks read extra scalars, so
        # per-tick attribution lets consumers report a MEDIAN instead of a
        # mean skewed by those outliers (bench_stream mesh rows)
        self.sync_log: list[int] = []
        # the compiled tick: a RecoveryPlan passes its pre-bound program so
        # the service runs EXACTLY what the plan compiled; standalone
        # construction binds the module-level program with this config
        if tick_program is None and control is None:
            from repro.deprecation import warn_deprecated_once

            warn_deprecated_once(
                "stream.RecoveryService",
                "direct RecoveryService(...) construction (and the service-internal "
                "tick jit path it binds) is deprecated; build a "
                "RecoverySpec(mode='stream') and use api.compile_plan(spec)"
                ".make_service() instead — the plan compiles the tick program "
                "(composite or banked, TickSpec.tick_kernel) alongside the others",
            )
        self._tick = tick_program or functools.partial(tick, cfg=cfg, scfg=scfg)
        self.key = jax.random.key(seed)
        self.state = init_slots(self.key, cfg, scfg, n_slots)
        if mesh is not None:
            self.state = shard_slots(self.state, mesh)
        # host admission queue: (stream_id, buf_y, buf_u, priority) entries;
        # pops take the highest tier first, FIFO within a tier (_queue_pop)
        self.queue: collections.deque = collections.deque()
        # bounded host-side spill for device-plane admissions when every
        # shard ring is full; drains back into the rings as capacity frees
        # (fill_slots / snapshot ticks). Beyond this, submit() REJECTs.
        self.overflow: collections.deque = collections.deque()
        self.overflow_capacity = int(overflow_capacity)
        # bounded LRU warm-start registry (stream_id -> evicted params): a
        # long-running service would otherwise accumulate one params tree per
        # stream it has EVER served; beyond capacity the least-recently-used
        # entry is dropped and a returning stream cold-starts
        self.warm: collections.OrderedDict[int, MRParams] = collections.OrderedDict()
        self.warm_capacity = int(warm_capacity)
        self.results: dict[int, StreamResult] = {}
        self.ticks = 0
        # host-side snapshot of the per-slot status, refreshed wherever the
        # status is already being read (fill_slots / tick_once / snapshots) so
        # polling `done`, `drain()` or `slot_streams()` never forces a fresh
        # device->host readback
        self._active_view = np.zeros((n_slots,), bool)
        self._slot_view = np.full((n_slots,), -1, np.int64)
        self._delta_view = np.full((n_slots,), np.inf, np.float32)
        self._loss_view = np.full((n_slots,), np.inf, np.float32)
        self._steps_view = np.zeros((n_slots,), np.int64)
        self._prio_view = np.zeros((n_slots,), np.int64)  # tier per slot
        self._prio_of: dict[int, int] = {}  # stream_id -> submitted tier
        self._undrained: list[StreamResult] = []
        # -- resilience / latency accounting (runtime/resilience.py) ---------
        # per-tick wall latency (ms); serve_mr surfaces p50/p99. checkpointer
        # is attached by RecoveryPlan.make_service when the TickSpec requests
        # periodic service snapshots.
        self.tick_ms: list[float] = []
        self.checkpointer = None
        # -- device-resident control plane (control.py) ----------------------
        self.control_plane = control
        self.control = None
        self._pending: set[int] = set()  # submitted, no result yet
        self._seen_done: set[int] = set()  # completed since last resubmission
        self._inflight: list[set[int]] = []  # per-shard: enqueued, not yet admitted
        self._ticks_since_snapshot = 0
        if control is not None:
            from repro.core import control as control_mod

            self.control = control_mod.init_control(
                self.key,
                cfg,
                scfg,
                n_slots,
                shards=control.shards,
                queue_capacity=control.queue_capacity,
                warm_capacity=control.warm_capacity,
                snapshot_period=control.snapshot_period,
            )
            if mesh is not None:
                self.control = control_mod.shard_control(self.control, mesh)
            self._inflight = [set() for _ in range(control.shards)]

    def _mesh_ctx(self):
        """Activate the slot mesh (jax.set_mesh via parallel/) around
        every compiled-program call; a no-op on the trivial mesh."""
        if self.mesh is None:
            return contextlib.nullcontext()
        return use_mesh_rules(self.mesh, SLOT_RULES)

    def _host_read(self, tree):
        """Counted device->host readback of an array or a pytree of arrays
        (one ``jax.device_get``, so one host-sync point; on a sharded service
        it gathers the slot axis across the whole mesh)."""
        self.counters["host_syncs"] += 1
        return jax.device_get(tree)

    def _reshard(self):
        """Re-pin the slot shard after a host-driven state update."""
        self.counters["reshards"] += 1
        self.state = shard_slots(self.state, self.mesh)

    # -- warm-start registry (bounded LRU) ----------------------------------
    def _warm_put(self, stream_id: int, params: MRParams):
        self.warm[stream_id] = params
        self.warm.move_to_end(stream_id)
        while len(self.warm) > self.warm_capacity:
            self.warm.popitem(last=False)

    def _warm_get(self, stream_id: int) -> MRParams | None:
        params = self.warm.get(stream_id)
        if params is not None:
            self.warm.move_to_end(stream_id)
        return params

    # -- admission ----------------------------------------------------------
    def submit(
        self,
        stream_id: int,
        history_y: np.ndarray,
        history_u: np.ndarray | None = None,
        priority: int = 0,
    ) -> SubmitResult:
        """Enqueue a stream with its initial buf_len-observation history.

        On the device control plane the history (and a cold params tree — the
        on-device warm cache overrides it on a hit) is appended straight into
        the least-loaded shard's on-device admission queue; the slot axis is
        never resharded. Returns a typed :class:`SubmitResult` instead of
        raising on pressure: a full shard ring spills into the bounded host
        overflow queue (OVERFLOW), and a full overflow queue REJECTs.

        ``priority`` is the admission tier (0 = default; higher pops first
        and may preempt a cold lower-tier slot under pressure).
        """
        from repro.core.control import PRIORITY_LIMIT

        L, m = self.scfg.buf_len, self.cfg.input_dim
        if history_y.shape != (L, self.cfg.state_dim):
            raise ValueError(f"history must be [{L}, {self.cfg.state_dim}], got {history_y.shape}")
        if not 0 <= priority < PRIORITY_LIMIT:
            raise ValueError(f"priority must be in [0, {PRIORITY_LIMIT}), got {priority}")
        if history_u is None:
            history_u = np.zeros((L, m), np.float32)
        sid = int(stream_id)
        self._prio_of[sid] = int(priority)
        if self.control_plane is None:
            self.queue.append(
                (sid, np.asarray(history_y), np.asarray(history_u), int(priority))
            )
            return SubmitResult(SubmitStatus.ENQUEUED, sid)
        shard = self._enqueue_device(sid, history_y, history_u, int(priority))
        if shard is not None:
            return SubmitResult(SubmitStatus.ENQUEUED, sid, shard)
        if len(self.overflow) >= self.overflow_capacity:
            self._prio_of.pop(sid, None)
            return SubmitResult(SubmitStatus.REJECTED, sid)
        self.overflow.append(
            (sid, np.asarray(history_y), np.asarray(history_u), int(priority))
        )
        self._pending.add(sid)
        self._seen_done.discard(sid)
        return SubmitResult(SubmitStatus.OVERFLOW, sid)

    def _enqueue_device(self, sid, history_y, history_u, priority) -> int | None:
        """Append one arrival into the least-loaded shard ring; None = all full.

        Host-side occupancy accounting is conservative: ``_inflight`` counts
        ids enqueued-but-not-admitted AND preempted-back-to-queue (snapshot
        reconciliation re-adds victims), so the compiled ``enqueue`` can
        never overflow a device queue.
        """
        cp = self.control_plane
        shard = min(range(cp.shards), key=lambda i: (len(self._inflight[i]), i))
        if len(self._inflight[shard]) >= cp.queue_capacity:
            return None
        with jax.profiler.TraceAnnotation("mr.enqueue", stream_id=sid):
            params, _ = cold_start(jax.random.fold_in(self.key, 1000 + sid), self.cfg)
            with self._mesh_ctx():
                self.control = cp.enqueue(
                    self.control,
                    jnp.int32(shard),
                    jnp.int32(sid),
                    jnp.asarray(history_y, jnp.float32),
                    jnp.asarray(history_u, jnp.float32),
                    params,
                    jnp.int32(priority),
                )
        self._inflight[shard].add(sid)
        self._pending.add(sid)
        self._seen_done.discard(sid)
        return shard

    def _drain_overflow(self) -> int:
        """Move overflowed arrivals into shard rings while capacity lasts."""
        moved = 0
        while self.overflow:
            sid, by, bu, prio = self.overflow[0]
            if self._enqueue_device(sid, by, bu, prio) is None:
                break
            self.overflow.popleft()
            moved += 1
        return moved

    def _queue_pop(self) -> tuple[int, np.ndarray, np.ndarray, int]:
        """Pop the host queue entry with the highest tier (FIFO within a
        tier): the host-plane mirror of the device queue's priority-composed
        sort key. ``max`` keeps the first index on ties, which IS the FIFO
        order — all-default-tier traffic reduces to ``popleft``."""
        best = max(range(len(self.queue)), key=lambda i: self.queue[i][3])
        entry = self.queue[best]
        del self.queue[best]
        return entry

    def _admit_into(self, slot: int):
        """Admit the next queued stream into ``slot`` (span ``mr.admit``), or
        mark the slot empty when nothing waits."""
        if not self.queue:
            with self._mesh_ctx():
                self.state = deactivate(self.state, jnp.int32(slot))
            if self.mesh is not None:
                # same propagation hazard as the admit path below: the
                # update mixes in replicated scalars, so re-pin the shard
                self._reshard()
            self._active_view[slot] = False
            self._slot_view[slot] = -1
            self._prio_view[slot] = 0
            return None
        with jax.profiler.TraceAnnotation("mr.admit", slot=slot) as span:
            stream_id, buf_y, buf_u, prio = self._queue_pop()
            span.set_metadata(stream_id=stream_id)
            warm_params = self._warm_get(stream_id)
            if warm_params is not None:
                params = warm_params
                opt = opt_init(params)
            else:
                params, opt = cold_start(jax.random.fold_in(self.key, 1000 + stream_id), self.cfg)
            with self._mesh_ctx():
                self.state = admit(
                    self.state,
                    jnp.int32(slot),
                    jnp.int32(stream_id),
                    jnp.asarray(buf_y),
                    jnp.asarray(buf_u),
                    params,
                    opt,
                )
            if self.mesh is not None:
                # admission mixes replicated single-slot operands into the
                # update; re-pin the slot shard so every later tick sees the
                # same layout
                self._reshard()
        self._active_view[slot] = True
        self._slot_view[slot] = int(stream_id)
        self._delta_view[slot] = np.inf
        self._loss_view[slot] = np.inf
        self._steps_view[slot] = 0
        self._prio_view[slot] = int(prio)
        return stream_id

    def _preempt_host(self):
        """Host-plane mirror of the device preemption pass: while a waiting
        arrival's tier strictly exceeds the lowest-tier COLD active slot
        (``steps < min_steps``), the victim's params go to the warm registry
        and the victim re-enters the queue with its LIVE buffers at its
        original tier, then the arrival is admitted into the freed slot.
        Warm slots (past min_steps) are never preempted — they are about to
        converge and evict on their own. Terminates: each displacement
        strictly raises the resident tier multiset. The pass runs under the
        span ``mr.preempt`` whenever an arrival waits."""
        if not self.queue:
            return
        with jax.profiler.TraceAnnotation("mr.preempt"):
            while self.queue:
                prio = max(e[3] for e in self.queue)
                cold = [
                    s
                    for s in range(self.n_slots)
                    if self._active_view[s] and self._steps_view[s] < self.scfg.min_steps
                ]
                if not cold:
                    return
                victim = min(cold, key=lambda s: (self._prio_view[s], s))
                if prio <= self._prio_view[victim]:
                    return
                vid = int(self._slot_view[victim])
                st = self.state
                self._warm_put(vid, jax.tree.map(lambda a: a[victim], st.params))
                self.queue.append(
                    (
                        vid,
                        self._host_read(st.buf_y[victim]),
                        self._host_read(st.buf_u[victim]),
                        int(self._prio_view[victim]),
                    )
                )
                # _admit_into pops by tier, so it picks the arrival we just
                # compared (the re-queued victim sits strictly below it)
                self._admit_into(victim)

    def fill_slots(self) -> list[int]:
        """Bootstrap: admit queued streams into every empty slot.

        Device control plane: one ``pump`` program drains the on-device rings
        into every idle slot, then a snapshot refreshes the host views (both
        under the span ``mr.pump``).
        """
        if self.control_plane is not None:
            self._drain_overflow()
            before = {int(i) for i in self._slot_view if i >= 0}
            with jax.profiler.TraceAnnotation("mr.pump"):
                with self._mesh_ctx():
                    self.state, self.control, status = self.control_plane.pump(
                        self.state, self.control
                    )
                self._snapshot(status)
            return [int(i) for i in self._slot_view if i >= 0 and int(i) not in before]
        admitted = []
        active = self._host_read(self.state.active)
        self._active_view = np.asarray(active, bool).copy()
        for s in range(self.n_slots):
            if not active[s] and self.queue:
                sid = self._admit_into(s)
                if sid is not None:
                    admitted.append(sid)
        return admitted

    # -- the tick loop ------------------------------------------------------
    def slot_streams(self) -> list[int]:
        """stream_id per slot (-1 = empty); the driver feeds chunks by this.

        Host path: a per-call device readback (the reference data router).
        Device path: the cached snapshot view — no readback; between
        snapshots the map is as fresh as the last snapshot tick.
        """
        if self.control_plane is not None:
            return [int(i) for i in self._slot_view]
        return [int(i) for i in self._host_read(self.state.stream_id)]

    def _evict(self, slot: int, reason: str) -> StreamResult:
        """Read ``slot``'s result back and keep its params warm (span
        ``mr.evict``, metadata from the host's slot view): one ``slot_result``
        program and one host read; the params stay on the device."""
        st = self.state
        with jax.profiler.TraceAnnotation(
            "mr.evict", stream_id=int(self._slot_view[slot]), slot=slot
        ):
            with self._mesh_ctx():
                fields, slot_params = slot_result(st, jnp.int32(slot))
            if self.quant:
                yw, uw = _slot_windows(
                    st.buf_y[slot], st.buf_u[slot], fields["mean"], fields["scale"], self.scfg
                )
                fields["theta"] = readout_theta(slot_params, self.cfg, yw, uw, quant=True)
            got = self._host_read(fields)
            sid = int(got["stream_id"])
            res = StreamResult(
                stream_id=sid,
                theta=got["theta"],
                mean=got["mean"],
                scale=got["scale"],
                steps=int(got["steps"]),
                reason=reason,
            )
            self.results[sid] = res
            self._undrained.append(res)
            self._warm_put(sid, slot_params)
        return res

    def _snapshot(self, status) -> list[StreamResult]:
        """Device control plane: refresh the host views from the packed
        [S, 5] status and drain the on-device event log into StreamResults.

        The ONLY device->host readbacks on the device path happen here — two
        per snapshot (status + event log), every ``snapshot_period`` ticks —
        all under the span ``mr.snapshot``.
        """
        from repro.core import control as control_mod

        with jax.profiler.TraceAnnotation("mr.snapshot"):
            cp = self.control_plane
            prev_slots = self._slot_view.copy()
            snap = self._host_read(status)
            self._delta_view = snap[:, 0].copy()
            self._loss_view = snap[:, 1].copy()
            self._steps_view = snap[:, 2].astype(np.int64)
            self._active_view = snap[:, 3] > 0
            self._slot_view = snap[:, 4].astype(np.int64)
            for s in range(self.n_slots):
                sid = int(self._slot_view[s])
                self._prio_view[s] = self._prio_of.get(sid, 0) if sid >= 0 else 0
            with self._mesh_ctx():
                self.control, events = cp.drain(self.control)
            new_results = []
            for sid, steps, code, theta, mean, scale in control_mod.decode_events(
                self._host_read(events), self.cfg
            ):
                res = StreamResult(
                    stream_id=sid,
                    theta=theta,
                    mean=mean,
                    scale=scale,
                    steps=steps,
                    reason="converged" if code == 1 else "budget",
                )
                self.results[sid] = res
                self._undrained.append(res)
                self._pending.discard(sid)
                self._seen_done.add(sid)
                new_results.append(res)
            # an enqueued id leaves its shard's in-flight set once the snapshot
            # shows it admitted (slot view) or already completed (event log); an
            # id that WAS resident and is now neither resident nor completed was
            # preempted back into its shard's queue — re-count it in-flight so
            # the host-side occupancy bound stays conservative
            resident = {int(i) for i in self._slot_view if i >= 0}
            slots_per_shard = self.n_slots // cp.shards
            for s in range(self.n_slots):
                sid = int(prev_slots[s])
                if sid >= 0 and sid not in resident and sid not in self._seen_done:
                    self._inflight[s // slots_per_shard].add(sid)
            settled = resident | self._seen_done
            for shard_ids in self._inflight:
                shard_ids.difference_update(settled)
            self._ticks_since_snapshot = 0
            self._drain_overflow()
        return new_results

    def tick_once(self, chunks_y: np.ndarray, chunks_u: np.ndarray | None = None) -> dict:
        """Advance the service one tick; returns an info dict of host scalars.

        Device control plane: ONE donated program runs tick + eviction mask +
        queue refill + warm-start gather; the host reads nothing back except
        at snapshot ticks (every ``snapshot_period``), so ``sync_log`` records
        0 for steady-state ticks. Between snapshots the info dict serves the
        cached (snapshot-stale) views.

        Profiler spans (``jax.profiler.TraceAnnotation``; they cost about a
        microsecond each when no profiler runs): ``mr.tick`` (metadata
        ``tick``, the number the info dict returns) encloses the whole tick,
        ``mr.tick.dispatch`` the launch of the compiled tick, and then each
        plane's own spans (``_host_plane_tick``, ``_device_plane_tick``).
        """
        t0 = time.perf_counter()
        syncs0 = self.counters["host_syncs"]
        if chunks_u is None:
            chunks_u = np.zeros((self.n_slots, self.scfg.chunk, self.cfg.input_dim), np.float32)
        cp = self.control_plane
        with jax.profiler.TraceAnnotation("mr.tick", tick=self.ticks + 1):
            with jax.profiler.TraceAnnotation("mr.tick.dispatch"), self._mesh_ctx():
                args = (
                    jnp.asarray(chunks_y, jnp.float32),
                    jnp.asarray(chunks_u, jnp.float32),
                    jax.random.fold_in(self.key, self.ticks),
                )
                if cp is None:
                    out = self._tick(self.state, *args)
                else:
                    self.state, self.control, out = cp.tick(self.state, self.control, *args)
            self.ticks += 1
            info = self._host_plane_tick(out) if cp is None else self._device_plane_tick(out)
            # checkpoint before closing the sync window so a snapshot tick's
            # staging readbacks land in THIS tick's sync_log delta (honest
            # per-tick attribution; period=0 keeps steady state untouched)
            if self.checkpointer is not None:
                self.checkpointer.after_tick(self)
        self.tick_ms.append((time.perf_counter() - t0) * 1e3)
        self.sync_log.append(self.counters["host_syncs"] - syncs0)
        return info

    def _device_plane_tick(self, status) -> dict:
        """After ``control.tick_device``: a snapshot every ``snapshot_period``
        ticks (span ``mr.snapshot``), else the cached views."""
        self._ticks_since_snapshot += 1
        evicted: list[StreamResult] = []
        if self._ticks_since_snapshot >= self.control_plane.snapshot_period:
            evicted = self._snapshot(status)
        return {
            "tick": self.ticks,
            "evicted": evicted,
            "active": int(self._active_view.sum()),
            "delta": self._delta_view,
            "loss": self._loss_view,
            "steps": self._steps_view,
        }

    def _host_plane_tick(self, out) -> dict:
        """After the compiled tick on the host plane: read the per-slot status
        back (span ``mr.tick.readback``), then evict, refill and preempt
        (span ``mr.tick.scan``, parent of ``mr.evict`` / ``mr.admit`` /
        ``mr.preempt``)."""
        # kernel-path-aware sync accounting: the banked tick returns (state,
        # status) with every per-slot scalar packed into ONE array, so the
        # whole eviction scan costs a single host readback; the composite
        # tick reads each SlotState leaf separately (the 5.17-syncs/tick
        # baseline of the ROADMAP device-resident-control-plane item).
        banked = not isinstance(out, SlotState)
        loss = None
        with jax.profiler.TraceAnnotation("mr.tick.readback"):
            if banked:
                self.state, status = out
                snap = self._host_read(status)
                delta, loss = snap[:, 0], snap[:, 1]
                steps, active = snap[:, 2].astype(np.int64), snap[:, 3] > 0
            else:
                self.state = out
                delta = self._host_read(self.state.delta)
                steps = self._host_read(self.state.steps)
                active = self._host_read(self.state.active)
        self._active_view = np.asarray(active, bool).copy()
        self._delta_view = np.asarray(delta).copy()
        if banked:
            self._loss_view = np.asarray(loss).copy()
        self._steps_view = np.asarray(steps, np.int64)
        evicted = []
        with jax.profiler.TraceAnnotation("mr.tick.scan"):
            for s in range(self.n_slots):
                if not active[s]:
                    continue
                converged = steps[s] >= self.scfg.min_steps and delta[s] <= self.scfg.delta_tol
                budget = steps[s] >= self.scfg.max_steps
                if converged or budget:
                    res = self._evict(s, "converged" if converged else "budget")
                    evicted.append(res)
                    self._admit_into(s)
            # under pressure a higher-tier waiting arrival may displace a cold
            # lower-tier slot (the host mirror of the device preemption pass)
            self._preempt_host()
        # eviction/admission updated the cached view in place, so the active
        # count never needs a second device readback (the polling-side fix:
        # `done` and `drain()` read the same host-side view)
        if not banked:
            with jax.profiler.TraceAnnotation("mr.tick.readback"):
                self._loss_view = np.array(self._host_read(self.state.loss))
        return {
            "tick": self.ticks,
            "evicted": evicted,
            "active": int(self._active_view.sum()),
            "delta": delta,
            "loss": self._loss_view,
            "steps": steps,
        }

    def drain(self) -> list[StreamResult]:
        """Completed-stream results accumulated since the last drain.

        Pure host-side bookkeeping (results land here at eviction on the host
        path, at snapshot ticks on the device path) — polling it never costs
        a device readback.
        """
        out, self._undrained = self._undrained, []
        return out

    @property
    def done(self) -> bool:
        """True when no stream is queued, running or awaiting a result.

        Served from the cached status views (host path) or the pending set
        (device path) — polling `done` in a serve loop is readback-free; it
        used to force a `_host_read(state.active)` per call.
        """
        if self.control_plane is not None:
            return not self._pending
        return not self.queue and not bool(self._active_view.any())
