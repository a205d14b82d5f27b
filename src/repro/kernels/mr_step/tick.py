"""Banked one-kernel service tick: the ``mr_tick`` kernel family.

The composite service tick (core/stream.tick) executes the serving side of a
tick as a sequence of XLA ops — ring-buffer roll, per-slot window gather +
normalization, the per-window encoder scan, the head readout, the EMA/delta
update — with the intermediate tensors round-tripping HBM between stages.
``mr_tick`` is the paper's banked-BRAM dataflow applied one level above the
per-window step: ONE ``pallas_call`` whose grid banks the S service slots
(``slots_per_bank`` slots per grid step, kernels/mr_step/tiling.py sizes the
bank against ``detect_vmem_budget``) and whose body runs, per bank,

  1. ring-buffer window ingest  — the roll (drop the oldest ``chunk`` rows,
     append the tick's chunk), the frozen-at-admission normalization and the
     static window slicing happen in-kernel; the rolled buffer is written
     back as a kernel output, so buffer maintenance and readout share one
     program;
  2. K unrolled recovery substeps — the T encoder gate updates of every
     window run as a static unroll over the VMEM-resident hidden state
     (``_gru_step_math``, the exact math of the fused per-window step);
  3. the EMA Theta readout      — head MLP, mean over windows, EMA blend
     with the previous readout (first-tick seeding included) and the
     relative coefficient delta the eviction policy watches.

Because every input block is indexed by the bank grid axis, Mosaic
double-buffers the streamed blocks automatically: bank ``i+1``'s window
buffer and weights DMA into VMEM while bank ``i`` computes — the ping-pong
window DMA of the paper's streaming pipeline, with no hand-written
semaphores. The tick is serve-only (the K optimizer steps of a training
tick stay in the XLA train scan, core/stream.tick_banked), so no
``custom_vjp`` is needed.

Variants: fp32 GRU(-flow) (``mr_tick_pallas``) and the int8/PWL serving
twin (``mr_tick_pallas_int8``: int8 gate + head weights with per-slot
per-channel scales, PWL sigmoid/tanh — standard GRU cell only, matching
``mr_step_pallas_int8``). ``mr_tick`` is the dispatch wrapper (compiled
kernel on TPU, interpret for CPU correctness sweeps, the ``ref.py`` oracle
otherwise); the oracle delegates to the existing ingest/step/readout
composition (data/windows.py + ``mr_step_reference``).

Control-plane composition contract (core/control.tick_device): under
``TickSpec(control="device")`` the banked tick body runs INSIDE the
device-resident control-plane program — the kernel's packed ``[S, 4]``
status block feeds the in-program eviction mask, queue refill and
warm-start push directly, with no intermediate host readback. The kernel
therefore must stay (a) shape-stable in the slot axis (eviction/refill
rewrite slot rows in place, never resize), (b) collective-free when the
slot axis is sharded (rules.predict_tick_collectives stays empty — audit
rule R5 covers the composed program), and (c) side-effect-free beyond its
declared outputs, so the surrounding program's donation of SlotState and
ControlState holds (audit rule R1)."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core import encoders
from repro.core.quant import make_sigmoid_table, make_tanh_table, quantize_int8
from repro.data.windows import roll_buffer
from repro.kernels import runtime as rt
from repro.kernels.gru_scan.kernel import _gru_q_step_math, _gru_step_math
from repro.kernels.mr_step import ref as _ref
from repro.kernels.mr_step.kernel import _head_math
from repro.kernels.mr_step.ops import _head_weights


def tick_supported(cfg, *, int8: bool = False) -> bool:
    """True when the banked tick kernel implements ``cfg``'s encoder cell.

    v1 banks the GRU(-flow) families (single gated update per window step);
    the multi-substep cells (ltc/node) stay on the composite tick —
    ``compile_plan`` resolves ``tick_kernel="auto"`` through this predicate.
    The int8 twin additionally needs the PWL cell mapping (standard GRU).
    """
    spec = encoders.get_encoder(cfg.encoder)
    if spec.family != "gru":
        return False
    return bool(spec.int8) if int8 else True


# ---------------------------------------------------------------------------
# shared bank body pieces
# ---------------------------------------------------------------------------
# Layout: every per-slot operand carries the slot axis first, and the
# per-slot vectors (stats, biases, scales, Theta, flags) are laid out
# [S, 1, X], so each block's last two dims are the full array dims for ANY
# bank size (Mosaic's block rule) and ``ref[s]`` reads one slot's row as a
# (1, X) tile. Outputs are stored as whole tiles — never scalars.
def _ingest(buf, new, out):
    """Ring-buffer roll of the whole bank into ``out``: drop the oldest
    ``chunk`` rows, append the tick's chunk."""
    L, chunk = buf.shape[1], new.shape[1]
    out[:, : L - chunk, :] = buf[:, chunk:, :]
    out[:, L - chunk :, :] = new[...]


def _load_windows(x_scr, buf_y_out, buf_u_out, mean, scale, s, n):
    """Slot ``s``'s normalized input rows (frozen admission stats) into the
    [L, D] scratch the window substeps read with strided loads."""
    x_scr[:, :n] = (buf_y_out[s] - mean[s]) / scale[s]
    if buf_u_out is not None:
        x_scr[:, n:] = buf_u_out[s]


def _readout(out, theta0, seed, active, theta_out, delta_out, s, *, n_coef, ema):
    """EMA Theta readout + relative delta (the eviction signal) for slot s."""
    raw = jnp.mean(out[:, :n_coef], axis=0, keepdims=True)
    prev = theta0[s]
    theta = jnp.where(seed[s] > 0, raw, ema * prev + (1.0 - ema) * raw)
    change = jnp.max(jnp.abs(theta - prev), axis=1, keepdims=True)
    delta = change / (jnp.max(jnp.abs(theta), axis=1, keepdims=True) + 1e-3)
    theta_out[s] = theta
    delta_out[s] = jnp.where(active[s] > 0, delta, jnp.inf)


def _bank_specs(bank: int):
    def blk(*shape):
        return ((bank, *shape), lambda ib: (ib,) + (0,) * len(shape))

    return blk


def _rows(x):
    """[S, X] -> [S, 1, X]: the per-slot vector layout of the bank blocks."""
    return x.reshape(x.shape[0], 1, -1)


# ---------------------------------------------------------------------------
# fp32 banked tick kernel
# ---------------------------------------------------------------------------
def _mr_tick_kernel(
    *refs,
    bank: int,
    window: int,
    stride: int,
    n_windows: int,
    n_coef: int,
    flow: bool,
    hidden: int,
    ema: float,
    has_u: bool,
):
    """One grid step = one bank of ``bank`` slots, ingest through readout."""
    (buf_y, new_y, mean, scale, theta0, seed, active, wx, wh, b, ts, w1, b1, w2, b2) = refs[:15]
    i = 15
    if has_u:
        buf_u, new_u = refs[i], refs[i + 1]
        i += 2
    buf_y_out, theta_out, delta_out = refs[i], refs[i + 1], refs[i + 2]
    buf_u_out = refs[i + 3] if has_u else None
    x_scr = refs[-1]

    # 1. ring-buffer window ingest: roll in-kernel, write the buffer back
    _ingest(buf_y, new_y, buf_y_out)
    if has_u:
        _ingest(buf_u, new_u, buf_u_out)

    n = buf_y.shape[-1]

    @pl.loop(0, bank)  # the bank's slots share the VMEM stay, one at a time
    def _(s):
        _load_windows(x_scr, buf_y_out, buf_u_out, mean, scale, s, n)
        # 2. K unrolled recovery substeps over the VMEM-resident hidden state;
        # step t of every window is one strided row load (data/windows
        # semantics: window w starts at row w * stride)
        h = jnp.zeros((n_windows, hidden), jnp.float32)
        for t in range(window):
            h = _gru_step_math(
                x_scr[pl.ds(t, n_windows, stride=stride), :],
                h,
                wx[s],
                wh[s],
                b[s, 0],
                ts[s, 0],
                jnp.float32(1.0),
                flow=flow,
                hidden=hidden,
            )
        # 3. EMA Theta readout + relative delta (the eviction signal)
        out = _head_math(h, w1[s], b1[s, 0], w2[s], b2[s, 0], None)
        _readout(out, theta0, seed, active, theta_out, delta_out, s, n_coef=n_coef, ema=ema)


@functools.partial(
    jax.jit, static_argnames=("flow", "window", "stride", "ema", "slots_per_bank", "interpret")
)
def mr_tick_pallas(
    buf_y: jnp.ndarray,  # [S, L, n] pre-roll ring buffers
    new_y: jnp.ndarray,  # [S, C, n] this tick's chunk
    mean: jnp.ndarray,  # [S, n] frozen admission stats
    scale: jnp.ndarray,  # [S, n]
    theta0: jnp.ndarray,  # [S, Kc] previous EMA readout (flattened)
    seed: jnp.ndarray,  # [S, 1] f32, 1.0 = seed the EMA this tick
    active: jnp.ndarray,  # [S, 1] f32
    wx: jnp.ndarray,  # [S, D, 3H] per-slot gate weights
    wh: jnp.ndarray,  # [S, H, 3H]
    b: jnp.ndarray,  # [S, 3H]
    time_scale: jnp.ndarray,  # [S, H]
    w1: jnp.ndarray,  # [S, H, Dh] per-slot head weights
    b1: jnp.ndarray,  # [S, Dh]
    w2: jnp.ndarray,  # [S, Dh, Ko]
    b2: jnp.ndarray,  # [S, Ko]
    buf_u: jnp.ndarray | None = None,  # [S, L, m] when m > 0
    new_u: jnp.ndarray | None = None,  # [S, C, m]
    *,
    flow: bool,
    window: int,
    stride: int,
    ema: float,
    slots_per_bank: int = 1,
    interpret: bool = False,
):
    """Banked tick. Returns (buf_y, theta [S, Kc], delta [S, 1][, buf_u])."""
    S, L, n = buf_y.shape
    C = new_y.shape[1]
    H = wh.shape[1]
    Dh = w1.shape[-1]
    Ko = w2.shape[-1]
    Kc = theta0.shape[-1]
    D = wx.shape[1]
    N = (L - window) // stride + 1
    bank = slots_per_bank
    assert S % bank == 0, f"{S} slots not divisible by slots_per_bank {bank}"
    has_u = buf_u is not None
    blk = _bank_specs(bank)

    in_specs = [
        blk(L, n),  # buf_y: streamed per bank (Mosaic ping-pongs the DMA)
        blk(C, n),  # new_y
        blk(1, n),  # mean
        blk(1, n),  # scale
        blk(1, Kc),  # theta0
        blk(1, 1),  # seed
        blk(1, 1),  # active
        blk(D, 3 * H),  # wx: the bank's slots resident together
        blk(H, 3 * H),  # wh
        blk(1, 3 * H),  # b
        blk(1, H),  # time_scale
        blk(H, Dh),  # head w1
        blk(1, Dh),  # head b1
        blk(Dh, Ko),  # head w2
        blk(1, Ko),  # head b2
    ]
    operands = [buf_y, new_y, _rows(mean), _rows(scale), _rows(theta0), _rows(seed)]
    operands += [_rows(active), wx, wh, _rows(b), _rows(time_scale)]
    operands += [w1, _rows(b1), w2, _rows(b2)]
    out_specs = [blk(L, n), blk(1, Kc), blk(1, 1)]
    out_shape = [
        jax.ShapeDtypeStruct((S, L, n), jnp.float32),
        jax.ShapeDtypeStruct((S, 1, Kc), jnp.float32),
        jax.ShapeDtypeStruct((S, 1, 1), jnp.float32),
    ]
    if has_u:
        m = buf_u.shape[-1]
        in_specs += [blk(L, m), blk(C, m)]
        operands += [buf_u, new_u]
        out_specs.append(blk(L, m))
        out_shape.append(jax.ShapeDtypeStruct((S, L, m), jnp.float32))

    kernel = functools.partial(
        _mr_tick_kernel,
        bank=bank,
        window=window,
        stride=stride,
        n_windows=N,
        n_coef=Kc,
        flow=flow,
        hidden=H,
        ema=ema,
        has_u=has_u,
    )
    out = rt.pallas_call(
        kernel,
        grid=(S // bank,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[((L, D), jnp.float32)],
        dimension_semantics=(rt.PARALLEL,),
        interpret=interpret,
        name="mr_tick_banked",
    )(*operands)
    return (out[0], out[1].reshape(S, Kc), out[2].reshape(S, 1), *out[3:])


# ---------------------------------------------------------------------------
# int8 + PWL serving twin (standard GRU cell)
# ---------------------------------------------------------------------------
def _mr_tick_q_kernel(
    *refs,
    bank: int,
    window: int,
    stride: int,
    n_windows: int,
    n_coef: int,
    hidden: int,
    ema: float,
    n_seg: int,
    has_u: bool,
):
    (buf_y, new_y, mean, scale, theta0, seed, active) = refs[:7]
    (wxq, whq, wx_scale, wh_scale, b, sig_tab, tanh_tab) = refs[7:14]
    (w1q, w1_scale, b1, w2q, w2_scale, b2) = refs[14:20]
    i = 20
    if has_u:
        buf_u, new_u = refs[i], refs[i + 1]
        i += 2
    buf_y_out, theta_out, delta_out = refs[i], refs[i + 1], refs[i + 2]
    buf_u_out = refs[i + 3] if has_u else None
    x_scr = refs[-1]

    _ingest(buf_y, new_y, buf_y_out)
    if has_u:
        _ingest(buf_u, new_u, buf_u_out)

    f32 = jnp.float32
    n = buf_y.shape[-1]

    @pl.loop(0, bank)
    def _(s):
        _load_windows(x_scr, buf_y_out, buf_u_out, mean, scale, s, n)
        h = jnp.zeros((n_windows, hidden), f32)
        for t in range(window):
            h = _gru_q_step_math(
                x_scr[pl.ds(t, n_windows, stride=stride), :],
                h,
                wxq[s],
                whq[s],
                wx_scale[s, 0],
                wh_scale[s, 0],
                b[s, 0],
                sig_tab[...],
                tanh_tab[...],
                hidden=hidden,
                n_seg=n_seg,
            )
        w1 = w1q[s].astype(f32) * w1_scale[s, 0]
        w2 = w2q[s].astype(f32) * w2_scale[s, 0]
        out = _head_math(h, w1, b1[s, 0], w2, b2[s, 0], None)
        _readout(out, theta0, seed, active, theta_out, delta_out, s, n_coef=n_coef, ema=ema)


@functools.partial(
    jax.jit, static_argnames=("window", "stride", "ema", "slots_per_bank", "interpret", "n_seg")
)
def mr_tick_pallas_int8(
    buf_y: jnp.ndarray,
    new_y: jnp.ndarray,
    mean: jnp.ndarray,
    scale: jnp.ndarray,
    theta0: jnp.ndarray,
    seed: jnp.ndarray,
    active: jnp.ndarray,
    wxq: jnp.ndarray,  # int8 [S, D, 3H]
    whq: jnp.ndarray,  # int8 [S, H, 3H]
    wx_scale: jnp.ndarray,  # [S, 3H] per-slot per-channel scales
    wh_scale: jnp.ndarray,  # [S, 3H]
    b: jnp.ndarray,  # [S, 3H]
    sig_tab: jnp.ndarray,  # [2, n_seg] shared PWL tables
    tanh_tab: jnp.ndarray,  # [2, n_seg]
    w1q: jnp.ndarray,  # int8 [S, H, Dh]
    w1_scale: jnp.ndarray,  # [S, Dh]
    b1: jnp.ndarray,  # [S, Dh]
    w2q: jnp.ndarray,  # int8 [S, Dh, Ko]
    w2_scale: jnp.ndarray,  # [S, Ko]
    b2: jnp.ndarray,  # [S, Ko]
    buf_u: jnp.ndarray | None = None,
    new_u: jnp.ndarray | None = None,
    *,
    window: int,
    stride: int,
    ema: float,
    slots_per_bank: int = 1,
    interpret: bool = False,
    n_seg: int = 16,
):
    S, L, n = buf_y.shape
    C = new_y.shape[1]
    H = whq.shape[1]
    Dh = w1q.shape[-1]
    Ko = w2q.shape[-1]
    Kc = theta0.shape[-1]
    D = wxq.shape[1]
    N = (L - window) // stride + 1
    bank = slots_per_bank
    assert S % bank == 0, f"{S} slots not divisible by slots_per_bank {bank}"
    has_u = buf_u is not None
    blk = _bank_specs(bank)

    tab = ((2, n_seg), lambda ib: (0, 0))
    in_specs = [blk(L, n), blk(C, n), blk(1, n), blk(1, n), blk(1, Kc), blk(1, 1), blk(1, 1)]
    in_specs += [blk(D, 3 * H), blk(H, 3 * H), blk(1, 3 * H), blk(1, 3 * H), blk(1, 3 * H)]
    in_specs += [tab, tab, blk(H, Dh), blk(1, Dh), blk(1, Dh), blk(Dh, Ko), blk(1, Ko), blk(1, Ko)]
    operands = [buf_y, new_y, _rows(mean), _rows(scale), _rows(theta0), _rows(seed)]
    operands += [_rows(active), wxq, whq, _rows(wx_scale), _rows(wh_scale), _rows(b)]
    operands += [sig_tab, tanh_tab, w1q, _rows(w1_scale), _rows(b1), w2q, _rows(w2_scale)]
    operands += [_rows(b2)]
    out_specs = [blk(L, n), blk(1, Kc), blk(1, 1)]
    out_shape = [
        jax.ShapeDtypeStruct((S, L, n), jnp.float32),
        jax.ShapeDtypeStruct((S, 1, Kc), jnp.float32),
        jax.ShapeDtypeStruct((S, 1, 1), jnp.float32),
    ]
    if has_u:
        m = buf_u.shape[-1]
        in_specs += [blk(L, m), blk(C, m)]
        operands += [buf_u, new_u]
        out_specs.append(blk(L, m))
        out_shape.append(jax.ShapeDtypeStruct((S, L, m), jnp.float32))

    kernel = functools.partial(
        _mr_tick_q_kernel,
        bank=bank,
        window=window,
        stride=stride,
        n_windows=N,
        n_coef=Kc,
        hidden=H,
        ema=ema,
        n_seg=n_seg,
        has_u=has_u,
    )
    out = rt.pallas_call(
        kernel,
        grid=(S // bank,),
        in_specs=in_specs,
        out_specs=out_specs,
        out_shape=out_shape,
        scratch_shapes=[((L, D), jnp.float32)],
        dimension_semantics=(rt.PARALLEL,),
        interpret=interpret,
        name="mr_tick_banked_int8_pwl",
    )(*operands)
    return (out[0], out[1].reshape(S, Kc), out[2].reshape(S, 1), *out[3:])


# ---------------------------------------------------------------------------
# dispatch wrapper
# ---------------------------------------------------------------------------
def mr_tick(
    params,  # slot-stacked MRParams (every leaf has leading axis S)
    cfg,  # merinda.MRConfig (GRU-family encoder)
    scfg,  # stream.StreamConfig (window/stride/chunk/ema geometry)
    buf_y: jnp.ndarray,  # [S, L, n] pre-roll buffers
    buf_u: jnp.ndarray,  # [S, L, m] (m may be 0)
    new_y: jnp.ndarray,  # [S, C, n]
    new_u: jnp.ndarray,  # [S, C, m]
    mean: jnp.ndarray,  # [S, n]
    scale: jnp.ndarray,  # [S, n]
    theta_prev: jnp.ndarray,  # [S, n_terms, n] previous EMA readout
    seed: jnp.ndarray,  # [S] bool: seed the EMA this tick
    active: jnp.ndarray,  # [S] bool
    *,
    quant: bool = False,
    slots_per_bank: int = 1,
    n_seg: int = 16,
    force_reference: bool = False,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray, jnp.ndarray, jnp.ndarray]:
    """One-kernel serve tick: ingest + window substeps + EMA readout + delta.

    Returns ``(buf_y, buf_u, theta [S, n_terms, n], delta [S])`` — the rolled
    buffers and the post-EMA readout/eviction signal, all produced by one
    banked program. ``quant=True`` serves through the int8/PWL twin.
    Backend policy matches ops.mr_step: Pallas kernel on TPU, interpret for
    CPU correctness sweeps, the ref.py oracle otherwise.
    """
    spec = encoders.get_encoder(cfg.encoder)
    if not tick_supported(cfg, int8=quant):
        raise ValueError(
            f"mr_tick banks the GRU families only (int8 twin: standard 'gru' cell); "
            f"got encoder={cfg.encoder!r} quant={quant} — use the composite tick"
        )
    S = buf_y.shape[0]
    d_in = cfg.state_dim + cfg.input_dim
    theta0 = theta_prev.reshape(S, cfg.n_coef)
    has_u = cfg.input_dim > 0
    disp = rt.resolve_dispatch(force_reference, interpret)
    interp = disp is rt.Dispatch.INTERPRET
    u_args = (buf_u, new_u) if has_u else (None, None)
    kw = dict(window=scfg.window, stride=scfg.stride, ema=scfg.ema)

    if quant:
        wxq = jax.vmap(lambda w: quantize_int8(w, axis=-1))(params.encoder.w[:, :d_in])
        whq = jax.vmap(lambda w: quantize_int8(w, axis=-1))(params.encoder.w[:, d_in:])
        w1q = jax.vmap(lambda w: quantize_int8(w, axis=-1))(params.head_w1)
        w2q = jax.vmap(lambda w: quantize_int8(w, axis=-1))(params.head_w2)
        sig_t, tanh_t = make_sigmoid_table(n_seg), make_tanh_table(n_seg)
        if disp is rt.Dispatch.REFERENCE:
            out = _ref.mr_tick_int8_reference(
                buf_y,
                new_y,
                mean,
                scale,
                theta0,
                seed,
                active,
                wxq.values,
                whq.values,
                wxq.scale,
                whq.scale,
                params.encoder.b,
                w1q.values,
                w1q.scale,
                params.head_b1,
                w2q.values,
                w2q.scale,
                params.head_b2,
                sig_t,
                tanh_t,
                *u_args,
                **kw,
            )
        else:
            out = mr_tick_pallas_int8(
                buf_y,
                new_y,
                mean,
                scale,
                theta0,
                seed.astype(jnp.float32).reshape(S, 1),
                active.astype(jnp.float32).reshape(S, 1),
                wxq.values,
                whq.values,
                wxq.scale.reshape(S, -1),
                whq.scale.reshape(S, -1),
                params.encoder.b,
                jnp.stack([sig_t.slopes, sig_t.intercepts]),
                jnp.stack([tanh_t.slopes, tanh_t.intercepts]),
                w1q.values,
                w1q.scale.reshape(S, -1),
                params.head_b1,
                w2q.values,
                w2q.scale.reshape(S, -1),
                params.head_b2,
                *u_args,
                slots_per_bank=slots_per_bank,
                interpret=interp,
                n_seg=n_seg,
                **kw,
            )
    else:
        enc = encoders.quantized_gru_params(params.encoder, cfg)
        wx, wh = enc.w[:, :d_in], enc.w[:, d_in:]
        w1, b1, w2, b2 = _head_weights(params, cfg)
        if disp is rt.Dispatch.REFERENCE:
            out = _ref.mr_tick_reference(
                buf_y,
                new_y,
                mean,
                scale,
                theta0,
                seed,
                active,
                wx,
                wh,
                enc.b,
                enc.time_scale,
                w1,
                b1,
                w2,
                b2,
                *u_args,
                flow=spec.flow,
                **kw,
            )
        else:
            out = mr_tick_pallas(
                buf_y,
                new_y,
                mean,
                scale,
                theta0,
                seed.astype(jnp.float32).reshape(S, 1),
                active.astype(jnp.float32).reshape(S, 1),
                wx,
                wh,
                enc.b,
                enc.time_scale,
                w1,
                b1,
                w2,
                b2,
                *u_args,
                flow=spec.flow,
                slots_per_bank=slots_per_bank,
                interpret=interp,
                **kw,
            )

    buf_y2, theta_flat, delta = out[0], out[1], out[2]
    buf_u2 = out[3] if has_u else roll_buffer(buf_u, new_u)
    theta = theta_flat.reshape(S, cfg.n_terms, cfg.state_dim)
    return buf_y2, buf_u2, theta, delta.reshape(S)
