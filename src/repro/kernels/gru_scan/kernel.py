"""Fused GRU(-flow) sequence-scan Pallas kernel — the MERINDA core kernel.

TPU re-derivation of the paper's FPGA dataflow (§5):

  FPGA mechanism                      ->  this kernel
  -------------------------------------   -----------------------------------
  one setup, then continuous streaming ->  ONE pallas_call per sequence;
  (no per-step kernel launches)            grid = (batch_tiles, T); zero
                                           per-step dispatch overhead
  BRAM-resident weights, banked for    ->  gate weights live in VMEM for the
  per-cycle operand supply                 whole scan (BlockSpec index map is
                                           constant in t); the three gate
                                           affines are FUSED into one wide
                                           [D,3H] / [H,2H] GEMM pair so each
                                           MXU pass streams full tiles
  DATAFLOW stage overlap (II ~= 1)     ->  sequential grid over t: Mosaic
                                           double-buffers the x_t DMA against
                                           the step-(t-1) MXU compute
  LUT sigmoid/tanh                     ->  VPU transcendentals (float path) or
                                           unrolled piecewise-linear segments
                                           (int8/PWL path, quant variant)
  hidden state held on-chip           ->   h carried in a VMEM scratch across
                                           grid steps (never round-trips HBM)

Layouts: the wrappers take batch-major xs [B, T, D] and hand the kernel a
time-major view [T, B, D], so each grid step streams one ``(bb, D)`` x_t
tile (the time dim squeezed) and writes one ``(bb, H)`` h_t tile of the
time-major output — blocks whose last two dims are the full array dims or
(8, 128)-aligned, as Mosaic requires. Per-step dts live in SMEM. The grid
iterates batch tiles in the OUTER dimension so each tile completes its full
time scan with the same scratch buffer (t==0 re-initializes from h0).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.neural_flow import INV_LIPSCHITZ_ALPHA
from repro.kernels import runtime as rt


def _gru_step_math(x, h, wx, wh, b, time_scale, dt, *, flow: bool, hidden: int):
    """Shared step math (f32 accumulation). x:[bb,D] h:[bb,H] -> new h."""
    f32 = jnp.float32
    gx = jax.lax.dot_general(  # fused input affine for all three gates
        x, wx, (((1,), (0,)), ((), ())), preferred_element_type=f32
    )  # [bb, 3H]
    gh = jax.lax.dot_general(  # fused recurrent affine for r,z
        h, wh[:, : 2 * hidden], (((1,), (0,)), ((), ())), preferred_element_type=f32
    )  # [bb, 2H]
    r = jax.nn.sigmoid(gx[:, :hidden] + gh[:, :hidden] + b[:hidden])
    z = jax.nn.sigmoid(gx[:, hidden : 2 * hidden] + gh[:, hidden:] + b[hidden : 2 * hidden])
    ch = jax.lax.dot_general(
        (r * h).astype(wh.dtype),
        wh[:, 2 * hidden :],
        (((1,), (0,)), ((), ())),
        preferred_element_type=f32,
    )
    c = jnp.tanh(gx[:, 2 * hidden :] + ch + b[2 * hidden :])
    if flow:
        phi = jnp.tanh(jax.nn.softplus(time_scale) * dt)  # phi(0)=0 flow gate
        return h + phi * INV_LIPSCHITZ_ALPHA * (1.0 - z) * (c - h)
    return (1.0 - z) * c + z * h


def _gru_scan_kernel(
    # inputs
    xs_ref,  # [bb, D]      x_t tile (time-major stream, double-buffered by Mosaic)
    h0_ref,  # [bb, H]
    wx_ref,  # [D, 3H]      VMEM-resident across the whole scan
    wh_ref,  # [H, 3H]
    b_ref,  # [1, 3H]
    ts_ref,  # [1, H]       time-gate log-scales
    dts_ref,  # SMEM [T, 1] per-step dt
    # outputs
    hs_ref,  # [bb, H]      h_t tile of the time-major output
    # scratch
    h_scr,  # VMEM [bb, H] f32 — the on-chip hidden state ("BRAM" analogue)
    *,
    flow: bool,
    hidden: int,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    x = xs_ref[...]
    h = h_scr[...]
    h_new = _gru_step_math(
        x,
        h,
        wx_ref[...],
        wh_ref[...],
        b_ref[0, :],
        ts_ref[0, :],
        dts_ref[t, 0],
        flow=flow,
        hidden=hidden,
    )
    h_scr[...] = h_new
    hs_ref[...] = h_new.astype(hs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("flow", "block_b", "interpret"))
def gru_scan_pallas(
    xs: jnp.ndarray,  # [B, T, D]
    h0: jnp.ndarray,  # [B, H]
    wx: jnp.ndarray,  # [D, 3H]
    wh: jnp.ndarray,  # [H, 3H]
    b: jnp.ndarray,  # [3H]
    time_scale: jnp.ndarray,  # [H]
    dts: jnp.ndarray,  # [T]
    flow: bool = True,
    block_b: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns hs [B, T, H]."""
    B, T, D = xs.shape
    H = h0.shape[-1]
    bb = block_b or B
    assert B % bb == 0, f"batch {B} not divisible by block_b {bb}"
    nb = B // bb

    grid = (nb, T)
    kernel = functools.partial(_gru_scan_kernel, flow=flow, hidden=H)
    out = rt.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            ((None, bb, D), lambda ib, t: (t, ib, 0)),  # xs: stream x_t (time-major)
            ((bb, H), lambda ib, t: (ib, 0)),  # h0
            ((D, 3 * H), lambda ib, t: (0, 0)),  # wx: resident
            ((H, 3 * H), lambda ib, t: (0, 0)),  # wh: resident
            ((1, 3 * H), lambda ib, t: (0, 0)),  # b
            ((1, H), lambda ib, t: (0, 0)),  # time_scale
            rt.smem_spec(),  # dts: per-step scalars
        ],
        out_specs=((None, bb, H), lambda ib, t: (t, ib, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, H), xs.dtype),
        scratch_shapes=[((bb, H), jnp.float32)],
        dimension_semantics=(rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="gru_scan",
    )(
        jnp.swapaxes(xs, 0, 1),
        h0,
        wx,
        wh,
        b.reshape(1, -1),
        time_scale.reshape(1, -1),
        dts.reshape(-1, 1),
    )
    return jnp.swapaxes(out, 0, 1)


# ---------------------------------------------------------------------------
# int8 + piecewise-linear variant — the paper's fixed-point/LUT configuration
# ---------------------------------------------------------------------------
def _pwl_eval(x, slopes, intercepts, x_min, x_max, n_seg, left, right):
    """Branch-free PWL evaluation, unrolled over segments (no gather needed —
    the segment-select chain vectorizes on the VPU; n_seg is small/static)."""
    width = (x_max - x_min) / n_seg
    idx = jnp.clip(((x - x_min) / width).astype(jnp.int32), 0, n_seg - 1)
    y = jnp.zeros_like(x)
    for s in range(n_seg):  # static unroll — becomes selects/FMAs
        y = jnp.where(idx == s, slopes[s] * x + intercepts[s], y)
    y = jnp.where(x < x_min, left, y)
    return jnp.where(x > x_max, right, y)


def _gru_q_step_math(x, h, wxq, whq, wx_scale, wh_scale, b, sig_tab, tanh_tab, *, hidden, n_seg):
    """Shared int8+PWL step math (standard GRU; f32 accumulation).

    Single source of truth for the fixed-point serving cell — used by the
    gru_scan int8 kernel below AND the fused mr_step int8 kernel
    (kernels/mr_step). Dequantizes once per step; weights stay int8 in VMEM
    (2x density vs bf16, the ap_fixed analogue). Per-output-channel scales.
    """
    f32 = jnp.float32
    wx = wxq.astype(f32) * wx_scale
    wh = whq.astype(f32) * wh_scale
    gx = jax.lax.dot_general(x, wx, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    gh = jax.lax.dot_general(
        h, wh[:, : 2 * hidden], (((1,), (0,)), ((), ())), preferred_element_type=f32
    )

    def sig(v):
        return _pwl_eval(v, sig_tab[0, :], sig_tab[1, :], -8.0, 8.0, n_seg, 0.0, 1.0)

    def tnh(v):
        return _pwl_eval(v, tanh_tab[0, :], tanh_tab[1, :], -4.0, 4.0, n_seg, -1.0, 1.0)

    r = sig(gx[:, :hidden] + gh[:, :hidden] + b[:hidden])
    z = sig(gx[:, hidden : 2 * hidden] + gh[:, hidden:] + b[hidden : 2 * hidden])
    ch = jax.lax.dot_general(
        r * h, wh[:, 2 * hidden :], (((1,), (0,)), ((), ())), preferred_element_type=f32
    )
    c = tnh(gx[:, 2 * hidden :] + ch + b[2 * hidden :])
    return (1.0 - z) * c + z * h


def _gru_scan_q_kernel(
    xs_ref,
    h0_ref,
    wxq_ref,  # int8 [D, 3H]
    whq_ref,  # int8 [H, 3H]
    wx_scale_ref,  # [1, 3H]
    wh_scale_ref,  # [1, 3H]
    b_ref,
    dts_ref,
    sig_tab_ref,  # [2, n_seg]  (slopes; intercepts)
    tanh_tab_ref,  # [2, n_seg]
    hs_ref,
    h_scr,
    *,
    hidden: int,
    n_seg: int,
):
    """Standard-GRU int8 weights + PWL activations (serving configuration)."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    h_new = _gru_q_step_math(
        xs_ref[...].astype(jnp.float32),
        h_scr[...],
        wxq_ref[...],
        whq_ref[...],
        wx_scale_ref[0, :],
        wh_scale_ref[0, :],
        b_ref[0, :],
        sig_tab_ref[...],
        tanh_tab_ref[...],
        hidden=hidden,
        n_seg=n_seg,
    )
    h_scr[...] = h_new
    hs_ref[...] = h_new.astype(hs_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret", "n_seg"))
def gru_scan_pallas_int8(
    xs: jnp.ndarray,  # [B, T, D]
    h0: jnp.ndarray,  # [B, H]
    wxq: jnp.ndarray,  # int8 [D, 3H]
    whq: jnp.ndarray,  # int8 [H, 3H]
    wx_scale: jnp.ndarray,  # [3H]
    wh_scale: jnp.ndarray,  # [3H]
    b: jnp.ndarray,  # [3H]
    dts: jnp.ndarray,  # [T]
    sig_tab: jnp.ndarray,  # [2, n_seg]
    tanh_tab: jnp.ndarray,  # [2, n_seg]
    block_b: int | None = None,
    interpret: bool = False,
    n_seg: int = 16,
) -> jnp.ndarray:
    B, T, D = xs.shape
    H = h0.shape[-1]
    bb = block_b or B
    assert B % bb == 0
    nb = B // bb
    kernel = functools.partial(_gru_scan_q_kernel, hidden=H, n_seg=n_seg)
    out = rt.pallas_call(
        kernel,
        grid=(nb, T),
        in_specs=[
            ((None, bb, D), lambda ib, t: (t, ib, 0)),
            ((bb, H), lambda ib, t: (ib, 0)),
            ((D, 3 * H), lambda ib, t: (0, 0)),
            ((H, 3 * H), lambda ib, t: (0, 0)),
            ((1, 3 * H), lambda ib, t: (0, 0)),
            ((1, 3 * H), lambda ib, t: (0, 0)),
            ((1, 3 * H), lambda ib, t: (0, 0)),
            rt.smem_spec(),
            ((2, n_seg), lambda ib, t: (0, 0)),
            ((2, n_seg), lambda ib, t: (0, 0)),
        ],
        out_specs=((None, bb, H), lambda ib, t: (t, ib, 0)),
        out_shape=jax.ShapeDtypeStruct((T, B, H), jnp.float32),
        scratch_shapes=[((bb, H), jnp.float32)],
        dimension_semantics=(rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="gru_scan_int8_pwl",
    )(
        jnp.swapaxes(xs, 0, 1),
        h0,
        wxq,
        whq,
        wx_scale.reshape(1, -1),
        wh_scale.reshape(1, -1),
        b.reshape(1, -1),
        dts.reshape(-1, 1),
        sig_tab,
        tanh_tab,
    )
    return jnp.swapaxes(out, 0, 1)
