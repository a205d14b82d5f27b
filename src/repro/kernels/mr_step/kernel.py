"""Stage-fused MR per-window step Pallas kernels (the 4th kernel family).

Fuses the whole per-window recovery stage map of merinda.mr_forward —
encoder sequence scan, RMS normalization, and the dense coefficient head —
into ONE ``pallas_call``. Four encoder variants share the structure:

  GRU(-flow)      one gated update per input step (``mr_step_pallas`` +
                  the int8/PWL serving twin ``mr_step_pallas_int8``)
  LTC             the paper's PRIMARY baseline: K fused-solver semi-implicit
                  substeps per input step (``mr_step_ltc_pallas`` + int8/PWL
                  twin) — the iterative-solver loop of paper Table 2 kept
                  entirely VMEM-resident instead of K XLA dispatch hops
  NODE (ODE-RNN)  K fixed-step Euler substeps of a learned vector field per
                  input step (``mr_step_node_pallas``) — paper Table 1's
                  "ODE solver = 88% of the forward pass" hot loop, fused

For the multi-substep cells the substep loop is unrolled INSIDE the kernel
body (K is static): every substep's matvec + update chain runs against
VMEM-resident weights and the VMEM hidden-state scratch, so the sequential
dependency the paper profiles costs VMEM-hop latency instead of an XLA
dispatch + HBM round-trip per substep. This is the TPU re-derivation of the
paper's stage-fused FPGA dataflow (§4, Table 8) one level above
kernels/gru_scan:

  FPGA mechanism                      ->  this kernel
  -------------------------------------   -----------------------------------
  no inter-stage synchronization       ->  encoder, norm and head execute in
  (stage outputs stream directly           one kernel body; the hidden state
  into the next stage)                     and the head input NEVER round-trip
                                           HBM between stages
  BRAM-resident hidden state           ->  h carried in a VMEM scratch across
                                           the whole (scan + head) stage map
  pruned dense layer fed on-chip       ->  head weights VMEM-resident next to
                                           the gate weights; the head GEMM
                                           issues the cycle after the last
                                           scan step retires
  fixed-point + LUT configuration      ->  int8 gate AND head weights with
                                           per-channel scales + PWL
                                           sigmoid/tanh (quant variant)

Per sequence the only HBM traffic is x_t in and theta out — the [B, T, H]
hidden-state tensor that the unfused pipeline materializes between the scan
and head dispatches simply does not exist.

Grid/layout mirrors kernels/gru_scan: grid = (batch_tiles, T), batch tiles
outer (PARALLEL), time inner (ARBITRARY), x_t streamed from a time-major
view with the time dim squeezed and per-step dts in SMEM; the head fires
under ``pl.when(t == T-1)`` and writes the per-window head output tile.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.merinda import RMS_EPS
from repro.core.quant import quantize_fixed
from repro.kernels import runtime as rt
from repro.kernels.gru_scan.kernel import _gru_step_math, _gru_q_step_math, _pwl_eval


def _head_math(h, w1, b1, w2, b2, act_bits):
    """merinda.head_math in Pallas dot_general spellings (shared RMS_EPS);
    parity-tested against the shared helper in tests/test_kernels_mr_step.py."""
    f32 = jnp.float32
    h = h * jax.lax.rsqrt(jnp.mean(jnp.square(h), axis=-1, keepdims=True) + RMS_EPS)
    if act_bits is not None:
        # pure-jnp Qm.n grid; the STE wrapper is irrelevant here (the fused
        # op's backward runs through the reference, ops._mr_bwd)
        h = quantize_fixed(h, *act_bits)
    z = jax.lax.dot_general(h, w1, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    z = jnp.maximum(z + b1, 0.0)
    out = jax.lax.dot_general(z, w2, (((1,), (0,)), ((), ())), preferred_element_type=f32)
    return out + b2


def _mr_step_kernel(
    # inputs
    xs_ref,  # [bb, D]      x_t tile (time-major stream, double-buffered by Mosaic)
    h0_ref,  # [bb, H]
    wx_ref,  # [D, 3H]      VMEM-resident across the whole stage map
    wh_ref,  # [H, 3H]
    b_ref,  # [1, 3H]
    ts_ref,  # [1, H]
    dts_ref,  # SMEM [T, 1] per-step dt
    w1_ref,  # [H, Dh]      head weights, VMEM-resident
    b1_ref,  # [1, Dh]
    w2_ref,  # [Dh, K]
    b2_ref,  # [1, K]
    # outputs
    out_ref,  # [bb, K]     per-window head output (theta ++ shifts)
    # scratch
    h_scr,  # VMEM [bb, H] f32 — BRAM-resident hidden state analogue
    *,
    flow: bool,
    hidden: int,
    act_bits: tuple[int, int] | None,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    h_new = _gru_step_math(
        xs_ref[...],
        h_scr[...],
        wx_ref[...],
        wh_ref[...],
        b_ref[0, :],
        ts_ref[0, :],
        dts_ref[t, 0],
        flow=flow,
        hidden=hidden,
    )
    h_scr[...] = h_new

    # stage handoff without synchronization: the head consumes h straight
    # from VMEM the step the scan retires — no [B, T, H] HBM materialization
    @pl.when(t == pl.num_programs(1) - 1)
    def _head():
        out = _head_math(h_new, w1_ref[...], b1_ref[0, :], w2_ref[...], b2_ref[0, :], act_bits)
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("flow", "act_bits", "block_b", "interpret"))
def mr_step_pallas(
    xs: jnp.ndarray,  # [B, T, D]
    h0: jnp.ndarray,  # [B, H]
    wx: jnp.ndarray,  # [D, 3H]
    wh: jnp.ndarray,  # [H, 3H]
    b: jnp.ndarray,  # [3H]
    time_scale: jnp.ndarray,  # [H]
    dts: jnp.ndarray,  # [T]
    w1: jnp.ndarray,  # [H, Dh]
    b1: jnp.ndarray,  # [Dh]
    w2: jnp.ndarray,  # [Dh, K]
    b2: jnp.ndarray,  # [K]
    flow: bool = True,
    act_bits: tuple[int, int] | None = None,
    block_b: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Returns the per-window head output [B, K] (K = n_coef + n_shifts)."""
    B, T, D = xs.shape
    H = h0.shape[-1]
    Dh = w1.shape[-1]
    K = w2.shape[-1]
    bb = block_b or B
    assert B % bb == 0, f"batch {B} not divisible by block_b {bb}"
    nb = B // bb

    kernel = functools.partial(_mr_step_kernel, flow=flow, hidden=H, act_bits=act_bits)
    return rt.pallas_call(
        kernel,
        grid=(nb, T),
        in_specs=[
            ((None, bb, D), lambda ib, t: (t, ib, 0)),  # xs: stream x_t (time-major)
            ((bb, H), lambda ib, t: (ib, 0)),  # h0
            ((D, 3 * H), lambda ib, t: (0, 0)),  # wx: resident
            ((H, 3 * H), lambda ib, t: (0, 0)),  # wh: resident
            ((1, 3 * H), lambda ib, t: (0, 0)),  # b
            ((1, H), lambda ib, t: (0, 0)),  # time_scale
            rt.smem_spec(),  # dts: per-step scalars
            ((H, Dh), lambda ib, t: (0, 0)),  # head w1: resident
            ((1, Dh), lambda ib, t: (0, 0)),  # head b1
            ((Dh, K), lambda ib, t: (0, 0)),  # head w2: resident
            ((1, K), lambda ib, t: (0, 0)),  # head b2
        ],
        out_specs=((bb, K), lambda ib, t: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        scratch_shapes=[((bb, H), jnp.float32)],
        dimension_semantics=(rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="mr_step_fused",
    )(
        jnp.swapaxes(xs, 0, 1),
        h0,
        wx,
        wh,
        b.reshape(1, -1),
        time_scale.reshape(1, -1),
        dts.reshape(-1, 1),
        w1,
        b1.reshape(1, -1),
        w2,
        b2.reshape(1, -1),
    )


# ---------------------------------------------------------------------------
# int8 + piecewise-linear variant — fixed-point weights through BOTH stages
# ---------------------------------------------------------------------------
def _mr_step_q_kernel(
    xs_ref,
    h0_ref,
    wxq_ref,  # int8 [D, 3H]
    whq_ref,  # int8 [H, 3H]
    wx_scale_ref,  # [1, 3H]
    wh_scale_ref,  # [1, 3H]
    b_ref,
    dts_ref,
    sig_tab_ref,  # [2, n_seg]
    tanh_tab_ref,  # [2, n_seg]
    w1q_ref,  # int8 [H, Dh]
    w1_scale_ref,  # [1, Dh]
    b1_ref,
    w2q_ref,  # int8 [Dh, K]
    w2_scale_ref,  # [1, K]
    b2_ref,
    out_ref,
    h_scr,
    *,
    hidden: int,
    n_seg: int,
):
    """Standard-GRU scan + head, int8 weights + PWL activations end to end."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    f32 = jnp.float32
    h_new = _gru_q_step_math(
        xs_ref[...].astype(f32),
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

    @pl.when(t == pl.num_programs(1) - 1)
    def _head():
        w1 = w1q_ref[...].astype(f32) * w1_scale_ref[0, :]
        w2 = w2q_ref[...].astype(f32) * w2_scale_ref[0, :]
        out = _head_math(h_new, w1, b1_ref[0, :], w2, b2_ref[0, :], None)
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_b", "interpret", "n_seg"))
def mr_step_pallas_int8(
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
    w1q: jnp.ndarray,  # int8 [H, Dh]
    w1_scale: jnp.ndarray,  # [Dh]
    b1: jnp.ndarray,  # [Dh]
    w2q: jnp.ndarray,  # int8 [Dh, K]
    w2_scale: jnp.ndarray,  # [K]
    b2: jnp.ndarray,  # [K]
    block_b: int | None = None,
    interpret: bool = False,
    n_seg: int = 16,
) -> jnp.ndarray:
    B, T, D = xs.shape
    H = h0.shape[-1]
    Dh = w1q.shape[-1]
    K = w2q.shape[-1]
    bb = block_b or B
    assert B % bb == 0
    nb = B // bb
    kernel = functools.partial(_mr_step_q_kernel, hidden=H, n_seg=n_seg)
    return rt.pallas_call(
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
            ((H, Dh), lambda ib, t: (0, 0)),
            ((1, Dh), lambda ib, t: (0, 0)),
            ((1, Dh), lambda ib, t: (0, 0)),
            ((Dh, K), lambda ib, t: (0, 0)),
            ((1, K), lambda ib, t: (0, 0)),
            ((1, K), lambda ib, t: (0, 0)),
        ],
        out_specs=((bb, K), lambda ib, t: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        scratch_shapes=[((bb, H), jnp.float32)],
        dimension_semantics=(rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="mr_step_fused_int8_pwl",
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
        w1q,
        w1_scale.reshape(1, -1),
        b1.reshape(1, -1),
        w2q,
        w2_scale.reshape(1, -1),
        b2.reshape(1, -1),
    )


# ---------------------------------------------------------------------------
# multi-substep variants — LTC (fused-solver) and NODE (fixed-step Euler)
# ---------------------------------------------------------------------------
def _ltc_step_math(x, h, w_in, w_rec, bias, a, inv_tau, *, sub_dt: float, n_substeps: int):
    """One LTC input step = n_substeps semi-implicit fused-solver iterations.

    Matches core.ltc.ltc_cell: the input drive is loop-invariant; each
    substep's recurrent sigmoid + sum + fused Euler update (the profiled
    hotspots of paper Table 2) depends on the previous substep. The loop is
    a static unroll — h and all weights stay VMEM-resident for the whole
    chain, so the sequential dependency costs VMEM hops, not XLA dispatches.
    """
    f32 = jnp.float32
    drive = (
        jax.lax.dot_general(x, w_in, (((1,), (0,)), ((), ())), preferred_element_type=f32) + bias
    )
    for _ in range(n_substeps):
        f = jax.nn.sigmoid(
            drive
            + jax.lax.dot_general(h, w_rec, (((1,), (0,)), ((), ())), preferred_element_type=f32)
        )
        num = h + sub_dt * f * a
        den = 1.0 + sub_dt * (inv_tau + f)
        h = num / den
    return h


def _mr_step_ltc_kernel(
    # inputs
    xs_ref,  # [bb, D]      x_t tile
    h0_ref,  # [bb, H]
    w_in_ref,  # [D, H]     VMEM-resident across the whole stage map
    w_rec_ref,  # [H, H]
    bias_ref,  # [1, H]
    a_ref,  # [1, H]
    inv_tau_ref,  # [1, H]
    w1_ref,  # [H, Dh]      head weights, VMEM-resident
    b1_ref,  # [1, Dh]
    w2_ref,  # [Dh, K]
    b2_ref,  # [1, K]
    # outputs
    out_ref,  # [bb, K]
    # scratch
    h_scr,  # VMEM [bb, H] f32
    *,
    sub_dt: float,
    n_substeps: int,
    act_bits: tuple[int, int] | None,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    h_new = _ltc_step_math(
        xs_ref[...],
        h_scr[...],
        w_in_ref[...],
        w_rec_ref[...],
        bias_ref[0, :],
        a_ref[0, :],
        inv_tau_ref[0, :],
        sub_dt=sub_dt,
        n_substeps=n_substeps,
    )
    h_scr[...] = h_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _head():
        out = _head_math(h_new, w1_ref[...], b1_ref[0, :], w2_ref[...], b2_ref[0, :], act_bits)
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("dt", "n_substeps", "act_bits", "block_b", "interpret")
)
def mr_step_ltc_pallas(
    xs: jnp.ndarray,  # [B, T, D]
    h0: jnp.ndarray,  # [B, H]
    w_in: jnp.ndarray,  # [D, H]
    w_rec: jnp.ndarray,  # [H, H]
    bias: jnp.ndarray,  # [H]
    a: jnp.ndarray,  # [H]
    inv_tau: jnp.ndarray,  # [H]
    w1: jnp.ndarray,  # [H, Dh]
    b1: jnp.ndarray,  # [Dh]
    w2: jnp.ndarray,  # [Dh, K]
    b2: jnp.ndarray,  # [K]
    dt: float = 1.0,
    n_substeps: int = 6,
    act_bits: tuple[int, int] | None = None,
    block_b: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused multi-substep LTC stage. Returns the head output [B, K]."""
    B, T, D = xs.shape
    H = h0.shape[-1]
    Dh = w1.shape[-1]
    K = w2.shape[-1]
    bb = block_b or B
    assert B % bb == 0, f"batch {B} not divisible by block_b {bb}"
    nb = B // bb

    kernel = functools.partial(
        _mr_step_ltc_kernel,
        sub_dt=dt / n_substeps,
        n_substeps=n_substeps,
        act_bits=act_bits,
    )
    return rt.pallas_call(
        kernel,
        grid=(nb, T),
        in_specs=[
            ((None, bb, D), lambda ib, t: (t, ib, 0)),  # xs: stream x_t (time-major)
            ((bb, H), lambda ib, t: (ib, 0)),  # h0
            ((D, H), lambda ib, t: (0, 0)),  # w_in: resident
            ((H, H), lambda ib, t: (0, 0)),  # w_rec: resident
            ((1, H), lambda ib, t: (0, 0)),  # bias
            ((1, H), lambda ib, t: (0, 0)),  # a
            ((1, H), lambda ib, t: (0, 0)),  # inv_tau
            ((H, Dh), lambda ib, t: (0, 0)),  # head w1: resident
            ((1, Dh), lambda ib, t: (0, 0)),  # head b1
            ((Dh, K), lambda ib, t: (0, 0)),  # head w2: resident
            ((1, K), lambda ib, t: (0, 0)),  # head b2
        ],
        out_specs=((bb, K), lambda ib, t: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        scratch_shapes=[((bb, H), jnp.float32)],
        dimension_semantics=(rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="mr_step_fused_ltc",
    )(
        jnp.swapaxes(xs, 0, 1),
        h0,
        w_in,
        w_rec,
        bias.reshape(1, -1),
        a.reshape(1, -1),
        inv_tau.reshape(1, -1),
        w1,
        b1.reshape(1, -1),
        w2,
        b2.reshape(1, -1),
    )


def _node_step_math(x, h, w_f1, b_f1, w_f2, b_f2, w_in, b_in, *, sub_dt: float, n_substeps: int):
    """One ODE-RNN input step: n_substeps Euler substeps + input injection.

    Matches core.node_mr.node_scan (multi_step_solver_cell with
    method="euler"): h += sub_dt * f_theta(h) per substep, then the linear
    observation injection. Static unroll, all operands VMEM-resident.
    """
    f32 = jnp.float32

    def dot(p, q):
        return jax.lax.dot_general(p, q, (((1,), (0,)), ((), ())), preferred_element_type=f32)

    for _ in range(n_substeps):
        z = jnp.tanh(dot(h, w_f1) + b_f1)
        h = h + sub_dt * (dot(z, w_f2) + b_f2)
    return h + dot(x, w_in) + b_in


def _mr_step_node_kernel(
    xs_ref,  # [bb, D]
    h0_ref,  # [bb, H]
    w_f1_ref,  # [H, H]     vector-field MLP, VMEM-resident
    b_f1_ref,  # [1, H]
    w_f2_ref,  # [H, H]
    b_f2_ref,  # [1, H]
    w_in_ref,  # [D, H]     observation injection
    b_in_ref,  # [1, H]
    w1_ref,  # [H, Dh]
    b1_ref,  # [1, Dh]
    w2_ref,  # [Dh, K]
    b2_ref,  # [1, K]
    out_ref,  # [bb, K]
    h_scr,  # VMEM [bb, H] f32
    *,
    sub_dt: float,
    n_substeps: int,
    act_bits: tuple[int, int] | None,
):
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    h_new = _node_step_math(
        xs_ref[...],
        h_scr[...],
        w_f1_ref[...],
        b_f1_ref[0, :],
        w_f2_ref[...],
        b_f2_ref[0, :],
        w_in_ref[...],
        b_in_ref[0, :],
        sub_dt=sub_dt,
        n_substeps=n_substeps,
    )
    h_scr[...] = h_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _head():
        out = _head_math(h_new, w1_ref[...], b1_ref[0, :], w2_ref[...], b2_ref[0, :], act_bits)
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("dt", "n_substeps", "act_bits", "block_b", "interpret")
)
def mr_step_node_pallas(
    xs: jnp.ndarray,  # [B, T, D]
    h0: jnp.ndarray,  # [B, H]
    w_f1: jnp.ndarray,  # [H, H]
    b_f1: jnp.ndarray,  # [H]
    w_f2: jnp.ndarray,  # [H, H]
    b_f2: jnp.ndarray,  # [H]
    w_in: jnp.ndarray,  # [D, H]
    b_in: jnp.ndarray,  # [H]
    w1: jnp.ndarray,  # [H, Dh]
    b1: jnp.ndarray,  # [Dh]
    w2: jnp.ndarray,  # [Dh, K]
    b2: jnp.ndarray,  # [K]
    dt: float = 1.0,
    n_substeps: int = 6,
    act_bits: tuple[int, int] | None = None,
    block_b: int | None = None,
    interpret: bool = False,
) -> jnp.ndarray:
    """Fused multi-substep NODE (ODE-RNN) stage. Returns [B, K]."""
    B, T, D = xs.shape
    H = h0.shape[-1]
    Dh = w1.shape[-1]
    K = w2.shape[-1]
    bb = block_b or B
    assert B % bb == 0, f"batch {B} not divisible by block_b {bb}"
    nb = B // bb

    kernel = functools.partial(
        _mr_step_node_kernel,
        sub_dt=dt / n_substeps,
        n_substeps=n_substeps,
        act_bits=act_bits,
    )
    return rt.pallas_call(
        kernel,
        grid=(nb, T),
        in_specs=[
            ((None, bb, D), lambda ib, t: (t, ib, 0)),
            ((bb, H), lambda ib, t: (ib, 0)),
            ((H, H), lambda ib, t: (0, 0)),  # w_f1: resident
            ((1, H), lambda ib, t: (0, 0)),
            ((H, H), lambda ib, t: (0, 0)),  # w_f2: resident
            ((1, H), lambda ib, t: (0, 0)),
            ((D, H), lambda ib, t: (0, 0)),  # w_in: resident
            ((1, H), lambda ib, t: (0, 0)),
            ((H, Dh), lambda ib, t: (0, 0)),  # head w1: resident
            ((1, Dh), lambda ib, t: (0, 0)),
            ((Dh, K), lambda ib, t: (0, 0)),  # head w2: resident
            ((1, K), lambda ib, t: (0, 0)),
        ],
        out_specs=((bb, K), lambda ib, t: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        scratch_shapes=[((bb, H), jnp.float32)],
        dimension_semantics=(rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="mr_step_fused_node",
    )(
        jnp.swapaxes(xs, 0, 1),
        h0,
        w_f1,
        b_f1.reshape(1, -1),
        w_f2,
        b_f2.reshape(1, -1),
        w_in,
        b_in.reshape(1, -1),
        w1,
        b1.reshape(1, -1),
        w2,
        b2.reshape(1, -1),
    )


def _ltc_q_step_math(
    x, h, w_in, w_rec, bias, a, inv_tau, sig_tab, *, sub_dt: float, n_substeps: int, n_seg: int
):
    """Int8-dequant + PWL-sigmoid LTC step (weights pre-dequantized by the
    kernel body once per grid step; the PWL segment-select chain reuses
    gru_scan's branch-free evaluator)."""
    f32 = jnp.float32
    drive = (
        jax.lax.dot_general(x, w_in, (((1,), (0,)), ((), ())), preferred_element_type=f32) + bias
    )
    for _ in range(n_substeps):
        pre = drive + jax.lax.dot_general(
            h, w_rec, (((1,), (0,)), ((), ())), preferred_element_type=f32
        )
        f = _pwl_eval(pre, sig_tab[0, :], sig_tab[1, :], -8.0, 8.0, n_seg, 0.0, 1.0)
        num = h + sub_dt * f * a
        den = 1.0 + sub_dt * (inv_tau + f)
        h = num / den
    return h


def _mr_step_ltc_q_kernel(
    xs_ref,
    h0_ref,
    w_inq_ref,  # int8 [D, H]
    w_in_scale_ref,  # [1, H]
    w_recq_ref,  # int8 [H, H]
    w_rec_scale_ref,  # [1, H]
    bias_ref,  # [1, H]
    a_ref,  # [1, H]
    inv_tau_ref,  # [1, H]
    sig_tab_ref,  # [2, n_seg]
    w1q_ref,  # int8 [H, Dh]
    w1_scale_ref,  # [1, Dh]
    b1_ref,
    w2q_ref,  # int8 [Dh, K]
    w2_scale_ref,  # [1, K]
    b2_ref,
    out_ref,
    h_scr,
    *,
    sub_dt: float,
    n_substeps: int,
    n_seg: int,
):
    """LTC substep scan + head, int8 weights + PWL sigmoid end to end."""
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        h_scr[...] = h0_ref[...].astype(jnp.float32)

    f32 = jnp.float32
    w_in = w_inq_ref[...].astype(f32) * w_in_scale_ref[0, :]
    w_rec = w_recq_ref[...].astype(f32) * w_rec_scale_ref[0, :]
    h_new = _ltc_q_step_math(
        xs_ref[...].astype(f32),
        h_scr[...],
        w_in,
        w_rec,
        bias_ref[0, :],
        a_ref[0, :],
        inv_tau_ref[0, :],
        sig_tab_ref[...],
        sub_dt=sub_dt,
        n_substeps=n_substeps,
        n_seg=n_seg,
    )
    h_scr[...] = h_new

    @pl.when(t == pl.num_programs(1) - 1)
    def _head():
        w1 = w1q_ref[...].astype(f32) * w1_scale_ref[0, :]
        w2 = w2q_ref[...].astype(f32) * w2_scale_ref[0, :]
        out = _head_math(h_new, w1, b1_ref[0, :], w2, b2_ref[0, :], None)
        out_ref[...] = out.astype(out_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("dt", "n_substeps", "block_b", "interpret", "n_seg")
)
def mr_step_ltc_pallas_int8(
    xs: jnp.ndarray,  # [B, T, D]
    h0: jnp.ndarray,  # [B, H]
    w_inq: jnp.ndarray,  # int8 [D, H]
    w_in_scale: jnp.ndarray,  # [H]
    w_recq: jnp.ndarray,  # int8 [H, H]
    w_rec_scale: jnp.ndarray,  # [H]
    bias: jnp.ndarray,  # [H]
    a: jnp.ndarray,  # [H]
    inv_tau: jnp.ndarray,  # [H]
    sig_tab: jnp.ndarray,  # [2, n_seg]
    w1q: jnp.ndarray,  # int8 [H, Dh]
    w1_scale: jnp.ndarray,  # [Dh]
    b1: jnp.ndarray,  # [Dh]
    w2q: jnp.ndarray,  # int8 [Dh, K]
    w2_scale: jnp.ndarray,  # [K]
    b2: jnp.ndarray,  # [K]
    dt: float = 1.0,
    n_substeps: int = 6,
    block_b: int | None = None,
    interpret: bool = False,
    n_seg: int = 16,
) -> jnp.ndarray:
    """Fixed-point fused LTC stage: int8 substep + head weights, PWL sigmoid."""
    B, T, D = xs.shape
    H = h0.shape[-1]
    Dh = w1q.shape[-1]
    K = w2q.shape[-1]
    bb = block_b or B
    assert B % bb == 0
    nb = B // bb
    kernel = functools.partial(
        _mr_step_ltc_q_kernel, sub_dt=dt / n_substeps, n_substeps=n_substeps, n_seg=n_seg
    )
    return rt.pallas_call(
        kernel,
        grid=(nb, T),
        in_specs=[
            ((None, bb, D), lambda ib, t: (t, ib, 0)),
            ((bb, H), lambda ib, t: (ib, 0)),
            ((D, H), lambda ib, t: (0, 0)),
            ((1, H), lambda ib, t: (0, 0)),
            ((H, H), lambda ib, t: (0, 0)),
            ((1, H), lambda ib, t: (0, 0)),
            ((1, H), lambda ib, t: (0, 0)),
            ((1, H), lambda ib, t: (0, 0)),
            ((1, H), lambda ib, t: (0, 0)),
            ((2, n_seg), lambda ib, t: (0, 0)),
            ((H, Dh), lambda ib, t: (0, 0)),
            ((1, Dh), lambda ib, t: (0, 0)),
            ((1, Dh), lambda ib, t: (0, 0)),
            ((Dh, K), lambda ib, t: (0, 0)),
            ((1, K), lambda ib, t: (0, 0)),
            ((1, K), lambda ib, t: (0, 0)),
        ],
        out_specs=((bb, K), lambda ib, t: (ib, 0)),
        out_shape=jax.ShapeDtypeStruct((B, K), jnp.float32),
        scratch_shapes=[((bb, H), jnp.float32)],
        dimension_semantics=(rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="mr_step_fused_ltc_int8_pwl",
    )(
        jnp.swapaxes(xs, 0, 1),
        h0,
        w_inq,
        w_in_scale.reshape(1, -1),
        w_recq,
        w_rec_scale.reshape(1, -1),
        bias.reshape(1, -1),
        a.reshape(1, -1),
        inv_tau.reshape(1, -1),
        sig_tab,
        w1q,
        w1_scale.reshape(1, -1),
        b1.reshape(1, -1),
        w2q,
        w2_scale.reshape(1, -1),
        b2.reshape(1, -1),
    )
