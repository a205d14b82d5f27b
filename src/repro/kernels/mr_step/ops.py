"""Public jit'd wrappers for the stage-fused MR per-window step.

``mr_step`` is the fused replacement for merinda's encode -> RMS-norm ->
dense-head stage sequence; ``mr_step_int8`` is the fixed-point serving
variant (int8 weights + PWL activations). Both dispatch on the encoder
registry row: the GRU(-flow) families take the single-update kernels, the
multi-substep families (``ltc``, ``node``) take the fused-solver kernels
that keep the hidden state, cell constants and head weights VMEM-resident
across all K solver substeps of every input step. Every variant resolves
its backend through kernels/runtime.resolve_dispatch — compiled Pallas
kernel on TPU, kernel body under the interpreter for CPU correctness
sweeps, the pure-JAX reference otherwise — so every consumer (engine epoch
scan, stream tick, serve_mr) shares one code path regardless of backend.

Gradients flow through a custom_vjp whose backward is the reference program
(same structure as kernels/gru_scan.ops), so every fused stage trains
inside the scan-jitted engine exactly like the unfused one. Every family's
backward (the reference replay and its transpose) runs under the device
scope ``mr.encoder_bwd`` (``ENCODER_BWD_SCOPE``), which reaches the compiled
program only as ``op_name`` metadata, so a profiler trace can tell it apart.
"""

from __future__ import annotations

import functools as _functools

import jax
import jax.numpy as jnp

from repro.core import encoders
from repro.core.quant import make_sigmoid_table, make_tanh_table, quantize_int8
from repro.kernels import runtime as rt
from repro.kernels.mr_step import kernel as _k
from repro.kernels.mr_step import ref as _ref

ENCODER_BWD_SCOPE = "mr.encoder_bwd"


@_functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13))
def _mr_step_cvjp(xs, h0, wx, wh, b, time_scale, dts, w1, b1, w2, b2, flow, act_bits, block_b):
    return _k.mr_step_pallas(
        xs,
        h0,
        wx,
        wh,
        b,
        time_scale,
        dts,
        w1,
        b1,
        w2,
        b2,
        flow=flow,
        act_bits=act_bits,
        block_b=block_b,
        interpret=not rt.on_tpu(),
    )


def _mr_fwd(xs, h0, wx, wh, b, time_scale, dts, w1, b1, w2, b2, flow, act_bits, block_b):
    out = _mr_step_cvjp(xs, h0, wx, wh, b, time_scale, dts, w1, b1, w2, b2, flow, act_bits, block_b)
    return out, (xs, h0, wx, wh, b, time_scale, dts, w1, b1, w2, b2)


def _mr_bwd(flow, act_bits, block_b, res, ct):
    with jax.named_scope(ENCODER_BWD_SCOPE):
        _, vjp = jax.vjp(lambda *a: _ref.mr_step_reference(*a, flow=flow, act_bits=act_bits), *res)
        return vjp(ct)


_mr_step_cvjp.defvjp(_mr_fwd, _mr_bwd)


# -- multi-substep LTC: fused-solver substeps, reference backward ------------
@_functools.partial(jax.custom_vjp, nondiff_argnums=(11, 12, 13, 14))
def _mr_step_ltc_cvjp(
    xs, h0, w_in, w_rec, bias, a, inv_tau, w1, b1, w2, b2, dt, n_substeps, act_bits, block_b
):
    return _k.mr_step_ltc_pallas(
        xs,
        h0,
        w_in,
        w_rec,
        bias,
        a,
        inv_tau,
        w1,
        b1,
        w2,
        b2,
        dt=dt,
        n_substeps=n_substeps,
        act_bits=act_bits,
        block_b=block_b,
        interpret=not rt.on_tpu(),
    )


def _ltc_fwd(xs, h0, w_in, w_rec, bias, a, inv_tau, w1, b1, w2, b2, dt, n_substeps, act_bits, bb):
    out = _mr_step_ltc_cvjp(
        xs, h0, w_in, w_rec, bias, a, inv_tau, w1, b1, w2, b2, dt, n_substeps, act_bits, bb
    )
    return out, (xs, h0, w_in, w_rec, bias, a, inv_tau, w1, b1, w2, b2)


def _ltc_bwd(dt, n_substeps, act_bits, block_b, res, ct):
    with jax.named_scope(ENCODER_BWD_SCOPE):
        _, vjp = jax.vjp(
            lambda *args: _ref.mr_step_ltc_reference(
                *args, dt=dt, n_substeps=n_substeps, act_bits=act_bits
            ),
            *res,
        )
        return vjp(ct)


_mr_step_ltc_cvjp.defvjp(_ltc_fwd, _ltc_bwd)


# -- multi-substep NODE (ODE-RNN): Euler substeps, reference backward --------
@_functools.partial(jax.custom_vjp, nondiff_argnums=(12, 13, 14, 15))
def _mr_step_node_cvjp(
    xs, h0, w_f1, b_f1, w_f2, b_f2, w_in, b_in, w1, b1, w2, b2, dt, n_substeps, act_bits, block_b
):
    return _k.mr_step_node_pallas(
        xs,
        h0,
        w_f1,
        b_f1,
        w_f2,
        b_f2,
        w_in,
        b_in,
        w1,
        b1,
        w2,
        b2,
        dt=dt,
        n_substeps=n_substeps,
        act_bits=act_bits,
        block_b=block_b,
        interpret=not rt.on_tpu(),
    )


def _node_fwd(xs, h0, w_f1, b_f1, w_f2, b_f2, w_in, b_in, w1, b1, w2, b2, dt, n_sub, ab, bb):
    out = _mr_step_node_cvjp(
        xs, h0, w_f1, b_f1, w_f2, b_f2, w_in, b_in, w1, b1, w2, b2, dt, n_sub, ab, bb
    )
    return out, (xs, h0, w_f1, b_f1, w_f2, b_f2, w_in, b_in, w1, b1, w2, b2)


def _node_bwd(dt, n_substeps, act_bits, block_b, res, ct):
    with jax.named_scope(ENCODER_BWD_SCOPE):
        _, vjp = jax.vjp(
            lambda *args: _ref.mr_step_node_reference(
                *args, dt=dt, n_substeps=n_substeps, act_bits=act_bits
            ),
            *res,
        )
        return vjp(ct)


_mr_step_node_cvjp.defvjp(_node_fwd, _node_bwd)


def _split_gru(params, cfg):
    """(wx, wh, b, time_scale) with the QAT weight fake-quant applied."""
    enc = encoders.quantized_gru_params(params.encoder, cfg)
    d_in = cfg.state_dim + cfg.input_dim
    return enc.w[:d_in], enc.w[d_in:], enc.b, enc.time_scale


def _head_weights(params, cfg):
    """(w1, b1, w2, b2) with the shared QAT weight treatment applied."""
    from repro.core.quant import qat_weight

    w1 = qat_weight(params.head_w1, cfg.quant)
    w2 = qat_weight(params.head_w2, cfg.quant)
    return w1, params.head_b1, w2, params.head_b2


def _fusable_spec(cfg, *, int8: bool) -> encoders.EncoderSpec:
    spec = encoders.get_encoder(cfg.encoder)
    if not spec.fusable:
        raise ValueError(
            f"fused mr_step has no stage for encoder {cfg.encoder!r} "
            f"(fusable: {encoders.fusable_names()})"
        )
    if int8 and not spec.int8:
        raise ValueError(
            f"int8 mr_step implements the fixed-point cells with a PWL activation "
            f"mapping — encoder='gru' (standard cell, paper Eq. 12-15) or "
            f"encoder='ltc' (sigmoid-only substep) — got {cfg.encoder!r} "
            f"(int8-capable: {encoders.int8_names()})"
        )
    return spec


def _split_out(out, cfg):
    theta = out[..., : cfg.n_coef].reshape(out.shape[0], cfg.n_terms, cfg.state_dim)
    return theta, out[..., cfg.n_coef :]


def _legal_block_b(block_b: int | None, B: int) -> int | None:
    """Drop a tile the batch can't take. A plan resolves ``block_b`` against
    its COMPILE-TIME batch (e.g. the training minibatch), but the same config
    also serves full-window readouts whose batch differs; the kernel asserts
    ``B % block_b == 0``, so a non-dividing tile falls back to full batch
    here (a per-shape static decision — jit retraces per batch shape)."""
    return block_b if block_b and B % block_b == 0 else None


def mr_step(
    params,  # merinda.MRParams (any fusable registry encoder)
    cfg,  # merinda.MRConfig
    xs: jnp.ndarray,  # [B, T, n+m] normalized (+ activation-quantized) windows
    dts: jnp.ndarray | None = None,
    block_b: int | None = None,
    force_reference: bool = False,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fused per-window recovery stage. Returns (theta [B, n_terms, n], shifts [B, q]).

    Dispatches on the encoder registry row: GRU(-flow) takes the
    single-update kernel, ``ltc``/``node`` take the multi-substep kernels
    (``dts`` applies to the GRU families only; the substep cells integrate
    on ``cfg.dt`` with ``cfg.ltc_substeps`` solver substeps, matching their
    unfused scans). Backend: Pallas kernel on TPU; reference (identical
    math) elsewhere. Tests pass interpret=True to run the kernel body on CPU.
    """
    spec = _fusable_spec(cfg, int8=False)
    B, T, _ = xs.shape
    block_b = _legal_block_b(block_b, B)
    h0 = jnp.zeros((B, cfg.hidden), xs.dtype)
    w1, b1, w2, b2 = _head_weights(params, cfg)
    act_bits = None
    if cfg.quant is not None:
        act_bits = (cfg.quant.act_int_bits, cfg.quant.act_frac_bits)
    reference = rt.resolve_dispatch(force_reference, interpret) is rt.Dispatch.REFERENCE

    if spec.family == "ltc":
        enc = params.encoder
        args = (xs, h0, enc.w_in, enc.w_rec, enc.bias, enc.a, enc.inv_tau, w1, b1, w2, b2)
        if reference:
            out = _ref.mr_step_ltc_reference(
                *args,
                dt=cfg.dt,
                n_substeps=cfg.ltc_substeps,
                act_bits=act_bits,
                unroll=cfg.substep_unroll,
            )
        else:
            out = _mr_step_ltc_cvjp(*args, cfg.dt, cfg.ltc_substeps, act_bits, block_b)
        return _split_out(out, cfg)

    if spec.family == "node":
        enc = params.encoder
        args = (
            xs,
            h0,
            enc.w_f1,
            enc.b_f1,
            enc.w_f2,
            enc.b_f2,
            enc.w_in,
            enc.b_in,
            w1,
            b1,
            w2,
            b2,
        )
        if reference:
            out = _ref.mr_step_node_reference(
                *args,
                dt=cfg.dt,
                n_substeps=cfg.ltc_substeps,
                act_bits=act_bits,
                unroll=cfg.substep_unroll,
            )
        else:
            out = _mr_step_node_cvjp(*args, cfg.dt, cfg.ltc_substeps, act_bits, block_b)
        return _split_out(out, cfg)

    if dts is None:
        dts = jnp.ones((T,), xs.dtype)
    wx, wh, b, time_scale = _split_gru(params, cfg)
    if reference:
        out = _ref.mr_step_reference(
            xs,
            h0,
            wx,
            wh,
            b,
            time_scale,
            dts,
            w1,
            b1,
            w2,
            b2,
            flow=spec.flow,
            act_bits=act_bits,
            unroll=cfg.substep_unroll,
        )
    else:
        out = _mr_step_cvjp(
            xs,
            h0,
            wx,
            wh,
            b,
            time_scale,
            dts,
            w1,
            b1,
            w2,
            b2,
            spec.flow,
            act_bits,
            block_b,
        )
    return _split_out(out, cfg)


def mr_step_int8(
    params,
    cfg,
    xs: jnp.ndarray,
    dts: jnp.ndarray | None = None,
    n_seg: int = 16,
    block_b: int | None = None,
    force_reference: bool = False,
    interpret: bool | None = None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-point serving stage: int8 substep + head weights, PWL activations.

    Quantizes on the fly from float params (production would cache the int8
    tensors; the kernel signatures take them pre-quantized). Implemented for
    the families whose cell nonlinearities have a PWL mapping: the standard
    GRU (paper Eq. 12-15) and the LTC substep cell (sigmoid-only).
    """
    spec = _fusable_spec(cfg, int8=True)
    B, T, _ = xs.shape
    block_b = _legal_block_b(block_b, B)
    d_in = cfg.state_dim + cfg.input_dim
    h0 = jnp.zeros((B, cfg.hidden), xs.dtype)
    if dts is None:
        dts = jnp.ones((T,), jnp.float32)

    if spec.family == "ltc":
        return _mr_step_ltc_int8(
            params,
            cfg,
            xs,
            h0,
            n_seg=n_seg,
            block_b=block_b,
            force_reference=force_reference,
            interpret=interpret,
        )
    wxq = quantize_int8(params.encoder.w[:d_in], axis=-1)
    whq = quantize_int8(params.encoder.w[d_in:], axis=-1)
    w1q = quantize_int8(params.head_w1, axis=-1)
    w2q = quantize_int8(params.head_w2, axis=-1)
    sig_t, tanh_t = make_sigmoid_table(n_seg), make_tanh_table(n_seg)
    if rt.resolve_dispatch(force_reference, interpret) is rt.Dispatch.REFERENCE:
        out = _ref.mr_step_int8_reference(
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
            sig_t,
            tanh_t,
        )
    else:
        out = _k.mr_step_pallas_int8(
            xs,
            h0,
            wxq.values,
            whq.values,
            wxq.scale.reshape(-1),
            whq.scale.reshape(-1),
            params.encoder.b,
            dts,
            jnp.stack([sig_t.slopes, sig_t.intercepts]),
            jnp.stack([tanh_t.slopes, tanh_t.intercepts]),
            w1q.values,
            w1q.scale.reshape(-1),
            params.head_b1,
            w2q.values,
            w2q.scale.reshape(-1),
            params.head_b2,
            block_b=block_b,
            interpret=not rt.on_tpu(),
            n_seg=n_seg,
        )
    return _split_out(out, cfg)


def _mr_step_ltc_int8(
    params,
    cfg,
    xs: jnp.ndarray,
    h0: jnp.ndarray,
    *,
    n_seg: int,
    block_b: int | None,
    force_reference: bool,
    interpret: bool | None,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Fixed-point fused LTC serving stage (int8 weights + PWL sigmoid)."""
    enc = params.encoder
    w_inq = quantize_int8(enc.w_in, axis=-1)
    w_recq = quantize_int8(enc.w_rec, axis=-1)
    w1q = quantize_int8(params.head_w1, axis=-1)
    w2q = quantize_int8(params.head_w2, axis=-1)
    sig_t = make_sigmoid_table(n_seg)
    if rt.resolve_dispatch(force_reference, interpret) is rt.Dispatch.REFERENCE:
        out = _ref.mr_step_ltc_int8_reference(
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
            sig_t,
            dt=cfg.dt,
            n_substeps=cfg.ltc_substeps,
        )
    else:
        out = _k.mr_step_ltc_pallas_int8(
            xs,
            h0,
            w_inq.values,
            w_inq.scale.reshape(-1),
            w_recq.values,
            w_recq.scale.reshape(-1),
            enc.bias,
            enc.a,
            enc.inv_tau,
            jnp.stack([sig_t.slopes, sig_t.intercepts]),
            w1q.values,
            w1q.scale.reshape(-1),
            params.head_b1,
            w2q.values,
            w2q.scale.reshape(-1),
            params.head_b2,
            dt=cfg.dt,
            n_substeps=cfg.ltc_substeps,
            block_b=block_b,
            interpret=not rt.on_tpu(),
            n_seg=n_seg,
        )
    return _split_out(out, cfg)
