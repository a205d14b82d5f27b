"""Chunked SSD (Mamba2) scan — Pallas TPU kernel.

Applies the paper's locality methodology to the SSD recurrence: the (N, P)
state stays resident in VMEM across the whole sequence (the "BRAM-resident
hidden state"), chunks stream through one DMA at a time, and all heavy math is
MXU matmuls over (L, N) / (L, L) / (L, P) tiles with L = chunk = 128.

Grid = (B, H, n_chunks); the chunk dimension is innermost/sequential
(ARBITRARY), batch x head are PARALLEL, so each (b, h) pair completes its
state pass with the same scratch buffer (re-initialized at chunk 0).

The in-chunk cumulative decay is computed with a lower-triangular ones matmul
(MXU) instead of lax.cumsum — Mosaic-friendly and contributes negligible
FLOPs at L=128.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import runtime as rt


def _ssd_chunk_kernel(
    x_ref,  # [1, L, 1, P]
    dt_ref,  # [1, L, 1]
    a_ref,  # [1, 1]  A[h]
    b_ref,  # [1, L, 1, N]
    c_ref,  # [1, L, 1, N]
    d_ref,  # [1, 1]  D[h]
    y_ref,  # [1, L, 1, P] out
    s_out_ref,  # [1, 1, N, P] out (final state; persists via constant index map)
    s_scr,  # VMEM [N, P] f32 — resident state
    *,
    chunk: int,
):
    ic = pl.program_id(2)

    @pl.when(ic == 0)
    def _init():
        s_scr[...] = jnp.zeros_like(s_scr)

    f32 = jnp.float32
    L = chunk
    x = x_ref[0, :, 0, :].astype(f32)  # [L, P]
    dt = dt_ref[0, :, 0].astype(f32)  # [L]
    bm = b_ref[0, :, 0, :].astype(f32)  # [L, N]
    cm = c_ref[0, :, 0, :].astype(f32)  # [L, N]
    A = a_ref[0, 0]
    Dh = d_ref[0, 0]

    a = dt * A  # [L] negative
    # inclusive cumsum via lower-triangular matmul (MXU, Mosaic-safe)
    tril = jnp.tril(jnp.ones((L, L), f32))
    cum = jax.lax.dot_general(tril, a[:, None], (((1,), (0,)), ((), ())),
                              preferred_element_type=f32)[:, 0]  # [L]
    total = cum[L - 1]

    # intra-chunk attention-like term
    seg = cum[:, None] - cum[None, :]  # [L, L]
    causal = jnp.tril(jnp.ones((L, L), jnp.bool_))
    decay_mat = jnp.where(causal, jnp.exp(seg), 0.0)
    scores = jax.lax.dot_general(cm, bm, (((1,), (1,)), ((), ())),
                                 preferred_element_type=f32)  # [L, L] c_i . b_j
    scores = scores * decay_mat * dt[None, :]
    y = jax.lax.dot_general(scores, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=f32)  # [L, P]

    # inter-chunk: contribution of the state entering this chunk
    s_in = s_scr[...]
    c_dec = cm * jnp.exp(cum)[:, None]  # [L, N]
    y = y + jax.lax.dot_general(c_dec, s_in, (((1,), (0,)), ((), ())), preferred_element_type=f32)

    # state update: S = exp(total) * S_in + sum_j exp(total - cum_j) dt_j b_j (x) x_j
    w = jnp.exp(total - cum) * dt  # [L]
    bw = bm * w[:, None]  # [L, N]
    s_new = jnp.exp(total) * s_in + jax.lax.dot_general(
        bw, x, (((0,), (0,)), ((), ())), preferred_element_type=f32
    )  # [N, P]
    s_scr[...] = s_new

    y_ref[0, :, 0, :] = (y + Dh * x).astype(y_ref.dtype)
    s_out_ref[0, 0, :, :] = s_new  # last chunk's write is the final state


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan_pallas(
    x: jnp.ndarray,  # [B, T, H, P]
    dt: jnp.ndarray,  # [B, T, H] positive
    A: jnp.ndarray,  # [H] negative
    bm: jnp.ndarray,  # [B, T, G, N]
    cm: jnp.ndarray,  # [B, T, G, N]
    D: jnp.ndarray,  # [H]
    chunk: int = 128,
    interpret: bool = False,
) -> tuple[jnp.ndarray, jnp.ndarray]:
    """Returns (y [B,T,H,P], final_state [B,H,N,P])."""
    B, T, H, P = x.shape
    G, N = bm.shape[2], bm.shape[3]
    assert T % chunk == 0, f"T={T} % chunk={chunk} != 0"
    nc = T // chunk
    kernel = functools.partial(_ssd_chunk_kernel, chunk=chunk)

    grid = (B, H, nc)
    y, s_final = rt.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            ((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            ((1, chunk, 1), lambda b, h, c: (b, c, h)),
            ((1, 1), lambda b, h, c: (h, 0)),
            ((1, chunk, 1, N), lambda b, h, c: (b, c, h * G // H, 0)),
            ((1, chunk, 1, N), lambda b, h, c: (b, c, h * G // H, 0)),
            ((1, 1), lambda b, h, c: (h, 0)),
        ],
        out_specs=[
            ((1, chunk, 1, P), lambda b, h, c: (b, c, h, 0)),
            ((1, 1, N, P), lambda b, h, c: (b, h, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, T, H, P), x.dtype),
            jax.ShapeDtypeStruct((B, H, N, P), jnp.float32),
        ],
        scratch_shapes=[((N, P), jnp.float32)],
        dimension_semantics=(rt.PARALLEL, rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="ssd_scan",
    )(x, dt, A.reshape(-1, 1), bm, cm, D.reshape(-1, 1))
    return y, s_final
