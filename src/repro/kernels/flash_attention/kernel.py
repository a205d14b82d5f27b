"""Blockwise (flash) attention forward — Pallas TPU kernel.

Same co-design methodology as the GRU kernel, applied to the prefill
hot-spot: KV blocks stream through VMEM while the online-softmax accumulator
(acc, m, l) stays resident in VMEM scratch — the II~=1 "accumulate every
cycle" structure of the paper, with HBM traffic O(S) per query block instead
of the O(S^2) score materialization of the naive path.

Grid = (B, QH, num_q_blocks, num_kv_blocks); kv innermost (ARBITRARY) so the
scratch accumulator carries across kv blocks for one (b, h, q-block).
GQA is handled in the index map (kv head = q head * KH // QH). Causal and
sliding-window masks are applied per-element inside the block; fully-masked
blocks produce exp(-inf)=0 contributions and are skipped via pl.when on the
block-level bound.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import runtime as rt

NEG_INF = -1e30


def _flash_kernel(
    q_ref,  # [1, 1, bq, Dh]
    k_ref,  # [1, 1, bk, Dh]
    v_ref,  # [1, 1, bk, Dh]
    o_ref,  # [1, 1, bq, Dh]
    acc_scr,  # VMEM [bq, Dh] f32
    m_scr,  # VMEM [bq, 1] f32
    l_scr,  # VMEM [bq, 1] f32
    *,
    bq: int,
    bk: int,
    causal: bool,
    window: int | None,
    q_offset: int,
    num_kv_blocks: int,
):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)

    q_start = iq * bq + q_offset
    k_start = ik * bk

    # block-level relevance: skip fully-masked kv blocks (causal: block starts
    # after the last query; window: block ends before the window's left edge)
    relevant = jnp.bool_(True)
    if causal:
        relevant &= k_start <= q_start + bq - 1
    if window is not None:
        relevant &= (k_start + bk - 1) > (q_start - window)

    @pl.when(relevant)
    def _compute():
        f32 = jnp.float32
        q = q_ref[0, 0].astype(f32)
        k = k_ref[0, 0].astype(f32)
        v = v_ref[0, 0].astype(f32)
        scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], f32))
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=f32) * scale  # [bq, bk]
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= kpos <= qpos
        if window is not None:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + jnp.sum(p, axis=-1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=f32
        )
        m_scr[...] = m_new

    @pl.when(ik == num_kv_blocks - 1)
    def _finalize():
        l = l_scr[...]
        safe_l = jnp.where(l == 0.0, 1.0, l)  # fully-masked rows -> 0 output
        o_ref[0, 0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("causal", "window", "q_offset", "block_q", "block_k", "interpret")
)
def flash_attention_pallas(
    q: jnp.ndarray,  # [B, QH, Sq, Dh]
    k: jnp.ndarray,  # [B, KH, Sk, Dh]
    v: jnp.ndarray,  # [B, KH, Sk, Dh]
    causal: bool = True,
    window: int | None = None,
    q_offset: int = 0,
    block_q: int = 128,
    block_k: int = 128,
    interpret: bool = False,
) -> jnp.ndarray:
    B, QH, Sq, Dh = q.shape
    KH, Sk = k.shape[1], k.shape[2]
    bq = min(block_q, Sq)
    bk = min(block_k, Sk)
    assert Sq % bq == 0 and Sk % bk == 0, (Sq, bq, Sk, bk)
    nq, nk = Sq // bq, Sk // bk
    kernel = functools.partial(
        _flash_kernel,
        bq=bq,
        bk=bk,
        causal=causal,
        window=window,
        q_offset=q_offset,
        num_kv_blocks=nk,
    )
    return rt.pallas_call(
        kernel,
        grid=(B, QH, nq, nk),
        in_specs=[
            ((1, 1, bq, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
            ((1, 1, bk, Dh), lambda b, h, iq, ik: (b, h * KH // QH, ik, 0)),
            ((1, 1, bk, Dh), lambda b, h, iq, ik: (b, h * KH // QH, ik, 0)),
        ],
        out_specs=((1, 1, bq, Dh), lambda b, h, iq, ik: (b, h, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, QH, Sq, Dh), q.dtype),
        scratch_shapes=[
            ((bq, Dh), jnp.float32),
            ((bq, 1), jnp.float32),
            ((bq, 1), jnp.float32),
        ],
        dimension_semantics=(rt.PARALLEL, rt.PARALLEL, rt.PARALLEL, rt.ARBITRARY),
        interpret=interpret,
        name="flash_attention",
    )(q, k, v)
