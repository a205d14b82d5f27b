"""Pallas TPU kernels for the paper's compute hot-spots.

- gru_scan:        fused GRU(-flow) sequence scan — the MERINDA core kernel.
                   TPU analogue of the paper's DSP/LUT/BRAM-banked FPGA dataflow.
- mr_step:         stage-FUSED per-window recovery step: GRU scan + RMS-norm +
                   dense head in one pallas_call (fp32 + int8/PWL) — the
                   paper's "no inter-stage synchronization" dataflow, one
                   level above gru_scan.
- ssd_scan:        Mamba2 SSD chunked recurrence (same locality methodology).
- flash_attention: blockwise causal/sliding-window attention for prefill.

Each kernel package ships kernel.py (pallas kernel body + VMEM tiling),
ops.py (jit'd public wrapper with interpret/XLA fallbacks) and ref.py (pure-jnp
oracle used by the allclose test sweeps).

runtime.py is the shared kernel runtime: the one pallas_call constructor
(spec pairs, VMEM scratch, SMEM operands, compiler params), plus the
TPU/interpret/reference dispatch policy every ops.py consults.
"""
