"""Kernel runtime: the one ``pallas_call`` constructor + the dispatch policy.

Every kernel family (gru_scan, mr_step, flash_attention, ssd_scan) goes
through this module instead of touching ``pl.pallas_call`` directly.
``pallas_call`` takes the kernel body, grid, (block_shape, index_map) spec
pairs, output shapes, scratch shapes and dimension semantics, and assembles
the ``pl.pallas_call`` with ``pltpu.CompilerParams``. ``smem_spec`` places a
whole small operand (per-step scalars such as ``dts``) in SMEM, where the
kernel reads it as scalars instead of through a ``(1, 1)`` VMEM block.

``resolve_dispatch`` centralizes the backend decision: the compiled kernel on
TPU, the kernel body under the Pallas interpreter when explicitly requested
(CPU correctness sweeps), and the pure-JAX reference everywhere else.
"""

from __future__ import annotations

import enum
from typing import Any, Callable, Sequence

import jax
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

PARALLEL = pltpu.PARALLEL
ARBITRARY = pltpu.ARBITRARY


def compiler_params(dimension_semantics: Sequence[Any] | None = None, **kw) -> Any:
    """``pltpu.CompilerParams`` with the grid's dimension semantics."""
    if dimension_semantics is not None:
        kw["dimension_semantics"] = tuple(dimension_semantics)
    return pltpu.CompilerParams(**kw)


def block_spec(block_shape: tuple, index_map: Callable | None = None) -> pl.BlockSpec:
    """BlockSpec from a (block_shape, index_map) pair (``None`` dims squeeze)."""
    return pl.BlockSpec(tuple(block_shape), index_map)


def smem_spec() -> pl.BlockSpec:
    """The whole operand resident in SMEM (scalar reads inside the kernel)."""
    return pl.BlockSpec(memory_space=pltpu.SMEM)


class Dispatch(enum.Enum):
    """Where a kernel-family call executes."""

    KERNEL = "kernel"  # compiled Pallas kernel (TPU)
    INTERPRET = "interpret"  # kernel body under the Pallas interpreter (CPU tests)
    REFERENCE = "reference"  # pure-JAX oracle (lax.scan / jnp)


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def resolve_dispatch(
    force_reference: bool = False,
    interpret: bool | None = None,
    backend: str | None = None,
) -> Dispatch:
    """The shared dispatch policy for all kernel families.

    - ``force_reference`` always wins (callers use it for oracle comparisons
      and for features only the reference implements, e.g. carried state).
    - On TPU the compiled kernel runs.
    - Off TPU, ``interpret=True`` runs the kernel body under the interpreter
      (semantics-identical to the TPU kernel — what the CPU test sweeps use);
      otherwise the reference runs.
    """
    if force_reference:
        return Dispatch.REFERENCE
    backend = backend if backend is not None else jax.default_backend()
    if backend == "tpu":
        return Dispatch.KERNEL
    if interpret:
        return Dispatch.INTERPRET
    return Dispatch.REFERENCE


def pallas_call(
    kernel: Callable,
    *,
    grid: tuple[int, ...],
    in_specs: Sequence[Any],
    out_specs,
    out_shape,
    scratch_shapes: Sequence[Any] = (),
    dimension_semantics: Sequence[Any] | None = None,
    interpret: bool = False,
    name: str | None = None,
    **compiler_kw,
):
    """The one ``pl.pallas_call`` constructor for every kernel family.

    ``in_specs``/``out_specs`` are (block_shape, index_map) pairs or prebuilt
    BlockSpecs (``smem_spec()``). Convention: a single-output kernel passes
    ``out_specs`` as ONE pair; a multi-output kernel passes a LIST of pairs
    (mirroring ``out_shape``). ``scratch_shapes`` entries may be (shape,
    dtype) pairs (VMEM implied) or prebuilt scratch objects.
    """

    def to_spec(s):
        if isinstance(s, tuple) and len(s) == 2 and not isinstance(s, pl.BlockSpec):
            return block_spec(s[0], s[1])
        return s

    def to_scratch(s):
        if isinstance(s, tuple) and len(s) == 2 and isinstance(s[0], tuple):
            return pltpu.VMEM(tuple(s[0]), s[1])
        return s

    if isinstance(out_specs, list):
        out_specs_built = [to_spec(s) for s in out_specs]
    else:
        out_specs_built = to_spec(out_specs)

    return pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[to_spec(s) for s in in_specs],
        out_specs=out_specs_built,
        out_shape=out_shape,
        scratch_shapes=[to_scratch(s) for s in scratch_shapes],
        compiler_params=compiler_params(dimension_semantics, **compiler_kw),
        interpret=interpret,
        name=name,
    )
