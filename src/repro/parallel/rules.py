"""Logical-axis sharding rules -> mesh PartitionSpecs.

One rule table covers every (arch x shape x mesh) cell via two safety
properties applied *per tensor* at spec-resolution time:

1. divisibility fallback — a candidate mesh assignment is taken only if the
   dimension is divisible by the product of the candidate's mesh-axis sizes;
   otherwise the next candidate (or replication) is used. E.g. kv_heads=8 on
   a model=16 axis replicates instead of forcing GSPMD padding.
2. conflict resolution — earlier tensor dims claim mesh axes first; later
   dims fall back. E.g. decode batch=128 claims `data`; the cache seq dim
   then replicates. With batch=1 (long_500k) the batch dim fails
   divisibility, so the cache seq dim claims `data` — sequence parallelism
   falls out of the same table.

Default placement strategy (MaxText-style fsdp x tensor):
  weights' d_model dim -> data (FSDP / ZeRO-3), heads/ffn/vocab/expert dim
  -> model (TP/EP); activations' batch -> (pod, data).
"""

from __future__ import annotations

import contextlib
import functools
import math
import threading
from typing import Optional, Sequence

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec

Candidate = tuple[str, ...]

# logical axis -> ordered candidates (each a tuple of mesh axes)
DEFAULT_RULES: dict[str, list[Candidate]] = {
    # activations
    "batch": [("pod", "data"), ("data",)],
    "seq": [],  # replicated by default; "seq_sharded" opts in
    # Megatron-style sequence parallelism: the residual stream between blocks
    # is seq-sharded over `model`, turning the per-layer TP all-reduce into a
    # reduce-scatter + all-gather pair (equal wire bytes, Nx less live memory)
    "seq_sharded": [("model",), ("data",)],
    # KV cache length: `data` when free (long_500k, batch=1), else `model`
    # (decode_32k, batch takes data) — never replicated, or big caches OOM
    "cache_seq": [("data",), ("model",)],
    "act_embed": [],
    "act_heads": [("model",)],
    "act_mlp": [("model",)],
    "act_vocab": [("model",)],
    "act_expert": [("model",)],
    # parameters
    "embed": [("data",)],  # FSDP dim of weight matrices
    "vocab": [("model",)],
    "heads": [("model",)],
    "kv_heads": [("model",)],
    "head_dim": [],
    "mlp": [("model",)],
    "expert": [("model",)],
    "ssm_heads": [("model",)],
    "ssm_groups": [],
    "ssm_state": [],
    "ssm_inner": [("model",)],
    "conv": [],
    "layers": [],
    "frontend": [],
    # pipeline (only present on pp meshes)
    "stage": [("stage",)],
}


# Named rule-table variants for the perf hillclimb (dryrun --rules <name>).
# Each is a full table; cells are compiled under exactly one variant so
# before/after deltas are attributable to the sharding change alone.
def _variant(**overrides) -> dict[str, list[Candidate]]:
    table = dict(DEFAULT_RULES)
    table.update(overrides)
    return table


RULE_VARIANTS: dict[str, dict[str, list[Candidate]]] = {
    "default": DEFAULT_RULES,
    # pure tensor parallelism: no FSDP gather on the embed dim (weights
    # replicated across `data`) — trades memory for zero weight all-gathers
    "tp_only": _variant(embed=[]),
    # megatron-style sequence sharding of activations between layers
    "no_seq": _variant(seq_sharded=[]),
    # shard the cache over model axis too when data is taken (decode)
    "cache_model": _variant(cache_seq=[("data",), ("model",)]),
    # expert-parallel first: experts claim `data` too when model is taken
    "ep_wide": _variant(expert=[("model",), ("data",)]),
    # 2-D FSDP / pure data parallelism: batch spreads over BOTH mesh axes and
    # weights are ZeRO-3 sharded over both; TP rules starve automatically via
    # conflict resolution (model axis already used by batch). The right
    # regime for models whose per-layer weights are small relative to the
    # per-device activation footprint (mamba2-130m, qwen-3b class) — all
    # per-layer TP/SP collectives vanish, leaving only the (small) weight
    # all-gathers and gradient reduce-scatters.
    "fsdp2d": _variant(
        batch=[("pod", "data", "model"), ("data", "model"), ("pod", "data"), ("data",)],
        embed=[("data", "model"), ("data",)],
        seq_sharded=[],
    ),
}


def partition_spec(
    shape: Sequence[int],
    axes: Sequence[Optional[str]],
    mesh: Mesh,
    rules: dict[str, list[Candidate]] | None = None,
) -> PartitionSpec:
    """Resolve logical axes to a PartitionSpec for this mesh (see module doc)."""
    rules = rules or _active_rules() or DEFAULT_RULES
    assert len(shape) == len(axes), (shape, axes)
    mesh_sizes = dict(zip(mesh.axis_names, mesh.devices.shape))
    used: set[str] = set()
    out: list = []
    for dim, ax in zip(shape, axes):
        assignment = None
        for cand in rules.get(ax, []) if ax else []:
            if not all(a in mesh_sizes for a in cand):
                continue
            if any(a in used for a in cand):
                continue
            prod = math.prod(mesh_sizes[a] for a in cand)
            if dim % prod != 0:
                continue
            assignment = cand if len(cand) > 1 else cand[0]
            used.update(cand)
            break
        out.append(assignment)
    # trim trailing Nones for readability
    while out and out[-1] is None:
        out.pop()
    return PartitionSpec(*out)


def named_sharding(
    mesh: Mesh,
    shape: Sequence[int],
    axes: Sequence[Optional[str]],
    rules: dict[str, list[Candidate]] | None = None,
) -> NamedSharding:
    return NamedSharding(mesh, partition_spec(shape, axes, mesh, rules))


# ---------------------------------------------------------------------------
# logical sharding-constraint context (used inside model code)
# ---------------------------------------------------------------------------
_ctx = threading.local()


def _active() -> tuple[Mesh, dict] | None:
    return getattr(_ctx, "mesh_rules", None)


def _active_rules() -> dict | None:
    mr = _active()
    return mr[1] if mr else None


def make_mesh(shape: Sequence[int], axes: Sequence[str], devices=None) -> Mesh:
    """The one mesh constructor: every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, under which sharding is
    part of every value's type: a one-slot admission update would have to be
    resharded by hand before ``dynamic_update_slice`` accepts it, and
    ``with_sharding_constraint`` inside model code would fight the types.
    Every program here places data with ``NamedSharding`` + constraints and
    lets the partitioner propagate, which is ``Auto`` semantics — so every
    mesh (slot mesh, LM meshes, pipeline mesh, elastic re-mesh) is built
    here, and meshes of both kinds never mix. ``devices`` (default: all)
    takes the first ``prod(shape)`` devices in order, as an elastic re-mesh
    onto survivors needs.
    """
    shape, axes = tuple(shape), tuple(axes)
    types = (jax.sharding.AxisType.Auto,) * len(axes)
    if devices is None:
        return jax.make_mesh(shape, axes, axis_types=types)
    arr = np.asarray(list(devices)[: math.prod(shape)]).reshape(shape)
    return Mesh(arr, axes, axis_types=types)


@contextlib.contextmanager
def use_mesh_rules(mesh: Mesh, rules: dict[str, list[Candidate]] | None = None):
    """Activate logical sharding constraints for model code traced within."""
    prev = _active()
    _ctx.mesh_rules = (mesh, rules or DEFAULT_RULES)
    try:
        with jax.set_mesh(mesh):
            yield
    finally:
        _ctx.mesh_rules = prev


def constraint(x: jax.Array, axes: Sequence[Optional[str]]) -> jax.Array:
    """with_sharding_constraint by logical axes; identity when no mesh active.

    Inside a hybrid shard_map (e.g. manual over `pod`, auto over data/model —
    the compressed-gradient path) constraints must be expressed against the
    CURRENT abstract mesh and must not mention manual axes (those dims are
    already local); both are handled here so model code stays oblivious.
    """
    mr = _active()
    if mr is None:
        return x
    mesh, rules = mr
    spec = partition_spec(x.shape, axes, mesh, rules)
    cur = jax.sharding.get_abstract_mesh()
    manual: set[str] = set()
    use_mesh = mesh
    if not cur.empty and tuple(cur.axis_names) == tuple(mesh.axis_names):
        use_mesh = cur
        manual = {
            name
            for name, ty in zip(cur.axis_names, cur.axis_types)
            if ty == jax.sharding.AxisType.Manual
        }
    if manual:

        def strip(entry):
            if entry is None:
                return None
            names = entry if isinstance(entry, tuple) else (entry,)
            kept = tuple(n for n in names if n not in manual)
            return kept[0] if len(kept) == 1 else (kept or None)

        spec = PartitionSpec(*(strip(e) for e in spec))
    return jax.lax.with_sharding_constraint(x, NamedSharding(use_mesh, spec))


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2))
def constraint_vjp(x: jax.Array, fwd_axes: tuple, bwd_axes: tuple) -> jax.Array:
    """Sharding constraint with an independent cotangent constraint.

    with_sharding_constraint's transpose re-applies the FORWARD sharding to
    the cotangent. At a sequence-parallel boundary that is exactly wrong: the
    forward is an all-gather (seq-sharded -> replicated), so the transpose
    constraint forces the partial-sum cotangent to replicate — a full
    all-reduce — where a reduce-scatter (cotangent constrained back to
    seq-sharded) moves 2n/(n-1)~2x fewer wire bytes and lands already
    sharded. Semantically both are identity functions, so any cotangent
    sharding is valid; this picks the cheap one.
    """
    return constraint(x, fwd_axes)


def _cvjp_fwd(x, fwd_axes, bwd_axes):
    return constraint_vjp(x, fwd_axes, bwd_axes), None


def _cvjp_bwd(fwd_axes, bwd_axes, _, ct):
    return (constraint(ct, bwd_axes),)


constraint_vjp.defvjp(_cvjp_fwd, _cvjp_bwd)


def sp_gather(x: jax.Array) -> jax.Array:
    """Sequence-parallel boundary: gather seq shards fwd, reduce-scatter bwd."""
    return constraint_vjp(x, ("batch", "seq", "act_embed"), ("batch", "seq_sharded", "act_embed"))


def predict_tick_collectives(mesh: Mesh | None) -> dict[str, int]:
    """Predicted collective set of the slot-sharded streaming tick: EMPTY.

    Every SlotState leaf is sharded on its leading slot axis only
    (stream.SLOT_RULES) and the tick's computation is independent per slot —
    the vmapped recovery steps, readout and eviction signals never contract
    or permute across slots — so a correctly-sharded tick compiles with ZERO
    collectives regardless of mesh size. The device-resident control plane
    (core/control.py) preserves this census: ControlState leaves carry a
    leading per-shard axis sharded the same way, and eviction, queue refill
    and the warm-start gather inside ``tick_device`` are computed per shard
    (the [slots] -> [shards, slots_per_shard] reshape is a local relabeling
    of the already-sharded axis, not a permutation across devices). Rule R5
    (analysis/rules.py) holds the compiled HLO to this prediction: any
    all-reduce/all-gather appearing in a sharded tick means a sharding rule
    regressed (e.g. a replicated operand forcing a gather) and the service
    would pay cross-mesh wire bytes on every tick.
    """
    del mesh
    return {}
