"""Each fault a cell can have, planted underneath the timed path, makes ``correct`` false.

The run goes through the harness with the look for a chip skipped, at the
tiny size; the program is broken underneath with ``monkeypatch`` and JAX's
caches cleared so that its tick is traced again with the fault in it. One
chip holds no exchange between chips, so that fault does not apply.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest
from rehearsal import no_compile_cache, tiny  # noqa: F401  fixtures


def stale_tick(monkeypatch):
    """A tick that returns its state unchanged."""
    from repro.core import stream

    @functools.partial(jax.jit, static_argnames=("cfg", "scfg"))
    def tick(state, new_y, new_u, key, *, cfg, scfg):
        return state

    monkeypatch.setattr(stream, "tick", tick)


def half_batch(monkeypatch):
    """Half of each slot's windows left out, the mean taken over the rest."""
    from repro.core import stream

    forward, steps = stream.mr_forward, stream._recover_steps

    def half_forward(params, cfg, ys, us):
        n = ys.shape[0] // 2
        return forward(params, cfg, ys[:n], us[:n])

    def half_steps(params, opt, yw, uw, key, steps0, *, cfg, scfg):
        n = yw.shape[0] // 2
        return steps(params, opt, yw[:n], uw[:n], key, steps0, cfg=cfg, scfg=scfg)

    monkeypatch.setattr(stream, "mr_forward", half_forward)
    monkeypatch.setattr(stream, "_recover_steps", half_steps)


def altered_answer(monkeypatch):
    """Answers altered where they are produced: every slot's Theta readout has
    one coefficient moved by a hundredth of the slot's largest, and every
    evicted result is sent out under the id of the stream evicted before it."""
    from repro.core import stream

    impl, evict = stream._tick_impl, stream.RecoveryService._evict

    def tick_impl(state, new_y, new_u, key, *, cfg, scfg):
        state = impl(state, new_y, new_u, key, cfg=cfg, scfg=scfg)
        bump = 0.01 * jnp.abs(state.theta).max(axis=(1, 2))
        return state._replace(theta=state.theta.at[:, 0, 0].add(bump))

    previous = []

    def evict_altered(self, slot, reason):
        res = evict(self, slot, reason)
        true_id = res.stream_id
        if previous:
            res = res._replace(stream_id=previous[-1])
            self._undrained[-1] = res
        previous.append(true_id)
        return res

    monkeypatch.setattr(stream, "_tick_impl", tick_impl)
    monkeypatch.setattr(stream.RecoveryService, "_evict", evict_altered)


@pytest.mark.parametrize("fault", [stale_tick, half_batch, altered_answer])
@pytest.mark.parametrize("workload", ["tiny_gru.tiny_serve", "tiny_gru.tiny_backlog"])
def test_fault_makes_run_incorrect(tiny, monkeypatch, workload, fault):
    import run

    fault(monkeypatch)
    jax.clear_caches()
    try:
        result = run.execute(workload, 4242, 0.6, False, root=tiny, require_chip=False,
                             log=lambda s: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert result["correct"] is False, result["compared"]


def kept_on_admission(field: str):
    """Admission that leaves the slot's ``field`` as its last stream left it."""

    def plant(monkeypatch):
        from repro.core import stream

        admit = stream.admit

        def admit_keeping(state, slot, *args):
            old = jax.tree.map(jnp.copy, getattr(state, field))  # admit donates the state
            return admit(state, slot, *args)._replace(**{field: old})

        monkeypatch.setattr(stream, "admit", admit_keeping)

    plant.__name__ = f"kept_{field}"
    return plant


@pytest.mark.parametrize("fault", [kept_on_admission("opt"), kept_on_admission("steps")],
                         ids=["opt", "steps"])
def test_refill_fault_fails_only_the_refilled_streams(tiny, monkeypatch, fault):
    """A slot refilled with its last stream's AdamW moments or step count:
    the fleet's first streams, admitted into fresh slots, still read sound;
    the sample of refilled streams does not."""
    import check
    import run

    fault(monkeypatch)
    jax.clear_caches()
    try:
        result = run.execute("tiny_gru.tiny_backlog", 4343, 0.6, False, root=tiny,
                             require_chip=False, log=lambda s: None)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    compared = {k: c["value"] for k, c in result["compared"].items()}
    limits = {k: c["limit"] for k, c in result["compared"].items()}
    first = {k: v for k, v in compared.items() if k in ("loss_gap", "moment_gap", "change_gap")}
    refill = {k: v for k, v in compared.items() if k.startswith("recycled_") or k == "identity_gap"}
    assert check.judge(first, limits), compared
    assert not check.judge(refill, limits), compared
    assert result["correct"] is False
