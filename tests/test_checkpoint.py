"""Checkpoint subsystem: roundtrip, atomicity, retention, integrity, async."""

from __future__ import annotations

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import (
    CheckpointManager,
    latest_step,
    restore_checkpoint,
    save_checkpoint,
)
from repro.parallel import make_mesh


def _state(key=0):
    k = jax.random.key(key)
    return {
        "params": {
            "w": jax.random.normal(k, (8, 16)).astype(jnp.bfloat16),
            "b": jnp.arange(16, dtype=jnp.float32),
        },
        "opt": {"m": jnp.zeros((8, 16)), "step": jnp.asarray(7, jnp.int32)},
    }


def test_roundtrip_including_bf16(tmp_path):
    s = _state()
    save_checkpoint(tmp_path, 10, s)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), s)
    r, manifest = restore_checkpoint(tmp_path, 10, like)
    assert manifest["step"] == 10
    for a, b in zip(jax.tree.leaves(s), jax.tree.leaves(r)):
        assert a.dtype == b.dtype
        np.testing.assert_array_equal(np.asarray(a, np.float32), np.asarray(b, np.float32))


def test_latest_ignores_torn_tmp(tmp_path):
    save_checkpoint(tmp_path, 5, _state())
    (tmp_path / "step_00000009.tmp").mkdir()  # simulated crash mid-write
    (tmp_path / "step_00000009.tmp" / "x.npy").write_bytes(b"garbage")
    assert latest_step(tmp_path) == 5


def test_retention_keeps_newest(tmp_path):
    for step in (1, 2, 3, 4, 5):
        save_checkpoint(tmp_path, step, _state(), keep=2)
    steps = sorted(int(p.name[5:]) for p in tmp_path.iterdir() if p.name.startswith("step_"))
    assert steps == [4, 5]


def test_corruption_detected(tmp_path):
    s = _state()
    save_checkpoint(tmp_path, 3, s)
    d = tmp_path / "step_00000003"
    manifest = json.loads((d / "manifest.json").read_text())
    fn = manifest["leaves"]["params/w"]["file"]
    raw = bytearray((d / fn).read_bytes())
    raw[-1] ^= 0xFF
    (d / fn).write_bytes(bytes(raw))
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), s)
    with pytest.raises(IOError, match="corruption"):
        restore_checkpoint(tmp_path, 3, like)


def test_shape_mismatch_rejected(tmp_path):
    s = _state()
    save_checkpoint(tmp_path, 1, s)
    bad = jax.tree.map(lambda a: jax.ShapeDtypeStruct((1,) + a.shape, a.dtype), s)
    with pytest.raises(ValueError, match="shape"):
        restore_checkpoint(tmp_path, 1, bad)


def test_mesh_axes_mismatch_rejected(tmp_path):
    """A checkpoint written on one set of mesh axes refuses to restore into a
    plan sharding over DIFFERENT axes — up front, with a clear error, not a
    shape mismatch deep inside device_put. Matching (or absent) axes pass."""
    s = _state()
    mesh = make_mesh((1,), ("data",))
    save_checkpoint(tmp_path, 2, s, mesh=mesh)
    like = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), s)
    with pytest.raises(ValueError, match="mesh axes .* shards over"):
        restore_checkpoint(tmp_path, 2, like, expect_axes=("slots",))
    r, _ = restore_checkpoint(tmp_path, 2, like, expect_axes=("data",))
    assert r is not None
    # an unsharded save carries no axes and is compatible with anything
    save_checkpoint(tmp_path, 3, s)
    r, _ = restore_checkpoint(tmp_path, 3, like, expect_axes=("slots",))
    assert r is not None


def test_async_manager(tmp_path):
    mgr = CheckpointManager(tmp_path, keep=3, save_every=2)
    s = _state()
    for step in range(6):
        mgr.maybe_save(step, s)
    mgr.wait()
    assert mgr.latest() == 4
    r, manifest = mgr.restore_latest(
        jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), s)
    )
    assert manifest["step"] == 4


def test_reshard_on_restore_across_meshes(run_devices_fixture=None):
    """Save under (4,2) mesh, restore under (2,2) — shards re-placed."""
    from conftest import run_devices

    run_devices(
        """
        import jax, jax.numpy as jnp, numpy as np, tempfile
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.checkpoint import save_checkpoint, restore_checkpoint
        from repro.parallel import make_mesh
        d = tempfile.mkdtemp()
        mesh8 = make_mesh((4, 2), ("data", "model"))
        x = jnp.arange(64, dtype=jnp.float32).reshape(8, 8)
        xs = jax.device_put(x, NamedSharding(mesh8, P("data", "model")))
        save_checkpoint(d, 1, {"x": xs}, mesh=mesh8)
        mesh4 = make_mesh((2, 2), ("data", "model"))
        sh = {"x": NamedSharding(mesh4, P("model", "data"))}
        like = {"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)}
        r, man = restore_checkpoint(d, 1, like, sh)
        assert man["mesh"]["shape"] == [4, 2]
        np.testing.assert_array_equal(np.asarray(r["x"]), np.asarray(x))
        assert r["x"].sharding.spec == P("model", "data")
        print("PASS")
        """,
        n_devices=8,
    )
