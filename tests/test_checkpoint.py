"""Checkpointing: atomic write, restore, prune, async, elastic restore."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import checkpoint as ck


def tree(seed=0):
    rng = np.random.default_rng(seed)
    return {
        "params": {"w": jnp.asarray(rng.normal(size=(4, 4)), jnp.float32),
                   "layers": [jnp.ones((2,)), jnp.zeros((3,))]},
        "opt": {"m": {"w": jnp.zeros((4, 4))}},
    }


def test_save_restore_roundtrip(tmp_path):
    t = tree()
    ck.save(str(tmp_path), 7, t)
    assert ck.latest_step(str(tmp_path)) == 7
    template = jax.tree.map(lambda x: jnp.zeros_like(x), t)
    r = ck.restore(str(tmp_path), 7, template)
    for a, b in zip(jax.tree.leaves(t), jax.tree.leaves(r)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_no_partial_files_on_disk(tmp_path):
    ck.save(str(tmp_path), 1, tree())
    names = os.listdir(tmp_path)
    assert all(not n.startswith(".tmp") for n in names)


def test_shape_mismatch_rejected(tmp_path):
    ck.save(str(tmp_path), 1, {"w": jnp.zeros((4,))})
    with pytest.raises(ValueError):
        ck.restore(str(tmp_path), 1, {"w": jnp.zeros((5,))})


def test_prune_keeps_latest(tmp_path):
    for s in (1, 2, 3, 4, 5):
        ck.save(str(tmp_path), s, {"w": jnp.zeros((2,))})
    ck.prune(str(tmp_path), keep=2)
    steps = sorted(
        int(f[5:-4]) for f in os.listdir(tmp_path) if f.endswith(".npz")
    )
    assert steps == [4, 5]


def test_async_checkpointer(tmp_path):
    c = ck.AsyncCheckpointer(str(tmp_path), keep=2)
    t = tree()
    for s in (10, 20, 30):
        c.save(s, t)
    c.wait()
    assert ck.latest_step(str(tmp_path)) == 30


def test_elastic_restore_with_shardings(tmp_path):
    """Restore onto an explicit device placement (the re-mesh path)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    t = {"w": jnp.arange(16, dtype=jnp.float32).reshape(4, 4)}
    ck.save(str(tmp_path), 3, t)
    mesh = jax.make_mesh((1,), ("data",),
                         axis_types=(jax.sharding.AxisType.Auto,))
    sh = {"w": NamedSharding(mesh, P())}
    r = ck.restore(str(tmp_path), 3, jax.tree.map(jnp.zeros_like, t), sh)
    np.testing.assert_array_equal(np.asarray(r["w"]), np.asarray(t["w"]))
    assert r["w"].sharding == sh["w"]
