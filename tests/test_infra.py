"""Infrastructure: checkpointing."""
import os
import tempfile

import jax
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager


# --- checkpoint ---------------------------------------------------------------

def test_checkpoint_roundtrip_and_gc():
    tree = {"a": np.arange(12).reshape(3, 4).astype(np.float32),
            "b": {"c": np.ones((5,), np.int32)}}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for step in (1, 2, 3):
            mgr.save(step, tree, metadata={"step": step})
        assert mgr.all_steps() == [2, 3]  # gc keeps last 2
        out = mgr.restore(3, jax.tree.map(np.zeros_like, tree))
        np.testing.assert_array_equal(out["a"], tree["a"])
        np.testing.assert_array_equal(out["b"]["c"], tree["b"]["c"])
        m = mgr.manifest(3)
        assert m["metadata"]["step"] == 3


def test_checkpoint_async():
    tree = {"x": np.random.default_rng(0).normal(size=(64, 64))}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save_async(7, tree)
        mgr.wait()
        out = mgr.restore(7, np.zeros_like(tree["x"]) if False else
                          {"x": np.zeros_like(tree["x"])})
        np.testing.assert_array_equal(out["x"], tree["x"])


def test_checkpoint_async_write_failure_propagates():
    """A failed background write must raise on the caller's thread at
    wait() — otherwise the scheduler reports durable checkpoints that
    never landed."""
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        blocker = os.path.join(d, "blocker")
        with open(blocker, "w") as f:
            f.write("x")
        mgr.directory = os.path.join(blocker, "sub")  # mkdir under a FILE
        mgr.save_async(1, {"a": np.ones(3)})
        with pytest.raises(OSError):
            mgr.wait()
        # the failure is reported once, then the manager is usable again
        mgr.directory = d
        mgr.save_async(2, {"a": np.ones(3)})
        mgr.wait()
        assert mgr.all_steps() == [2]


def test_checkpoint_gc_retention_ordering():
    """keep= retains the numerically-largest steps regardless of the order
    they were written in — retention is by step id, not recency of write."""
    tree = {"a": np.arange(4, dtype=np.float32)}
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        for step in (3, 1, 5, 2):
            mgr.save(step, {"a": tree["a"] + step})
        assert mgr.all_steps() == [3, 5]
        assert mgr.latest_step() == 5
        out = mgr.restore(5, {"a": np.zeros(4, np.float32)})
        np.testing.assert_array_equal(out["a"], tree["a"] + 5)
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=1)
        for step in (2, 7, 4):
            mgr.save(step, tree)
        assert mgr.all_steps() == [7]


def test_checkpoint_async_failure_surfaces_at_next_save_async():
    """save_async waits on the previous write first, so a background
    failure cannot be silently overwritten by the next snapshot."""
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        blocker = os.path.join(d, "blocker")
        with open(blocker, "w") as f:
            f.write("x")
        mgr.directory = os.path.join(blocker, "sub")  # mkdir under a FILE
        mgr.save_async(1, {"a": np.ones(3)})
        mgr.directory = d
        with pytest.raises(OSError):
            mgr.save_async(2, {"a": np.ones(3)})      # raises for step 1
        mgr.save_async(3, {"a": np.ones(3)})
        mgr.wait()
        assert mgr.all_steps() == [3]


def test_checkpoint_resume_after_torn_final_write():
    """A crash between serialization and the atomic rename leaves a
    .tmp.<step> directory: it must be invisible to step listing and
    restore, and a scheduler resume must use the last GOOD step."""
    import jax.numpy as jnp  # noqa: F401  (jax imported at module top)
    from repro import qa
    from repro.rdf import synth_encoded
    tensor = synth_encoded(4000, seed=23)
    with tempfile.TemporaryDirectory() as d:
        res = qa.assess(tensor, metrics="paper", chunks=6,
                        checkpoint_dir=d, checkpoint_every=3)
        # simulate a torn write of a LATER checkpoint: partial tmp dir
        torn = os.path.join(d, ".tmp.9")
        os.makedirs(torn)
        with open(os.path.join(torn, "arrays.npz"), "wb") as f:
            f.write(b"PK\x03\x04 torn half-written npz")
        mgr = CheckpointManager(d)
        assert 9 not in mgr.all_steps()
        assert mgr.latest_step() == 6
        res2 = qa.assess(tensor, metrics="paper", chunks=6,
                         checkpoint_dir=d)
        assert res2.exec_stats.resumed_from == 6
        assert res2.exec_stats.attempts == 0
        assert res2.values == res.values


def test_checkpoint_missing_key_raises():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d)
        mgr.save(1, {"a": np.ones(3)})
        with pytest.raises(KeyError):
            mgr.restore(1, {"a": np.zeros(3), "b": np.zeros(2)})
