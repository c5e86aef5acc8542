"""Checkpointing: sharded-agnostic pytree snapshots + manifest.

Design goals for 1000+-node deployments:
* **device-independent state** — arrays are gathered to host numpy before
  serialization, so a checkpoint written under one mesh restores onto a mesh
  of another shape (elastic restart; ``tests/test_multidevice.py`` writes
  under a (4, 2) mesh and restores onto a (2, 4) one); resharding happens
  at ``device_put`` time from the target mesh's shardings.
* **atomic** — writes go to ``<dir>/.tmp.<step>`` then ``os.replace`` into
  place; a crash mid-write never corrupts the latest checkpoint.
* **async** — ``save_async`` hands the serialized bytes to a writer thread so
  the assessment loop is not blocked on disk.
* **self-describing** — ``manifest.json`` records step, tree structure, and
  user metadata (mesh shape, config digest) for audit and compatibility
  checks on restore.
"""
from __future__ import annotations

import json
import os
import threading
from typing import Any

import jax
import numpy as np


def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    flat = {}
    for path, leaf in leaves:
        key = jax.tree_util.keystr(path)
        flat[key] = np.asarray(leaf)
    return flat, treedef


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._writer: threading.Thread | None = None
        self._writer_exc: BaseException | None = None

    # -- save ------------------------------------------------------------------
    def _write(self, step: int, flat: dict[str, np.ndarray],
               metadata: dict[str, Any]):
        tmp = os.path.join(self.directory, f".tmp.{step}")
        os.makedirs(tmp, exist_ok=True)
        np.savez(os.path.join(tmp, "arrays.npz"), **flat)
        manifest = {
            "step": step,
            "keys": sorted(flat.keys()),
            "shapes": {k: list(v.shape) for k, v in flat.items()},
            "dtypes": {k: str(v.dtype) for k, v in flat.items()},
            "metadata": metadata,
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f, indent=2)
        final = os.path.join(self.directory, f"step_{step:010d}")
        if os.path.exists(final):
            import shutil
            shutil.rmtree(final)
        os.replace(tmp, final)
        self._gc()

    def _gc(self):
        steps = self.all_steps()
        for s in steps[:-self.keep]:
            import shutil
            shutil.rmtree(os.path.join(self.directory, f"step_{s:010d}"),
                          ignore_errors=True)

    def save(self, step: int, tree, metadata: dict[str, Any] | None = None):
        flat, _ = _flatten(tree)
        self._write(step, flat, metadata or {})

    def save_async(self, step: int, tree,
                   metadata: dict[str, Any] | None = None):
        self.wait()  # one outstanding write at a time (raises if it failed)
        flat, _ = _flatten(tree)  # device→host copy happens on caller thread

        def _write_capturing():
            try:
                self._write(step, flat, metadata or {})
            except BaseException as e:  # re-raised on the caller's thread
                self._writer_exc = e

        self._writer = threading.Thread(target=_write_capturing, daemon=True)
        self._writer.start()

    def wait(self):
        """Join any in-flight async write; re-raises its exception (disk
        full, permissions, ...) on the caller's thread — a joined write
        either landed durably or this raises."""
        if self._writer is not None:
            self._writer.join()
            self._writer = None
        if self._writer_exc is not None:
            exc, self._writer_exc = self._writer_exc, None
            raise exc

    # -- restore ---------------------------------------------------------------
    def all_steps(self) -> list[int]:
        steps = []
        for name in os.listdir(self.directory):
            if name.startswith("step_"):
                steps.append(int(name.split("_")[1]))
        return sorted(steps)

    def latest_step(self) -> int | None:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def manifest(self, step: int) -> dict:
        with open(os.path.join(self.directory, f"step_{step:010d}",
                               "manifest.json")) as f:
            return json.load(f)

    def restore(self, step: int, template, shardings=None):
        """Restore into the structure of ``template``; optionally re-shard.

        ``shardings`` (same pytree structure, jax.sharding.Sharding leaves)
        places each leaf onto the *current* mesh — this is how elastic
        restarts onto a different topology work.
        """
        self.wait()
        path = os.path.join(self.directory, f"step_{step:010d}", "arrays.npz")
        data = np.load(path)
        flat_t, treedef = _flatten(template)
        missing = set(flat_t) - set(data.files)
        if missing:
            raise KeyError(f"checkpoint missing keys: {sorted(missing)[:5]}")
        leaves_paths, _ = jax.tree_util.tree_flatten_with_path(template)
        out_leaves = []
        shard_leaves = (jax.tree_util.tree_leaves(shardings)
                        if shardings is not None else None)
        for i, (p, leaf) in enumerate(leaves_paths):
            arr = data[jax.tree_util.keystr(p)]
            if shard_leaves is not None:
                arr = jax.device_put(arr, shard_leaves[i])
            out_leaves.append(arr)
        return jax.tree_util.tree_unflatten(treedef, out_leaves)
