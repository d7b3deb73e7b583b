"""Atomic + async checkpointing with elastic (re-mesh) restore.

Layout (one directory per step, atomically renamed into place):

    <dir>/step_00000100/
        manifest.json     tree structure, leaf dtypes/shapes, user metadata
        arrays.npz        one entry per leaf (key = flattened path)

Writes go to ``step_<n>.tmp.<pid>`` and are ``os.rename``d (atomic on
POSIX) only after fsync — a crash mid-write never corrupts the latest
checkpoint, and ``latest_step`` only ever sees complete directories.

Checkpoints are *logical*: every leaf is saved as a full (unsharded) host
array. Restore therefore works onto ANY mesh/device count — the caller
re-applies shardings afterwards (`jax.device_put(tree, shardings)`), which
is what makes elastic restarts (N devices -> M devices) exact.

``Checkpointer`` adds async saves (background thread; ``wait()`` joins),
retention (keep last k), and bit-exact save/restore of optimizer + data
iterator state alongside params.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

__all__ = ["Checkpointer", "latest_step", "restore", "save"]

_PREFIX = "step_"


def _leaf_key(path) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        elif hasattr(p, "name"):
            parts.append(str(p.name))
        else:
            parts.append(str(p))
    return "/".join(parts)


def _tree_structure_repr(tree) -> str:
    return str(jax.tree.structure(tree))


def save(
    directory: str,
    step: int,
    tree: Any,
    metadata: Optional[Dict[str, Any]] = None,
) -> str:
    """Atomically write one checkpoint. Returns the final path."""
    os.makedirs(directory, exist_ok=True)
    final = os.path.join(directory, f"{_PREFIX}{step:08d}")
    tmp = f"{final}.tmp.{os.getpid()}"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    try:
        leaves_with_path = jax.tree.flatten_with_path(tree)[0]
        arrays: Dict[str, np.ndarray] = {}
        manifest_leaves: List[Dict[str, Any]] = []
        for path, leaf in leaves_with_path:
            key = _leaf_key(path)
            arr = np.asarray(jax.device_get(leaf))
            arrays[key] = arr
            manifest_leaves.append(
                {"key": key, "shape": list(arr.shape), "dtype": str(arr.dtype)}
            )
        manifest = {
            "step": step,
            "format": 1,
            "treedef": _tree_structure_repr(tree),
            "leaves": manifest_leaves,
            "metadata": metadata or {},
            "written_at": time.time(),
        }
        np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)
    except BaseException:
        shutil.rmtree(tmp, ignore_errors=True)
        raise
    return final


def latest_step(directory: str) -> Optional[int]:
    """Largest complete checkpoint step, or None."""
    if not os.path.isdir(directory):
        return None
    steps = []
    for name in os.listdir(directory):
        if name.startswith(_PREFIX) and ".tmp." not in name:
            if os.path.exists(os.path.join(directory, name, "manifest.json")):
                steps.append(int(name[len(_PREFIX):]))
    return max(steps) if steps else None


def restore(
    directory: str,
    template: Any,
    step: Optional[int] = None,
) -> Tuple[Any, Dict[str, Any]]:
    """Restore a checkpoint into the structure of ``template``.

    ``template`` supplies the pytree structure (its leaves may be arrays or
    ShapeDtypeStructs — only the structure and leaf order are used). Shapes
    are validated against the stored manifest. Returns (tree, metadata).
    """
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {directory}")
    path = os.path.join(directory, f"{_PREFIX}{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    data = np.load(os.path.join(path, "arrays.npz"))
    leaves_with_path, treedef = jax.tree.flatten_with_path(template)
    stored = {l["key"]: l for l in manifest["leaves"]}
    out = []
    for p, leaf in leaves_with_path:
        key = _leaf_key(p)
        if key not in stored:
            raise KeyError(f"checkpoint {path} missing leaf {key!r}")
        arr = data[key]
        # numpy has no native bfloat16: npz round-trips it as V2 raw bytes;
        # re-view using the manifest's dtype string (ml_dtypes-registered)
        want_dtype = stored[key]["dtype"]
        if str(arr.dtype) != want_dtype:
            arr = arr.view(np.dtype(want_dtype))
        want = tuple(getattr(leaf, "shape", arr.shape))
        if tuple(arr.shape) != want:
            raise ValueError(
                f"leaf {key!r}: stored shape {arr.shape} != template {want}"
            )
        out.append(arr)
    tree = jax.tree.unflatten(treedef, out)
    return tree, manifest["metadata"]


class Checkpointer:
    """Async checkpoint manager with retention.

    save() snapshots to host synchronously (cheap) and writes on a
    background thread; wait() joins outstanding writes. keep=k retains the
    newest k checkpoints (older ones are pruned after a successful write).
    """

    def __init__(self, directory: str, keep: int = 3, async_save: bool = True):
        self.directory = directory
        self.keep = keep
        self.async_save = async_save
        self._thread: Optional[threading.Thread] = None
        self._error: Optional[BaseException] = None

    # ------------------------------------------------------------- public
    def save(self, step: int, tree: Any, metadata: Optional[Dict] = None):
        self.wait()  # one outstanding write at a time
        host_tree = jax.tree.map(lambda x: np.asarray(jax.device_get(x)), tree)
        if not self.async_save:
            save(self.directory, step, host_tree, metadata)
            self._prune()
            return

        def work():
            try:
                save(self.directory, step, host_tree, metadata)
                self._prune()
            except BaseException as e:  # surfaced on next wait()
                self._error = e

        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._error is not None:
            err, self._error = self._error, None
            raise err

    def restore(self, template: Any, step: Optional[int] = None):
        self.wait()
        return restore(self.directory, template, step)

    def latest_step(self) -> Optional[int]:
        self.wait()
        return latest_step(self.directory)

    # ------------------------------------------------------------ private
    def _prune(self):
        if not self.keep:
            return
        steps = sorted(
            int(n[len(_PREFIX):])
            for n in os.listdir(self.directory)
            if n.startswith(_PREFIX) and ".tmp." not in n
        )
        for s in steps[: -self.keep]:
            shutil.rmtree(
                os.path.join(self.directory, f"{_PREFIX}{s:08d}"),
                ignore_errors=True,
            )
