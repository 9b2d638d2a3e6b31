"""Sharded, atomic, elastic checkpointing.

Layout: <dir>/step_<N>/ with one .npy per pytree leaf plus manifest.json
(tree structure, shapes, dtypes, step, mesh shape at save time). Writes go
to a temp dir that is atomically renamed, so a crash mid-save never corrupts
the latest checkpoint; `latest_step` only sees complete directories.

Elastic restore: leaves are loaded as full host arrays and re-placed with
``jax.device_put`` under the *current* mesh/sharding — restoring a run onto
a different mesh shape (scale up/down) works out of the box. An async mode
hands the host copy to a writer thread so the training loop does not stall.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Optional

import ml_dtypes
import numpy as np

import jax


# numpy can't save/cast bfloat16 natively; store as uint16 bit patterns
_WIDE = {"bfloat16": (ml_dtypes.bfloat16, np.uint16)}


def _to_storable(arr: np.ndarray) -> tuple[np.ndarray, str]:
    name = arr.dtype.name
    if name in _WIDE:
        return arr.view(_WIDE[name][1]), name
    return arr, name


def _from_storable(arr: np.ndarray, dtype_name: str) -> np.ndarray:
    if dtype_name in _WIDE:
        return arr.view(_WIDE[dtype_name][0])
    return arr


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "_".join(
            str(getattr(p, "key", getattr(p, "idx", p))) for p in path
        )
        out.append((name, leaf))
    return out, treedef


def save(ckpt_dir: str, step: int, tree: Any, *, extra: Optional[dict] = None,
         async_write: bool = False):
    """Save a pytree checkpoint. Blocks unless async_write."""
    leaves, _ = _flatten_with_paths(tree)
    host = []
    for name, leaf in leaves:
        arr, dtype_name = _to_storable(np.asarray(jax.device_get(leaf)))
        host.append((name, arr, dtype_name))
    manifest = {
        "step": int(step),
        "leaves": [
            {"name": n, "shape": list(a.shape), "dtype": d}
            for n, a, d in host
        ],
        "n_devices": jax.device_count(),
        "extra": extra or {},
    }

    def write():
        final = os.path.join(ckpt_dir, f"step_{step:08d}")
        tmp = final + ".tmp"
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp, exist_ok=True)
        for name, arr, _ in host:
            np.save(os.path.join(tmp, f"{name}.npy"), arr)
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.rename(tmp, final)

    if async_write:
        t = threading.Thread(target=write, daemon=True)
        t.start()
        return t
    write()
    return None


def latest_step(ckpt_dir: str) -> Optional[int]:
    if not os.path.isdir(ckpt_dir):
        return None
    steps = []
    for d in os.listdir(ckpt_dir):
        if d.startswith("step_") and not d.endswith(".tmp"):
            if os.path.exists(os.path.join(ckpt_dir, d, "manifest.json")):
                steps.append(int(d[5:]))
    return max(steps) if steps else None


def restore(ckpt_dir: str, step: int, like: Any, *, shardings: Any = None) -> tuple[Any, dict]:
    """Restore into the structure of ``like``. If ``shardings`` (a pytree of
    NamedSharding matching ``like``) is given, leaves are placed sharded —
    use this to restore onto a *different* mesh than the one that saved.

    Returns (tree, manifest_extra)."""
    d = os.path.join(ckpt_dir, f"step_{step:08d}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    leaves, treedef = _flatten_with_paths(like)
    shard_leaves = (
        jax.tree.leaves(shardings) if shardings is not None else [None] * len(leaves)
    )
    dtype_by_name = {l["name"]: l["dtype"] for l in manifest["leaves"]}
    out = []
    for (name, ref_leaf), sh in zip(leaves, shard_leaves):
        arr = _from_storable(np.load(os.path.join(d, f"{name}.npy")),
                             dtype_by_name[name])
        if list(arr.shape) != list(ref_leaf.shape):
            raise ValueError(
                f"checkpoint leaf {name} shape {arr.shape} != expected {ref_leaf.shape}"
            )
        arr = arr.astype(ref_leaf.dtype)
        out.append(jax.device_put(arr, sh) if sh is not None else jax.device_put(arr))
    return jax.tree.unflatten(treedef, out), manifest.get("extra", {})


def restore_latest(ckpt_dir: str, like: Any, *, shardings: Any = None):
    """Returns (step, tree, extra) or None when no checkpoint exists."""
    step = latest_step(ckpt_dir)
    if step is None:
        return None
    tree, extra = restore(ckpt_dir, step, like, shardings=shardings)
    return step, tree, extra
