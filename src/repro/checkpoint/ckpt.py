"""Sharded, async, elastic checkpointing.

Layout (one directory per step):

    <dir>/step_<N>/
        manifest.json            — treedef paths, shapes, dtypes, specs,
                                   mesh shape/axis names, step
        <leaf-path>.shard<i>.npy — one file per addressable shard
        _COMMITTED               — written last; restore ignores
                                   uncommitted (crashed) checkpoints

Each process writes only its addressable shards (single-process on CPU
writes all of them).  Restore is *elastic*: shards are reassembled into
full host arrays by their index metadata and re-placed with any target
sharding/mesh — restoring a (4,2)-mesh checkpoint onto (2,2) or (1,1)
works by construction (tested in tests/test_checkpoint.py).

Async mode: device->host copies happen synchronously (cheap), file writes
happen on a background thread; ``wait()`` joins before the next save.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


_MANIFEST = "manifest.json"
_COMMITTED = "_COMMITTED"

# shared holder for the async writer thread (save() joins the previous
# write; wait() joins the outstanding one)
_WRITER = {"thread": None}


def _to_savable(arr: np.ndarray) -> np.ndarray:
    """bfloat16 isn't a native numpy dtype — persist as a uint16 view."""
    if str(arr.dtype) == "bfloat16":
        return arr.view(np.uint16)
    return arr


def _from_saved(arr: np.ndarray, dtype_str: str) -> np.ndarray:
    if dtype_str == "bfloat16":
        import ml_dtypes
        return arr.view(ml_dtypes.bfloat16)
    return arr


def _leaf_paths(tree) -> List[Tuple[str, Any]]:
    flat, _ = jax.tree.flatten_with_path(tree)
    out = []
    for path, leaf in flat:
        name = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                        for p in path)
        out.append((name, leaf))
    return out


def save(state, step: int, directory: str, asynchronous: bool = False,
         _thread_holder: Dict = _WRITER):
    """Save a pytree of (possibly sharded) jax arrays."""
    prev = _thread_holder.get("thread")
    if prev is not None:
        prev.join()

    stepdir = os.path.join(directory, f"step_{step:08d}")
    tmpdir = stepdir + ".tmp"
    if os.path.exists(tmpdir):
        shutil.rmtree(tmpdir)
    os.makedirs(tmpdir, exist_ok=True)

    manifest: Dict[str, Any] = {"step": step, "leaves": {}}
    writes: List[Tuple[str, np.ndarray]] = []
    for name, leaf in _leaf_paths(state):
        arr = jax.numpy.asarray(leaf) if not isinstance(
            leaf, (np.ndarray, jax.Array)) else leaf
        entry = {"shape": list(arr.shape), "dtype": str(arr.dtype),
                 "shards": []}
        if isinstance(arr, jax.Array) and hasattr(arr, "addressable_shards"):
            for i, sh in enumerate(arr.addressable_shards):
                if sh.replica_id != 0:
                    continue
                idx = [[s.start, s.stop] if isinstance(s, slice)
                       and s.start is not None
                       else None for s in sh.index]
                fname = f"{name.replace('/', '__')}.shard{i}.npy"
                entry["shards"].append({"file": fname, "index": idx})
                writes.append((os.path.join(tmpdir, fname),
                               _to_savable(np.asarray(sh.data))))
        else:
            fname = f"{name.replace('/', '__')}.shard0.npy"
            entry["shards"].append({"file": fname, "index": None})
            writes.append((os.path.join(tmpdir, fname),
                           _to_savable(np.asarray(arr))))
        manifest["leaves"][name] = entry

    def _write():
        for path, data in writes:
            np.save(path, data)
        with open(os.path.join(tmpdir, _MANIFEST), "w") as f:
            json.dump(manifest, f)
        with open(os.path.join(tmpdir, _COMMITTED), "w") as f:
            f.write("ok")
        if os.path.exists(stepdir):
            shutil.rmtree(stepdir)
        os.rename(tmpdir, stepdir)

    if asynchronous:
        t = threading.Thread(target=_write, daemon=True)
        t.start()
        _thread_holder["thread"] = t
    else:
        _write()
        _thread_holder["thread"] = None
    return stepdir


def wait(_thread_holder: Dict = _WRITER):
    t = _thread_holder.get("thread")
    if t is not None:
        t.join()


def latest_step(directory: str) -> Optional[int]:
    if not os.path.isdir(directory):
        return None
    steps = []
    for d in os.listdir(directory):
        if d.startswith("step_") and not d.endswith(".tmp"):
            if os.path.exists(os.path.join(directory, d, _COMMITTED)):
                steps.append(int(d.split("_")[1]))
    return max(steps) if steps else None


def _assemble(entry: Dict, stepdir: str) -> np.ndarray:
    shape = tuple(entry["shape"])
    dtype = np.dtype(entry["dtype"]) if entry["dtype"] != "bfloat16" else \
        jax.numpy.bfloat16
    shards = entry["shards"]
    if len(shards) == 1 and shards[0]["index"] is None:
        return _from_saved(np.load(os.path.join(stepdir, shards[0]["file"])),
                           entry["dtype"])
    out = np.zeros(shape, dtype=dtype)
    for sh in shards:
        data = _from_saved(np.load(os.path.join(stepdir, sh["file"])),
                           entry["dtype"])
        idx = tuple(slice(*s) if s is not None else slice(None)
                    for s in sh["index"])
        out[idx] = data
    return out


def restore(directory: str, target, step: Optional[int] = None,
            shardings=None):
    """Restore into the structure of `target` (a pytree of arrays or
    ShapeDtypeStructs).  `shardings`: optional matching pytree of
    NamedShardings for elastic re-placement on the current mesh."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no committed checkpoint in {directory}")
    stepdir = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(stepdir, _MANIFEST)) as f:
        manifest = json.load(f)

    names = [n for n, _ in _leaf_paths(target)]
    leaves_t, treedef = jax.tree.flatten(target)
    shard_list = (jax.tree.leaves(shardings,
                                  is_leaf=lambda x: x is None
                                  or isinstance(x, jax.sharding.Sharding))
                  if shardings is not None else [None] * len(leaves_t))
    out = []
    for name, tgt, shd in zip(names, leaves_t, shard_list):
        entry = manifest["leaves"][name]
        arr = _assemble(entry, stepdir)
        if shd is not None:
            out.append(jax.device_put(arr, shd))
        else:
            out.append(jax.numpy.asarray(arr))
    return jax.tree.unflatten(treedef, out), step
