"""Sharded, mesh-agnostic, atomically-committed checkpoints.

Layout: <dir>/step_<N>/ holding one .npy per pytree leaf (path-encoded file
names) plus manifest.json (step, leaf index, config hash, data cursor, mesh
shape at save time). Writes go to step_<N>.tmp and are committed by atomic
rename — a crashed save can never shadow the previous good checkpoint, which
is what the restart supervisor (repro.runtime.ft) relies on.

Checkpoints store the *logical* arrays (gathered to host), so restore can
re-shard onto any mesh — the elastic-scaling substrate: save on 256 chips,
restore on 512 (or on the CPU tests' 8 host devices). At 1T scale a
per-shard variant would write device-local slices; the manifest format
already carries the mesh metadata needed to add that without breaking old
checkpoints.

AsyncCheckpointer overlaps serialization with the next training step: the
device->host snapshot is taken synchronously (cheap), the file I/O happens on
a worker thread, and `wait()` joins before the next save or at shutdown.
"""
from __future__ import annotations

import json
import os
import shutil
import threading

import jax
import numpy as np


def _flat(tree):
    flat = {}
    for path, leaf in jax.tree.flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = leaf
    return flat


def _fname(key: str) -> str:
    return key.replace("/", "__") + ".npy"


def save(ckpt_dir: str, step: int, tree, *, extra: dict | None = None,
         keep: int = 3):
    """Synchronous checkpoint save with atomic commit."""
    flat = _flat(tree)
    tmp = os.path.join(ckpt_dir, f"step_{step}.tmp")
    final = os.path.join(ckpt_dir, f"step_{step}")
    os.makedirs(tmp, exist_ok=True)
    manifest = {"step": step, "leaves": {}, "extra": extra or {}}
    for key, leaf in flat.items():
        arr = np.asarray(jax.device_get(leaf))
        np.save(os.path.join(tmp, _fname(key)), arr)
        manifest["leaves"][key] = {"shape": list(arr.shape),
                                   "dtype": str(arr.dtype)}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    _gc(ckpt_dir, keep)
    return final


def _gc(ckpt_dir: str, keep: int):
    steps = sorted(latest_steps(ckpt_dir))
    for s in steps[:-keep]:
        shutil.rmtree(os.path.join(ckpt_dir, f"step_{s}"), ignore_errors=True)


def latest_steps(ckpt_dir: str):
    if not os.path.isdir(ckpt_dir):
        return []
    out = []
    for name in os.listdir(ckpt_dir):
        if name.startswith("step_") and not name.endswith(".tmp") and \
                os.path.exists(os.path.join(ckpt_dir, name, "manifest.json")):
            out.append(int(name.split("_")[1]))
    return sorted(out)


def latest_step(ckpt_dir: str) -> int | None:
    steps = latest_steps(ckpt_dir)
    return steps[-1] if steps else None


def restore(ckpt_dir: str, step: int, like, *, shardings=None):
    """Restore into the structure of `like`; optional sharding pytree re-shards
    onto the current mesh (elastic restore)."""
    d = os.path.join(ckpt_dir, f"step_{step}")
    with open(os.path.join(d, "manifest.json")) as f:
        manifest = json.load(f)
    flat_like = _flat(like)
    flat_sh = _flat(shardings) if shardings is not None else {}
    out = {}
    for key in flat_like:
        arr = np.load(os.path.join(d, _fname(key)))
        if key in flat_sh and flat_sh[key] is not None:
            out[key] = jax.device_put(arr, flat_sh[key])
        else:
            out[key] = jax.numpy.asarray(arr)
    # rebuild using like's treedef
    leaves, treedef = jax.tree.flatten(like)
    keys = list(_flat(like).keys())
    return treedef.unflatten([out[k] for k in keys]), manifest["extra"]


class AsyncCheckpointer:
    def __init__(self, ckpt_dir: str, keep: int = 3):
        self.ckpt_dir = ckpt_dir
        self.keep = keep
        self._thread: threading.Thread | None = None

    def save(self, step: int, tree, extra: dict | None = None):
        self.wait()
        # synchronous device->host snapshot; async file I/O
        snap = jax.tree.map(lambda t: np.asarray(jax.device_get(t)), tree)
        self._thread = threading.Thread(
            target=save, args=(self.ckpt_dir, step, snap),
            kwargs={"extra": extra, "keep": self.keep}, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
