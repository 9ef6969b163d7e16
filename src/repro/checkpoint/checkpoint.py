"""Checkpointing: pytree snapshots with async save and reshard-on-restore.

Format: one directory per step containing
  * ``tree.json``   — pytree structure + per-leaf shape/dtype,
  * ``data.npz``    — zstd-compressed concatenated leaf buffers,
  * ``meta.json``   — step, epoch, data-pipeline cursor, mesh shape.

Restore accepts a *different* mesh than the one that saved (elastic
rescale): leaves are loaded host-side and ``jax.device_put`` with the new
``NamedSharding`` does the resharding.  Epoch-boundary snapshots are the
paper's undo/resume mechanism (EaCO Alg. 1 line 18) and double as the
node-failure recovery path.
"""

from __future__ import annotations

import io
import json
import os
import shutil
import threading
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np

try:
    import zstandard as zstd

    _HAVE_ZSTD = True
except Exception:  # pragma: no cover
    _HAVE_ZSTD = False


def _flatten_with_paths(tree: Any):
    leaves, treedef = jax.tree.flatten(tree)
    return leaves, treedef


def save_checkpoint(
    directory: str,
    step: int,
    tree: Any,
    meta: Optional[Dict[str, Any]] = None,
    keep: int = 3,
) -> str:
    """Synchronous snapshot. Returns the checkpoint path."""
    path = os.path.join(directory, f"step_{step:010d}")
    tmp = path + ".tmp"
    os.makedirs(tmp, exist_ok=True)
    leaves, treedef = _flatten_with_paths(tree)
    arrays = [np.asarray(l) for l in leaves]
    manifest = {
        "treedef": str(treedef),
        "leaves": [{"shape": list(a.shape), "dtype": str(a.dtype)} for a in arrays],
        "n": len(arrays),
    }
    with open(os.path.join(tmp, "tree.json"), "w") as f:
        json.dump(manifest, f)
    # npz cannot round-trip ml_dtypes (bf16 etc.) -> store raw bytes; the
    # manifest carries the true dtype/shape for the view on restore.
    raw = {
        f"leaf_{i}": np.frombuffer(np.ascontiguousarray(a).tobytes(), np.uint8)
        for i, a in enumerate(arrays)
    }
    npz_path = os.path.join(tmp, "data.npz")
    np.savez(npz_path, **raw)
    if _HAVE_ZSTD:
        with open(npz_path, "rb") as f:
            blob = f.read()
        with open(npz_path + ".zst", "wb") as f:
            f.write(zstd.ZstdCompressor(level=3).compress(blob))
        os.remove(npz_path)
    with open(os.path.join(tmp, "meta.json"), "w") as f:
        json.dump({"step": step, **(meta or {})}, f)
    if os.path.exists(path):
        shutil.rmtree(path)
    os.rename(tmp, path)
    _gc(directory, keep)
    return path


def _gc(directory: str, keep: int) -> None:
    ckpts = sorted(
        d for d in os.listdir(directory) if d.startswith("step_") and not d.endswith(".tmp")
    )
    for d in ckpts[:-keep]:
        shutil.rmtree(os.path.join(directory, d), ignore_errors=True)


class AsyncCheckpointer:
    """Fire-and-forget snapshots on a background thread (one in flight)."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        self._thread: Optional[threading.Thread] = None
        self.last_path: Optional[str] = None

    def save(self, step: int, tree: Any, meta: Optional[Dict[str, Any]] = None):
        self.wait()
        # materialize on host *before* handing to the thread so the device
        # buffers can be donated/overwritten by the next step
        host_tree = jax.tree.map(np.asarray, tree)

        def run():
            self.last_path = save_checkpoint(
                self.directory, step, host_tree, meta, self.keep
            )

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None


def latest_checkpoint(directory: str) -> Optional[str]:
    if not os.path.isdir(directory):
        return None
    ckpts = sorted(
        d for d in os.listdir(directory) if d.startswith("step_") and not d.endswith(".tmp")
    )
    return os.path.join(directory, ckpts[-1]) if ckpts else None


def restore_checkpoint(
    path: str,
    like: Any,
    shardings: Optional[Any] = None,
) -> Tuple[Any, Dict[str, Any]]:
    """Restore into the structure of ``like``; optionally reshard.

    ``shardings``: pytree of ``NamedSharding`` congruent with ``like`` —
    pass the *new* mesh's shardings for an elastic restart.
    """
    npz_path = os.path.join(path, "data.npz")
    if not os.path.exists(npz_path) and os.path.exists(npz_path + ".zst"):
        with open(npz_path + ".zst", "rb") as f:
            blob = zstd.ZstdDecompressor().decompress(f.read())
        data = np.load(io.BytesIO(blob))
    else:
        data = np.load(npz_path)
    with open(os.path.join(path, "tree.json")) as f:
        manifest = json.load(f)
    leaves, treedef = jax.tree.flatten(like)
    if manifest["n"] != len(leaves):
        raise ValueError(
            f"checkpoint has {manifest['n']} leaves, expected {len(leaves)}"
        )
    arrays = []
    for i, (l, spec) in enumerate(zip(leaves, manifest["leaves"])):
        dtype = jax.numpy.dtype(spec["dtype"])
        a = data[f"leaf_{i}"].view(dtype).reshape(spec["shape"])
        if tuple(a.shape) != tuple(l.shape):
            raise ValueError(f"checkpoint leaf shape {a.shape} != expected {l.shape}")
        arrays.append(a)
    if shardings is not None:
        shard_leaves = jax.tree.leaves(shardings, is_leaf=lambda s: s is not None)
        arrays = [
            jax.device_put(a.astype(l.dtype), s)
            for a, l, s in zip(arrays, leaves, shard_leaves)
        ]
    else:
        arrays = [jax.numpy.asarray(a.astype(l.dtype)) for a, l in zip(arrays, leaves)]
    with open(os.path.join(path, "meta.json")) as f:
        meta = json.load(f)
    return jax.tree.unflatten(treedef, arrays), meta
