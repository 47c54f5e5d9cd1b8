"""Fault-tolerant checkpointing: atomic, versioned, async, reshardable.

Layout:  <dir>/step_<N>/arrays.npz + manifest.json, written to a tmp dir and
atomically renamed — a crash mid-save can never corrupt the latest
checkpoint.  Restore takes a *target sharding tree* so a checkpoint saved on
one mesh can be loaded onto a different mesh/host-count (elastic rescale):
arrays are device_put against the new shardings.

The loader position (epoch, cursor) is stored in the manifest, making
mid-epoch restart exact at batch granularity (see core/prefetcher.state).

``np.savez`` cannot store dtypes NumPy does not know (bfloat16, float8):
it writes them as opaque ``|V2`` records that no cast reads back.  Such
leaves are saved as a same-width unsigned-int bit view, with the dtype's
name in the manifest, and viewed back on restore — bit-exact.
"""

from __future__ import annotations

import json
import os
import shutil
import threading
import time
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np


def _flatten_with_paths(tree: Any) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                       for p in path)
        flat[key] = np.asarray(leaf)
    return flat


def _bit_views(flat: Dict[str, np.ndarray]) -> Dict[str, str]:
    """Replace leaves of dtypes NumPy cannot save (kind ``V``) by unsigned
    bit views, in place; returns ``{key: dtype name}`` for those leaves."""
    viewed = {}
    for key, arr in flat.items():
        if arr.dtype.kind == "V":
            viewed[key] = arr.dtype.name
            flat[key] = arr.view(f"u{arr.dtype.itemsize}")
    return viewed


class CheckpointManager:
    def __init__(self, directory: str, keep: int = 3) -> None:
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._async_thread: Optional[threading.Thread] = None

    # -- save -----------------------------------------------------------
    def save(self, step: int, state: Any, extra: Optional[Dict] = None,
             blocking: bool = True) -> str:
        # Snapshot to host memory synchronously (cheap), write async if asked.
        flat = _flatten_with_paths(state)
        manifest = {"step": int(step), "time": time.time(),
                    "keys": sorted(flat.keys()), "dtypes": _bit_views(flat),
                    "extra": extra or {}}

        def write():
            final = os.path.join(self.directory, f"step_{step:08d}")
            tmp = final + ".tmp"
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            np.savez(os.path.join(tmp, "arrays.npz"), **flat)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)          # atomic publish
            self._gc()

        self.wait()                        # one save in flight at most
        if blocking:
            write()
        else:
            self._async_thread = threading.Thread(target=write, daemon=True)
            self._async_thread.start()
        return os.path.join(self.directory, f"step_{step:08d}")

    def wait(self) -> None:
        if self._async_thread is not None:
            self._async_thread.join()
            self._async_thread = None

    def _gc(self) -> None:
        steps = self.all_steps()
        for s in steps[:-self.keep]:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)

    # -- restore ---------------------------------------------------------
    def all_steps(self):
        out = []
        for name in os.listdir(self.directory):
            if name.startswith("step_") and not name.endswith(".tmp"):
                out.append(int(name.split("_")[1]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    def restore(self, template: Any, step: Optional[int] = None,
                shardings: Optional[Any] = None) -> Tuple[Any, Dict]:
        """Load into the structure of ``template``; reshard if asked.

        ``shardings``: optional matching tree of NamedSharding for the target
        mesh (elastic restore onto a different topology).
        """
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {self.directory}")
        path = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(path, "manifest.json")) as f:
            manifest = json.load(f)
        data = np.load(os.path.join(path, "arrays.npz"))
        viewed = manifest.get("dtypes", {})

        leaves_t, treedef = jax.tree_util.tree_flatten(template)
        paths = [
            "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                     for p in path_)
            for path_, _ in jax.tree_util.tree_flatten_with_path(template)[0]]
        shard_leaves = (treedef.flatten_up_to(shardings)
                        if shardings is not None else [None] * len(leaves_t))
        out = []
        for key, tmpl, sh in zip(paths, leaves_t, shard_leaves):
            if key not in data:
                raise KeyError(f"checkpoint missing key {key}")
            arr = data[key]
            if key in viewed:
                arr = arr.view(jnp.dtype(viewed[key]))
            if tuple(arr.shape) != tuple(tmpl.shape):
                raise ValueError(f"shape mismatch for {key}: "
                                 f"{arr.shape} vs {tmpl.shape}")
            arr = arr.astype(tmpl.dtype)
            out.append(jax.device_put(arr, sh) if sh is not None
                       else jax.device_put(arr))
        return treedef.unflatten(out), manifest


__all__ = ["CheckpointManager"]
