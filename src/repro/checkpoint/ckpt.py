"""Checkpointing: async, atomic, integrity-checked, mesh-agnostic.

Fault-tolerance properties (the large-scale requirements):

* **Atomicity** — writes go to ``step_<n>.tmp`` then ``os.replace`` to the
  final name; a crash mid-write never corrupts the latest checkpoint.
* **Integrity** — a manifest records per-array checksums (crc via zlib) and
  shapes; ``load_checkpoint`` verifies before restoring and falls back to the
  previous step on mismatch (torn-write recovery).
* **Mesh-agnostic restore** — arrays are saved unsharded (gathered) with
  their pytree paths; restore re-shards onto whatever mesh/sharding the new
  job uses (elastic scaling: a 512-chip checkpoint restores onto 256 chips).
* **Writer election** — in multi-host jobs exactly one host writes; election
  runs on the paper's ALock via :class:`repro.coord.CoordinationService`
  (the owning host pays zero fabric ops — the asymmetric design's point).
* **Async** — the device→host gather happens on the caller thread
  (cheap), serialization+fsync on a background thread, so the train loop
  stalls only for the gather.
"""

from __future__ import annotations

import json
import os
import threading
import zlib
from typing import Any, Dict, Optional, Tuple

import jax
import numpy as np


_ML_DTYPES = ("bfloat16", "float8_e4m3fn", "float8_e5m2")


def _encode(arr: np.ndarray):
    """npz cannot store ml_dtypes (bf16 → void); view as uint bits + tag."""
    if arr.dtype.name in _ML_DTYPES:
        bits = np.uint8 if arr.dtype.itemsize == 1 else np.uint16
        return arr.view(bits), arr.dtype.name
    return arr, str(arr.dtype)


def _decode(arr: np.ndarray, dtype_name: str) -> np.ndarray:
    if dtype_name in _ML_DTYPES:
        import ml_dtypes

        return arr.view(getattr(ml_dtypes, dtype_name))
    return arr


def _path_key(path) -> str:
    return jax.tree_util.keystr(path, simple=True, separator="/")


def _flatten_with_paths(tree) -> Dict[str, np.ndarray]:
    flat = {}
    for path, leaf in jax.tree_util.tree_flatten_with_path(tree)[0]:
        key = _path_key(path)
        flat[key] = np.asarray(leaf)
    return flat


def save_checkpoint(
    directory: str,
    step: int,
    state: Any,
    *,
    extra: Optional[Dict] = None,
    _async: bool = False,
) -> threading.Thread | None:
    """Write ``state`` (pytree of arrays) for ``step``. Returns the writer
    thread when ``_async`` (join it before exiting the process)."""
    os.makedirs(directory, exist_ok=True)
    raw = _flatten_with_paths(state)
    flat, dtypes = {}, {}
    for k, v in raw.items():
        enc, name = _encode(v)
        flat[k] = enc
        dtypes[k] = name
    manifest = {
        "step": int(step),
        "extra": extra or {},
        "arrays": {
            k: {
                "shape": list(v.shape),
                "dtype": dtypes[k],
                "crc": zlib.crc32(np.ascontiguousarray(v).tobytes()),
            }
            for k, v in flat.items()
        },
    }

    def write():
        tmp = os.path.join(directory, f"step_{step:08d}.tmp.npz")
        final = os.path.join(directory, f"step_{step:08d}.npz")
        mtmp = os.path.join(directory, f"step_{step:08d}.tmp.json")
        mfinal = os.path.join(directory, f"step_{step:08d}.json")
        np.savez(tmp, **flat)
        with open(mtmp, "w") as f:
            json.dump(manifest, f)
            f.flush()
            os.fsync(f.fileno())
        os.replace(tmp, final)
        os.replace(mtmp, mfinal)

    if _async:
        t = threading.Thread(target=write, daemon=False)
        t.start()
        return t
    write()
    return None


def _available_steps(directory: str):
    if not os.path.isdir(directory):
        return []
    steps = []
    for name in os.listdir(directory):
        if name.endswith(".json") and name.startswith("step_") and ".tmp" not in name:
            steps.append(int(name[len("step_"):-len(".json")]))
    return sorted(steps)


def load_checkpoint(
    directory: str,
    like: Any,
    *,
    step: Optional[int] = None,
    shardings: Any = None,
) -> Tuple[Any, int, Dict]:
    """Restore the newest (or given) verified checkpoint.

    ``like`` provides the target pytree structure; ``shardings`` (optional
    matching pytree of NamedSharding) re-shards on load — the elastic path.
    Falls back to older steps if integrity verification fails.
    """
    steps = _available_steps(directory)
    if step is not None:
        steps = [s for s in steps if s == step]
    if not steps:
        raise FileNotFoundError(f"no checkpoints in {directory}")
    for s in reversed(steps):
        try:
            with open(os.path.join(directory, f"step_{s:08d}.json")) as f:
                manifest = json.load(f)
            data = np.load(os.path.join(directory, f"step_{s:08d}.npz"))
            flat = {}
            for k, meta in manifest["arrays"].items():
                arr = data[k]
                if zlib.crc32(np.ascontiguousarray(arr).tobytes()) != meta["crc"]:
                    raise IOError(f"checksum mismatch for {k} at step {s}")
                flat[k] = _decode(arr, meta["dtype"])
        except Exception:
            if s == steps[0]:
                raise
            continue  # torn/corrupt: fall back to the previous step
        # Rebuild the pytree in `like`'s structure.
        paths = jax.tree_util.tree_flatten_with_path(like)
        leaves = []
        for path, leaf in paths[0]:
            key = _path_key(path)
            if key not in flat:
                raise KeyError(f"checkpoint missing array {key}")
            arr = flat[key]
            if tuple(arr.shape) != tuple(leaf.shape):
                raise ValueError(
                    f"shape mismatch for {key}: ckpt {arr.shape} vs model {leaf.shape}"
                )
            leaves.append(arr)
        tree = jax.tree_util.tree_unflatten(paths[1], leaves)
        if shardings is not None:
            tree = jax.tree.map(
                lambda a, sh: jax.device_put(a, sh), tree, shardings
            )
        return tree, s, manifest.get("extra", {})
    raise IOError("no verifiable checkpoint found")


class CheckpointManager:
    """Periodic async checkpoints with writer election + retention."""

    def __init__(
        self,
        directory: str,
        every: int = 200,
        keep: int = 3,
        svc=None,            # repro.coord.CoordinationService
        host: int = 0,
        writer_home: int = 0,
    ):
        self.directory = directory
        self.every = max(1, every)
        self.keep = keep
        self.svc = svc
        self.host = host
        self.writer_home = writer_home
        self._proc = svc.host_process(host) if svc is not None else None
        self._pending: Optional[threading.Thread] = None

    def maybe_save(self, step: int, state, extra: Optional[Dict] = None) -> bool:
        if step % self.every != 0:
            return False
        if self.svc is not None:
            # Exactly one host wins the epoch election (paper's ALock inside).
            if not self.svc.elect("ckpt-writer", self._proc, epoch=step,
                                  home_host=self.writer_home):
                return False
        self.wait()  # never two in-flight writes
        host_state = jax.tree.map(np.asarray, state)  # device→host gather
        self._pending = save_checkpoint(
            self.directory, step, host_state, extra=extra, _async=True
        )
        self._gc()
        return True

    def wait(self):
        if self._pending is not None:
            self._pending.join()
            self._pending = None

    def _gc(self):
        # One write is in flight (not yet on disk): keep `keep - 1` of the
        # existing checkpoints so `keep` remain once it lands.
        if not self.keep:
            return
        steps = _available_steps(self.directory)
        keep_existing = max(self.keep - 1, 0)
        doomed = steps[:-keep_existing] if keep_existing else steps
        for s in doomed:
            for suffix in (".npz", ".json"):
                try:
                    os.remove(os.path.join(self.directory, f"step_{s:08d}{suffix}"))
                except OSError:
                    pass
