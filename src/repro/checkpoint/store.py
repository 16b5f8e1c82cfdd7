"""Fault-tolerant checkpoint store.

- step-granular directories ``<dir>/step_<n>/`` with a JSON manifest + one
  safetensors payload (named leaves from the state pytree)
- atomic: written to ``.tmp-<n>`` then os.rename'd — a crash mid-write never
  corrupts the latest checkpoint (restart test covers this)
- async: ``CheckpointStore.save_async`` snapshots to host memory on the
  caller's thread, writes on a background thread (training continues)
- elastic: ``restore`` places leaves with *target* shardings — restoring onto
  a different mesh shape / preset / device count just works because the
  payload stores the full logical arrays (single-host container semantics;
  on a real pod each host writes its addressable shards — noted in DESIGN.md)
- retention: keep the newest ``keep`` checkpoints.
- offload-aware: under ``--offload-segments`` the state already lives in mmap
  segment files, so ``save_offload`` just hardlinks them (zero-copy) and
  ``restore_offload`` hardlinks them back (see repro/offload/).
"""
from __future__ import annotations

import functools
import json
import os
import shutil
import threading
import time
from typing import Any, Dict, Optional

import jax
import numpy as np

from repro.checkpoint.safetensors import load_safetensors, save_safetensors
from repro.param import flatten_names


def _state_to_named(state) -> Dict[str, np.ndarray]:
    return {name: np.asarray(leaf) for name, leaf in flatten_names(state)}


def save(state, directory: str, step: int, keep: int = 3,
         extra_meta: Optional[Dict[str, Any]] = None):
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f".tmp-{step}")
    final = os.path.join(directory, f"step_{step:08d}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    named = _state_to_named(jax.device_get(state))
    save_safetensors(os.path.join(tmp, "state.safetensors"), named,
                     metadata={"step": str(step),
                               **{k: str(v) for k, v in
                                  (extra_meta or {}).items()}})
    manifest = {"step": step, "time": time.time(),
                "meta": dict(extra_meta or {}),
                "leaves": {k: [list(v.shape), str(v.dtype)]
                           for k, v in named.items()}}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    _gc(directory, keep)
    return final


def _gc(directory: str, keep: int):
    steps = sorted(_list_steps(directory))
    for s in steps[:-keep] if keep > 0 else []:
        shutil.rmtree(os.path.join(directory, f"step_{s:08d}"),
                      ignore_errors=True)


def _list_steps(directory: str):
    out = []
    if not os.path.isdir(directory):
        return out
    for d in os.listdir(directory):
        if d.startswith("step_"):
            try:
                out.append(int(d[5:]))
            except ValueError:
                pass
    return out


def latest_step(directory: str) -> Optional[int]:
    steps = _list_steps(directory)
    return max(steps) if steps else None


def restore(directory: str, like_state, step: Optional[int] = None,
            shardings=None):
    """Restore into the structure of ``like_state`` (values ignored).  If
    ``shardings`` (matching pytree of NamedSharding) is given, leaves are
    device_put into that layout — this is the elastic-rescale path."""
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {directory}")
    path = os.path.join(directory, f"step_{step:08d}", "state.safetensors")
    named, _ = load_safetensors(path)
    names = [n for n, _ in flatten_names(like_state)]
    leaves_like = jax.tree.leaves(like_state)
    treedef = jax.tree.structure(like_state)
    new_leaves = []
    sh_leaves = (jax.tree.leaves(shardings) if shardings is not None
                 else [None] * len(names))
    for name, like, sh in zip(names, leaves_like, sh_leaves):
        arr = np.asarray(named[name])
        if hasattr(like, "dtype") and str(arr.dtype) != str(like.dtype):
            arr = arr.astype(like.dtype)
        new_leaves.append(jax.device_put(arr, sh) if sh is not None
                          else jax.numpy.asarray(arr))
    return jax.tree.unflatten(treedef, new_leaves), step


# ----------------------------------------------------------------------------
# Segment-offload checkpoints (paper C1 phone realization; repro/offload/)
# ----------------------------------------------------------------------------
# The offload engine already keeps the whole state in mmap segment files, so
# a checkpoint is just a hardlink snapshot of those files (zero-copy: no byte
# of state is staged through RAM).  The engine flips to copy-on-write, so
# later training steps never mutate the snapshot's inodes.
#
# ``ostate.snapshot`` runs behind the engine's flush barrier: with async
# write-back enabled, every dirty segment still in the background write
# queue lands on flash *before* the hardlinks are taken — a snapshot can
# never capture a segment file whose write-back is mid-flight.

def save_offload(ostate, directory: str, step: int, keep: int = 3) -> str:
    """Snapshot an ``OffloadedTrainState`` into ``<dir>/step_<n>/segments``.
    Atomic (tmp + rename) and subject to the same retention as ``save``."""
    os.makedirs(directory, exist_ok=True)
    tmp = os.path.join(directory, f".tmp-{step}")
    final = os.path.join(directory, f"step_{step:08d}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    ostate.snapshot(os.path.join(tmp, "segments"))
    # the storage codecs travel with the checkpoint (the hardlinked mapping
    # table is authoritative; the manifest copy makes them greppable and
    # feeds the resume guards without opening the segment store)
    manifest = {"step": step, "time": time.time(), "offload": True,
                "state_bytes": int(ostate.state_bytes),
                "moment_dtype": ostate.moment_dtype,
                "base_quant": getattr(ostate, "base_quant", "")}
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    _gc(directory, keep)
    return final


def is_offload_checkpoint(directory: str, step: int) -> bool:
    return os.path.isdir(os.path.join(directory, f"step_{step:08d}",
                                      "segments"))


def checkpoint_meta(directory: str, step: int) -> Dict[str, Any]:
    """Extra metadata stamped into a checkpoint's manifest at save time
    (e.g. the seed/LoRA hyperparameters an adapter-only checkpoint depends
    on).  Empty for checkpoints written before the field existed."""
    path = os.path.join(directory, f"step_{step:08d}", "manifest.json")
    if not os.path.isfile(path):
        return {}
    with open(path) as f:
        return json.load(f).get("meta", {})


def is_adapter_checkpoint(directory: str, step: int) -> bool:
    """True for adapter-only checkpoints (frozen-base streamed LoRA): the
    manifest lists ``lora.*`` leaves but no base/params tree — the frozen
    base is re-derived from the seed on resume, never persisted."""
    path = os.path.join(directory, f"step_{step:08d}", "manifest.json")
    if not os.path.isfile(path):
        return False
    with open(path) as f:
        leaves = json.load(f).get("leaves", {})
    return (any(k.startswith("lora.") for k in leaves)
            and not any(k.startswith(("base.", "params.")) for k in leaves))


def offload_checkpoint_layout(directory: str, step: int) -> str:
    """Segment layout of an offload checkpoint: "layer_v1" (layer-aligned,
    param-streaming) or "" (byte-balanced optimizer offload)."""
    table = os.path.join(directory, f"step_{step:08d}", "segments",
                         "table.json")
    with open(table) as f:
        return json.load(f).get("meta", {}).get("layout", "")


def restore_offload(directory: str, work_dir: str, like_params,
                    step: Optional[int] = None, *, max_resident: int = 2,
                    prefetch: bool = True, async_writeback: bool = True,
                    io_backend: str = ""):
    """Reattach to an offload checkpoint by hardlinking its segment files
    into ``work_dir`` (copy-on-write).  Dispatches on the stored segment
    layout: layer-aligned checkpoints come back as ``LayerStreamedState``,
    byte-balanced ones as ``OffloadedTrainState``.  Returns (state, step)."""
    from repro.offload.state import (LAYER_LAYOUT, LayerStreamedState,
                                     OffloadedTrainState)
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints in {directory}")
    seg_dir = os.path.join(directory, f"step_{step:08d}", "segments")
    cls = (LayerStreamedState
           if offload_checkpoint_layout(directory, step) == LAYER_LAYOUT
           else OffloadedTrainState)
    ostate = cls.from_checkpoint(
        seg_dir, work_dir, like_params, max_resident=max_resident,
        prefetch=prefetch, async_writeback=async_writeback,
        io_backend=io_backend)
    return ostate, step


# a save's stall on the training thread, as a span in a profiler trace
_checkpoint_span = functools.partial(jax.profiler.annotate_function,
                                     name="train.checkpoint")


class CheckpointStore:
    """Async wrapper with SIGTERM-safe flush (preemption tolerance)."""

    def __init__(self, directory: str, keep: int = 3):
        self.directory = directory
        self.keep = keep
        # _thread is owned by the caller thread (save_*/wait are never
        # called concurrently); _error crosses the writer boundary
        self._thread: Optional[threading.Thread] = None
        self._lock = threading.Lock()
        self._error: Optional[BaseException] = None  # guarded-by: _lock

    def wait(self):
        """Join the in-flight background write, then surface any exception
        it stored — a failed async save must fail the *next*
        synchronization point (mirrors ``AsyncWriter._error``), not vanish
        with its thread while training keeps overwriting the window."""
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        with self._lock:
            err, self._error = self._error, None
        if err is not None:
            raise RuntimeError(
                "async checkpoint write failed") from err

    @_checkpoint_span
    def save_async(self, state, step: int, extra_meta=None):
        self.wait()
        host_state = jax.device_get(state)  # snapshot before returning

        def _write():
            try:
                save(host_state, self.directory, step, keep=self.keep,
                     extra_meta=extra_meta)
            except BaseException as e:  # surfaced on next wait()/save_*
                with self._lock:
                    self._error = e

        self._thread = threading.Thread(target=_write, daemon=False)
        self._thread.start()

    @_checkpoint_span
    def save_sync(self, state, step: int, extra_meta=None):
        self.wait()
        return save(state, self.directory, step, keep=self.keep,
                    extra_meta=extra_meta)

    @_checkpoint_span
    def save_offload(self, ostate, step: int):
        """Zero-copy (hardlink) snapshot of an OffloadedTrainState — cheap
        enough that no async thread is needed."""
        self.wait()
        return save_offload(ostate, self.directory, step, keep=self.keep)
