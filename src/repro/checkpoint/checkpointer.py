"""Sharded checkpointing: atomic, async-capable, mesh-agnostic restore.

Format: one directory per step containing
  manifest.json          - tree structure, shapes, dtypes, logical specs
  arr_<i>.npy            - one file per leaf (host-gathered)

Writes go to ``<dir>.tmp`` and are atomically renamed — a crash mid-write
never corrupts the latest checkpoint (restart-safe, the fault-tolerance
contract).  ``async_save`` runs serialization on a worker thread so the
training loop only blocks on device->host transfer of the *previous*
checkpoint (standard large-cluster practice).

Restore is mesh-agnostic: leaves are placed with the *target* mesh's
NamedShardings, so a checkpoint taken on N hosts restores onto M hosts
(elastic scaling).
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any

import jax
import numpy as np


def _flatten_with_paths(tree):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in path)
             for path, _ in flat]
    leaves = [leaf for _, leaf in flat]
    return paths, leaves, treedef


def leaf_to_host(leaf) -> np.ndarray:
    """Full host value of one leaf, multi-process safe.

    A leaf sharded across processes is not fully addressable —
    ``device_get`` would throw — so its shards are gathered through
    ``process_allgather`` (a *collective*: on a multi-process mesh every
    process must reach the save point, and every process receives the
    full value).  Fully-addressable leaves take the direct path."""
    if getattr(leaf, "is_fully_addressable", True):
        return np.asarray(jax.device_get(leaf))
    from jax.experimental import multihost_utils

    return np.asarray(multihost_utils.process_allgather(leaf, tiled=True))


def tree_to_host(tree) -> Any:
    """Host values for a whole tree, multi-process safe.

    Per-leaf :func:`leaf_to_host` is *not* safe for a multi-leaf tree on
    the gloo CPU transport: ``process_allgather`` forces only the first
    addressable shard of each gathered leaf, so the executable's
    all-gathers for the remaining local devices can still be in flight
    when the next leaf's gather dispatches — and interleaved collectives
    from different executables crash gloo.  Here every cross-process
    leaf is gathered by ONE jitted replicated-output computation (XLA
    orders collectives within a single executable) and the whole result
    is blocked on before any host read."""
    leaves, treedef = jax.tree.flatten(tree)
    gathered = [i for i, leaf in enumerate(leaves)
                if not getattr(leaf, "is_fully_addressable", True)]
    if gathered:
        from jax.sharding import NamedSharding, PartitionSpec

        sub = [leaves[i] for i in gathered]
        reps = [NamedSharding(x.sharding.mesh, PartitionSpec())
                for x in sub]
        out = jax.jit(lambda xs: xs, out_shardings=reps)(sub)
        out = jax.block_until_ready(out)
        for i, o in zip(gathered, out):
            leaves[i] = np.asarray(o.addressable_data(0))
    leaves = [np.asarray(jax.device_get(leaf))
              for leaf in jax.block_until_ready(leaves)]
    return jax.tree.unflatten(treedef, leaves)


def host_to_device(arr, sharding=None):
    """Collective-free placement of a host value (the inverse of
    :func:`leaf_to_host`).

    ``device_put`` onto a non-fully-addressable sharding runs jax's
    cross-process equal-value check — a per-leaf broadcast *collective*
    whose gloo messages can interleave with neighbouring puts and crash
    the transport.  ``make_array_from_callback`` builds the same global
    array purely locally: each process materializes only the shards it
    addresses from the host value."""
    if sharding is None:
        return jax.device_put(arr)
    if getattr(sharding, "is_fully_addressable", True):
        return jax.device_put(arr, sharding)
    arr = np.asarray(arr)
    return jax.make_array_from_callback(arr.shape, sharding,
                                        lambda idx: arr[idx])


def save_checkpoint(directory: str, step: int, tree: Any) -> str:
    """Synchronous sharded save with atomic rename.  Returns final path.

    Multi-process: every process participates in the host gather (it is
    collective), but only process 0 touches the filesystem — the
    standard single-writer checkpoint layout."""
    final = os.path.join(directory, f"step_{step:08d}")
    paths, leaves, _ = _flatten_with_paths(tree)
    # Serialize behind in-flight step work: the gather below issues its
    # own cross-process collectives, and on the gloo CPU transport they
    # must not interleave with a still-executing step's collectives.
    leaves = jax.block_until_ready(leaves)
    host = tree_to_host(leaves)
    if jax.process_index() != 0:
        return final
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)

    manifest = {"step": step, "leaves": []}
    for i, (p, arr) in enumerate(zip(paths, host)):
        np.save(os.path.join(tmp, f"arr_{i}.npy"), arr)
        manifest["leaves"].append(
            {"path": p, "file": f"arr_{i}.npy",
             "shape": list(arr.shape), "dtype": str(arr.dtype)})
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.rename(tmp, final)
    return final


class AsyncCheckpointer:
    """Overlaps checkpoint serialization with training compute."""

    def __init__(self):
        self._thread: threading.Thread | None = None
        self.last_path: str | None = None
        self._error: Exception | None = None

    def save(self, directory: str, step: int, tree: Any):
        self.wait()
        # device_get / cross-process gather on the main thread (orders
        # against in-flight steps and keeps the collective out of the
        # worker thread), file IO on the worker thread.  Block first so
        # the gather's collectives cannot interleave with a
        # still-executing step's (fatal on the gloo transport).
        tree = jax.block_until_ready(tree)
        host_tree = tree_to_host(tree)

        def work():
            try:
                self.last_path = save_checkpoint(directory, step, host_tree)
            except Exception as e:  # surfaced on next wait()
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


def restore_checkpoint(path: str, target_tree: Any, shardings: Any | None = None):
    """Restore into the structure of ``target_tree``; place with
    ``shardings`` (a matching tree of NamedShardings) when given —
    this is the elastic/cross-mesh path."""
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    by_path = {e["path"]: e for e in manifest["leaves"]}
    paths, leaves, treedef = _flatten_with_paths(target_tree)
    shard_leaves = (jax.tree.leaves(shardings) if shardings is not None
                    else [None] * len(leaves))
    out = []
    for p, leaf, sh in zip(paths, leaves, shard_leaves):
        entry = by_path.get(p)
        if entry is None:
            raise KeyError(f"checkpoint missing leaf {p!r}")
        arr = np.load(os.path.join(path, entry["file"]))
        if tuple(arr.shape) != tuple(leaf.shape):
            raise ValueError(f"shape mismatch for {p}: {arr.shape} vs {leaf.shape}")
        out.append(host_to_device(arr, sh))
    return jax.tree.unflatten(treedef, out), manifest["step"]
