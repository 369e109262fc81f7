"""Checkpointing *is* migration to a storage environment (DESIGN.md §1).

A checkpoint directory is a ``kind="storage"`` :class:`ExecutionEnvironment`
backed by an on-disk content-addressed chunk store.  ``save`` flattens the
trees and migrates them into that env with the same reducer/engine every
other state transfer uses — per-name delta (unchanged leaves don't
re-serialize), per-chunk dedup (changed leaves re-ship only changed chunks),
tombstones for leaves that disappeared.  Each save then writes one
*self-contained* JSON manifest: every leaf's chunk manifest + digest, so any
step restores without replaying a delta chain and GC is just "drop old
manifests, then drop unreferenced chunks".  Manifests are atomic
tmp->rename; chunk files carry an integrity footer, so corrupted or torn
writes surface on restore.  ``AsyncCheckpointer`` overlaps serialization
with compute (background thread).
"""
from __future__ import annotations

import json
import os
import threading
import time
from dataclasses import dataclass

import jax
import numpy as np

from repro.core.chunkstore import CHUNK_BYTES
from repro.core.fabric import ExecutionEnvironment
from repro.core.migration import MigrationEngine
from repro.core.reducer import SerializedName, SerializedState, StateReducer


def _flatten(tree, prefix: str) -> dict[str, np.ndarray]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    return {prefix + jax.tree_util.keystr(path): np.asarray(leaf)
            for path, leaf in flat}


def _unflatten(template, prefix: str, store: dict):
    flat, treedef = jax.tree_util.tree_flatten_with_path(template)
    leaves = [store[prefix + jax.tree_util.keystr(p)] for p, _ in flat]
    return jax.tree_util.tree_unflatten(treedef, leaves)


def _meta_to_json(blob: SerializedName) -> dict:
    return {"pickle": blob.pickle_bytes.hex(), "arrays": [
        {**a, "shape": list(a["shape"]),
         **({"scales": a["scales"].hex()} if "scales" in a else {})}
        for a in blob.arrays]}


def _meta_from_json(rec: dict) -> SerializedName:
    arrays = []
    for a in rec["arrays"]:
        a = dict(a)
        a["shape"] = tuple(a["shape"])
        if "scales" in a:
            a["scales"] = bytes.fromhex(a["scales"])
        arrays.append(a)
    return SerializedName(bytes.fromhex(rec["pickle"]), arrays)


@dataclass
class CheckpointInfo:
    step: int
    nbytes: int
    n_leaves_written: int
    n_leaves_total: int
    seconds: float


class Checkpointer:
    def __init__(self, directory: str, codec: str = "zstd", keep: int = 3,
                 delta: bool = True, rebase_every: int = 5,
                 chunk_bytes: int = CHUNK_BYTES):
        self.dir = directory
        os.makedirs(directory, exist_ok=True)
        self.reducer = StateReducer(codec=codec, reduce_state=False,
                                    chunk_bytes=chunk_bytes)
        self.codec = codec
        self.keep = keep
        self.rebase_every = max(rebase_every, 1)
        self._count = 0
        # the checkpoint target: a storage env over an on-disk CAS — saving
        # is the same engine call as migrating to any other environment
        self.storage = ExecutionEnvironment("ckpt-storage", kind="storage",
                                            storage_dir=directory)
        self.engine = MigrationEngine(self.reducer, delta=delta)
        self._blob_meta: dict[str, SerializedName] = {}  # leaf -> manifest

    # ------------------------------------------------------------------
    def _manifest_path(self, step: int) -> str:
        return os.path.join(self.dir, f"manifest-{step:08d}.json")

    def save(self, step: int, trees: dict) -> CheckpointInfo:
        """trees: e.g. {"params": params, "opt": opt_state, "data_step": ...}"""
        t0 = time.perf_counter()
        store: dict[str, np.ndarray] = {}
        for k, tree in trees.items():
            store.update(_flatten(tree, k + "/"))
        live = ExecutionEnvironment("ckpt-live", globals_seed=store)
        names = set(store)

        res = self.engine.migrate(live, self.storage, names=names)
        for name in res.deleted:
            self._blob_meta.pop(name, None)
        if self.engine.last_ser is not None:
            self._blob_meta.update(self.engine.last_ser.blobs)

        # every k-th manifest is tagged "full" for operator tooling parity
        # with the pre-CAS delta chains — but *every* manifest is
        # self-contained now, so restore never replays a chain
        full = (self._count % self.rebase_every == 0)
        self._count += 1
        view = self.engine.synced.get(self.storage.name, {})
        manifest = {
            "step": step, "codec": self.codec, "full": full,
            "digests": {n: view[n] for n in names},
            "written": sorted(res.names), "deleted": sorted(res.deleted),
            "names": {n: _meta_to_json(self._blob_meta[n]) for n in names},
            "keys": sorted(trees),
        }
        mtmp = self._manifest_path(step) + ".tmp"
        with open(mtmp, "w") as f:
            json.dump(manifest, f)
        os.replace(mtmp, self._manifest_path(step))

        self._gc()
        return CheckpointInfo(step, res.nbytes, len(res.names), len(names),
                              time.perf_counter() - t0)

    # ------------------------------------------------------------------
    def _steps(self) -> list[int]:
        out = []
        for fn in os.listdir(self.dir):
            if fn.startswith("manifest-") and fn.endswith(".json"):
                out.append(int(fn[len("manifest-"):-len(".json")]))
        return sorted(out)

    def _manifest(self, step: int) -> dict:
        with open(self._manifest_path(step)) as f:
            return json.load(f)

    def _gc(self) -> None:
        """Drop manifests beyond ``keep`` (every one is self-contained),
        then drop chunks no surviving manifest references."""
        steps = self._steps()
        if len(steps) <= self.keep + 1:
            return
        drop, survive = steps[:-(self.keep + 1)], steps[-(self.keep + 1):]
        referenced: set[int] = set()
        for s in survive:
            for rec in self._manifest(s)["names"].values():
                for a in rec["arrays"]:
                    referenced.update(a["chunks"])
        for s in drop:
            p = self._manifest_path(s)
            if os.path.exists(p):
                os.remove(p)
        for d in self.storage.chunk_store.digests() - referenced:
            self.storage.chunk_store.remove(d)

    # ------------------------------------------------------------------
    def latest_step(self) -> int | None:
        steps = self._steps()
        return steps[-1] if steps else None

    def restore(self, templates: dict, step: int | None = None) -> tuple[dict, int]:
        """Rebuild from the step's self-contained manifest + the disk CAS;
        verifies chunk integrity footers and per-leaf content digests."""
        steps = self._steps()
        if not steps:
            raise FileNotFoundError(f"no checkpoints in {self.dir}")
        target = step if step is not None else steps[-1]
        candidates = [x for x in steps if x <= target]
        if not candidates:
            raise FileNotFoundError(f"no checkpoint at or before {target}")
        manifest = self._manifest(candidates[-1])

        blobs = {n: _meta_from_json(rec)
                 for n, rec in manifest["names"].items()}
        ser = SerializedState(codec=manifest["codec"], blobs=blobs)
        store = self.reducer.deserialize(
            ser, chunk_store=self.storage.chunk_store)

        for name, want in manifest["digests"].items():
            if name not in store:
                raise IOError(f"checkpoint missing leaf {name}")
            got = self.reducer.digest(store[name])
            if want != -1 and got != want:
                raise IOError(f"checkpoint digest mismatch for {name}")

        out = {k: _unflatten(t, k + "/", store) for k, t in templates.items()}
        return out, manifest["step"]


class AsyncCheckpointer:
    """Overlap checkpoint writes with compute (single background writer)."""

    def __init__(self, inner: Checkpointer):
        self.inner = inner
        self._thread: threading.Thread | None = None
        self.last_info: CheckpointInfo | None = None

    def save(self, step: int, trees: dict) -> None:
        self.wait()
        # snapshot to host first (cheap on CPU; device_get on TPU).  Host
        # numpy leaves are copied: the capture reads arrays in place, and
        # the caller may change them while the writer runs
        host = jax.tree_util.tree_map(
            lambda x: x.copy() if isinstance(x, np.ndarray) else np.asarray(x),
            trees)

        def run():
            self.last_info = self.inner.save(step, host)

        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()

    def wait(self) -> None:
        if self._thread is not None:
            self._thread.join()
            self._thread = None
