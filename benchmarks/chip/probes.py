"""Wrappers around the program's calls: spans, counters and samples.

The benchmark times each layer from outside the program.  ``Probes``
replaces a few bound methods of one runtime (its analyzer, engine, reducer
and environments) and two module functions of the hash path with wrappers
that

- add the wall seconds of every call to a span total, inside a
  ``jax.profiler.TraceAnnotation`` so that a traced run shows the span on
  the profiler's clock;
- count the bytes that crossed to the destination and the bytes the hash
  kernels had to read;
- log every migration with the names its delta pass considered and the
  names it sent, for the reference's delta check;
- keep a seeded sample of the last digest call's leaves and of the last
  chunk-key call's chunks, for the kernel check.

Spans that end in device work wait for it (``block_until_ready``), so a
span holds the device time its call caused.
"""
from __future__ import annotations

import time
from collections import defaultdict

import jax
import numpy as np

CHUNK_SAMPLES = 3      # chunks of the last chunk-key call drawn from the seed
LEAF_SAMPLES = 2       # leaves of the last digest call drawn from the seed

def env_arrays(env) -> list:
    """Every ``jax.Array`` leaf in an environment's namespace."""
    leaves = jax.tree_util.tree_leaves(list(env.state.ns.values()))
    return [x for x in leaves if isinstance(x, jax.Array)]


def block_env(env) -> None:
    jax.block_until_ready(env_arrays(env))


class Probes:
    def __init__(self, rt, rng: np.random.Generator):
        self.rt = rt
        self.rng = rng
        self.spans: dict[str, float] = defaultdict(float)
        self.calls: dict[str, int] = defaultdict(int)
        self.wire_bytes = 0
        self.hashed_bytes = 0
        self.migrations: list[dict] = []
        self.cell_pos = -1          # position of the running cell in the log
        self.executed = False       # whether that cell has executed yet
        self.exec_env: str | None = None
        self.leaf_sample: tuple[list, list[int]] | None = None
        self.chunk_sample: list[tuple[bytes, int]] = []
        self._names: set[str] | None = None
        self._undo: list = []

    # -- bookkeeping ------------------------------------------------------
    def reset_counters(self) -> None:
        self.spans.clear()
        self.calls.clear()
        self.wire_bytes = 0
        self.hashed_bytes = 0

    def _span(self, name, fn, *, after=None):
        def wrapper(*a, **k):
            with jax.profiler.TraceAnnotation(f"bench.{name}"):
                t0 = time.perf_counter()
                out = fn(*a, **k)
                if after is not None:
                    after(out, *a, **k)
                self.spans[name] += time.perf_counter() - t0
                self.calls[name] += 1
            return out
        return wrapper

    def _patch(self, obj, attr, new) -> None:
        # a module keeps its function in __dict__; an instance finds its
        # method on the class, so undoing the patch deletes the attribute
        self._undo.append((obj, attr, obj.__dict__.get(attr, _MISSING)))
        setattr(obj, attr, new)

    # -- install ------------------------------------------------------------
    def install(self) -> None:
        from repro.core import reducer as reducer_mod
        from repro.kernels.hash_delta import ops

        rt = self.rt
        an, eng, red = rt.analyzer, rt.engine, rt.reducer
        self._patch(an, "decide", self._span("decide", an.decide))
        self._patch(red, "delta_names",
                    self._span("delta", self._delta(red.delta_names)))
        self._patch(red, "serialize_names",
                    self._span("capture", red.serialize_names))
        self._patch(red, "deserialize",
                    self._span("restore", red.deserialize))
        self._patch(eng, "migrate", self._span(
            "migrate", self._migrate(eng.migrate),
            after=lambda res, src, dst, *a, **k: block_env(dst)))
        for env in rt.envs.values():
            self._patch(env, "execute", self._span(
                "exec", self._execute(env, env.execute),
                after=lambda out, *a, _env=env, **k: block_env(_env)))
        self._patch(ops, "digest_leaves", self._digest(ops.digest_leaves))
        self._patch(reducer_mod, "array_chunk_digests_many",
                    self._chunk_keys(reducer_mod.array_chunk_digests_many))

    def uninstall(self) -> None:
        while self._undo:
            obj, attr, old = self._undo.pop()
            if old is _MISSING:
                delattr(obj, attr)
            else:
                setattr(obj, attr, old)

    # -- wrappers -------------------------------------------------------------
    def _delta(self, fn):
        def delta_names(state, names, known):
            self._names = set(names)
            return fn(state, names, known)
        return delta_names

    def _migrate(self, fn):
        def migrate(src, dst, *a, **k):
            self._names = None
            res = fn(src, dst, *a, **k)
            self.wire_bytes += res.nbytes
            self.migrations.append({
                "pos": self.cell_pos,
                "phase": "post" if self.executed else "pre",
                "src": src.name, "dst": dst.name,
                "considered": sorted(self._names or ()),
                "sent": list(res.names), "deleted": list(res.deleted),
                "nbytes": res.nbytes})
            return res
        return migrate

    def _execute(self, env, fn):
        def execute(source, cost=None):
            out = fn(source, cost)
            self.executed = True
            self.exec_env = env.name
            return out
        return execute

    def _digest(self, fn):
        from roofline import leaf_bytes

        def digest_leaves(leaves, **kw):
            leaves = list(leaves)
            out = fn(leaves, **kw)
            self.hashed_bytes += sum(leaf_bytes(x.shape, x.dtype)
                                     for x in leaves)
            if leaves:
                self.leaf_sample = self._pick_leaves(leaves, out)
            return out
        return digest_leaves

    def _chunk_keys(self, fn):
        def array_chunk_digests_many(payloads, chunk_bytes, **kw):
            payloads = list(payloads)
            out = fn(payloads, chunk_bytes, **kw)
            self.hashed_bytes += sum(len(p) for p in payloads)
            if any(len(p) for p in payloads):
                self.chunk_sample = self._pick_chunks(payloads, chunk_bytes,
                                                      out[0])
            return out
        return array_chunk_digests_many

    # -- samples ----------------------------------------------------------------
    def _pick_leaves(self, leaves, digests):
        """The largest and the smallest leaf, every leaf that crosses a
        batch boundary of the packed grid, and a few drawn from the seed."""
        from hashref import BATCH_ROWS, BLOCK
        sizes = [int(np.size(x)) for x in leaves]
        pick = {int(np.argmax(sizes)), int(np.argmin(sizes))}
        nbs = [-(-s // BLOCK) for s in sizes]
        ends = np.cumsum(nbs)
        pick |= {i for i, (nb, end) in enumerate(zip(nbs, ends))
                 if nb and (end - nb) // BATCH_ROWS != (end - 1) // BATCH_ROWS}
        pick |= {int(i) for i in self.rng.choice(
            len(leaves), min(LEAF_SAMPLES, len(leaves)), replace=False)}
        pick = sorted(pick)
        return [leaves[i] for i in pick], [digests[i] for i in pick]

    def _pick_chunks(self, payloads, chunk_bytes, keys):
        """The last chunk of the largest payload (often short) and a few
        chunks drawn from the seed, each copied with the key the program
        gave it."""
        from hashref import chunk_spans
        spans = [(p, c) for p in range(len(payloads))
                 for c in range(len(keys[p]))]
        big = int(np.argmax([len(p) for p in payloads]))
        pick = {(big, len(keys[big]) - 1)}
        for j in self.rng.choice(len(spans), min(CHUNK_SAMPLES, len(spans)),
                                 replace=False):
            pick.add(spans[int(j)])
        out = []
        for p, c in sorted(pick):
            lo, hi = chunk_spans(len(payloads[p]), chunk_bytes)[c]
            out.append((bytes(memoryview(payloads[p])[lo:hi]), keys[p][c]))
        return out


_MISSING = object()
