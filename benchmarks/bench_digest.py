"""Digest/delta-plane benchmark: batched single-launch digesting and the
zero-copy wire path against their per-array / copying predecessors.

Four sections, one synthetic namespace (~1 GiB full run, ~32 MiB smoke;
many ragged leaves — the ElasticNotebook-style shape where per-leaf launch
and sync overhead dominates):

- ``digest``  — whole-manifest digesting: per-leaf ``tensor_digest`` (one
  kernel launch + one host round-trip per leaf, what the reducer did
  before) vs ``digest_leaves`` (every leaf packed into block grids, ONE
  launch and ONE sync per batch).  Reports GB/s, the measured host-sync counts, and a
  bit-identity flag — the batched digests must equal the per-leaf digests
  exactly, or fig5/fig11 decisions and CAS chunk keys would drift.
- ``delta``   — the digest-then-compare path (``digest_leaves_delta``):
  mutate ~1%% of leaves, compare against the prior manifest, and check
  the changed-index list is exact.
- ``chunk``   — ``array_chunk_digests_many`` vs per-payload
  ``array_chunk_digests`` on raw buffers (the serialize hot path), plus a
  prior-reuse pass over an almost-unchanged capture (the fused compare
  kernel lets unchanged chunks skip their host blake2b fold).
- ``wire``    — CHUNK-frame encode/decode GB/s: scatter-gather segments +
  view-slicing decoder vs the old join-everything/copy-everything path.
  The decoder must hand back payload *views* into the fed buffer.

Deterministic metrics (sync counts, bit-identity flags) gate tightly in
``benchmarks/baselines/tolerances.json``; throughputs gate with generous
tolerances (machines vary), and speedups are reported for the record.
"""
from __future__ import annotations

import json
import time

import numpy as np

# leaf element counts (float32), mostly small with a few ragged sizes and
# a modest tail — the notebook-realistic profile (many variables, few
# giants) where per-leaf launch+sync overhead is the cost the batched
# path exists to eliminate.
_LEAF_SIZES = (1_024, 1_024, 1_000, 1_024, 2_048, 1_024, 1_024, 3_072,
               1_024, 1_024, 2_048, 1_024, 1_024, 1_024, 4_096, 8_192)


def _namespace(smoke: bool) -> list[np.ndarray]:
    total = (32 << 20) if smoke else (1 << 30)
    rng = np.random.default_rng(0xD161)
    leaves: list[np.ndarray] = []
    acc = 0
    i = 0
    while acc < total:
        n = _LEAF_SIZES[i % len(_LEAF_SIZES)]
        leaves.append(rng.random(n, dtype=np.float32))
        acc += n * 4
        i += 1
    return leaves


def _gbps(nbytes: int, seconds: float) -> float:
    return round(nbytes / max(seconds, 1e-9) / 1e9, 3)


def _timed(fn, reps: int = 2):
    """min-of-``reps`` wall time (noise shield) + the last result.

    Host-sync counters are reset per rep, so ``ops.HOST_SYNCS`` afterwards
    reflects a single pass."""
    from repro.kernels.hash_delta import ops

    best, out = float("inf"), None
    for _ in range(reps):
        ops.reset_host_syncs()
        t0 = time.perf_counter()
        out = fn()
        best = min(best, time.perf_counter() - t0)
    return best, out


def bench_manifest_digest(leaves, *, smoke: bool) -> tuple[dict, list[int]]:
    from repro.kernels.hash_delta import ops

    nbytes = sum(a.nbytes for a in leaves)
    # warm both jit caches so neither path pays compile time in the
    # measured pass (per-leaf compiles once per distinct shape, and the
    # size table cycles, so one cycle covers every shape)
    for a in leaves[:len(_LEAF_SIZES)]:
        ops.tensor_digest(a)
    ops.digest_leaves(leaves)

    t_per, per_leaf = _timed(
        lambda: [ops.tensor_digest(a) for a in leaves])
    syncs_per = ops.HOST_SYNCS

    t_bat, batched = _timed(lambda: ops.digest_leaves(leaves))
    syncs_bat = ops.HOST_SYNCS

    return {
        "namespace_bytes": nbytes,
        "leaves": len(leaves),
        "per_leaf": {"wall_seconds": round(t_per, 4),
                     "gbps": _gbps(nbytes, t_per),
                     "host_syncs": syncs_per},
        "batched": {"wall_seconds": round(t_bat, 4),
                    "gbps": _gbps(nbytes, t_bat),
                    "host_syncs": syncs_bat},
        "speedup": round(t_per / max(t_bat, 1e-9), 2),
        "bit_identical": int(per_leaf == batched),
    }, per_leaf


def bench_delta(leaves, prior, *, smoke: bool) -> dict:
    from repro.kernels.hash_delta import ops

    mutated = list(leaves)
    expect = sorted(range(0, len(leaves), 97))   # ~1% of leaves change
    for j in expect:
        mutated[j] = mutated[j].copy()
        mutated[j][0] += 1.0
    ops.digest_leaves_delta(mutated, prior)   # warm
    t, (digests, changed) = _timed(
        lambda: ops.digest_leaves_delta(mutated, prior))
    nbytes = sum(a.nbytes for a in leaves)
    return {
        "wall_seconds": round(t, 4),
        "gbps": _gbps(nbytes, t),
        "host_syncs": ops.HOST_SYNCS,
        "changed_expected": len(expect),
        "changed_found": len(changed),
        "exact": int(changed == expect
                     and all(digests[j] == prior[j]
                             for j in range(len(prior))
                             if j not in set(expect))),
    }


def bench_chunk_digests(*, smoke: bool) -> dict:
    from repro.core.chunkstore import (
        array_chunk_digests, array_chunk_digests_many,
    )
    from repro.kernels.hash_delta import ops

    total = (8 << 20) if smoke else (256 << 20)
    cb = 8 << 10     # small chunks so multi-chunk payloads are exercised
    rng = np.random.default_rng(0xCA5)
    payloads, acc = [], 0
    while acc < total:
        # mostly small serialized arrays, some multi-chunk, sizes ragged
        n = (4_352 if len(payloads) % 3 else (32 << 10))
        payloads.append(rng.integers(0, 256, n, dtype=np.uint8).tobytes())
        acc += n
    array_chunk_digests_many(payloads[:2], cb)            # warm
    array_chunk_digests(payloads[0], cb)

    t_per, per = _timed(
        lambda: [array_chunk_digests(p, cb) for p in payloads])
    syncs_per = ops.HOST_SYNCS

    t_many, (many, h64s) = _timed(
        lambda: array_chunk_digests_many(payloads, cb))
    syncs_many = ops.HOST_SYNCS

    # prior-reuse pass: one payload mutated, the rest reuse their prior
    # chunk digests via the fused on-device compare
    priors = [(h, d, len(p)) for h, d, p in zip(h64s, many, payloads)]
    mutated = list(payloads)
    mutated[0] = b"\xff" + mutated[0][1:]
    t_reuse, (again, _h) = _timed(
        lambda: array_chunk_digests_many(mutated, cb, priors=priors))
    fresh = [array_chunk_digests(p, cb) for p in mutated]

    return {
        "payload_bytes": acc,
        "payloads": len(payloads),
        "per_payload": {"wall_seconds": round(t_per, 4),
                        "gbps": _gbps(acc, t_per),
                        "host_syncs": syncs_per},
        "batched": {"wall_seconds": round(t_many, 4),
                    "gbps": _gbps(acc, t_many),
                    "host_syncs": syncs_many},
        "reuse_wall_seconds": round(t_reuse, 4),
        "speedup": round(t_per / max(t_many, 1e-9), 2),
        "bit_identical": int(per == many and again == fresh),
    }


def bench_wire(*, smoke: bool) -> dict:
    from repro.core import wire

    chunk_len = 256 << 10
    total = (16 << 20) if smoke else (256 << 20)
    nframes = total // chunk_len
    payload = np.random.default_rng(7).integers(
        0, 256, chunk_len, dtype=np.uint8).tobytes()
    digests = list(range(nframes))

    # --- encode: old join-everything vs scatter-gather segments ---------
    import struct
    t0 = time.perf_counter()
    legacy = [wire.encode_frame(wire.CHUNK,
                                struct.pack("<Q", d) + payload)
              for d in digests]
    t_copy = time.perf_counter() - t0
    t0 = time.perf_counter()
    seg_lists = [wire.chunk_frame(d, payload).segments() for d in digests]
    t_zero = time.perf_counter() - t0

    # --- decode: payload views vs forced materialization ----------------
    buf = b"".join(legacy)
    dec = wire.FrameDecoder()
    dec.feed(buf)
    t0 = time.perf_counter()
    copied = [bytes(f.payload) for f in dec.frames()]   # the old contract
    t_dcopy = time.perf_counter() - t0
    dec2 = wire.FrameDecoder()
    dec2.feed(buf)
    t0 = time.perf_counter()
    frames = list(dec2.frames())
    t_dzero = time.perf_counter() - t0
    views_ok = (len(frames) == nframes == len(copied)
                and all(isinstance(f.payload, memoryview)
                        for f in frames))
    # scatter-gather bytes must equal the joined-encode bytes exactly
    wire_ok = all(b"".join(bytes(s) for s in segs) == enc
                  for segs, enc in zip(seg_lists[:8], legacy[:8]))

    return {
        "frame_bytes": len(buf),
        "frames": nframes,
        "encode": {"copying_gbps": _gbps(len(buf), t_copy),
                   "zero_copy_gbps": _gbps(len(buf), t_zero),
                   "ratio": round(t_copy / max(t_zero, 1e-9), 2)},
        "decode": {"copying_gbps": _gbps(len(buf), t_dcopy),
                   "zero_copy_gbps": _gbps(len(buf), t_dzero),
                   "ratio": round(t_dcopy / max(t_dzero, 1e-9), 2)},
        "payloads_are_views": int(views_ok),
        "bytes_identical": int(wire_ok),
    }


def run(smoke: bool = False) -> list[tuple[str, float, str]]:
    leaves = _namespace(smoke)
    digest, per_leaf = bench_manifest_digest(leaves, smoke=smoke)
    delta = bench_delta(leaves, per_leaf, smoke=smoke)
    chunk = bench_chunk_digests(smoke=smoke)
    wirep = bench_wire(smoke=smoke)
    report = {"digest": digest, "delta": delta, "chunk": chunk,
              "wire": wirep}
    with open("BENCH_digest.json", "w") as f:
        json.dump(report, f, indent=2)

    rows = [
        ("digest/namespace_gib",
         round(digest["namespace_bytes"] / 2**30, 3),
         f"{digest['leaves']} ragged leaves"),
        ("digest/per_leaf_gbps", digest["per_leaf"]["gbps"],
         f"{digest['per_leaf']['host_syncs']} host syncs (one per leaf)"),
        ("digest/batched_gbps", digest["batched"]["gbps"],
         f"{digest['batched']['host_syncs']} host sync, single launch"),
        ("digest/speedup", digest["speedup"],
         "batched vs per-leaf, same bytes"),
        ("digest/bit_identical", digest["bit_identical"],
         "batched digests == per-leaf digests"),
        ("delta/gbps", delta["gbps"],
         f"fused compare+gather, {delta['host_syncs']} host sync"),
        ("delta/exact", delta["exact"],
         f"{delta['changed_found']}/{delta['changed_expected']} changed"),
        ("chunk/batched_gbps", chunk["batched"]["gbps"],
         f"{chunk['payloads']} payloads, one launch"),
        ("chunk/speedup", chunk["speedup"], "vs per-payload digesting"),
        ("chunk/bit_identical", chunk["bit_identical"],
         "CAS chunk keys unchanged"),
        ("wire/encode_zero_copy_gbps", wirep["encode"]["zero_copy_gbps"],
         f"{wirep['encode']['ratio']}x vs joining copy"),
        ("wire/decode_zero_copy_gbps", wirep["decode"]["zero_copy_gbps"],
         f"{wirep['decode']['ratio']}x vs materializing copy"),
        ("wire/payloads_are_views", wirep["payloads_are_views"],
         "decoder slices, never copies"),
    ]
    return rows


if __name__ == "__main__":
    for name, val, note in run():
        print(f"{name},{val},{note}")
