"""Delta checkpointing + restart."""
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import AsyncCheckpointer, Checkpointer


def _tree(x=0.0):
    return {"params": {"w": jnp.arange(100, dtype=jnp.float32) + x,
                       "frozen": jnp.ones((50,), jnp.float32)},
            "meta": {"step": np.int64(3)}}


def test_roundtrip(tmp_path):
    ck = Checkpointer(str(tmp_path))
    t = _tree()
    ck.save(1, {"state": t})
    out, step = ck.restore({"state": t})
    assert step == 1
    np.testing.assert_array_equal(np.asarray(out["state"]["params"]["w"]),
                                  np.asarray(t["params"]["w"]))


def test_delta_skips_unchanged_leaves(tmp_path):
    ck = Checkpointer(str(tmp_path))
    i1 = ck.save(1, {"state": _tree(0.0)})
    assert i1.n_leaves_written == i1.n_leaves_total
    i2 = ck.save(2, {"state": _tree(1.0)})   # only "w" changed
    assert i2.n_leaves_written < i2.n_leaves_total
    out, step = ck.restore({"state": _tree()})
    assert step == 2
    np.testing.assert_array_equal(np.asarray(out["state"]["params"]["w"]),
                                  np.arange(100, dtype=np.float32) + 1.0)
    np.testing.assert_array_equal(np.asarray(out["state"]["params"]["frozen"]),
                                  np.ones(50, np.float32))


def test_restore_specific_step(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"state": _tree(0.0)})
    ck.save(2, {"state": _tree(5.0)})
    out, step = ck.restore({"state": _tree()}, step=1)
    assert step == 1
    np.testing.assert_array_equal(np.asarray(out["state"]["params"]["w"]),
                                  np.arange(100, dtype=np.float32))


def test_corruption_detected(tmp_path):
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"state": _tree()})
    blob = [f for f in os.listdir(tmp_path) if f.endswith(".bin")][0]
    p = os.path.join(tmp_path, blob)
    data = bytearray(open(p, "rb").read())
    data[len(data) // 2] ^= 0xFF
    open(p, "wb").write(bytes(data))
    with pytest.raises(Exception):
        ck.restore({"state": _tree()})


def test_async_checkpointer(tmp_path):
    ck = AsyncCheckpointer(Checkpointer(str(tmp_path)))
    ck.save(1, {"state": _tree()})
    ck.wait()
    assert ck.last_info is not None and ck.last_info.step == 1
    out, step = ck.inner.restore({"state": _tree()})
    assert step == 1


def test_async_checkpointer_saves_host_arrays_as_they_were_at_save(tmp_path):
    ck = AsyncCheckpointer(Checkpointer(str(tmp_path)))
    host = np.arange(300000, dtype=np.float32)
    ck.save(1, {"state": {"h": host}})
    host[:] = -1.0                 # while the writer may still be capturing
    ck.wait()
    out, _ = ck.inner.restore({"state": {"h": host}})
    np.testing.assert_array_equal(out["state"]["h"],
                                  np.arange(300000, dtype=np.float32))


def test_gc_rebase_chain(tmp_path):
    ck = Checkpointer(str(tmp_path), keep=2, rebase_every=5)
    for s in range(1, 7):
        ck.save(s, {"state": _tree(float(s))})
    steps = ck._steps()
    # save #6 is a FULL rebase -> everything older is GC-safe to drop
    assert steps[-1] == 6
    assert ck._manifest(6)["full"]
    out, step = ck.restore({"state": _tree()})
    assert step == 6
    np.testing.assert_array_equal(np.asarray(out["state"]["params"]["w"]),
                                  np.arange(100, dtype=np.float32) + 6.0)


def test_tombstone_through_storage_checkpoint_cycle(tmp_path):
    """A leaf dropped between saves is a tombstone on the storage env: the
    next manifest records it deleted, the storage namespace drops it, and a
    restore of the later step never resurrects it."""
    ck = Checkpointer(str(tmp_path))
    ck.save(1, {"state": {"a": np.arange(10.0), "b": np.ones(5)}})
    info = ck.save(2, {"state": {"a": np.arange(10.0) + 1.0}})
    assert info.n_leaves_total == 1
    m2 = ck._manifest(2)
    dead = [n for n in m2["deleted"] if n.startswith("state/")]
    assert len(dead) == 1                      # the vanished "b" leaf
    assert dead[0] not in m2["names"] and dead[0] not in m2["digests"]
    # storage envs are manifest + CAS only — no leaf is ever materialized
    # into the namespace, deleted or otherwise
    assert dead[0] not in ck.storage.state.ns
    assert not ck.storage.state.ns
    out, step = ck.restore({"state": {"a": np.arange(10.0)}})
    assert step == 2
    np.testing.assert_array_equal(np.asarray(out["state"]["a"]),
                                  np.arange(10.0) + 1.0)
    # the earlier step still restores the full structure from its manifest
    out1, step1 = ck.restore({"state": {"a": np.arange(10.0),
                                        "b": np.ones(5)}}, step=1)
    assert step1 == 1
    np.testing.assert_array_equal(np.asarray(out1["state"]["b"]), np.ones(5))


def test_checkpoint_chunk_delta_reships_only_changed_chunks(tmp_path):
    """A 1-element update to a large leaf writes ~one chunk, not the leaf."""
    ck = Checkpointer(str(tmp_path), codec="zstd", chunk_bytes=16 << 10)
    big = np.arange(1 << 18, dtype=np.float32)          # 1 MiB, 64 chunks
    i1 = ck.save(1, {"state": {"big": big}})
    big2 = big.copy()
    big2[3] += 1.0
    i2 = ck.save(2, {"state": {"big": big2}})
    assert i2.n_leaves_written == 1                     # leaf digest changed
    assert i2.nbytes < i1.nbytes / 10                   # but ~1 chunk moved
    out, step = ck.restore({"state": {"big": big}})
    assert step == 2
    np.testing.assert_array_equal(np.asarray(out["state"]["big"]), big2)


def test_restart_mid_chain(tmp_path):
    ck = Checkpointer(str(tmp_path), rebase_every=10)
    for s in range(1, 5):
        ck.save(s, {"state": _tree(float(s))})
    # fresh process: new Checkpointer over the same dir
    ck2 = Checkpointer(str(tmp_path), rebase_every=10)
    out, step = ck2.restore({"state": _tree()})
    assert step == 4
    np.testing.assert_array_equal(np.asarray(out["state"]["params"]["w"]),
                                  np.arange(100, dtype=np.float32) + 4.0)
