"""Batched digest/delta plane: bit-identity with the per-leaf path.

The whole-manifest digest (`digest_leaves`) and the fused
digest->compare->gather (`digest_leaves_delta`) must produce digests
bit-identical to per-leaf `tensor_digest` — fig5/fig11 decisions and CAS
chunk keys key off these bits, so any drift is a correctness bug, not a
tolerance question.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from _hyp_compat import given, settings, st

from repro.kernels.hash_delta import ops

_DTYPES = (np.float32, np.float64, np.float16, np.int32, np.uint32,
           np.int64, np.int8, np.bool_)


def _leaf(rng: np.random.Generator, spec: int) -> np.ndarray:
    """Deterministic ragged leaf from one sampled integer."""
    dtype = _DTYPES[spec % len(_DTYPES)]
    n = (spec * 131) % 3000          # 0..2999: empty, sub-block, multi-block
    a = rng.standard_normal(n) * 100
    if dtype == np.bool_:
        return a > 0
    return a.astype(dtype)


@settings(max_examples=25, deadline=None)
@given(st.lists(st.integers(0, 10_000), min_size=0, max_size=12),
       st.integers(0, 2**31 - 1))
def test_batched_digests_match_per_leaf_bit_for_bit(specs, seed):
    rng = np.random.default_rng(seed)
    leaves = [_leaf(rng, s) for s in specs]
    # sprinkle device-resident leaves so packing mixes host and jax parts
    leaves = [jnp.asarray(a) if i % 3 == 2 and a.dtype == np.float32 else a
              for i, a in enumerate(leaves)]
    per = [ops.tensor_digest(a) for a in leaves]
    assert ops.digest_leaves(leaves) == per


def test_batched_matches_interpret_kernel():
    rng = np.random.default_rng(11)
    leaves = [rng.standard_normal(n).astype(np.float32)
              for n in (1, 1000, 1024, 2049, 0, 4096)]
    # per-leaf reference via xla: the interpret Pallas path cannot launch a
    # 0-block grid for the empty leaf, while the batched grid packs it away
    per = [ops.tensor_digest(a) for a in leaves]
    assert ops.digest_leaves(leaves, interpret=True) == per
    nonempty = [a for a in leaves if a.size]
    assert (ops.digest_leaves(nonempty, interpret=True)
            == [ops.tensor_digest(a, interpret=True) for a in nonempty])


def test_delta_reports_exactly_the_changed_leaves():
    rng = np.random.default_rng(5)
    leaves = [rng.standard_normal(300).astype(np.float32) for _ in range(9)]
    prior = ops.digest_leaves(leaves)
    mutated = [a.copy() for a in leaves]
    mutated[2][7] += 1.0
    mutated[6][0] -= 0.5
    priors = list(prior)
    priors[4] = None                 # unknown prior counts as changed
    digests, changed = ops.digest_leaves_delta(mutated, priors)
    assert changed == [2, 4, 6]
    assert digests == ops.digest_leaves(mutated)


def test_delta_empty_and_all_unchanged():
    assert ops.digest_leaves_delta([], []) == ([], [])
    rng = np.random.default_rng(6)
    leaves = [rng.standard_normal(64).astype(np.float32) for _ in range(3)]
    prior = ops.digest_leaves(leaves)
    digests, changed = ops.digest_leaves_delta(leaves, prior)
    assert changed == [] and digests == prior


def test_fused_compare_kernel_matches_oracle():
    from repro.kernels.hash_delta.kernel import block_hash_compare_kernel
    from repro.kernels.hash_delta.ref import block_hash_compare_ref

    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.integers(0, 2**32, (6, ops.BLOCK), dtype=np.uint32))
    w = jnp.asarray(ops._W)
    h_ref = np.asarray(block_hash_compare_ref(
        x, w, jnp.zeros((6, ops.LANES), jnp.uint32),
        jnp.zeros((6, 1), jnp.uint32))[0])
    prior = jnp.asarray(h_ref.copy())
    prior = prior.at[3, 0].add(np.uint32(1))        # one block differs
    has = jnp.ones((6, 1), jnp.uint32)
    has = has.at[5, 0].set(0)                       # one block has no prior
    hk, ck = block_hash_compare_kernel(x, w, prior, has, interpret=True)
    hr, cr = block_hash_compare_ref(x, w, prior, has)
    np.testing.assert_array_equal(np.asarray(hk), np.asarray(hr))
    np.testing.assert_array_equal(np.asarray(ck), np.asarray(cr))
    np.testing.assert_array_equal(np.asarray(hk), h_ref)
    assert list(np.asarray(ck)[:, 0]) == [0, 0, 0, 1, 0, 1]


def test_host_sync_counter_is_o1_for_batched():
    rng = np.random.default_rng(13)
    leaves = [rng.standard_normal(256).astype(np.float32) for _ in range(40)]
    ops.reset_host_syncs()
    for a in leaves:
        ops.tensor_digest(a)
    assert ops.HOST_SYNCS == 40
    ops.reset_host_syncs()
    ops.digest_leaves(leaves)
    assert ops.HOST_SYNCS == 1
    ops.reset_host_syncs()
    ops.digest_leaves_delta(leaves, [None] * 40)
    assert ops.HOST_SYNCS == 1


def test_staging_reuse_cannot_corrupt_consecutive_calls():
    # back-to-back batched digests reuse the same staging buffer; the
    # second call must not disturb results derived from the first
    rng = np.random.default_rng(21)
    a = [rng.standard_normal(2000).astype(np.float32) for _ in range(4)]
    b = [rng.standard_normal(2000).astype(np.float32) for _ in range(4)]
    da1 = ops.digest_leaves(a)
    db = ops.digest_leaves(b)
    da2 = ops.digest_leaves(a)
    assert da1 == da2 and da1 != db


def test_object_dtype_leaf_is_rejected_not_misdigested():
    from repro.core.reducer import StateReducer
    with pytest.raises(TypeError):
        StateReducer._hashable_leaf(np.array([object()], dtype=object))


# -- placement: capture grids live on the default device -------------------

def _record_grids(monkeypatch):
    """Wrap the grid packer and the host->device placement; return the
    list every array they build is appended to."""
    seen = []
    pack, place = ops._pack_batch, ops.to_device

    def _pack(*a, **k):
        seen.append(pack(*a, **k))
        return seen[-1]

    def _place(*a, **k):
        seen.append(place(*a, **k))
        return seen[-1]

    monkeypatch.setattr(ops, "_pack_batch", _pack)
    monkeypatch.setattr(ops, "to_device", _place)
    return seen


def test_mixed_manifest_grids_live_on_the_default_device(monkeypatch):
    from repro.core.chunkstore import array_chunk_digests_many
    seen = _record_grids(monkeypatch)
    rng = np.random.default_rng(17)
    leaves = [rng.standard_normal(3000).astype(np.float32),
              jnp.asarray(rng.standard_normal(1500).astype(np.float32)),
              rng.integers(0, 9, 700).astype(np.int8),
              jnp.arange(2048, dtype=jnp.int32)]
    ops.digest_leaves(leaves)
    array_chunk_digests_many([a.tobytes() for a in leaves[::2]], 4096)
    assert len(seen) >= 3          # host runs, the grid, the chunk bytes
    home = jax.devices()[0]
    for x in seen:
        assert x.devices() == {home}


# -- batches: a bounded grid per launch, same bits as one launch ------------

def _split_manifest():
    rng = np.random.default_rng(23)
    return [rng.standard_normal(5 * ops.BLOCK + 7).astype(np.float32),
            np.zeros(0, np.float32),
            jnp.asarray(rng.standard_normal(4 * ops.BLOCK + 1)
                        .astype(np.float32)),       # device leaf, split
            rng.standard_normal(300).astype(np.float32),
            jnp.arange(2 * ops.BLOCK, dtype=jnp.int32)]


def test_small_batches_are_bit_identical_to_one_launch(monkeypatch):
    leaves = _split_manifest()
    one = ops.digest_leaves(leaves)
    prior = list(one)
    prior[3] = None
    monkeypatch.setattr(ops, "BATCH_ROWS", 3)
    nbatches = len(ops.plan_batches([-(-np.size(a) // ops.BLOCK)
                                     for a in leaves]))
    assert nbatches == 5           # 6 + 0 + 5 + 1 + 2 rows in 3-row batches
    ops.reset_host_syncs()
    assert ops.digest_leaves(leaves) == one
    assert ops.HOST_SYNCS == nbatches
    ops.reset_host_syncs()
    assert ops.digest_leaves_delta(leaves, prior) == (one, [3])
    assert ops.HOST_SYNCS == nbatches
    assert ops.digest_leaves(leaves, interpret=True) == one


def test_small_batches_keep_chunk_keys(monkeypatch):
    from repro.core.chunkstore import array_chunk_digests_many
    rng = np.random.default_rng(29)
    payloads = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
                for n in (7 * 1024 + 5, 0, 3000, 9 * 1024)]
    cb = 4096
    keys, h64s = array_chunk_digests_many(payloads, cb)
    priors = [(h, k, len(p)) for h, k, p in zip(h64s, keys, payloads)]
    mutated = list(payloads)
    mutated[3] = mutated[3][:5000] + b"\x01" + mutated[3][5001:]
    fresh, _ = array_chunk_digests_many(mutated, cb)
    monkeypatch.setattr(ops, "BATCH_ROWS", 4)
    ops.reset_host_syncs()
    again, h_again = array_chunk_digests_many(payloads, cb)
    assert again == keys
    assert all((a == b).all() for a, b in zip(h_again, h64s))
    assert ops.HOST_SYNCS == 5     # 8 + 0 + 3 + 9 block rows, 4 per batch
    ops.reset_host_syncs()
    reused, _ = array_chunk_digests_many(mutated, cb, priors=priors)
    assert reused == fresh and reused[3] != keys[3]
    assert ops.HOST_SYNCS == 5


# -- retiled kernels: the (8, 128)-aligned tiles keep the reference bits ------

@pytest.mark.parametrize("rows", [5, 13, 300])
def test_retiled_kernels_match_refs_in_interpret_mode(rows):
    from repro.kernels.hash_delta.kernel import (
        block_hash_compare_kernel, block_hash_kernel,
    )
    from repro.kernels.hash_delta.ref import (
        block_hash_compare_ref, block_hash_ref,
    )
    from repro.kernels.quant_blockwise.kernel import (
        dequantize_kernel, quantize_kernel,
    )
    from repro.kernels.quant_blockwise.ref import dequantize_ref, quantize_ref

    rng = np.random.default_rng(rows)
    x = jnp.asarray(rng.integers(0, 2**32, (rows, ops.BLOCK),
                                 dtype=np.uint32))
    w = jnp.asarray(ops._W)
    h = np.asarray(block_hash_ref(x, w))
    np.testing.assert_array_equal(
        np.asarray(block_hash_kernel(x, w, interpret=True)), h)
    prior = jnp.asarray(h).at[rows // 2, 1].add(np.uint32(1))
    has = jnp.ones((rows, 1), jnp.uint32).at[rows - 1, 0].set(0)
    for k, r in zip(block_hash_compare_kernel(x, w, prior, has,
                                              interpret=True),
                    block_hash_compare_ref(x, w, prior, has)):
        np.testing.assert_array_equal(np.asarray(k), np.asarray(r))
    xf = jnp.asarray(rng.standard_normal((rows, ops.BLOCK)) * 10,
                     jnp.float32)
    qk, sk = quantize_kernel(xf, interpret=True)
    qr, sr = quantize_ref(xf)
    np.testing.assert_array_equal(np.asarray(qk), np.asarray(qr))
    np.testing.assert_array_equal(np.asarray(sk), np.asarray(sr))
    np.testing.assert_array_equal(
        np.asarray(dequantize_kernel(qk, sk, jnp.bfloat16, interpret=True)),
        np.asarray(dequantize_ref(qr, sr, jnp.bfloat16)))


# -- failures: a device error is not the paper's serialization failure -------

def test_device_error_propagates_but_pickling_failure_falls_back(monkeypatch):
    import threading

    from repro.core import reducer as R
    from repro.core.state import ExecutionState

    red = R.StateReducer(codec="none")
    state = ExecutionState({"lock": threading.Lock(),
                            "w": jnp.ones((64,), jnp.float32)})
    with pytest.raises(R.SerializationFailure):
        red.serialize_names(state, {"lock"})

    def _oom(a, codec):
        raise jax.errors.JaxRuntimeError("RESOURCE_EXHAUSTED: out of memory")

    monkeypatch.setattr(R, "_prepare_array", _oom)
    with pytest.raises(jax.errors.JaxRuntimeError):
        red.serialize_names(state, {"w"})
    with pytest.raises(jax.errors.JaxRuntimeError):
        red.serialize_names(state, {"w"}, on_error="skip")
