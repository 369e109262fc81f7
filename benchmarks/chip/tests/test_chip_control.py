"""The comparison fails the lower-precision controls and planted faults.

The control is the program's own lossy path, the ``quant8+zstd`` codec
(int8 blocks for the bfloat16 and float32 state), and, for state that
path cannot carry on the chip, every restore handed back one precision
down (``control.round_restores``).  The faults are planted
in the program when the window opens: a migration that leaves the
destination as it was, a delta that leaves out half of the changed names,
a restored value with one bit altered, and a leaf digest altered where the
kernel path returns it."""
import chip_tiny
import control
import jax
import numpy as np
import pytest


@pytest.mark.parametrize("workload", chip_tiny.WORKLOADS)
def test_lower_precision_control_is_not_correct(workload):
    r = chip_tiny.run(workload, codec="quant8+zstd")
    assert r["correct"] is False
    assert r["checks"]["restore_bad"]["value"] > 0 \
        or r["checks"]["output_bad"]["value"] > 0


@pytest.mark.parametrize("workload", chip_tiny.WORKLOADS)
def test_rounded_restore_control_is_not_correct(workload):
    r = chip_tiny.run(workload, fault=control.round_restores)
    assert r["correct"] is False
    assert r["checks"]["restore_bad"]["value"] > 0 \
        or r["checks"]["output_bad"]["value"] > 0


def unchanged(rt):
    rt.reducer.deserialize = lambda ser, target_ns=None, chunk_store=None: {}


def half_left_out(rt):
    delta = rt.reducer.delta_names

    def half(state, names, known):
        send, dead, here = delta(state, names, known)
        return set(sorted(send)[: len(send) // 2]), dead, here
    rt.reducer.delta_names = half


def altered(rt):
    restore = rt.reducer.deserialize

    def flip(ser, target_ns=None, chunk_store=None):
        out = restore(ser, target_ns=target_ns, chunk_store=chunk_store)
        for v in out.values():
            for leaf in jax.tree_util.tree_leaves(v):
                if isinstance(leaf, np.ndarray) and leaf.size \
                        and leaf.flags.writeable:
                    leaf.reshape(-1).view(np.uint8)[0] ^= 1
                    return out
        return out
    rt.reducer.deserialize = flip


def digest_altered(rt):
    from repro.kernels.hash_delta import ops
    fold = ops._fold_digests
    ops._fold_digests = lambda lanes: [d ^ 1 for d in fold(lanes)]
    return lambda: setattr(ops, "_fold_digests", fold)


@pytest.mark.parametrize("fault", [unchanged, half_left_out, altered,
                                   digest_altered])
def test_planted_fault_is_not_correct(fault):
    undo = []
    try:
        r = chip_tiny.run(chip_tiny.FAULT_CELL,
                          fault=lambda rt: undo.append(fault(rt)))
    finally:
        for u in undo:
            if u is not None:
                u()
    assert r["correct"] is False
