"""The yardstick: the host formulation of the hash equals the program's
digests and chunk keys, the roofline's bytes come from shapes alone, and
the peak table refuses a device it does not know."""
import chip_tiny  # noqa: F401  (puts the benchmark on sys.path)
import hashref
import jax
import jax.numpy as jnp
import numpy as np
import pytest
import roofline
from repro.core.chunkstore import array_chunk_digests_many
from repro.kernels.hash_delta.ops import digest_leaves

LEAVES = [
    np.arange(3000, dtype=np.float32),
    np.random.default_rng(0).integers(0, 255, (17, 33), dtype=np.uint8),
    np.random.default_rng(1).integers(-9, 9, (5, 1024), dtype=np.int32),
    np.zeros((0,), np.float32),
]


def test_leaf_digest_matches_the_program():
    bf16 = jax.random.normal(jax.random.PRNGKey(0), (2049,), jnp.bfloat16)
    leaves = LEAVES + [bf16]
    assert [hashref.leaf_digest(np.asarray(x)) for x in leaves] == \
        digest_leaves(leaves)


@pytest.mark.parametrize("n", [1, 1023, 1024, 5000, 3 * 4096 + 17])
def test_chunk_keys_match_the_program(n):
    payload = np.random.default_rng(n).integers(0, 256, n,
                                                dtype=np.uint8).tobytes()
    keys, _ = array_chunk_digests_many([payload], 4096)
    spans = hashref.chunk_spans(n, 4096)
    assert [hashref.chunk_key(payload[a:b]) for a, b in spans] == keys[0]


def test_leaf_bytes_come_from_shape_and_dtype():
    assert roofline.leaf_bytes((1024, 1024), jnp.bfloat16) == 2 * 1024 * 1024
    assert roofline.leaf_bytes((3, 5), np.uint8) == 15
    assert roofline.leaf_bytes((), np.float32) == 4
    assert roofline.leaf_bytes((7, 0), np.float32) == 0


def test_roofline_uses_the_peak_table_and_refuses_an_unknown_device():
    assert roofline.hash_least_seconds(819e9, "TPU v5 lite") == \
        pytest.approx(1.0)
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
