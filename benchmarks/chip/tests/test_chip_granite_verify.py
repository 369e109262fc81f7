"""The Granite cell's ``verify`` at the tiny size: it passes on the sound
program and leaves the session's state as it found it, and it raises when
the cache copy or the reference's weights are held below the stated
precision."""
import chip_tiny
import jax
import numpy as np
import pytest
import runner

WORKLOAD = "granite_4_0_h_small_generate.decode"


def _session(**verify):
    """The set-up cells' sources at the tiny size, with ``verify``'s
    settings changed as given."""
    cell = runner.load_cell(WORKLOAD, bench=chip_tiny.BENCH)
    size = chip_tiny.tiny(cell.config_name)
    config = {**cell.config, **size["config"]}
    config["verify"] = dict(config["verify"], **verify)
    traffic = {**cell.traffic, **size["traffic"]}
    keys, _ = runner.derive_keys(2**31 + 17, traffic)
    return cell.notebook.cells(config, traffic, keys)


def _through_prefill(src):
    ns = {}
    exec(src["setup"], ns)
    exec(src["prefill"], ns)
    return ns


def test_verify_checks_the_decode_function_on_a_copy_of_the_cache():
    src = _session()
    ns = _through_prefill(src)
    before = {k: v for k, v in ns.items() if k != "__builtins__"}
    cache = jax.tree_util.tree_map(np.array, ns["cache"])
    exec(src["verify"], ns)
    assert set(ns) - set(before) - {"__builtins__"} == {"verify_errors"}
    assert all(ns[k] is v for k, v in before.items())
    assert set(ns["verify_errors"]) == {"prefill", "decode"}
    # the donating calls consumed a copy: the cache is whole and unchanged
    for a, b in zip(jax.tree_util.tree_leaves(ns["cache"]),
                    jax.tree_util.tree_leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a), b)
    exec(src["generate"], ns)
    assert int(ns["filled"]) == ns["prompts"].shape[1] + 1


@pytest.mark.parametrize("control,moved", [
    ({"cache_dtype": "float8_e4m3fn"}, {"decode"}),
    ({"reference_weights": "float8_e4m3fn"}, {"prefill", "decode"}),
])
def test_verify_fails_below_the_stated_precision(control, moved):
    src = _session(**control)
    ns = _through_prefill(src)
    with pytest.raises(AssertionError) as e:
        exec(src["verify"], ns)
    over = str(e.value).split("tolerance")[0]
    assert {k for k in ("prefill", "decode") if f"'{k}'" in over} == moved
